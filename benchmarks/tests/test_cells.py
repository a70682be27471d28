"""The data files fit together and BENCHMARK.json says what they say."""

import json
import os

import pytest

from lib import cell as cells
from lib.peaks import peaks_for

WORKLOADS = sorted(f[:-5] for f in os.listdir(
    os.path.join(cells.ROOT, "workloads")))
MANIFEST = cells.manifest()


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_cell_file_loads_and_names_real_readers(name):
    cell = cells.load_cell(name)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.reader(m["reader"]["kind"]))
    assert len(cell.spec["why"]) <= 200


def test_listed_cells_match_their_files():
    assert MANIFEST["workloads"], "BENCHMARK.json lists no cell"
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.listed
        assert cell.spec["config"] == w["config"]
        assert cell.spec["traffic"] == w["traffic"]
        assert cell.config["chips"] == w["chips"]
        entry = configs[w["config"]]
        assert entry["file"] == f"benchmarks/configs/{w['config']}.json"
        assert entry["source"] == cell.config["source"]
        assert entry["reduced"] == cell.config["reduced"]


def test_manifest_metrics_are_the_files_metrics():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    listed = [cells.load_cell(w["name"]) for w in MANIFEST["workloads"]]
    all_names = [c.name for c in listed]
    for table, key in ((e2e, "end_to_end"), (layer, "per_layer")):
        reported: dict = {}
        for c in listed:
            for m in getattr(c, key):
                reported.setdefault(m["name"], []).append(c.name)
                entry = table[m["name"]]
                for k in ("unit", "better", "source"):
                    assert entry[k] == m[k], (m["name"], k)
                if key == "per_layer":
                    assert entry["layer"] == m["layer"]
                    assert entry["moves"] == m["moves"]
        assert set(reported) == set(table)
        for name, where in reported.items():
            want = table[name].get("workloads", all_names)
            assert sorted(want) == sorted(where), name


def test_unlisted_rehearsal_is_added_by_files_alone():
    cell = cells.load_cell("tiny-rehearsal.rehearsal")
    assert not cell.listed


def test_a_layer_metric_needs_the_metric_it_moves(tmp_path, monkeypatch):
    spec = cells.load_json("workloads", "tiny-rehearsal.rehearsal.json")
    spec["end_to_end"] = ["out_tok_s", "setup_s"]  # drops ttft_p50_ms
    root = tmp_path / "benchmarks"
    for sub in ("workloads", "configs", "traffic", "end_to_end",
                "layer_metrics"):
        os.symlink(os.path.join(cells.ROOT, sub), root / sub) \
            if sub != "workloads" else os.makedirs(root / sub)
    with open(root / "workloads" / "x.json", "w") as f:
        json.dump(spec, f)
    monkeypatch.setattr(cells, "ROOT", str(root))
    with pytest.raises(cells.CellError, match="moves"):
        cells.load_cell("x")


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9")
