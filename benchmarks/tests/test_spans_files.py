"""The two unlisted proving files are their listed cells plus the new
layer metrics, and every metric PR 27 added loads and is reported next
to the end-to-end metric it moves."""

import pytest

from lib import cell as cells

NEW = ["sched.window_wait_ms", "sched.backlog_wait_ms",
       "step.decode_ms_per_step", "step.prefill_device_share",
       "kv.computed_token_share", "step.prefill_padding_share",
       "sched.steps_per_window"]
# kernel.pool_layout_time_share, the issue's seventh, is left out: a TPU
# trace carries no scope name in any field ProfileData reads (PERF.md
# section 3), so its reader would find nothing to match
PAIRS = [("qwen2-7b-w8.chat", "qwen2-7b-w8.chat-spans"),
         ("qwen2-7b-w8.docs", "qwen2-7b-w8.docs-spans")]


@pytest.mark.parametrize("listed,proving", PAIRS)
def test_proving_file_is_its_cell_but_for_three_keys(listed, proving):
    a = cells.load_json("workloads", listed + ".json")
    b = cells.load_json("workloads", proving + ".json")
    assert set(a) == set(b)
    for key in a:
        if key not in ("name", "layer_metrics", "why"):
            assert a[key] == b[key], key
    assert b["name"] == proving
    n = len(a["layer_metrics"])
    assert b["layer_metrics"][:n] == a["layer_metrics"]
    assert sorted(b["layer_metrics"][n:]) == sorted(NEW)
    assert not cells.load_cell(proving).listed
    assert cells.load_cell(listed).listed


@pytest.mark.parametrize("name", NEW)
def test_new_metric_loads_and_its_file_reports_what_it_moves(name):
    m = cells.load_json("layer_metrics", name + ".json")
    assert m["name"] == name
    assert {"layer", "unit", "better", "source", "moves", "what",
            "reader"} <= set(m)
    assert callable(cells.reader(m["reader"]["kind"]))
    for _, proving in PAIRS:
        cell = cells.load_cell(proving)  # raises if `moves` is missing
        assert name in [x["name"] for x in cell.per_layer]
        assert m["moves"] in cell.spec["end_to_end"]


def test_docs_divides_no_host_duration_by_prompt_tokens():
    docs = cells.load_json("workloads", "qwen2-7b-w8.docs-spans.json")
    assert "step.prefill_ms_per_ktok" not in docs["layer_metrics"]
    assert "step.decode_ms_per_tok" not in docs["layer_metrics"]


def test_decode_ms_per_step_covers_the_windows_its_steps_come_from():
    # kubeinfer_engine_decode_steps_total sums K over decode AND verify
    # windows; dividing decode durations alone by it reads low under
    # --speculative-draft
    m = cells.load_json("layer_metrics", "step.decode_ms_per_step.json")
    phases = {t["labels"]["phase"] for t in m["reader"]["num"]}
    assert phases == {"decode", "verify"}
    k = cells.load_json("layer_metrics", "sched.steps_per_window.json")
    assert {t["labels"]["phase"] for t in k["reader"]["den"]} == phases
