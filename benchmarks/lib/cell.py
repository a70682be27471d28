"""Loading a cell: everything that belongs to one thing is one file,
found by its name. Nothing here knows a cell's, a mix's or a model's
name."""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class CellError(Exception):
    """A cell's files do not fit together."""


def load_json(*parts: str) -> dict:
    path = os.path.join(ROOT, *parts)
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"no file {os.path.relpath(path, CHECKOUT)}") \
            from None


def _named(kind: str, name: str) -> dict:
    if not NAME.match(name):
        raise CellError(f"{name!r} is not a name")
    return load_json(kind, name + ".json")


@dataclass
class Cell:
    name: str
    listed: bool  # an entry of BENCHMARK.json's workloads
    spec: dict  # workloads/<name>.json
    config: dict
    traffic: dict
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)
    checks: list = field(default_factory=list)  # checks/<kind>.py modules


def manifest() -> dict:
    path = os.path.join(CHECKOUT, "BENCHMARK.json")
    if not os.path.exists(path):
        return {"workloads": []}
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str) -> Cell:
    spec = _named("workloads", name)
    cell = Cell(
        name=name,
        listed=any(w["name"] == name for w in manifest()["workloads"]),
        spec=spec,
        config=_named("configs", spec["config"]),
        traffic=_named("traffic", spec["traffic"]),
        end_to_end=[_named("end_to_end", m) for m in spec["end_to_end"]],
        per_layer=[_named("layer_metrics", m)
                   for m in spec["layer_metrics"]],
        checks=[check(k) for k in spec.get("checks", [])],
    )
    sizes(cell.config["model_type"])  # a model with no sizes/ file stops here
    have = set(spec["end_to_end"])
    if "setup_s" not in have or len(have) < 2:
        raise CellError(f"{name}: a cell reports setup_s and at least one "
                        "other end-to-end metric")
    for m in cell.per_layer:
        if m["moves"] not in have:
            raise CellError(
                f"{name}: layer metric {m['name']} moves {m['moves']}, "
                "which this cell does not report")
    loop = cell.traffic["loop"]
    if (loop == "open") != ("rate_req_s" in spec) or \
            (loop == "closed") != ("clients" in spec):
        raise CellError(f"{name}: an open loop takes rate_req_s, a closed "
                        "loop clients")
    return cell


_LOADED: dict = {}


def _module(package: str, name: str):
    """``<package>/<name>.py`` under ROOT, loaded by its path: the file
    is the registration, so a model type, a check, a reader kind or a
    kernel nobody wrote a file for is a CellError that names the file."""
    if not NAME.match(name):
        raise CellError(f"{name!r} is not a name")
    path = os.path.join(ROOT, package, name + ".py")
    if path not in _LOADED:
        if not os.path.isfile(path):
            raise CellError(f"no file {os.path.relpath(path, CHECKOUT)}")
        spec = importlib.util.spec_from_file_location(
            f"{package}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def reader(kind: str):
    """readers/<kind>.py, found by name: ``read(args, ctx)`` returns a
    number, or None when there was nothing to read."""
    return _module("readers", kind).read


def opcount(kernel: str):
    """opcount/<kernel>.py: ``count`` and ``shapes_from_hlo``."""
    return _module("opcount", kernel)


def sizes(model_type: str):
    """sizes/<model_type>.py: ``param_bytes(conf, weight_dtype)`` and
    ``flops_per_token(conf)``, the only place a model's shape is known."""
    return _module("sizes", model_type)


def check(kind: str):
    """checks/<kind>.py: ``before_window(run)`` and ``after_exit(run)``,
    either of which may be missing; each returns what it found wrong."""
    return _module("checks", kind)
