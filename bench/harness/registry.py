"""Finds what the manifest names: every configuration, traffic mix,
per-layer metric, kernel family, model family and set of limits is a
file of its own under ``bench/``, located by its name alone, so adding
one never edits a file that exists.

  configs/<config>.json        sizes, source, assumed, reduced, family
  traffic/<traffic>.json       parameters of the general generator
                               (``harness/traffic.py``); its ``kind``
                               names the harness that runs the cell,
                               ``harness/<kind>.py``
  limits/<workload>.json       the correctness limits of one cell
  metrics/<metric>.py          reader of one per-layer metric
  kernels/<family>.py          FLOPs and bytes of one kernel family
  models/<family>.py           the program's entry points for a family
  reference/<family>.py        the plain reference of that family
  datasets/<data>.py           the inputs a configuration's cells use
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"


def manifest(path: Path = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def path(kind: str, name: str, ext: str) -> Path:
    p = BENCH / kind / f"{name}{ext}"
    if not p.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {p}")
    return p


def load_json(kind: str, name: str) -> dict:
    with open(path(kind, name, ".json")) as f:
        return json.load(f)


def names(kind: str, ext: str) -> List[str]:
    d = BENCH / kind
    return sorted(p.name[: -len(ext)] for p in d.glob(f"*{ext}")
                  if not p.name.startswith("_"))


_MODULES: dict = {}


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (metric names hold dots,
    which an import statement cannot spell)."""
    p = path(kind, name, ".py")
    key = str(p)
    if key not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_')}", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def cell(workload: str, bench: dict = None) -> dict:
    """The workload entry with its configuration and traffic files read."""
    bench = manifest() if bench is None else bench
    w = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if w is None:
        have = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"unknown workload {workload!r}; have {have}")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(ROOT / conf["file"]) as f:
        config = json.load(f)
    return {"workload": w, "config": config,
            "traffic": load_json("traffic", w["traffic"]),
            "limits": load_json("limits", workload)}


def per_layer(workload: str, bench: dict = None) -> List[dict]:
    """The per-layer metrics a traced run of ``workload`` reports."""
    bench = manifest() if bench is None else bench
    reported = {m["name"] for m in end_to_end(workload, bench)}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in reported]


def end_to_end(workload: str, bench: dict = None) -> List[dict]:
    bench = manifest() if bench is None else bench
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]
