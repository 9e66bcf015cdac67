"""The manifest resolves, by name alone, to the files that hold each
cell's configuration, traffic, limits and per-layer readers, and a cell
added as new files is found without editing any file that exists."""
from __future__ import annotations

import importlib.util
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import kernels, registry  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
MANIFEST = registry.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves(workload):
    cell = registry.cell(workload)
    cfg, traffic = cell["config"], cell["traffic"]
    harness = BENCH / "harness" / f"{traffic['kind']}.py"
    assert harness.is_file(), "the traffic's kind names its harness"
    for kind in ("models", "reference", "datasets"):
        key = "data" if kind == "datasets" else "family"
        assert registry.path(kind, cfg[key], ".py").is_file()
    assert cell["limits"], "a cell compares at least one number"
    for spec in cell["limits"].values():
        assert spec["limit"] > 0
    reported = {m["name"] for m in registry.end_to_end(workload)}
    assert "setup_s" in reported and len(reported) >= 2
    layer = registry.per_layer(workload)
    assert layer, "every cell reports a per-layer metric"
    for m in layer:
        assert callable(registry.load_module("metrics", m["name"]).read)


def test_manifest_shape():
    bench = MANIFEST
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in bench["configs"]] + CELLS + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", CELLS):
            assert w in e2e[m["moves"]].get("workloads", CELLS)
    for c in bench["configs"]:
        assert (registry.ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        with open(registry.ROOT / c["file"]) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_every_kernel_family_loads():
    fams = kernels.families()
    assert set(fams) == {"cp", "dense", "fused", "lshared"}
    prefixes = [m.PREFIX for m in fams.values()]
    assert len(set(prefixes)) == len(prefixes)


def test_added_cell_needs_no_edit(tmp_path):
    """A copy of the benchmark with one more configuration, traffic mix,
    limits file and manifest entry: the copy's registry finds them."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((BENCH / "configs" / "fno_darcy.json").read_text())
    cfg.update(hidden_channels=32, assumed={"batch": 4,
                                            "dataset_samples": 8})
    (root / "bench" / "configs" / "fno_small.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((BENCH / "traffic" / "serve_saturated.json")
                         .read_text())
    traffic["rate_per_s"] = 10
    (root / "bench" / "traffic" / "serve_slow.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "limits" / "fno_small.serve_slow.json").write_text(
        json.dumps({"field_gap": {"limit": 0.1}}))
    bench = json.loads(json.dumps(MANIFEST))
    bench["configs"].append({"name": "fno_small", "source": "x",
                             "file": "bench/configs/fno_small.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "fno_small.serve_slow",
                               "config": "fno_small",
                               "traffic": "serve_slow", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "fno_darcy.serve_saturated" in m.get("workloads", []):
            m["workloads"].append("fno_small.serve_slow")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = importlib.util.spec_from_file_location(
        "copied_registry", root / "bench" / "harness" / "registry.py")
    copied = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copied)
    cell = copied.cell("fno_small.serve_slow")
    assert cell["config"]["hidden_channels"] == 32
    assert cell["traffic"]["rate_per_s"] == 10
    assert cell["limits"]["field_gap"]["limit"] == 0.1
    names = {m["name"] for m in copied.per_layer("fno_small.serve_slow")}
    assert "batch_fill.serve" in names
