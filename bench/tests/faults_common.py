"""Shared by the fault tests: a cell cut to what a CPU test holds, and a
benchmark run of it that skips the look for a chip."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import registry  # noqa: E402

SEED = 2 ** 32 + 17
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def small(workload: str) -> dict:
    """The cell with its widths cut to what a CPU test holds; traffic,
    precision and limits as committed."""
    cell = registry.cell(workload)
    cfg = cell["config"]
    if cfg["family"] == "fno":
        cfg.update(hidden_channels=8, lifting_channels=16,
                   projection_channels=16, n_layers=2, modes=[4, 4],
                   grid=[16, 16], assumed={"batch": 4,
                                           "dataset_samples": 16})
    else:
        cfg.update(hidden_channels=8, lifting_channels=8,
                   projection_channels=8, n_layers=2, nlat=16, nlon=32,
                   lmax=8, mmax=8, assumed={"batch": 4,
                                            "dataset_samples": 8})
    if cell["traffic"]["kind"] == "serve":
        cell["traffic"].update(rate_per_s=40, pool=16, check_sample=8)
    return cell


def run_cell(cell: dict) -> dict:
    """Set-up, a one-second window and the comparison, as bench/run.py
    runs them after its chip check."""
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.measure_cell(cell, SEED, 1.0, False, 1, PEAKS)


def caught_by(broken: dict, sound: dict, limits: dict) -> list:
    """The compared numbers that a sound run at this size keeps within
    their limits and the broken run exceeds."""
    return [k for k, spec in limits.items()
            if sound[k] <= spec["limit"] < broken[k]]


def workloads(kind: str) -> list:
    return [w["name"] for w in registry.manifest()["workloads"]
            if registry.cell(w["name"])["traffic"]["kind"] == kind]
