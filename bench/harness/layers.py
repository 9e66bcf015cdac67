"""What a per-layer metric reader is given, and the loop over readers.

A reader is ``bench/metrics/<name>.py`` with ``read(run) -> float | None``.
It returns None where the run holds nothing for it to read, and the
metric is then left out of the result line; it never returns 0 for a
share of a roofline or a peak.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from . import registry


def is_kernel(name: str) -> bool:
    """A Pallas kernel: a custom call to the TPU's Mosaic target (the
    trace names it after the jitted wrapper that made it)."""
    return name.endswith("custom-call:tpu_custom_call")


@dataclasses.dataclass
class Run:
    kind: str                     # "train" or "serve"
    red: object                   # trace.Reduction of the window
    window: dict                  # what the window measured
    flops_per_sample: float       # model FLOPs, forward and backward
    peaks: dict                   # the chip's published peaks
    kernel_calls: Optional[list] = None   # harness.kernels.Call per step


def read(metrics: List[dict], run: Run) -> dict:
    out = {}
    for m in metrics:
        v = registry.load_module("metrics", m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
