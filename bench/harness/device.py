"""The chip the run is on, checked against the benchmark's own table of
published peaks (``bench/peaks.json``, keyed by ``device_kind``).  A
device that is not in the table, or a backend that is not a TPU, is an
error: no number is ever reported from another device."""
from __future__ import annotations

import json

from .registry import BENCH


class NoChip(RuntimeError):
    pass


def peaks_table() -> dict:
    with open(BENCH / "peaks.json") as f:
        return json.load(f)["devices"]


def check(chips: int) -> dict:
    """The device description of a run on ``chips`` TPU chips, with the
    chip's peaks; raises ``NoChip`` otherwise."""
    import jax

    devs = jax.devices()
    kind, platform = devs[0].device_kind, devs[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {platform!r})")
    table = peaks_table()
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json "
                     f"(have {sorted(table)})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": platform, "kind": kind, "count": chips,
            "peaks": table[kind]}


def memory_stats(chips: int) -> list:
    """The allocator's counts of each chip the run used (empty where the
    backend keeps none, as the CPU's does not)."""
    import jax

    return [d.memory_stats() or {} for d in jax.devices()[:chips]]


def memory_peak_bytes(stats: list) -> int:
    """``peak_bytes_in_use`` of the fullest chip: the buffers the process
    allocated (parameters, optimizer state, data, outputs)."""
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def hbm_peak_bytes(stats: list) -> int:
    """What the process held at its peak on the fullest chip: its
    buffers and the scratch the TPU runtime reserved for the compiled
    programs' temporaries, which ``peak_bytes_in_use`` leaves out."""
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)
