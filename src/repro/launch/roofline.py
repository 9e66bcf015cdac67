"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch × mesh), in seconds:

    compute    = HLO_FLOPs            / (chips × peak_FLOP/s)
    memory     = HLO_bytes_accessed   / (chips × HBM_bw)
    collective = collective_bytes     / (chips × link_bw)

``compiled.cost_analysis()`` counts each while-loop body ONCE (verified in
tests/test_roofline.py: a lax.scan of length 8 reports exactly 1/8 of the
true FLOPs), which makes it useless for scan-over-layers models.  We
therefore parse the post-partitioning HLO text ourselves:

  * FLOPs: every ``dot`` op (2 · |out| · K, K from lhs_contracting_dims),
    accumulated through fusions/calls, and multiplied by while-loop trip
    counts extracted from each loop condition's comparison constant.
  * bytes: operand+output bytes of every materialising op at fusion
    granularity (fusion boundaries = HBM round-trips), same loop scaling.
  * collective bytes: output bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, same loop scaling.

All values are per-device (the HLO is the post-SPMD per-device program).

Hardware model: the TPU v5e entry of :data:`PEAKS` for compute and HBM,
and ~50 GB/s per ICI link.
"""
from __future__ import annotations

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB of HBM at 819 GB/s.  A device that is not in
#: the table has no peak: callers report no roofline share for it.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9},
}
V5E = "TPU v5 lite"

PEAK_FLOPS = PEAKS[V5E]["bf16_flops"]   # bf16 FLOP/s per chip
HBM_BW = PEAKS[V5E]["hbm_bw"]           # bytes/s per chip
ICI_BW = 50e9                           # bytes/s per link


from .hlo_parse import HLOCounts, parse_hlo  # noqa: F401  (re-export)
import dataclasses as _dc


@_dc.dataclass
class CollectiveStats:
    bytes_by_kind: dict

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


def collective_bytes(hlo: str) -> CollectiveStats:
    return CollectiveStats(parse_hlo(hlo).collective_by_kind)


@_dc.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    n_devices: int
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_dict(self) -> dict:
        return _dc.asdict(self) | {
            "dominant": self.dominant, "step_time_s": self.step_time_s}


def analyze_counts(counts: HLOCounts, n_devices: int) -> Roofline:
    return Roofline(
        flops_per_device=counts.flops,
        bytes_per_device=counts.bytes,
        collective_bytes_per_device=counts.collective_bytes,
        n_devices=n_devices,
        compute_s=counts.flops / PEAK_FLOPS,
        memory_s=counts.bytes / HBM_BW,
        collective_s=counts.collective_bytes / ICI_BW,
    )


def model_flops(n_params_active: float, tokens: float) -> float:
    """6·N·D napkin-math (per the assignment: N_active for MoE)."""
    return 6.0 * n_params_active * tokens


def spectral_kernel_vmem(B: int, I: int, O: int, modes, *, rank: int = 0,
                         l_shared: bool = False,
                         itemsize: int = 2, dtype: str = None) -> dict:
    """Tiling record for the Pallas spectral-contraction kernel at one
    dry-run cell: the budget-chosen tile and the fwd/bwd VMEM working
    sets it implies — dense when ``rank == 0``, CP otherwise, or the
    SFNO l-shared kernel when ``l_shared`` (then ``modes = (lmax, mmax)``
    and the tile runs over degrees).  The wrappers resolve the same
    ``pick_block_*`` choice at run time, so this record describes the
    tiling that actually executes.  When ``dtype`` is given and an
    active calibration state (``repro.tune``) holds a validated entry
    for the cell, the tuned fwd/bwd tiles replace the heuristic and the
    record says so via ``tile_source``.  Dry-runs attach it next to the
    roofline so a cell that would spill VMEM is visible without
    compiling for real hardware."""
    from repro.kernels.ops import (
        cp_vmem_bytes, lshared_vmem_bytes, pick_block_l, pick_block_m,
        vmem_bytes, vmem_bytes_bwd)
    from repro.kernels.spectral_contract import VMEM_BUDGET

    def _calibrated(family, shape):
        if dtype is None:
            return None
        from repro.tune.cache import active_cache

        cache = active_cache()
        if cache is None:
            return None
        ent = cache.lookup(family, shape, dtype)
        if ent is None:
            return None
        return int(ent["block_fwd"]), int(ent["block_bwd"])

    if l_shared:
        L, Mm = (int(m) for m in modes)
        tuned = _calibrated("lshared", (B, I, O, L, Mm))
        if tuned:
            bl, bl_bwd = tuned
        else:
            bl = bl_bwd = pick_block_l(B, I, O, L, Mm, itemsize=itemsize)
        fwd = lshared_vmem_bytes(B, I, O, Mm, bl, itemsize)
        bwd = lshared_vmem_bytes(B, I, O, Mm, bl_bwd, itemsize)
        tile, tile_bwd, n_tiled, kind = bl, bl_bwd, L, "l_shared"
    else:
        M = 1
        for m in modes:
            M *= int(m)
        shape = (B, I, O, rank, M) if rank else (B, I, O, M)
        tuned = _calibrated("cp" if rank else "dense", shape)
        if tuned:
            tile, tile_bwd = tuned
        else:
            tile = tile_bwd = pick_block_m(B, I, O, M, rank=rank,
                                           itemsize=itemsize)
        if rank:
            fwd = cp_vmem_bytes(B, I, O, rank, tile, itemsize)
            bwd = cp_vmem_bytes(B, I, O, rank, tile_bwd, itemsize)
        else:
            fwd = vmem_bytes(B, I, O, tile, itemsize)
            bwd = vmem_bytes_bwd(B, I, O, tile_bwd, itemsize)
        n_tiled, kind = M, ("cp" if rank else "dense")
    return {
        "kind": kind,
        "block": tile,
        "block_bwd": tile_bwd,
        "tiled_extent": n_tiled,
        "grid_steps": -(-n_tiled // tile),
        "rank": rank,
        "itemsize": itemsize,
        "dtype": dtype,
        "tile_source": "calibrated" if tuned else "heuristic",
        "vmem_fwd_bytes": fwd,
        "vmem_bwd_bytes": bwd,
        "fits_vmem": max(fwd, bwd) <= VMEM_BUDGET,
    }
