"""Search-space enumeration: legal tile candidates per kernel family.

Legality is defined by the same ``*vmem_bytes*`` estimators the static
heuristics (``pick_block_m`` / ``pick_block_l``) and the dry-run VMEM
reports use — a candidate the tuner may time is exactly a tile those
estimators price under the VMEM budget.  That shared vocabulary is what
lets ``repro.analyze``'s calibration-coverage check re-derive, offline,
that every cached tile was legal.

Forward and backward tiles are enumerated independently (their working
sets differ — the dense backward holds two gradient kernels' worth of
tiles), then crossed: the autotuner times each (block_fwd, block_bwd)
pair as one train step, because that is the unit the custom-VJP config
actually pins.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import List, Optional, Sequence, Tuple

from repro.kernels.spectral_contract import (
    VMEM_BUDGET,
    cp_vmem_bytes,
    lshared_vmem_bytes,
    vmem_bytes,
    vmem_bytes_bwd,
)

#: the block ladders the heuristics walk — the tuner searches the same
#: rungs so a calibrated tile is always one the heuristic *could* have
#: picked (just not necessarily the one it would)
BLOCKS_M = (512, 256, 128, 64, 32, 16, 8)
BLOCKS_L = (256, 128, 64, 32, 16, 8, 4, 2, 1)

#: same headroom the heuristics leave: half the physical VMEM
DEFAULT_BUDGET = VMEM_BUDGET // 2


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One (family, shape, dtype, fwd/bwd tile) point of the search."""

    family: str            # dense | dense-fused | cp | lshared
    shape: Tuple[int, ...]  # dense: (B,I,O,M)  cp: (B,I,O,R,M)
                            # lshared: (B,I,O,L,Mm)
    dtype: str             # storage dtype name, e.g. "bfloat16"
    block_fwd: int
    block_bwd: int


def family_itemsize(family: str, dtype: str) -> int:
    """Bytes/element the family's tiles stream: the storage dtype's —
    except the cast-fusing family (dense-fused), which streams f32
    operands and quantises on tiles in VMEM."""
    import jax.numpy as jnp

    if family == "dense-fused":
        return 4
    return jnp.dtype(dtype).itemsize


def tile_vmem_bytes(family: str, shape: Sequence[int], block: int,
                    itemsize: int, direction: str) -> int:
    """Price one tile with the family's estimator (the coverage check's
    workhorse).  ``direction``: "fwd" | "bwd"."""
    if family in ("dense", "dense-fused"):
        B, I, O, _M = shape
        if direction == "fwd":
            return vmem_bytes(B, I, O, block, itemsize)
        return vmem_bytes_bwd(B, I, O, block, itemsize)
    if family == "cp":
        B, I, O, R, _M = shape
        # one estimator for both directions: the CP backward dominates
        # and cp_vmem_bytes already prices it
        return cp_vmem_bytes(B, I, O, R, block, itemsize)
    if family == "lshared":
        B, I, O, _L, Mm = shape
        return lshared_vmem_bytes(B, I, O, Mm, block, itemsize)
    raise ValueError(f"unknown kernel family {family!r}")


def _tiled_extent(family: str, shape: Sequence[int]) -> int:
    """The axis length the family tiles over (M for mode-tiled kernels,
    L for the l-shared one)."""
    if family == "lshared":
        return int(shape[3])
    return int(shape[-1])


def legal_blocks(family: str, shape: Sequence[int], dtype: str,
                 direction: str, *,
                 budget: int = DEFAULT_BUDGET) -> List[int]:
    """Every ladder rung that (a) does not exceed the tiled extent by
    more than the heuristic's own floor allows and (b) fits the family's
    VMEM estimate under ``budget``."""
    itemsize = family_itemsize(family, dtype)
    extent = _tiled_extent(family, shape)
    if family == "lshared":
        ladder, floor = BLOCKS_L, 1
    else:
        ladder, floor = BLOCKS_M, 8
    out = []
    for b in ladder:
        if b > max(extent, floor):
            continue
        if tile_vmem_bytes(family, shape, b, itemsize, direction) <= budget:
            out.append(b)
    if not out:
        out = [floor]  # the heuristics' own last resort
    return out


def candidates(family: str, shape: Sequence[int], dtype: str, *,
               budget: int = DEFAULT_BUDGET,
               limit: Optional[int] = None) -> List[Candidate]:
    """The (block_fwd × block_bwd) cross of legal tiles for one key.

    ``limit`` caps the cross for smoke runs: pairs are ordered
    largest-tile-first (the heuristic's own preference), so a truncated
    search still covers the region the heuristic lives in plus its
    neighbours.
    """
    fwd = legal_blocks(family, shape, dtype, "fwd", budget=budget)
    bwd = legal_blocks(family, shape, dtype, "bwd", budget=budget)
    pairs = list(itertools.product(fwd, bwd))
    if limit is not None:
        pairs = pairs[:max(1, int(limit))]
    return [
        Candidate(family=family, shape=tuple(int(s) for s in shape),
                  dtype=dtype, block_fwd=f, block_bwd=b)
        for f, b in pairs
    ]
