"""On-hardware measurement of tile candidates.

Each candidate is timed as the unit its config actually pins: one
``value_and_grad`` train step through the kernel family's custom VJP
(forward + both backward kernels on the candidate's fwd/bwd tiles),
median-of-k with warmup, ``block_until_ready`` around every sample.

On a TPU the kernels compile to Mosaic and the walls are real; on CPU
the same loop runs in interpret mode so CI can exercise the full tune →
validate → cache → resolve cycle (the cache marks such entries
``interpret: true`` — their GB/s figures rank candidates relative to
each other but are not hardware bandwidth).

Alongside wall time every measurement reports achieved GB/s (a
per-family bytes-moved model over the measured wall) and, for a compiled
run on a device whose ``device_kind`` has published peaks
(``launch.roofline.PEAKS``), the fraction of its HBM bandwidth that
represents; otherwise ``roofline_fraction`` is None.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.spectral_contract import (
    resolve_interpret,
    spectral_contract_cp_pallas,
    spectral_contract_lshared_pallas,
    spectral_contract_pallas,
)
from repro.launch.roofline import PEAKS
from .space import Candidate, family_itemsize


def bytes_moved(family: str, shape, dtype: str) -> int:
    """HBM traffic model for one train step (fwd + both backward
    kernels): every operand read and every output written once, re+im
    planes, at the family's streaming itemsize.  A model, not a
    measurement — good enough to rank candidates and to normalise walls
    into achieved GB/s."""
    itemsize = family_itemsize(family, dtype)
    if family in ("dense", "dense-fused"):
        B, I, O, M = shape
        fwd = (B * I + I * O + B * O) * M
        bwd = 2 * (B * I + I * O + B * O) * M
        elems = fwd + bwd
    elif family == "cp":
        B, I, O, R, M = shape
        factors = (I + O) * R + R * M
        fwd = (B * I + B * O) * M + factors
        bwd = (2 * B * I + 2 * B * O) * M + 2 * factors
        elems = fwd + bwd
    elif family == "lshared":
        B, I, O, L, Mm = shape
        fwd = (B * I + B * O) * L * Mm + I * O * L
        bwd = (2 * B * I + 2 * B * O) * L * Mm + 2 * I * O * L
        elems = fwd + bwd
    else:
        raise ValueError(f"unknown kernel family {family!r}")
    return int(elems) * 2 * itemsize


def make_operands(family: str, shape, dtype: str, seed: int = 0):
    """Seeded split-real operands for one family — the same arrays the
    oracle check rebuilds, so a validated entry was validated on the
    data it was timed on."""
    rng = np.random.RandomState(seed)
    op_dtype = jnp.float32 if family == "dense-fused" else jnp.dtype(dtype)

    def arr(*s):
        return jnp.asarray(0.5 * rng.randn(*s), jnp.float32).astype(op_dtype)

    if family in ("dense", "dense-fused"):
        B, I, O, M = shape
        return (arr(B, I, M), arr(B, I, M), arr(I, O, M), arr(I, O, M))
    if family == "cp":
        B, I, O, R, M = shape
        return (arr(B, I, M), arr(B, I, M), arr(I, R), arr(I, R),
                arr(O, R), arr(O, R), arr(R, M), arr(R, M))
    if family == "lshared":
        B, I, O, L, Mm = shape
        return (arr(B, I, L, Mm), arr(B, I, L, Mm),
                arr(I, O, L), arr(I, O, L))
    raise ValueError(f"unknown kernel family {family!r}")


def build_step(cand: Candidate, *, interpret: Optional[bool] = None):
    """The jitted value_and_grad train step a candidate is timed on."""
    interpret = resolve_interpret(interpret)
    family = cand.family
    if family in ("dense", "dense-fused"):
        kern = functools.partial(
            spectral_contract_pallas,
            block_m=cand.block_fwd, block_m_bwd=cand.block_bwd,
            interpret=interpret, out_dtype=jnp.dtype(cand.dtype),
            cast_to=jnp.dtype(cand.dtype) if family == "dense-fused"
            else None,
        )
    elif family == "cp":
        kern = functools.partial(
            spectral_contract_cp_pallas,
            block_m=cand.block_fwd, block_m_bwd=cand.block_bwd,
            interpret=interpret, out_dtype=jnp.dtype(cand.dtype),
        )
    elif family == "lshared":
        kern = functools.partial(
            spectral_contract_lshared_pallas,
            block_l=cand.block_fwd, block_l_bwd=cand.block_bwd,
            interpret=interpret, out_dtype=jnp.dtype(cand.dtype),
        )
    else:
        raise ValueError(f"unknown kernel family {family!r}")

    def loss(*ops):
        yr, yi = kern(*ops)
        return (jnp.sum(yr.astype(jnp.float32) ** 2)
                + jnp.sum(yi.astype(jnp.float32) ** 2))

    n = len(make_operands(family, cand.shape, cand.dtype))
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(n))))


def _wall_us(fn, args, iters: int, warmup: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(samples))


def measure(cand: Candidate, *, interpret: Optional[bool] = None,
            iters: int = 3, warmup: int = 1, seed: int = 0) -> dict:
    """Time one candidate; returns the perf fields of a cache entry."""
    interpret = resolve_interpret(interpret)
    step = build_step(cand, interpret=interpret)
    ops = make_operands(cand.family, cand.shape, cand.dtype, seed=seed)
    wall = _wall_us(step, ops, iters, warmup)
    moved = bytes_moved(cand.family, cand.shape, cand.dtype)
    gbps = moved / (wall * 1e-6) / 1e9 if wall > 0 else 0.0
    peaks = None if interpret else PEAKS.get(jax.devices()[0].device_kind)
    return {
        "wall_us": wall,
        "bytes_moved": moved,
        "gbps": round(gbps, 3),
        "roofline_fraction": (round(gbps * 1e9 / peaks["hbm_bw"], 6)
                              if peaks else None),
        "interpret": bool(interpret),
    }
