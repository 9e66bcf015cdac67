"""Pallas TPU RMSNorm kernel (memory-bound substrate op for the LM pool).

RMSNorm is bandwidth-bound: one read + one write of the activation, so the
kernel's job is simply to keep the row tile resident in VMEM and fuse the
reduction with the scale — XLA does this well already, but the Pallas
version pins the block layout (rows × full feature dim) so fusion survives
surrounding sharding constraints, and demonstrates the reduction-in-f32 /
storage-in-half recipe that the paper's AMP blocklist mandates for
normalisation ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .spectral_contract import resolve_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # (TR, D) — reduce in f32 (AMP rule)
    w = w_ref[...].astype(jnp.float32)  # (D,)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w[None, :]
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "eps", "interpret"))
def rmsnorm(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """x: (N, D) rows to normalise; w: (D,) scale. Returns (N, D).

    ``interpret``: None resolves from the backend (compiled on a TPU).
    """
    N, D = x.shape
    pad = (-N) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    Np = N + pad
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(Np // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda r: (r, 0)),
            pl.BlockSpec((D,), lambda _r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, D), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, w)
    return out[:N]
