"""Pallas TPU flash attention (blocked online-softmax).

Not a paper contribution — it is the substrate kernel the LM-architecture
pool (prefill_32k cells) needs so that 32k-token attention has an O(seq)
memory footprint instead of materialising the (S×S) score matrix.

Grid: (batch·heads, q_blocks, kv_blocks); the kv axis is the minor-most
(sequential on TPU), so VMEM scratch accumulators carry the running
max / normaliser / weighted sum across kv steps (FlashAttention-2 schedule
adapted to the TPU sequential-grid model).

VMEM per step: (block_q + 2·block_k)·D half words + block_q·(D+2) f32
scratch — D=128, blocks=128 is ~180 KiB, far under the ~16 MiB budget, so
block sizes can grow to 512 on real hardware (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spectral_contract import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, block_q, block_k, kv_len,
):
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # (TQ, D)
    k = k_ref[0]  # (TK, D)
    v = v_ref[0]  # (TK, D)

    s = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * scale
    )  # (TQ, TK)

    # always mask kv padding beyond the true length
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)
    if causal:
        qi = pl.program_id(1)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[...] = m_new

    @pl.when(kb == nkb - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q/k/v: (BH, S, D) — batch·heads pre-flattened. Returns (BH, S, D).

    ``interpret``: None resolves from the backend (compiled on a TPU).
    """
    BH, S, D = q.shape
    Sk = k.shape[1]
    assert k.shape == (BH, Sk, D) and v.shape == (BH, Sk, D), (q.shape, k.shape, v.shape)
    scale = 1.0 / (D ** 0.5)

    pad_q = (-S) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded kv columns masked out via the causal/k_pos mask below when
        # causal; for non-causal, pad keys with NEG_INF scores via zero keys
        # and rely on softmax normaliser (zeros add exp(-inf)≈0 after mask).
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    Sp, Skp = S + pad_q, Sk + pad_k

    grid = (BH, Sp // block_q, Skp // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=Sk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, _kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, _qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, _qi, kb: (bh, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, _kb: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out[:, :S, :]
