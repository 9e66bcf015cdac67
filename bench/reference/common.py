"""Pieces shared by the plain references: seeded initialisation, the
dense layers, the storage rounding of the low-precision control, the
relative-L2 loss and AdamW.

Nothing here imports the program under test.  Every reference function
takes a ``q`` rounding function that every contraction operand and every
spectrum passes through: the identity for the float32 reference, and
``fp8_rounding`` for the control, which computes the same mathematics
with its operands stored one format below bfloat16.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (seeds may exceed 32 bits):
    the low 32 bits make the key, the high bits are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def identity(x):
    return x


def _e4m3(x):
    """``x`` on the float8_e4m3fn grid with one scale per tensor (largest
    magnitude mapped to the format's largest finite value, as fp8
    training recipes do), at its own dtype.  Complex values round their
    real and imaginary parts."""
    if jnp.iscomplexobj(x):
        return jax.lax.complex(_e4m3(jnp.real(x)), _e4m3(jnp.imag(x)))
    f8 = jnp.float8_e4m3fn
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(f8).max), 1.0)
    y = (x.astype(jnp.float32) / scale).astype(f8).astype(jnp.float32)
    return (y * scale).astype(x.dtype)


@jax.custom_vjp
def fp8_rounding(x):
    """``x`` stored as fp8 (e4m3, one scale per tensor).  The backward
    pass stores the cotangent the same way, with a scale of its own, so
    that gradients too are computed one format below bfloat16 (and not
    flushed to zero on the forward's scale)."""
    return _e4m3(x)


def _fp8_fwd(x):
    return _e4m3(x), None


def _fp8_bwd(_, g):
    return (_e4m3(g),)


fp8_rounding.defvjp(_fp8_fwd, _fp8_bwd)


ROUNDINGS = {"float32": identity, "fp8_e4m3": fp8_rounding}


def linear_init(key, d_in: int, d_out: int) -> dict:
    """Weights N(0, 1/d_in), zero bias."""
    return {"w": (1.0 / d_in) ** 0.5 * jax.random.normal(
        key, (d_in, d_out), jnp.float32),
        "b": jnp.zeros((d_out,), jnp.float32)}


def stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def linear(p, x, q):
    """Channel-last dense layer ``x @ w + b``."""
    return jnp.einsum("...i,io->...o", q(x), q(p["w"])) + p["b"]


def gelu(x):
    # the tanh approximation, as the program under test uses it
    return jax.nn.gelu(x, approximate=True)


def relative_l2_per_sample(pred, target, eps: float = 1e-8):
    """||pred - target|| / ||target|| for each sample of the batch."""
    axes = tuple(range(1, pred.ndim))
    num = jnp.sqrt(jnp.sum((pred - target) ** 2, axis=axes))
    den = jnp.sqrt(jnp.sum(target ** 2, axis=axes)) + eps
    return num / den


@dataclasses.dataclass(frozen=True)
class AdamWHyper:
    """AdamW with global-norm gradient clipping: the optimizer the
    training cells configure, written out plainly."""

    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 1e-4
    clip_norm: float = 1.0


def adamw_init(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"count": 0, "mu": z, "nu": z}


def clip_grads(grads, clip_norm: float):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def adamw_update(hp: AdamWHyper, grads, state, params):
    """One AdamW step on already-clipped gradients."""
    count = state["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: hp.b1 * m + (1 - hp.b1) * g,
                                state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: hp.b2 * v + (1 - hp.b2) * g * g,
                                state["nu"], grads)
    mh = 1.0 / (1 - hp.b1 ** count)
    vh = 1.0 / (1 - hp.b2 ** count)

    def step(p, m, v):
        upd = (m * mh) / (jnp.sqrt(v * vh) + hp.eps)
        return p - hp.lr * (upd + hp.weight_decay * p)

    new = jax.tree_util.tree_map(step, params, mu, nu)
    return new, {"count": count, "mu": mu, "nu": nu}
