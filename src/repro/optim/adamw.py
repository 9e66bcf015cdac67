"""AdamW with f32 master weights, built from scratch (no optax on box).

Mixed-precision contract (Micikevicius et al. 2017, the substrate the
paper's recipe sits on): parameters and optimizer moments stay f32;
gradients arrive possibly in half (after the compressed DP all-reduce,
``optim.grad_comm``) and are upcast before the moment update.

ZeRO-style state sharding: the moment tensors inherit the parameters'
NamedSharding but can additionally be sharded over the ``data`` axis via
``dist.sharding.zero_shard_rules`` — wired up in launch/dryrun.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    count: jnp.ndarray  # scalar int32
    mu: Any             # first moments (pytree like params)
    nu: Any             # second moments


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 1e-4
    grad_clip_norm: Optional[float] = 1.0

    def init(self, params) -> AdamWState:
        zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return AdamWState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params),
        )

    def update(self, grads, state: AdamWState, params, lr_scale=1.0):
        """Returns (new_params, new_state).  grads may be half precision.

        The operations carry the ``optim/adamw`` scope into the compiled
        program, where a device trace can tell the optimizer's time."""
        with jax.named_scope("optim/adamw"):
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            if self.grad_clip_norm is not None:
                gnorm = global_norm(grads)
                scale = jnp.minimum(1.0, self.grad_clip_norm / (gnorm + 1e-9))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

            count = state.count + 1
            b1, b2 = self.b1, self.b2
            mu = jax.tree_util.tree_map(
                lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads
            )
            nu = jax.tree_util.tree_map(
                lambda v, g: b2 * v + (1 - b2) * (g * g), state.nu, grads
            )
            c = count.astype(jnp.float32)
            mu_hat_scale = 1.0 / (1 - b1 ** c)
            nu_hat_scale = 1.0 / (1 - b2 ** c)
            lr = self.lr * lr_scale

            def step(p, m, v):
                upd = (m * mu_hat_scale) / (jnp.sqrt(v * nu_hat_scale) + self.eps)
                return (p - lr * (upd + self.weight_decay * p)).astype(p.dtype)

            new_params = jax.tree_util.tree_map(step, params, mu, nu)
            return new_params, AdamWState(count=count, mu=mu, nu=nu)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def all_finite(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.all(
        jnp.stack([jnp.all(jnp.isfinite(x.astype(jnp.float32))) for x in leaves])
    )
