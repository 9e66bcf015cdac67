"""GINO on synthetic Shape-Net-Car-like CFD (the paper's irregular-geometry
setting): GNO encoder -> latent 3-D mixed-precision FNO -> GNO decoder,
predicting surface pressure from geometry.

    PYTHONPATH=src python examples/gino_car_cfd.py [--steps 15]
"""
import argparse

import jax
import jax.numpy as jnp

from repro.core import FULL, get_policy
from repro.data import sample_car_batch
from repro.models import GINOConfig, gino_apply, init_gino
from repro.models.fno import FNOConfig
from repro.optim import AdamW
from repro.train.losses import relative_l2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=15)
    args = ap.parse_args()

    cfg = GINOConfig(
        hidden=16, latent_grid=6, k_neighbors=6,
        fno=FNOConfig(in_channels=16, out_channels=16, hidden_channels=16,
                      lifting_channels=16, projection_channels=16,
                      n_layers=2, modes=(3, 3, 3), positional_embedding=False),
    )
    params = init_gino(jax.random.PRNGKey(0), cfg)
    policy = get_policy("mixed_fno_bf16")
    opt = AdamW(lr=2e-3)
    state = opt.init(params)

    def to_jnp(d):
        return {k: jnp.asarray(v) for k, v in d.items()}

    @jax.jit
    def step(p, s, batch, labels):
        def loss_fn(pp):
            pred = gino_apply(pp, batch, cfg, policy)
            return relative_l2(pred, labels)
        loss, g = jax.value_and_grad(loss_fn)(p)
        p2, s2 = opt.update(g, s, p)
        return p2, s2, loss

    for i in range(args.steps):
        batch, labels = sample_car_batch(
            seed=i, batch=4, n_points=128, latent_grid=cfg.latent_grid,
            k=cfg.k_neighbors)
        params, state, loss = step(params, state, to_jnp(batch), jnp.asarray(labels))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  rel-L2 {float(loss):.4f}")

    batch, labels = sample_car_batch(seed=999, batch=4, n_points=128,
                                     latent_grid=cfg.latent_grid, k=cfg.k_neighbors)
    pred = gino_apply(params, to_jnp(batch), cfg, FULL)
    e = float(relative_l2(pred, jnp.asarray(labels)))
    print(f"eval rel-L2 on fresh geometries: {e:.4f}")


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
