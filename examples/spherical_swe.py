"""SFNO on the spherical shallow-water equations (paper's SWE protocol):
data generated on the fly each epoch by the in-repo spherical solver,
trained under the mixed-precision policy with tanh stabilisation.

    PYTHONPATH=src python examples/spherical_swe.py [--steps 20]
"""
import argparse

import jax

from repro.core import FULL, get_policy
from repro.data import sample_swe_batch
from repro.models import SFNOConfig, init_sfno, sfno_apply
from repro.optim import AdamW
from repro.train.losses import relative_l2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    cfg = SFNOConfig(in_channels=3, out_channels=3, hidden_channels=16,
                     n_layers=2, nlat=32, nlon=64, lmax=16, mmax=16,
                     lifting_channels=16, projection_channels=16)
    params = init_sfno(jax.random.PRNGKey(0), cfg)
    policy = get_policy("mixed_fno_bf16")
    opt = AdamW(lr=2e-3)
    state = opt.init(params)

    @jax.jit
    def step(p, s, x, y):
        def loss_fn(pp):
            return relative_l2(sfno_apply(pp, x, cfg, policy), y)
        loss, g = jax.value_and_grad(loss_fn)(p)
        p2, s2 = opt.update(g, s, p)
        return p2, s2, loss

    for i in range(args.steps):
        # on-the-fly data generation, as in the paper's SWE setup
        x, y = sample_swe_batch(jax.random.PRNGKey(100 + i), 32, 64, 4, steps=40)
        params, state, loss = step(params, state, x, y)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  rel-L2 {float(loss):.4f}")

    x, y = sample_swe_batch(jax.random.PRNGKey(999), 32, 64, 4, steps=40)
    e = float(relative_l2(sfno_apply(params, x, cfg, FULL), y))
    print(f"eval rel-L2 (fresh ICs): {e:.4f}")


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
