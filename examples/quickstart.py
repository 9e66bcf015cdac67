"""Quickstart: the paper's mixed-precision FNO in ~40 lines.

Builds a small FNO, runs it under the full-precision and mixed-precision
policies, shows the memory-greedy contraction and the tanh stabiliser in
action, and verifies Theorem 3.1/3.2 empirically.

    PYTHONPATH=src python examples/quickstart.py
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    FULL, get_policy, greedy_path, path_intermediate_bytes, theory,
)
from repro.launch import compile_cache
from repro.models import FNOConfig, fno_apply, init_fno

compile_cache.place(Path(__file__).resolve().parents[1])

# 1. a small FNO
cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=32,
                lifting_channels=32, projection_channels=32,
                n_layers=4, modes=(12, 12))
params = init_fno(jax.random.PRNGKey(0), cfg)
x = jnp.asarray(np.random.RandomState(0).randn(4, 1, 64, 64), jnp.float32)

# 2. full vs mixed precision forward
y_full = fno_apply(params, x, cfg, FULL)
y_mixed = fno_apply(params, x, cfg, get_policy("mixed_fno_bf16"))
rel = float(jnp.linalg.norm(y_mixed.astype(jnp.float32) - y_full)
            / jnp.linalg.norm(y_full))
print(f"mixed-vs-full relative error: {rel:.4f}  (paper: <1%)")

# 3. the memory-greedy contraction order (paper §4.2 / Table 10)
expr = "bixy,r,ir,or,xr,yr->boxy"   # TFNO CP contraction
shapes = [(4, 32, 12, 12), (16,), (32, 16), (32, 16), (12, 16), (12, 16)]
p_mem = greedy_path(expr, shapes, "memory")
p_flop = greedy_path(expr, shapes, "flops")
print(f"greedy-memory path {p_mem}: peak intermediate "
      f"{path_intermediate_bytes(expr, shapes, p_mem)} B vs FLOP-optimal "
      f"{path_intermediate_bytes(expr, shapes, p_flop)} B")

# 4. theory: precision error is dominated by discretisation error
v = lambda xs: np.sin(2 * np.pi * xs[..., 0]) + 0.5 * np.prod(xs, axis=-1)
disc = theory.disc_error(v, m=64, d=2, omega=1.0)
prec = theory.prec_error(v, m=64, d=2, omega=1.0, dtype="float16")
print(f"disc error {disc:.2e} vs fp16 precision error {prec:.2e} "
      f"-> half precision is 'free' (Thm 3.1/3.2)")
print(f"3-D crossover mesh size for fp16: "
      f"{theory.crossover_mesh_size(1e-4, 3):.2e} points (paper: ~1e6)")
