"""Darcy flow, -div(a grad u) = 1 on the unit square (Li et al. 2021):
piecewise-constant coefficients a in {3, 12} from the sign of a Gaussian
random field, whitened to {-1, +1} as the FNO literature does.

Training pairs come from the program's own solver (``repro.data``, the
repository's Darcy protocol) and are held on the host.  Serving needs
coefficient fields only; they are drawn here on the host with numpy.
"""
from __future__ import annotations

import numpy as np

#: fields the program's solver handles at once while the pool is made
CHUNK = 256


def training_pool(seed: int, cfg: dict, n: int) -> dict:
    import jax

    from reference.common import seed_key
    from repro.data import sample_darcy_batch

    grid = cfg["grid"][0]
    key = jax.random.fold_in(seed_key(seed), 11)
    xs, ys = [], []
    for i in range(0, n, CHUNK):
        a, u = sample_darcy_batch(jax.random.fold_in(key, i), grid,
                                  min(CHUNK, n - i))
        xs.append(np.asarray(a))
        ys.append(np.asarray(u))
    return {"x": np.concatenate(xs), "y": np.concatenate(ys)}


def serving_fields(rng: np.random.Generator, cfg: dict, n: int,
                   grid=None) -> np.ndarray:
    """n coefficient fields (n, 1, S, S), float32, values in {-1, +1}:
    the sign of a GRF with covariance (-Laplacian + 9)^-2 on the torus.
    ``grid`` defaults to the configuration's."""
    S = (grid or cfg["grid"])[0]
    k = np.fft.fftfreq(S, d=1.0 / S)
    k2 = (k[:, None] ** 2 + k[None, :] ** 2) * (2 * np.pi) ** 2
    sqrt_eig = (k2 + 9.0) ** -1.0
    sqrt_eig[0, 0] = 0.0
    noise = rng.standard_normal((n, S, S)) + 1j * rng.standard_normal((n, S, S))
    g = np.fft.ifft2(noise * sqrt_eig, axes=(-2, -1)).real
    return np.where(g > 0, 1.0, -1.0).astype(np.float32)[:, None]
