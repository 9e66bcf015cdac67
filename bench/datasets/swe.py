"""Linearised rotating shallow water on the sphere (the repository's SWE
protocol, ``repro.data.swe``): random smooth geopotential fields mapped
to (phi, u, v) some hours later, on the Gauss-Legendre grid.  The pool is
made and held on the device."""
from __future__ import annotations


def training_pool(seed: int, cfg: dict, n: int) -> dict:
    import jax

    from reference.common import seed_key
    from repro.data import sample_swe_batch

    key = jax.random.fold_in(seed_key(seed), 11)
    x, y = sample_swe_batch(key, cfg["nlat"], cfg["nlon"], n)
    return {"x": x, "y": y}
