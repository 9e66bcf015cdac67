"""The spherical FNO family: the program's entry points and configuration
type, the plain reference beside them, and the model FLOPs per sample.

FLOP convention as in ``models/fno.py``; the Legendre transforms are real
matrices applied to complex spectra, 4 FLOPs per multiply-add, over the
whole (order, degree, latitude) product.
"""
from __future__ import annotations

import dataclasses
import math

from reference import sfno as reference  # noqa: F401  (the family's reference)

ENGINE_MODEL = "sfno"

#: leaves that change_gap reads per index of one axis as well as whole:
#: the spectral weights, per degree l (``harness/check.py``)
SUBLEAF_AXES = {"spectral": -1}


def program_config(cfg: dict):
    from repro.models import SFNOConfig

    fields = {f.name for f in dataclasses.fields(SFNOConfig)}
    return SFNOConfig(**{k: v for k, v in cfg.items() if k in fields})


def apply(params, x, pcfg, policy):
    from repro.models import sfno_apply

    return sfno_apply(params, x, pcfg, policy)


def forward_flops(cfg: dict) -> float:
    nlat, nlon = cfg["nlat"], cfg["nlon"]
    S = nlat * nlon
    h, L, M = cfg["hidden_channels"], cfg["lmax"], cfg["mmax"]
    lift, proj = cfg["lifting_channels"], cfg["projection_channels"]
    dense = 2.0 * S * (cfg["in_channels"] * lift + lift * h + h * proj
                       + proj * cfg["out_channels"])
    layer = (2.0 * S * h * h                          # skip
             + 2 * 4.0 * h * M * L * nlat             # Legendre, both ways
             + 2 * h * nlat * 2.5 * nlon * math.log2(nlon)  # longitude FFTs
             + 8.0 * h * h * L * M)                   # spectral contraction
    return dense + cfg["n_layers"] * layer
