"""The FNO family (dense spectral weights): the program's entry points
and configuration type, the plain reference beside them, and the model
FLOPs per sample.

FLOP convention (forward, per sample; training counts three times the
forward): a real multiply-add is 2 FLOPs, a complex one 8; an FFT of N
real points 2.5 * N * log2(N); elementwise work is not counted.
"""
from __future__ import annotations

import dataclasses
import math

from reference import fno as reference  # noqa: F401  (the family's reference)

ENGINE_MODEL = "fno"


def program_config(cfg: dict):
    from repro.models import FNOConfig

    fields = {f.name for f in dataclasses.fields(FNOConfig)}
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in cfg.items() if k in fields}
    return FNOConfig(**kw)


def apply(params, x, pcfg, policy):
    from repro.models import fno_apply

    return fno_apply(params, x, pcfg, policy)


def forward_flops(cfg: dict) -> float:
    S = math.prod(cfg["grid"])
    h = cfg["hidden_channels"]
    lift, proj = cfg["lifting_channels"], cfg["projection_channels"]
    in_ch = cfg["in_channels"] + len(cfg["grid"])
    dense = 2.0 * S * (in_ch * lift + lift * h + h * proj
                       + proj * cfg["out_channels"])
    kept = 2 ** (len(cfg["modes"]) - 1) * math.prod(cfg["modes"])
    layer = (2.0 * S * h * h                      # skip
             + 8.0 * h * h * kept                 # spectral contraction
             + 2 * h * 2.5 * S * math.log2(S))    # rFFT and irFFT
    return dense + cfg["n_layers"] * layer
