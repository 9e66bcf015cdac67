"""Order-shared spherical contraction kernels (``_lshared_fwd_kernel``,
``_lshared_bwd_x_kernel``, ``_lshared_bwd_w_kernel``): SFNO's per-degree
channel mixing, the weight shared over the orders m.

Call shapes (split-real pairs):
  fwd    x (B, I, L, M), w (I, O, L)     -> y  (B, O, L, M)
  bwd_x  g (B, O, L, M), w (I, O, L)     -> dx (B, I, L, M)
  bwd_w  x (B, I, L, M), g (B, O, L, M)  -> dw (I, O, L)
Each is B*I*O*L*M complex multiply-adds, 8 real FLOPs apiece; with a, b
the operands and c the result, a*b*c/L = (B*I*O*L*M)^2 in all three.
"""
from __future__ import annotations

import math

from harness.kernels import io_bytes, size

PREFIX = "_lshared_"


def flops(name, ins, outs) -> float:
    a, b, c = ins[0], ins[2], outs[0]
    L = a.shape[2]
    return 8.0 * math.sqrt(size(a) * size(b) * size(c) / L)


def hbm_bytes(name, ins, outs) -> float:
    return io_bytes(ins, outs)
