"""Dense spectral contraction kernels (``_dense_fwd_kernel``,
``_dense_bwd_x_kernel``, ``_dense_bwd_w_kernel``): a complex contraction
over one channel or batch axis, batched over the flattened modes M.

Call shapes (split-real pairs, so operands come as re, im):
  fwd    x (B, I, M), w (I, O, M)  -> y  (B, O, M)
  bwd_x  g (B, O, M), w (I, O, M)  -> dx (B, I, M)
  bwd_w  x (B, I, M), g (B, O, M)  -> dw (I, O, M)
Each is B*I*O*M complex multiply-adds, 8 real FLOPs apiece.  With a, b
the two operands and c the result, a*b*c/M = (B*I*O*M)^2 in all three.
"""
from __future__ import annotations

import math

from harness.kernels import io_bytes, size

PREFIX = "_dense_"


def flops(name, ins, outs) -> float:
    a, b, c = ins[0], ins[2], outs[0]
    m = a.shape[-1]
    return 8.0 * math.sqrt(size(a) * size(b) * size(c) / m)


def hbm_bytes(name, ins, outs) -> float:
    return io_bytes(ins, outs)
