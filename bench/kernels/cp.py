"""CP-factorised spectral contraction kernels (``_cp_fwd_kernel``,
``_cp_bwd_kernel``; TFNO): project onto the rank, scale per mode, expand.

Call shapes (split-real pairs):
  fwd  x (B, I, M), Ui (I, R), Uo (O, R), W (R, M)          -> y (B, O, M)
  bwd  the same operands and g (B, O, M) -> dx, dUi, dUo, dW
Forward: 8*B*I*R*M (project) + 6*B*R*M (complex scale) + 8*B*O*R*M
(expand).  Backward, without the kernel's recomputation of the forward
stages: du and dUo 8*B*O*R*M each, dt 6*B*R*M, dW 8*B*R*M, dx and dUi
8*B*I*R*M each.
"""
from __future__ import annotations

from harness.kernels import io_bytes

PREFIX = "_cp_"


def flops(name, ins, outs) -> float:
    B, I, M = ins[0].shape
    O, R = ins[4].shape
    brm = B * R * M
    if len(ins) == 8:
        return 8.0 * brm * (I + O) + 6.0 * brm
    return 16.0 * brm * (I + O) + 14.0 * brm


def hbm_bytes(name, ins, outs) -> float:
    return io_bytes(ins, outs)
