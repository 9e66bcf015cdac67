"""The fused rFFT -> contraction -> irFFT kernels (``_fused_fwd_kernel``,
``_fused_bwd_kernel``): the transforms as truncated-DFT factor matmuls.

Call shapes: x (B, I, *S) real, wg re/im (I, O, Mh), then per axis k the
forward factor pair (r_k, S_k) and, after all of them, the inverse pairs
(r_k, S_k); the backward adds g (B, O, *S).  Mh = prod(r_k).

Forward FLOPs, axis by axis on an operand of N elements: the real first
axis 4*N*r_k, each further complex axis 8*N*r_k; the contraction
8*B*I*O*Mh; the inverse on N spectral elements 8*N*S_k per complex axis
and 4*N*S_d for the real-output last one.  The backward needs the
adjoint transforms of g (priced as the forward ones on O channels), dx
and dw through the contraction (16*B*I*O*Mh) and the adjoint inverse to
dx (priced as the inverse on I channels); the kernel's recomputation of
x's spectrum is not counted.
"""
from __future__ import annotations

from harness.kernels import io_bytes

PREFIX = "_fused_"


def _forward_dft(lead: int, spatial, rows) -> float:
    n = lead
    for s in spatial:
        n *= s
    total = 0.0
    for k, (s, r) in enumerate(zip(spatial, rows)):
        total += (4.0 if k == 0 else 8.0) * n * r
        n = n // s * r
    return total


def _inverse_dft(lead: int, spatial, rows) -> float:
    n = lead
    for r in rows:
        n *= r
    total = 0.0
    d = len(spatial)
    for k, (s, r) in enumerate(zip(spatial, rows)):
        total += (4.0 if k == d - 1 else 8.0) * n * s
        n = n // r * s
    return total


def flops(name, ins, outs) -> float:
    x, wr = ins[0], ins[1]
    B, I, *spatial = x.shape
    O, Mh = wr.shape[1], wr.shape[2]
    ndim = len(spatial)
    rows = [ins[3 + 2 * k].shape[0] for k in range(ndim)]
    contract = 8.0 * B * I * O * Mh
    if len(ins) == 3 + 4 * ndim:
        return (_forward_dft(B * I, spatial, rows) + contract
                + _inverse_dft(B * O, spatial, rows))
    return (_forward_dft(B * O, spatial, rows) + 2.0 * contract
            + _inverse_dft(B * I, spatial, rows))


def hbm_bytes(name, ins, outs) -> float:
    return io_bytes(ins, outs)
