"""Real spherical harmonic transform (SHT) built from scratch:
longitude transform + per-order Legendre matmul on a Gauss-Legendre grid.

This is the substrate SFNO (Bonev et al. 2023) needs; torch-harmonics is
not available in JAX, and re-deriving it makes the spherical path
matmul-dominant — exactly the structure the paper's mixed-precision
contraction accelerates (the Legendre transform is a (lat × l) GEMM per
order m, batched over channels).

Both stages run in float32 as real matmuls at ``Precision.HIGHEST`` (on
a TPU a matmul at the default precision is one bfloat16 pass):

* the Legendre transform multiplies the real and the imaginary parts of
  the spectrum by the same real matrix ``P`` (``P·w`` for analysis), with
  the contracted axis (latitude, or degree for synthesis) in the matmul's
  contracting position and the order m as its batch axis;
* the longitude transform keeps only the ``mmax`` retained orders.  On a
  TPU it runs as truncated-DFT matmuls (``core.spectral.rfft_rows`` /
  ``irfft_rows``; the TPU's FFT computes every order of the row, and at
  SFNO_SWE's shape the matmuls are the faster), elsewhere as
  ``jnp.fft.rfft`` / ``irfft`` (``longitude_dft`` selects).

Conventions: fully-normalised spherical harmonics Y_lm = P̄_lm(cosθ)e^{imφ}
with ∫|Y_lm|²dΩ = 1; Gauss-Legendre latitude nodes make the analysis/
synthesis roundtrip exact for band-limited fields (lmax <= nlat-1).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spectral


@functools.lru_cache(maxsize=16)
def legendre_matrices(nlat: int, lmax: int, mmax: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Precompute (P, x, w): P[m, l, lat] = P̄_lm(x_lat) (0 for l < m),
    Gauss-Legendre nodes x and weights w.  float64 numpy for stability."""
    x, w = np.polynomial.legendre.leggauss(nlat)
    P = np.zeros((mmax, lmax, nlat), dtype=np.float64)
    sin2 = 1.0 - x * x
    # p̄_mm via upward recurrence in m
    pmm = np.full(nlat, math.sqrt(1.0 / (4.0 * math.pi)))
    for m in range(mmax):
        if m > 0:
            pmm = -np.sqrt((2.0 * m + 1.0) / (2.0 * m)) * np.sqrt(sin2) * pmm
        if m < lmax:
            P[m, m] = pmm
        if m + 1 < lmax:
            P[m, m + 1] = x * math.sqrt(2.0 * m + 3.0) * pmm
        for l in range(m + 2, lmax):
            a = math.sqrt((4.0 * l * l - 1.0) / (l * l - m * m))
            b = math.sqrt(((l - 1.0) ** 2 - m * m) / (4.0 * (l - 1.0) ** 2 - 1.0))
            P[m, l] = a * (x * P[m, l - 1] - b * P[m, l - 2])
    return P, x, w


def longitude_dft(nlon: int, mmax: int) -> bool:
    """Whether the longitude transform runs as truncated-DFT matmuls: on
    a TPU, where the retained orders lie within the row's spectrum."""
    return spectral._on_tpu() and mmax <= nlon // 2 + 1


def _legendre(mat: np.ndarray, z: jnp.ndarray, expr: str) -> jnp.ndarray:
    """One real matmul of ``z`` (real and imaginary parts on the axis
    before the order axis) against the real ``mat`` (m, l, lat), the
    order m its batch axis, at f32 ``Precision.HIGHEST``."""
    return jnp.einsum(expr, jnp.asarray(mat, jnp.float32), z,
                      precision=jax.lax.Precision.HIGHEST)


def sht_forward(f: jnp.ndarray, lmax: int, mmax: int) -> jnp.ndarray:
    """Analysis: f (..., nlat, nlon) real -> coeffs (..., lmax, mmax) complex.

    coeffs[l,m] = Σ_lat w_lat P̄_lm(x_lat) · (2π/nlon)·rfft(f)[lat, m]
    """
    nlat, nlon = f.shape[-2], f.shape[-1]
    P, _, w = legendre_matrices(nlat, lmax, mmax)
    f = f.astype(jnp.float32)
    if longitude_dft(nlon, mmax):
        z = spectral.rfft_rows(f, mmax)
        z = z.reshape(*z.shape[:-1], 2, mmax)  # (..., lat, re|im, m)
    else:
        Fm = jnp.fft.rfft(f, axis=-1)[..., :mmax]
        z = jnp.stack([jnp.real(Fm), jnp.imag(Fm)], axis=-2)
    Pw = P * w[None, None, :] * (2.0 * math.pi / nlon)
    c = _legendre(Pw, z, "mlt,...tkm->...klm")
    return jax.lax.complex(c[..., 0, :, :], c[..., 1, :, :])


def sht_inverse(coeffs: jnp.ndarray, nlat: int, nlon: int) -> jnp.ndarray:
    """Synthesis: coeffs (..., lmax, mmax) -> f (..., nlat, nlon) real.

    f[lat, lon] = G_0 + 2 Re Σ_{m>0} G_m e^{imφ},  G_m = Σ_l P̄_lm(x_lat)·c[l,m]
    """
    lmax, mmax = coeffs.shape[-2], coeffs.shape[-1]
    nfreq = nlon // 2 + 1
    if mmax > nfreq:  # orders beyond the grid's Nyquist cannot be realised
        coeffs, mmax = coeffs[..., :nfreq], nfreq
    P, _, _ = legendre_matrices(nlat, lmax, mmax)
    # irfft applies the hermitian doubling and 1/nlon, so the real-field
    # synthesis scales by nlon, folded into the Legendre matrix.
    # (Roundtrip identity: rfft∘irfft = id, quadrature ∫p̄²dx = 1/2π.)
    z = jnp.stack([jnp.real(coeffs), jnp.imag(coeffs)], axis=-2)
    g = _legendre(P * float(nlon), z, "mlt,...lkm->...tkm")  # (..., lat, 2, m)
    if longitude_dft(nlon, mmax):
        return spectral.irfft_rows(g.reshape(*g.shape[:-2], 2 * mmax), nlon)
    G = jax.lax.complex(g[..., 0, :], g[..., 1, :])
    G = jnp.pad(G, [(0, 0)] * (G.ndim - 1) + [(0, nfreq - mmax)])
    return jnp.fft.irfft(G, n=nlon, axis=-1)
