"""Plain spherical FNO (Bonev et al. 2023): lifting MLP, ``n_layers``
spherical layers (real SHT on a Gauss-Legendre grid, a per-degree channel
mixing shared over the orders, inverse SHT, plus a pointwise skip and
GELU), projection MLP; the optional ``tanh`` before each forward SHT as
in the mixed-precision method (Tu et al., ICLR 2024, §4.3).

Fully normalised harmonics, Gauss-Legendre latitudes, the longitude
transform by rFFT: the conventions of the program under test, written
out again here in plain ``jax.numpy``/``numpy`` at float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import gelu, linear, linear_init, seed_key, stack


def init_params(seed: int, cfg: dict) -> dict:
    """The weights, made on the device in one jitted call from ``seed``."""
    h, L = cfg["hidden_channels"], cfg["lmax"]

    def make(key):
        k = jax.random.split(key, 5)
        ws, skips = [], []
        for lk in jax.random.split(k[4], cfg["n_layers"]):
            k1, k2, k3 = jax.random.split(lk, 3)
            ws.append({
                "w_re": jax.random.normal(k1, (h, h, L), jnp.float32) / h,
                "w_im": jax.random.normal(k2, (h, h, L), jnp.float32) / h})
            skips.append(linear_init(k3, h, h))
        return {
            "lift1": linear_init(k[0], cfg["in_channels"],
                                 cfg["lifting_channels"]),
            "lift2": linear_init(k[1], cfg["lifting_channels"], h),
            "proj1": linear_init(k[2], h, cfg["projection_channels"]),
            "proj2": linear_init(k[3], cfg["projection_channels"],
                                 cfg["out_channels"]),
            "spectral": stack(ws),
            "skips": stack(skips),
        }

    return jax.jit(make)(seed_key(seed))


@functools.lru_cache(maxsize=4)
def legendre(nlat: int, lmax: int, mmax: int):
    """P[m, l, lat]: normalised associated Legendre functions at the
    Gauss-Legendre nodes (0 for l < m), and the quadrature weights."""
    x, w = np.polynomial.legendre.leggauss(nlat)
    P = np.zeros((mmax, lmax, nlat))
    sin2 = 1.0 - x * x
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
    return P, w


def sht(f, lmax: int, mmax: int, q):
    """f (..., nlat, nlon) -> coefficients (..., lmax, mmax)."""
    nlat, nlon = f.shape[-2:]
    P, w = legendre(nlat, lmax, mmax)
    Pw = jnp.asarray(P * w[None, None, :], jnp.float32)
    Fm = jnp.fft.rfft(q(f), axis=-1)[..., :mmax] * (2.0 * math.pi / nlon)
    Fm = q(Fm)
    return jnp.einsum("mlt,...tm->...lm", q(Pw).astype(jnp.complex64), Fm)


def isht(c, nlat: int, nlon: int, q):
    """Coefficients (..., lmax, mmax) -> f (..., nlat, nlon)."""
    lmax, mmax = c.shape[-2:]
    P, _ = legendre(nlat, lmax, mmax)
    G = jnp.einsum("mlt,...lm->...tm",
                   q(jnp.asarray(P, jnp.float32)).astype(jnp.complex64), q(c))
    nfreq = nlon // 2 + 1
    G = q(G[..., :nfreq])
    G = jnp.pad(G, [(0, 0)] * (G.ndim - 1) + [(0, nfreq - G.shape[-1])])
    return jnp.fft.irfft(G, n=nlon, axis=-1) * float(nlon)


def forward(params, x, cfg, pre_fft_tanh: bool, q):
    """x: (B, in_channels, nlat, nlon) -> (B, out_channels, nlat, nlon)."""
    nlat, nlon = x.shape[-2:]
    h = jnp.moveaxis(x, 1, -1)
    h = linear(params["lift2"], gelu(linear(params["lift1"], h, q)), q)
    h = jnp.moveaxis(h, -1, 1)
    for layer in range(cfg["n_layers"]):
        w = jax.tree_util.tree_map(lambda a: a[layer], params["spectral"])
        skip = jax.tree_util.tree_map(lambda a: a[layer], params["skips"])
        u = jnp.tanh(h) if pre_fft_tanh else h
        c = q(sht(u, cfg["lmax"], cfg["mmax"], q))
        wc = q(jax.lax.complex(w["w_re"], w["w_im"]))
        y = isht(jnp.einsum("bilm,iol->bolm", c, wc), nlat, nlon, q)
        s = jnp.moveaxis(linear(skip, jnp.moveaxis(h, 1, -1), q), -1, 1)
        h = gelu(y + s)
    h = jnp.moveaxis(h, 1, -1)
    h = linear(params["proj2"], gelu(linear(params["proj1"], h, q)), q)
    return jnp.moveaxis(h, -1, 1)
