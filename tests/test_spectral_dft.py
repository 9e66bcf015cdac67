"""The truncated transforms of the TPU spectral path against the FFT path.

``truncated_rfft`` computes the retained modes of ``rfftn`` as DFT
matmuls and ``truncated_irfft`` inverts the zero-filled spectrum from its
retained corners alone.  Both are called directly here, on the CPU, and
compared in float32 with ``jnp.fft`` — values and ``jax.vjp``
cotangents — over 1-D, 2-D and 3-D grids of even and odd sizes, with
the last axis's modes at the rfft spectrum's edge (Nyquist for an even
size) and full axes whose corner blocks just meet (2m = S).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spectral import (
    _corner_slices,
    _stack_corners,
    truncated_irfft,
    truncated_rfft,
)

jax.config.update("jax_platform_name", "cpu")

#: float32 transforms of these sizes agree to ~2e-7 of the largest
#: value; 1e-5 leaves room for the accumulation order of any backend.
RTOL = 1e-5

CASES = [
    ((32,), (8,)),
    ((33,), (17,)),             # odd size, all of the rfft spectrum
    ((32,), (17,)),             # even size, up to the Nyquist mode
    ((16, 16), (4, 9)),         # Nyquist edge on the last axis
    ((15, 17), (7, 9)),         # odd sizes on both axes
    ((12, 20), (6, 5)),         # low and high blocks meet: 2m = S
    ((8, 10, 12), (3, 4, 7)),
    ((9, 7, 11), (4, 3, 4)),
]
IDS = ["x".join(map(str, s)) + "-m" + "x".join(map(str, m))
       for s, m in CASES]


def _axes(ndim):
    return tuple(range(2, 2 + ndim))


def _corners(a, modes):
    return [a[(slice(None), slice(None), *sl)]
            for sl in _corner_slices(modes, a.shape[2:])]


def _fft_corners(x, modes):
    return _corners(jnp.fft.rfftn(x, axes=_axes(len(modes))), modes)


def _dft_corners(x, modes):
    return _corners(truncated_rfft(x, modes), modes)


def _fft_inverse(corners, spatial, modes):
    ndim = len(modes)
    shape = (*corners[0].shape[:2], *spatial[:-1], spatial[-1] // 2 + 1)
    out = jnp.zeros(shape, jnp.complex64)
    for yc, sl in zip(corners, _corner_slices(modes, shape[2:]), strict=True):
        out = out.at[(slice(None), slice(None), *sl)].set(yc)
    return jnp.fft.irfftn(out, s=spatial, axes=_axes(ndim))


def _dft_inverse(corners, spatial, modes):
    return truncated_irfft(_stack_corners(corners, len(modes)), spatial,
                           modes)


def _rel(got, want):
    got = jnp.asarray(got)
    want = jnp.asarray(want)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _x(spatial, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(2, 3, *spatial), jnp.float32)


def _spectrum_corners(spatial, modes, seed=1):
    """Random complex corners of the retained shape, DC and Nyquist
    imaginary parts included (the inverse must drop them as irfftn
    does)."""
    rng = np.random.RandomState(seed)
    xh = truncated_rfft(_x(spatial), modes)
    return [jnp.asarray(rng.randn(*c.shape) + 1j * rng.randn(*c.shape),
                        jnp.complex64) for c in _corners(xh, modes)]


@pytest.mark.parametrize("spatial,modes", CASES, ids=IDS)
def test_forward_matches_rfftn(spatial, modes):
    x = _x(spatial)
    xh = truncated_rfft(x, modes)
    rows = tuple(2 * m for m in modes[:-1]) + (modes[-1],)
    assert xh.shape == (2, 3, *rows) and xh.dtype == jnp.complex64
    for got, want in zip(_dft_corners(x, modes), _fft_corners(x, modes),
                         strict=True):
        assert _rel(got, want) <= RTOL


@pytest.mark.parametrize("spatial,modes", CASES, ids=IDS)
def test_inverse_matches_irfftn(spatial, modes):
    corners = _spectrum_corners(spatial, modes)
    got = _dft_inverse(corners, spatial, modes)
    want = _fft_inverse(corners, spatial, modes)
    assert got.shape == want.shape == (2, 3, *spatial)
    assert _rel(got, want) <= RTOL


@pytest.mark.parametrize("spatial,modes", CASES, ids=IDS)
def test_forward_vjp_matches_rfftn(spatial, modes):
    x = _x(spatial)
    ct = _spectrum_corners(spatial, modes, seed=2)
    _, vjp_dft = jax.vjp(lambda a: _dft_corners(a, modes), x)
    _, vjp_fft = jax.vjp(lambda a: _fft_corners(a, modes), x)
    (got,), (want,) = vjp_dft(ct), vjp_fft(ct)
    assert _rel(got, want) <= RTOL


@pytest.mark.parametrize("spatial,modes", CASES, ids=IDS)
def test_inverse_vjp_matches_irfftn(spatial, modes):
    corners = _spectrum_corners(spatial, modes)
    ct = _x(spatial, seed=3)
    _, vjp_dft = jax.vjp(lambda c: _dft_inverse(c, spatial, modes), corners)
    _, vjp_fft = jax.vjp(lambda c: _fft_inverse(c, spatial, modes), corners)
    (got,), (want,) = vjp_dft(ct), vjp_fft(ct)
    for g, w in zip(got, want, strict=True):
        assert _rel(g, w) <= RTOL
