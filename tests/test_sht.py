"""The spherical harmonic transform: real Legendre matmuls and both
longitude paths.

``sht_forward`` / ``sht_inverse`` run the Legendre transform as real
float32 matmuls at ``Precision.HIGHEST`` and the longitude transform as
truncated-DFT matmuls on a TPU, as ``rfft`` / ``irfft`` elsewhere.  Here
they are checked against the plain complex formulation (the complex
Legendre einsum after a full ``rfft``, and ``irfft`` of the zero-padded
spectrum) under ``highest`` precision, on a band-limited round trip, and
with the TPU's DFT path called directly: the CPU cannot select it.  The
SFNO's counter ``repro_sht_transforms_total`` records the path taken.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spectral
from repro.models import sht
from repro.models.sht import legendre_matrices, sht_forward, sht_inverse

#: float32 transforms at these sizes agree to ~1e-6 of the largest
#: value; 2e-5 leaves room for the accumulation order of any backend.
RTOL = 2e-5

#: (nlat, nlon, lmax, mmax): the SFNO_SWE aspect at a CPU size, an odd
#: longitude count, and mmax at the row's Nyquist order
SHAPES = [(16, 32, 8, 8), (12, 25, 10, 6), (10, 16, 9, 9)]
IDS = ["x".join(map(str, s)) for s in SHAPES]


def _complex_forward(f, lmax, mmax):
    nlat, nlon = f.shape[-2:]
    P, _, w = legendre_matrices(nlat, lmax, mmax)
    Pw = jnp.asarray(P * w[None, None, :], jnp.complex64)
    Fm = jnp.fft.rfft(f, axis=-1)[..., :mmax] * (2.0 * math.pi / nlon)
    return jnp.einsum("mlt,...tm->...lm", Pw, Fm)


def _complex_inverse(c, nlat, nlon):
    lmax, mmax = c.shape[-2:]
    P, _, _ = legendre_matrices(nlat, lmax, mmax)
    G = jnp.einsum("mlt,...lm->...tm", jnp.asarray(P, jnp.complex64), c)
    G = jnp.pad(G, [(0, 0)] * (G.ndim - 1) + [(0, nlon // 2 + 1 - mmax)])
    return jnp.fft.irfft(G, n=nlon, axis=-1) * float(nlon)


def _field(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


def _coeffs(lmax, mmax, lead=(2, 3), seed=1):
    """Random band-limited coefficients: zero for l < m, real at m = 0."""
    rng = np.random.RandomState(seed)
    c = rng.randn(*lead, lmax, mmax) + 1j * rng.randn(*lead, lmax, mmax)
    c *= np.arange(lmax)[:, None] >= np.arange(mmax)[None, :]
    c[..., 0] = c[..., 0].real
    return jnp.asarray(c, jnp.complex64)


def _close(a, b):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=0, atol=RTOL * np.abs(b).max())


@pytest.fixture
def dft(monkeypatch):
    """The TPU's selection, forced: the longitude transform runs as
    truncated-DFT matmuls."""
    monkeypatch.setattr(spectral, "_on_tpu", lambda: True)


@pytest.mark.parametrize("path", ["fft", "dft"])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_matches_complex_formulation(shape, path, request):
    """Analysis and synthesis equal the complex-einsum / FFT formulation
    under ``highest`` precision, on either longitude path."""
    if path == "dft":
        request.getfixturevalue("dft")
    nlat, nlon, lmax, mmax = shape
    assert sht.longitude_dft(nlon, mmax) == (path == "dft")
    f = _field((2, 3, nlat, nlon))
    c = _coeffs(lmax, mmax)
    with jax.default_matmul_precision("highest"):
        want_c = _complex_forward(f, lmax, mmax)
        want_f = _complex_inverse(c, nlat, nlon)
    _close(sht_forward(f, lmax, mmax), want_c)
    _close(sht_inverse(c, nlat, nlon), want_f)


@pytest.mark.parametrize("path", ["fft", "dft"])
@pytest.mark.parametrize("shape", SHAPES[:2], ids=IDS[:2])
def test_band_limited_round_trip(shape, path, request):
    """Synthesis then analysis returns band-limited coefficients (lmax
    <= nlat, the Gauss-Legendre quadrature exact), on either path.  (At
    an even row's Nyquist order a real grid keeps half the real part and
    none of the imaginary one, so the third shape has no round trip.)"""
    if path == "dft":
        request.getfixturevalue("dft")
    nlat, nlon, lmax, mmax = shape
    c = _coeffs(lmax, mmax)
    _close(sht_forward(sht_inverse(c, nlat, nlon), lmax, mmax), c)


def test_legendre_matmuls_are_real_at_highest(dft):
    """Every dot in the forward and backward transforms is real float32
    at ``Precision.HIGHEST``, and on the DFT path no FFT is left."""
    def loss(f):
        c = sht_forward(f, 8, 8)
        return jnp.sum(jnp.square(sht_inverse(c, 16, 32)))

    jaxpr = jax.make_jaxpr(jax.grad(loss))(_field((2, 16, 32)))
    eqns = jaxpr.jaxpr.eqns
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 8  # two Legendre and two DFT matmuls, each way
    for e in dots:
        assert all(v.aval.dtype == jnp.float32 for v in e.invars)
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
    assert "fft" not in {e.primitive.name for e in eqns}


def test_dft_rows_match_rfft():
    """``rfft_rows`` / ``irfft_rows``, the longitude path's matmuls,
    against ``jnp.fft`` at the SFNO_SWE row (512 points, 128 orders) and
    at the Nyquist edge of an even row."""
    for n, m in [(512, 128), (32, 17)]:
        x = _field((3, n), seed=n)
        z = spectral.rfft_rows(x, m)
        want = jnp.fft.rfft(x, axis=-1)[..., :m]
        _close(z, jnp.concatenate([jnp.real(want), jnp.imag(want)], -1))
        spec = jnp.pad(want, [(0, 0), (0, n // 2 + 1 - m)])
        _close(spectral.irfft_rows(z, n), jnp.fft.irfft(spec, n=n, axis=-1))


def _sht_total(path):
    from repro.obs import registry

    key = f'repro_sht_transforms_total{{path="{path}"}}'
    return registry().snapshot()["counters"].get(key, 0.0)


def test_transform_counter_per_layer_body_and_path(monkeypatch):
    """``repro_sht_transforms_total`` counts one per spherical layer body
    traced, under the longitude path it took: a layer scan traces its
    body once, an unrolled loop each layer."""
    from repro.autoprec.telemetry import TraceCollector, collecting
    from repro.core import FULL
    from repro.models import SFNOConfig, init_sfno, sfno_apply

    cfg = SFNOConfig(in_channels=1, out_channels=1, hidden_channels=4,
                     n_layers=3, nlat=8, nlon=16, lmax=4, mmax=4,
                     lifting_channels=4, projection_channels=4,
                     use_pallas=False)
    params = init_sfno(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 1, 8, 16), jnp.float32)

    def trace():
        jax.make_jaxpr(lambda x: sfno_apply(params, x, cfg, FULL))(x)

    before = {p: _sht_total(p) for p in ("dft", "fft")}
    trace()
    assert _sht_total("fft") == before["fft"] + 1
    monkeypatch.setattr(spectral, "_on_tpu", lambda: True)
    trace()
    assert _sht_total("dft") == before["dft"] + 1
    with collecting(TraceCollector()):   # unrolls the layer loop
        trace()
    assert _sht_total("dft") == before["dft"] + 1 + cfg.n_layers
    assert _sht_total("fft") == before["fft"] + 1
