"""Shared test helpers: tolerance math, random operands, shape tables.

One home for the budget/tolerance machinery that test_kernels.py,
test_kernels_diff.py and test_tune.py used to copy-paste: the Thm 3.2
elementwise budget assertion, the relative-error norm, seeded complex
operands, the per-policy grad tolerances, and the calibration-entry
builders the tune tests seed states with.
"""
import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import get_policy
from repro.core.precision import ComplexPair
from repro.core.theory import prec_upper_bound
from repro.precision import POLICIES

F32_EPS = float(np.finfo(np.float32).eps)

POLICY_NAMES = sorted(POLICIES)

#: policies whose contract site stores at a half format — only these
#: have a storage rounding to fuse / quantise
HALF_POLICY_NAMES = [
    n for n in POLICY_NAMES
    if get_policy(n).at("fno/layer0/spectral/contract").spectral_is_half
]

#: one small shape per mode dimensionality (kept tiny: every case jit-
#: compiles its own interpret-mode kernel)
MODES_BY_NDIM = {1: (7,), 2: (3, 5), 3: (2, 3, 2)}

#: odd / non-MXU-aligned spatial grids per dimensionality for the
#: truncated-DFT legs — the factors must be exact on grids that are not
#: powers of two and not even
SPATIAL_BY_NDIM = {1: (15,), 2: (9, 11), 3: (6, 7, 5)}

#: grad-parity tolerance per registry policy: tight where the contract
#: site stays f32 (full and the AMP-only sets), storage-precision-sized
#: where it quantises (half/fp8 families)
GRAD_TOLS = {
    "full": 1e-5,
    "amp_bf16": 1e-4,
    "amp_fp16": 1e-4,
    "half_fno_only": 0.03,
    "mixed_fno_bf16": 0.08,
    "mixed_fno_fp16": 0.03,
    "sim_fp8_e4m3": 0.03,
    "sim_fp8_e5m2": 0.03,
}


def rand_complex(rng, shape, scale=0.5):
    return jnp.asarray(
        scale * (rng.randn(*shape) + 1j * rng.randn(*shape)), jnp.complex64
    )


def to_np_complex(y):
    if isinstance(y, ComplexPair):
        y = y.to_complex()
    return np.asarray(y)


def rel_err(a, b):
    dt = np.complex128 if np.iscomplexobj(np.asarray(a)) else np.float64
    a = np.asarray(a, dt).ravel()
    b = np.asarray(b, dt).ravel()
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-12))


def assert_within_budget(y_pallas, y_einsum, eps, mag, stages, label,
                         f32_c=32, atol=1e-5):
    """|pallas − einsum| ≤ stages·4εM + f32_c·ε_f32·M + atol, elementwise.

    ``mag`` is the contraction of operand magnitudes — the per-output
    empirical M of Thm 3.2; each requantising stage of either path may
    contribute up to ``prec_upper_bound(eps, M) = 4εM``.
    """
    budget = stages * prec_upper_bound(eps, mag) + f32_c * F32_EPS * mag + atol
    diff = np.abs(to_np_complex(y_pallas) - to_np_complex(y_einsum))
    worst = float((diff - budget).max())
    assert np.all(diff <= budget), (
        f"{label}: pallas-vs-einsum exceeds the Thm 3.2 budget by {worst:.3e}"
        f" (max diff {diff.max():.3e}, min budget {budget.min():.3e})"
    )


@contextlib.contextmanager
def force_tpu_transforms():
    """Make the backend predicate read "tpu" inside the block, so the
    staged path takes the truncated-DFT transforms on the CPU (kernels
    stay interpreted).  A jitted function traced inside keeps that path."""
    from repro.core import spectral

    with mock.patch.object(spectral, "_on_tpu", lambda: True):
        yield


def spectral_mag(x, w_re, w_im, spatial, modes):
    """Composed per-output magnitude M of the spectral convolution: |x|
    pushed through the absolute forward DFT factors, the absolute
    per-corner weights, and the absolute inverse factors — the envelope
    every rounding stage of either the DFT or the FFT path lives under.
    ``w_re``/``w_im``: the layer's (corners, I, O, *modes) weights."""
    from repro.core.spectral import _corner_slices, dft_factors

    ndim = len(modes)
    facs = dft_factors(spatial, modes)

    def apply(a, f, axis, f_axis):
        return np.moveaxis(
            np.tensordot(a, f, axes=[[axis], [f_axis]]), -1, axis)

    mag = np.abs(np.asarray(x, np.float64))
    for k in range(ndim):
        fr, fi = facs[2 * k], facs[2 * k + 1]
        mag = apply(mag, np.abs(fr + 1j * fi), 2 + k, 1)
    # the forward factors keep each full axis's low block then its high
    # block, so the corners of the stacked rows are the spectrum's corners
    w_abs = np.abs(np.asarray(w_re, np.float64)
                   + 1j * np.asarray(w_im, np.float64))
    out = np.zeros((mag.shape[0], w_abs.shape[2], *mag.shape[2:]))
    for c, sl in enumerate(_corner_slices(modes, mag.shape[2:])):
        sl = (slice(None), slice(None), *sl)
        out[sl] = np.einsum("bi...,io...->bo...", mag[sl], w_abs[c])
    mag = out
    for k in range(ndim - 1):
        gr, gi = facs[2 * ndim + 2 * k], facs[2 * ndim + 2 * k + 1]
        mag = apply(mag, np.abs(gr + 1j * gi), 2 + k, 0)
    cr, ci = facs[4 * ndim - 2], facs[4 * ndim - 1]
    ax = 2 + ndim - 1
    return apply(mag, np.abs(cr) + np.abs(ci), ax, 0)


def calibration_entry(family, shape, dtype="bfloat16", block_fwd=8,
                      block_bwd=8, **kw):
    """A structurally-valid calibration-cache entry for the current
    backend/kernel version (override any field via ``kw``)."""
    from repro.kernels.spectral_contract import KERNEL_VERSION

    ent = {
        "family": family, "shape": list(shape), "dtype": dtype,
        "backend": jax.default_backend(), "kernel_version": KERNEL_VERSION,
        "block_fwd": block_fwd, "block_bwd": block_bwd, "validated": True,
    }
    ent.update(kw)
    return ent


def calibration_state(tmp_path, *entries, name="state.json", **header):
    """Write a calibration state holding ``entries`` and return its path."""
    from repro.tune import cache as cache_mod

    state = cache_mod.CalibrationCache(
        entries={}, backend=jax.default_backend())
    for ent in entries:
        state.put(ent)
    for k, v in header.items():
        setattr(state, k, v)
    return cache_mod.save(state, tmp_path / name)
