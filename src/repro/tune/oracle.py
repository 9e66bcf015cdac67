"""The admission gate: every candidate vs its einsum oracle, under the
paper's own tolerance.

Thm 3.2 bounds the precision error of a half-stored contraction by
``4·ε·M`` per requantising stage, where ``ε`` is the storage grid
spacing and ``M`` the contraction of operand magnitudes.  The
differential test suite (tests/test_kernels_diff.py) asserts the Pallas
kernels against the einsum reference under exactly

    budget = stages · 4εM + 32·ε_f32·M + atol      (elementwise)

and this module applies the same machinery at tuning time: a candidate
tile whose kernel output strays outside that envelope is *refused* — a
mistuned-but-wrong kernel is unrepresentable in the calibration cache.

``perturb`` injects a scaled multiple of the budget into the kernel
output before the comparison.  It exists so the gate itself is testable:
``python -m repro.tune validate --perturb 2`` must reject every entry
(the seeded-violation self-check CI can run), proving the oracle is
live, not vacuously green.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.precision import FORMAT_EPS
from repro.core.theory import prec_upper_bound
from repro.kernels.spectral_contract import (
    _fused_rows, fused_factors, resolve_interpret,
)
from .measure import make_operands
from .space import Candidate, fused_axes

F32_EPS = float(np.finfo(np.float32).eps)
ATOL = 1e-5

#: requantising stages per family — one 4εM term each, mirroring the
#: stage counts the differential tests budget for the same kernels.
#: spectral_fused composes four: the forward-DFT boundary quantisation,
#: the two contraction operand grids, and the half output store — the
#: composed Thm 3.2 budget the fused differential leg asserts too.
STAGES = {"dense": 2, "dense-fused": 2, "cp": 6, "lshared": 2,
          "spectral_fused": 4}

#: the fused pipeline's f32 transform stages (truncated DFT + inverse)
#: accumulate over prod(spatial) elements; 32·ε_f32 per magnitude unit
#: covers them at every shape the suite and tuner exercise
FUSED_F32_C = 32


def storage_eps(dtype: str) -> float:
    """Grid spacing ε of the storage dtype ("bfloat16", "float16", ...)."""
    return FORMAT_EPS[dtype]


def _c(re, im):
    return np.asarray(re, np.float64) + 1j * np.asarray(im, np.float64)


def _rounded(arr, dtype):
    """Round an f32 operand onto the storage grid the kernel will use
    (identity when it already lives there)."""
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(arr).astype(jnp.dtype(dtype))
                      .astype(jnp.float32))


def reference(cand: Candidate, ops) -> tuple:
    """(exact complex reference, magnitude contraction M) for the
    candidate's operands — computed at complex128 from the same storage-
    rounded values the kernel consumes, so the elementwise budget
    charges only the kernel's own stages."""
    family, dtype = cand.family, cand.dtype
    if family in ("dense", "dense-fused"):
        xr, xi, wr, wi = ops
        if family == "dense-fused":
            # the kernel rounds f32 tiles onto the half grid in-kernel;
            # the oracle must agree on the operands being contracted
            xr, xi, wr, wi = (_rounded(a, dtype) for a in (xr, xi, wr, wi))
        x, w = _c(xr, xi), _c(wr, wi)
        ref = np.einsum("bim,iom->bom", x, w)
        mag = np.einsum("bim,iom->bom", np.abs(x), np.abs(w))
    elif family == "cp":
        xr, xi, uir, uii, uor, uoi, wr, wi = ops
        x, ui, uo, w = _c(xr, xi), _c(uir, uii), _c(uor, uoi), _c(wr, wi)
        t = np.einsum("bim,ir->bmr", x, ui)
        u = t * np.transpose(w)[None]
        ref = np.einsum("bmr,or->bom", u, uo)
        tm = np.einsum("bim,ir->bmr", np.abs(x), np.abs(ui))
        mag = np.einsum("bmr,or->bom",
                        tm * np.abs(np.transpose(w))[None], np.abs(uo))
    elif family == "lshared":
        xr, xi, wr, wi = ops
        x, w = _c(xr, xi), _c(wr, wi)
        ref = np.einsum("bilm,iol->bolm", x, w)
        mag = np.einsum("bilm,iol->bolm", np.abs(x), np.abs(w))
    elif family == "spectral_fused":
        return _fused_reference(cand, ops)
    else:
        raise ValueError(f"unknown kernel family {family!r}")
    return ref, mag


def _apply_factor(a, f, axis, f_axis):
    return np.moveaxis(np.tensordot(a, f, axes=[[axis], [f_axis]]), -1, axis)


def _fused_reference(cand: Candidate, ops) -> tuple:
    """Composed f64 reference for the fused pipeline, from the same
    storage-rounded spectrum/weights the kernel contracts, with the
    magnitude ``M`` composed through the absolute factor matrices — the
    per-element Thm 3.2 envelope of the whole rFFT→contract→irFFT chain,
    not of one stage."""
    x, wgr, wgi = (np.asarray(a, np.float64) for a in ops)
    B, I, O, spatial, modes = fused_axes(cand.shape)
    ndim = len(modes)
    facs = fused_factors(spatial, modes)
    fwd = [(facs[2 * k], facs[2 * k + 1]) for k in range(ndim)]
    inv = [(facs[2 * ndim + 2 * k], facs[2 * ndim + 2 * k + 1])
           for k in range(ndim)]

    a = x.astype(np.complex128)
    mag_a = np.abs(x)
    for k, (fr, fi) in enumerate(fwd):
        F = fr + 1j * fi
        a = _apply_factor(a, F, 2 + k, 1)
        mag_a = _apply_factor(mag_a, np.abs(F), 2 + k, 1)
    ah = a.reshape(B, I, -1)
    mag_ah = mag_a.reshape(B, I, -1)
    if cand.dtype != "float32":
        ah = (_rounded(ah.real, cand.dtype)
              + 1j * _rounded(ah.imag, cand.dtype))
        wgr = _rounded(wgr, cand.dtype)
        wgi = _rounded(wgi, cand.dtype)
    w = wgr + 1j * wgi
    yh = np.einsum("bim,iom->bom", ah, w)
    mag_yh = np.einsum("bim,iom->bom", mag_ah, np.abs(w))
    rows = _fused_rows(spatial, modes)
    yh = yh.reshape(B, O, *rows)
    mag_yh = mag_yh.reshape(B, O, *rows)
    for k in range(ndim - 1):
        G = inv[k][0] + 1j * inv[k][1]
        yh = _apply_factor(yh, G, 2 + k, 0)
        mag_yh = _apply_factor(mag_yh, np.abs(G), 2 + k, 0)
    cr, ci = inv[-1]
    ax = 2 + ndim - 1
    ref = (_apply_factor(yh.real, cr, ax, 0)
           + _apply_factor(yh.imag, ci, ax, 0))
    mag = _apply_factor(mag_yh, np.abs(cr) + np.abs(ci), ax, 0)
    return ref, mag


def check(cand: Candidate, *, interpret: Optional[bool] = None,
          seed: int = 0, perturb: float = 0.0) -> dict:
    """Run the candidate's forward kernel and gate it against the einsum
    oracle.  Returns {passed, max_err, budget_min, worst_excess}."""
    import jax.numpy as jnp

    from repro.kernels.spectral_contract import (
        spectral_contract_cp_pallas as cp_kern,
        spectral_contract_lshared_pallas as l_kern,
        spectral_contract_pallas as d_kern,
    )

    interpret = resolve_interpret(interpret)
    ops = make_operands(cand.family, cand.shape, cand.dtype, seed=seed)
    out_dtype = jnp.dtype(cand.dtype)
    if cand.family == "spectral_fused":
        from repro.kernels.spectral_contract import spectral_fused_pallas

        _B, _I, _O, _spatial, modes = fused_axes(cand.shape)
        y = spectral_fused_pallas(
            *ops, modes=modes, block_b=cand.block_fwd,
            block_b_bwd=cand.block_bwd, interpret=interpret,
            cast_to=None if cand.dtype == "float32" else out_dtype)
        got = np.asarray(y.astype(jnp.float32), np.float64)
    elif cand.family in ("dense", "dense-fused"):
        yr, yi = d_kern(
            *ops, block_m=cand.block_fwd, block_m_bwd=cand.block_bwd,
            interpret=interpret, out_dtype=out_dtype,
            cast_to=out_dtype if cand.family == "dense-fused" else None)
    elif cand.family == "cp":
        yr, yi = cp_kern(
            *ops, block_m=cand.block_fwd, block_m_bwd=cand.block_bwd,
            interpret=interpret, out_dtype=out_dtype)
    else:
        yr, yi = l_kern(
            *ops, block_l=cand.block_fwd, block_l_bwd=cand.block_bwd,
            interpret=interpret, out_dtype=out_dtype)
    if cand.family != "spectral_fused":
        got = _c(np.asarray(yr.astype(jnp.float32)),
                 np.asarray(yi.astype(jnp.float32)))

    ref, mag = reference(cand, ops)
    eps = storage_eps(cand.dtype)
    f32_c = FUSED_F32_C if cand.family == "spectral_fused" else 32
    budget = (STAGES[cand.family] * prec_upper_bound(eps, mag)
              + f32_c * F32_EPS * mag + ATOL)
    if perturb:
        # seeded violation: shift the kernel output by perturb×budget so
        # any |perturb| > 1 must trip the gate everywhere
        got = got + perturb * budget
    diff = np.abs(got - ref)
    excess = float((diff - budget).max())
    result = {
        "passed": bool(np.all(diff <= budget)),
        "max_err": float(diff.max()),
        "budget_min": float(budget.min()),
        "worst_excess": excess,
    }
    if not result["passed"]:
        from repro.obs import oracle_reject
        from .cache import entry_key

        oracle_reject(
            f"{entry_key(cand.family, cand.shape, cand.dtype)}"
            f"|b{cand.block_fwd}x{cand.block_bwd}",
            max_err=result["max_err"], budget_min=result["budget_min"],
            worst_excess=excess)
    return result
