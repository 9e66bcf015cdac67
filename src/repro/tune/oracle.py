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
from repro.kernels.spectral_contract import resolve_interpret
from .measure import make_operands
from .space import Candidate

F32_EPS = float(np.finfo(np.float32).eps)
ATOL = 1e-5

#: requantising stages per family — one 4εM term each, mirroring the
#: stage counts the differential tests budget for the same kernels.
STAGES = {"dense": 2, "dense-fused": 2, "cp": 6, "lshared": 2}


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
    else:
        raise ValueError(f"unknown kernel family {family!r}")
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
    if cand.family in ("dense", "dense-fused"):
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
    got = _c(np.asarray(yr.astype(jnp.float32)),
             np.asarray(yi.astype(jnp.float32)))

    ref, mag = reference(cand, ops)
    eps = storage_eps(cand.dtype)
    budget = (STAGES[cand.family] * prec_upper_bound(eps, mag)
              + 32 * F32_EPS * mag + ATOL)
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
