"""Pallas kernel pass: static checks over grids, BlockSpecs and VMEM
estimators for every registered spectral-contraction kernel family.

Kernels are *traced, never run*: ``pl.pallas_call`` is temporarily
wrapped with a recorder and each family's public entry point is walked
with ``jax.eval_shape`` (forward) and ``jax.eval_shape(jax.grad(...))``
(the custom-VJP backward kernels).  Each recorded call is then checked
offline:

  index-oob (error)          a BlockSpec index map sends some grid step
      to a block that sticks out of the (padded) operand.
  output-not-covered (error) the output index maps, over the whole grid,
      fail to write every block of the output — silent garbage in the
      uncovered region.
  accum-discipline (error)   an output block revisited across grid steps
      without the init-or-accumulate pattern (``@pl.when(program_id ==
      0)`` zero-init + ``+=``) — the dUi/dUo hazard from the CP
      backward: Pallas output buffers are uninitialised on first touch.
  vmem-underestimate (error) the family's ``*vmem_bytes*`` estimator
      reports fewer bytes than the BlockSpec tiles actually constructed
      occupy — the dry-run ``fits_vmem`` verdicts would be lies.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import itertools
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .findings import ERROR, Finding


@dataclasses.dataclass
class KernelCall:
    """One recorded ``pl.pallas_call`` invocation (trace-time only)."""

    kernel: Callable
    grid: Tuple[int, ...]
    in_specs: Sequence
    out_specs: Sequence
    out_shape: Sequence
    arg_shapes: List[Tuple[Tuple[int, ...], Any]]  # (shape, dtype) per input

    @property
    def name(self) -> str:
        return getattr(self.kernel, "__name__", repr(self.kernel))


@contextlib.contextmanager
def record_pallas_calls() -> Iterator[List[KernelCall]]:
    """Swap ``pl.pallas_call`` for a recorder that captures the specs and
    the concrete (padded) operand shapes, then delegates.  The kernel
    modules resolve ``pl.pallas_call`` at call time, so patching the
    pallas module attribute reaches them all."""
    from jax.experimental import pallas as pl

    records: List[KernelCall] = []
    orig = pl.pallas_call

    def recording(kernel, **kwargs):
        inner = orig(kernel, **kwargs)

        @functools.wraps(inner)
        def wrapped(*args):
            grid = kwargs.get("grid", ())
            if isinstance(grid, int):
                grid = (grid,)
            out_shape = kwargs.get("out_shape")
            if not isinstance(out_shape, (tuple, list)):
                out_shape = [out_shape]
            out_specs = kwargs.get("out_specs") or []
            if not isinstance(out_specs, (tuple, list)):
                # single-output calls may pass one bare BlockSpec
                out_specs = [out_specs]
            records.append(KernelCall(
                kernel=kernel,
                grid=tuple(grid),
                in_specs=list(kwargs.get("in_specs") or []),
                out_specs=list(out_specs),
                out_shape=list(out_shape),
                arg_shapes=[(tuple(a.shape), jnp.dtype(a.dtype))
                            for a in args],
            ))
            return inner(*args)

        return wrapped

    pl.pallas_call = recording
    try:
        yield records
    finally:
        pl.pallas_call = orig


# ---------------------------------------------------------------------------
# Per-call structural checks
# ---------------------------------------------------------------------------


def _spec_blocks(spec, grid: Tuple[int, ...]):
    """Evaluate a BlockSpec's index map at every grid point.  Yields
    (grid_point, block_index_tuple)."""
    index_map = spec.index_map
    for pt in itertools.product(*(range(n) for n in grid)):
        idx = index_map(*pt)
        if not isinstance(idx, tuple):
            idx = (idx,)
        yield pt, tuple(int(i) for i in idx)


def _check_spec(call: KernelCall, role: str, pos: int, spec, shape,
                findings: List[Finding], where: str) -> Optional[set]:
    """OOB check for one spec against its operand shape; returns the set
    of visited block indices (None on arity mismatch, already reported)."""
    bs = tuple(spec.block_shape)
    if len(bs) != len(shape):
        findings.append(Finding(
            pass_name="kernels", check="index-oob", severity=ERROR,
            site=None, where=where,
            detail=f"{role}[{pos}]: block shape {bs} has different rank "
                   f"than operand {shape}",
        ))
        return None
    visited = set()
    for pt, idx in _spec_blocks(spec, call.grid):
        if len(idx) != len(bs):
            findings.append(Finding(
                pass_name="kernels", check="index-oob", severity=ERROR,
                site=None, where=where,
                detail=f"{role}[{pos}]: index map returned {idx} for grid "
                       f"point {pt}, expected rank {len(bs)}",
            ))
            return None
        for d, (i, b, s) in enumerate(zip(idx, bs, shape, strict=True)):
            if i < 0 or i * b + b > s:
                findings.append(Finding(
                    pass_name="kernels", check="index-oob", severity=ERROR,
                    site=None, where=where,
                    detail=f"{role}[{pos}] dim {d}: grid point {pt} maps to "
                           f"block {i} of size {b}, out of bounds for "
                           f"extent {s}",
                ))
        visited.add(idx)
    return visited


_INIT_MARKERS = ("pl.when", "program_id")


def _has_accum_discipline(kernel: Callable) -> bool:
    """Source heuristic for the init-or-accumulate pattern on revisited
    output blocks: a ``pl.when(program_id(...) == 0)`` guarded zero-init
    plus in-place ``+=`` accumulation."""
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    try:
        src = inspect.getsource(kernel)
    except (OSError, TypeError):
        return False
    return all(m in src for m in _INIT_MARKERS) and "+=" in src


def check_call(call: KernelCall, where: str) -> List[Finding]:
    findings: List[Finding] = []
    for pos, (spec, (shape, _dt)) in enumerate(
            zip(call.in_specs, call.arg_shapes, strict=True)):
        _check_spec(call, "in", pos, spec, shape, findings, where)
    for pos, (spec, sds) in enumerate(zip(call.out_specs, call.out_shape, strict=True)):
        shape = tuple(sds.shape)
        visited = _check_spec(call, "out", pos, spec, shape, findings, where)
        if visited is None:
            continue
        bs = tuple(spec.block_shape)
        n_blocks = [s // b for s, b in zip(shape, bs, strict=True)]
        expected = set(itertools.product(*(range(n) for n in n_blocks)))
        missing = expected - visited
        if missing:
            findings.append(Finding(
                pass_name="kernels", check="output-not-covered",
                severity=ERROR, site=None, where=where,
                detail=f"out[{pos}]: {len(missing)}/{len(expected)} output "
                       f"blocks never written (e.g. {sorted(missing)[0]}) — "
                       f"uncovered regions hold garbage",
            ))
        n_steps = 1
        for g in call.grid:
            n_steps *= g
        revisited = n_steps > len(visited)
        if revisited and not _has_accum_discipline(call.kernel):
            findings.append(Finding(
                pass_name="kernels", check="accum-discipline",
                severity=ERROR, site=None, where=where,
                detail=f"out[{pos}]: output block revisited across grid "
                       f"steps but kernel source shows no "
                       f"init-or-accumulate pattern "
                       f"(@pl.when(program_id==0) zero-init + '+=')",
            ))
    return findings


def tile_bytes(call: KernelCall) -> int:
    """Bytes of VMEM the BlockSpec tiles of one call actually occupy."""
    total = 0
    for spec, (_shape, dt) in zip(call.in_specs, call.arg_shapes, strict=True):
        n = 1
        for b in spec.block_shape:
            n *= b
        total += n * dt.itemsize
    for spec, sds in zip(call.out_specs, call.out_shape, strict=True):
        n = 1
        for b in spec.block_shape:
            n *= b
        total += n * jnp.dtype(sds.dtype).itemsize
    return total


# ---------------------------------------------------------------------------
# Kernel-family registry: how to trace each family and which estimator
# budgets it
# ---------------------------------------------------------------------------

# representative trace shapes (padding-exercising: M not a block multiple)
_B, _I, _O, _R = 2, 8, 8, 4
_M, _BLOCK_M = 40, 16          # pads 40 -> 48, grid (3,)
_L, _MM, _BLOCK_L = 12, 9, 8   # pads 12 -> 16, grid (2,)
_DT = jnp.float16


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, _DT)


def _sds32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _unwrap(fn):
    # the public entry points are jit'd (static block/interpret args);
    # trace the underlying function so the recorder always sees the
    # pallas_call even when a jit cache entry exists
    return getattr(fn, "__wrapped__", fn)


def _grad_sum(fn, n_args: int):
    def loss(*args):
        out_re, out_im = fn(*args)
        return (out_re.astype(jnp.float32).sum()
                + out_im.astype(jnp.float32).sum())

    return jax.grad(loss, argnums=tuple(range(n_args)))


def _trace(fn, *abstract_args) -> List[KernelCall]:
    with record_pallas_calls() as records:
        jax.eval_shape(fn, *abstract_args)
    return records


def kernel_families() -> List[Tuple[str, Callable[[], List[KernelCall]], Callable[[], int]]]:
    """(family name, tracer, estimator) triples.  The estimator closure
    returns the family's ``*vmem_bytes*`` verdict for the exact shapes
    the tracer uses; the pass checks it covers the recorded tiles."""
    from repro.kernels.spectral_contract import (
        cp_vmem_bytes,
        lshared_vmem_bytes,
        spectral_contract_cp_pallas,
        spectral_contract_lshared_pallas,
        spectral_contract_pallas,
        vmem_bytes,
        vmem_bytes_bwd,
    )

    item = jnp.dtype(_DT).itemsize
    dense = functools.partial(
        _unwrap(spectral_contract_pallas),
        block_m=_BLOCK_M, interpret=True, out_dtype=_DT)
    dense_args = (_sds(_B, _I, _M), _sds(_B, _I, _M),
                  _sds(_I, _O, _M), _sds(_I, _O, _M))
    # the fused-cast variant streams f32 operand tiles and rounds onto
    # the half grid in the tile prologue — its working set prices at
    # itemsize 4
    dense_fused = functools.partial(
        _unwrap(spectral_contract_pallas),
        block_m=_BLOCK_M, interpret=True, out_dtype=_DT, cast_to=_DT)
    dense_fused_args = (_sds32(_B, _I, _M), _sds32(_B, _I, _M),
                        _sds32(_I, _O, _M), _sds32(_I, _O, _M))
    cp = functools.partial(
        _unwrap(spectral_contract_cp_pallas),
        block_m=_BLOCK_M, interpret=True, out_dtype=_DT)
    cp_args = (_sds(_B, _I, _M), _sds(_B, _I, _M),
               _sds(_I, _R), _sds(_I, _R), _sds(_O, _R), _sds(_O, _R),
               _sds(_R, _M), _sds(_R, _M))
    lsh = functools.partial(
        _unwrap(spectral_contract_lshared_pallas),
        block_l=_BLOCK_L, interpret=True, out_dtype=_DT)
    lsh_args = (_sds(_B, _I, _L, _MM), _sds(_B, _I, _L, _MM),
                _sds(_I, _O, _L), _sds(_I, _O, _L))
    return [
        ("dense/fwd", lambda: _trace(dense, *dense_args),
         lambda: vmem_bytes(_B, _I, _O, _BLOCK_M, item)),
        ("dense/bwd", lambda: _trace(_grad_sum(dense, 4), *dense_args),
         lambda: vmem_bytes_bwd(_B, _I, _O, _BLOCK_M, item)),
        ("dense-fused/fwd", lambda: _trace(dense_fused, *dense_fused_args),
         lambda: vmem_bytes(_B, _I, _O, _BLOCK_M, 4)),
        ("dense-fused/bwd",
         lambda: _trace(_grad_sum(dense_fused, 4), *dense_fused_args),
         lambda: vmem_bytes_bwd(_B, _I, _O, _BLOCK_M, 4)),
        ("cp/fwd", lambda: _trace(cp, *cp_args),
         lambda: cp_vmem_bytes(_B, _I, _O, _R, _BLOCK_M, item)),
        ("cp/bwd", lambda: _trace(_grad_sum(cp, 8), *cp_args),
         lambda: cp_vmem_bytes(_B, _I, _O, _R, _BLOCK_M, item)),
        ("lshared/fwd", lambda: _trace(lsh, *lsh_args),
         lambda: lshared_vmem_bytes(_B, _I, _O, _MM, _BLOCK_L, item)),
        ("lshared/bwd", lambda: _trace(_grad_sum(lsh, 4), *lsh_args),
         lambda: lshared_vmem_bytes(_B, _I, _O, _MM, _BLOCK_L, item)),
    ]


def calibration_pass(path: Optional[str] = None) -> List[Finding]:
    """calibration-coverage: every tuned entry in a calibration-state
    file must be priced under the VMEM budget by its own family's
    ``*vmem_bytes*`` estimator — the autotuner, the static heuristics
    and the dry-run ``fits_vmem`` verdicts all share that vocabulary, so
    an entry the estimators cannot cover is either corrupt or was tuned
    against a different memory model and must not steer tiling.

    ``path`` defaults to ``$REPRO_CALIBRATION_STATE``; no path means no
    findings (the check only gates states that would actually be used).
    """
    import os

    findings: List[Finding] = []
    from repro.tune import cache as tcache
    from repro.tune.space import family_itemsize, tile_vmem_bytes
    from repro.kernels.spectral_contract import VMEM_BUDGET

    path = path or os.environ.get(tcache.ENV_VAR)
    if not path:
        return findings
    try:
        state = tcache.load(path)
    except tcache.CalibrationError as e:
        findings.append(Finding(
            pass_name="kernels", check="calibration-coverage",
            severity=ERROR, site=None, where=str(path), detail=str(e)))
        return findings
    for key, ent in sorted(state.entries.items()):
        where = f"calibration:{key}"
        if not tcache._entry_ok(ent):
            findings.append(Finding(
                pass_name="kernels", check="calibration-coverage",
                severity=ERROR, site=None, where=where,
                detail="corrupt entry: unknown family or non-power-of-two "
                       "block (lookup would skip it; tuner must not have "
                       "written it)"))
            continue
        itemsize = family_itemsize(ent["family"], ent["dtype"])
        for direction, field in (("fwd", "block_fwd"), ("bwd", "block_bwd")):
            try:
                need = tile_vmem_bytes(ent["family"], ent["shape"],
                                       int(ent[field]), itemsize, direction)
            except (KeyError, TypeError, ValueError) as e:
                findings.append(Finding(
                    pass_name="kernels", check="calibration-coverage",
                    severity=ERROR, site=None, where=where,
                    detail=f"{direction} tile not priceable by the family "
                           f"estimator: {e}"))
                continue
            if need > VMEM_BUDGET:
                findings.append(Finding(
                    pass_name="kernels", check="calibration-coverage",
                    severity=ERROR, site=None, where=where,
                    detail=f"{direction} tile {ent[field]} prices at {need} "
                           f"B — over the {VMEM_BUDGET} B VMEM budget; the "
                           f"estimators do not cover this entry"))
    return findings


def kernels_pass() -> List[Finding]:
    findings: List[Finding] = []
    for family, tracer, estimator in kernel_families():
        records = tracer()
        if not records:
            findings.append(Finding(
                pass_name="kernels", check="no-kernel-traced",
                severity=ERROR, site=None, where=family,
                detail="tracing the family recorded no pallas_call — the "
                       "recorder or the entry point is broken",
            ))
            continue
        worst_tiles = 0
        for call in records:
            where = f"{family}:{call.name}"
            findings.extend(check_call(call, where))
            worst_tiles = max(worst_tiles, tile_bytes(call))
        est = estimator()
        if est < worst_tiles:
            findings.append(Finding(
                pass_name="kernels", check="vmem-underestimate",
                severity=ERROR, site=None, where=family,
                detail=f"vmem estimator reports {est} B but the BlockSpecs "
                       f"constructed occupy {worst_tiles} B of tiles — "
                       f"fits_vmem verdicts would underestimate",
            ))
    return findings
