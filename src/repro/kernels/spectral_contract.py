"""Pallas TPU kernels for the mixed-precision spectral tensor contraction.

This is the paper's compute hot-spot (Appendix B.4: complex-valued tensor
contraction = 4 of the top-5 GPU kernels, forward *and* backward).  The GPU
implementation uses ``view_as_real`` + cuBLAS half GEMMs; the TPU-native
adaptation tiles the contraction over *retained Fourier modes* into VMEM and
issues, per tile, a batched complex matmul as four real MXU matmuls with f32
accumulation:

    out[b,o,m] = Σ_i x[b,i,m] · w[i,o,m]          (complex, per mode m)

The op is **training-grade**: it carries a ``jax.custom_vjp`` whose backward
pass is two more Pallas kernels on the *same* mode-tiled schedule —

    dL/dx[b,i,m] = Σ_o g[b,o,m] · conj(w[i,o,m])     (contract O per tile)
    dL/dw[i,o,m] = Σ_b conj(x[b,i,m]) · g[b,o,m]     (contract B per tile)

— which are exactly the real-valued VJPs of the split-real 4-matmul forward
(the conjugations fall out of the rr−ii / ri+ir component algebra).  Both
accumulate at f32 (f64 under an ``enable_x64`` gradcheck) and store at the
primal dtypes, matching the forward's error model: half *storage*, full
*accumulation* — precisely what Theorem 3.2 bounds.

A second kernel family handles the **CP-factorised** contraction (TFNO,
paper §4.6).  The wrapper folds λ and the per-mode factors into one mode
factor ``W[r,m] = λ_r Π_k U_mk[m_k,r]`` (tiny, jnp, differentiable) and the
kernel then runs, per mode tile, the three factorised stages without ever
materialising the dense (I,O,M) weight:

    t[b,m,r] = Σ_i x[b,i,m] U_i[i,r]      rank-project   (4 real matmuls)
    u[b,m,r] = t[b,m,r] · W[r,m]          mode-scale     (VPU elementwise)
    o[b,o,m] = Σ_r u[b,m,r] U_o[o,r]      rank-expand    (4 real matmuls)

Its backward is one Pallas kernel that recomputes t,u in-tile (cheaper than
saving rank-space residuals to HBM) and emits all four gradients; dU_i/dU_o
are mode-independent, so their output blocks revisit across the sequential
grid and accumulate in place at f32.

Layout decisions (HBM→VMEM→MXU):
  * modes are flattened to one axis ``M`` and tiled by ``block_m`` — see
    ``vmem_bytes`` / ``cp_vmem_bytes`` for the per-step VMEM working set and
    ``pick_block_m`` for budget-driven tile selection;
  * channels (I, O) and CP ranks are contracted with
    ``preferred_element_type=float32`` so accumulation never happens in
    half precision — only *storage* is half;
  * the 4-multiply complex product (rr−ii, ri+ir) is used rather than
    Karatsuba 3-mult: on the MXU the extra multiply is free relative to
    the added adds/temporaries of the 3-mult form.

Validated against ``ref.spectral_contract_ref`` / ``spectral_contract_cp_ref``
in interpret mode on CPU (tests/test_kernels.py, tests/test_kernels_diff.py);
on TPU the same code path compiles natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Scoped VMEM the tile pickers pack their working-set estimates under.
VMEM_BUDGET = 32 * 2 ** 20

#: The scoped-VMEM limit every kernel here asks Mosaic for: twice the
#: budget (v5e holds 128 MiB of VMEM; Mosaic's default limit is 16 MiB).
#: The estimators below count the pipelined blocks and the f32 products;
#: Mosaic also keeps scratch they do not model (operand transposes, f32
#: matmul passes), up to as much again — at FNO_DARCY width under
#: ``full`` the dense kernel's smallest legal tile needs 17.8 MiB.
VMEM_LIMIT = 2 * VMEM_BUDGET
MOSAIC_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)

#: Version of the kernel *schedules* in this module (tile layouts, matmul
#: decomposition, accumulation discipline).  Bump whenever a change could
#: shift the perf landscape — every ``repro.tune`` calibration entry is
#: keyed by this value, so a bump invalidates stale tuning results
#: without anyone having to remember to delete the cache file.
#: v3: the fused rFFT→contract→irFFT family (``spectral_fused``) joins
#: the registry, batch-tiled with its own VMEM estimators.
#: v4: Mosaic-compilable layouts — lane-legal mode tiles, the CP backward
#: without two-dim contractions, the l-shared weight degree-major on a
#: (degree, batch-row) grid — and estimators that count double buffers.
KERNEL_VERSION = 4


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` (every kernel entry point's default) resolves
    from the backend: compiled Mosaic on a TPU, the Pallas interpreter
    elsewhere.  An explicit bool wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _acc_dtype(dtype) -> jnp.dtype:
    """Accumulator dtype: f32 everywhere except under an x64 gradcheck."""
    return jnp.float64 if jnp.dtype(dtype) == jnp.float64 else jnp.float32


def _pad_modes(a: jnp.ndarray, block_m: int) -> jnp.ndarray:
    pad = (-a.shape[-1]) % block_m
    if not pad:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])


# ---------------------------------------------------------------------------
# Dense kernels: forward + the two backward contractions
# ---------------------------------------------------------------------------


def _cast_tiles(cast_to, *tiles):
    """Fused storage-cast prologue: round freshly-loaded f32 tiles onto
    the site's half storage grid *in VMEM*, so the half copy of the
    operands never round-trips through HBM.  ``astype`` here performs
    exactly the rounding ``ComplexPair.from_complex`` would have done on
    the unfused path — the Thm 3.2 representation error is identical,
    only the HBM traffic changes."""
    if cast_to is None:
        return tiles
    return tuple(t.astype(cast_to) for t in tiles)


def _dense_fwd_kernel(xr_ref, xi_ref, wr_ref, wi_ref, or_ref, oi_ref,
                      *, cast_to=None):
    """One mode-tile step: batched (over modes) complex matmul.

    Refs (VMEM tiles):
      xr/xi: (B, I, TM)   wr/wi: (I, O, TM)   or/oi: (B, O, TM)
    ``cast_to``: fused-quantise mode — refs hold f32 and the storage
    rounding happens in the tile prologue (see ``_cast_tiles``).
    """
    xr, xi = xr_ref[...], xi_ref[...]
    wr, wi = wr_ref[...], wi_ref[...]
    xr, xi, wr, wi = _cast_tiles(cast_to, xr, xi, wr, wi)
    acc = _acc_dtype(xr.dtype)

    def bmm(a, b):
        # contract I; batch over the mode tile axis (last axis of both).
        # dot_general batch dims lead the output: (TM, B, O).
        return jax.lax.dot_general(
            a,
            b,
            dimension_numbers=(((1,), (0,)), ((2,), (2,))),
            preferred_element_type=acc,
        )

    rr = bmm(xr, wr)
    ii = bmm(xi, wi)
    ri = bmm(xr, wi)
    ir = bmm(xi, wr)
    or_ref[...] = jnp.transpose(rr - ii, (1, 2, 0)).astype(or_ref.dtype)
    oi_ref[...] = jnp.transpose(ri + ir, (1, 2, 0)).astype(oi_ref.dtype)


def _dense_bwd_x_kernel(gr_ref, gi_ref, wr_ref, wi_ref, dxr_ref, dxi_ref,
                        *, cast_to=None):
    """dx = g · conj(w): contract O per mode tile.

    Refs: gr/gi (B, O, TM), wr/wi (I, O, TM) -> dxr/dxi (B, I, TM).
    Split-real: dxr = Σ_o gr·wr + gi·wi ; dxi = Σ_o gi·wr − gr·wi.
    """
    gr, gi = gr_ref[...], gi_ref[...]
    wr, wi = wr_ref[...], wi_ref[...]
    gr, gi, wr, wi = _cast_tiles(cast_to, gr, gi, wr, wi)
    acc = _acc_dtype(gr.dtype)

    def bmm(a, b):
        # (B,O,TM) x (I,O,TM): contract O, batch TM -> (TM, B, I)
        return jax.lax.dot_general(
            a, b, (((1,), (1,)), ((2,), (2,))), preferred_element_type=acc
        )

    dxr = bmm(gr, wr) + bmm(gi, wi)
    dxi = bmm(gi, wr) - bmm(gr, wi)
    dxr_ref[...] = jnp.transpose(dxr, (1, 2, 0)).astype(dxr_ref.dtype)
    dxi_ref[...] = jnp.transpose(dxi, (1, 2, 0)).astype(dxi_ref.dtype)


def _dense_bwd_w_kernel(xr_ref, xi_ref, gr_ref, gi_ref, dwr_ref, dwi_ref,
                        *, cast_to=None):
    """dw = conj(x) · g: contract B per mode tile.

    Refs: xr/xi (B, I, TM), gr/gi (B, O, TM) -> dwr/dwi (I, O, TM).
    Split-real: dwr = Σ_b xr·gr + xi·gi ; dwi = Σ_b xr·gi − xi·gr.
    """
    xr, xi = xr_ref[...], xi_ref[...]
    gr, gi = gr_ref[...], gi_ref[...]
    xr, xi, gr, gi = _cast_tiles(cast_to, xr, xi, gr, gi)
    acc = _acc_dtype(xr.dtype)

    def bmm(a, b):
        # (B,I,TM) x (B,O,TM): contract B, batch TM -> (TM, I, O)
        return jax.lax.dot_general(
            a, b, (((0,), (0,)), ((2,), (2,))), preferred_element_type=acc
        )

    dwr = bmm(xr, gr) + bmm(xi, gi)
    dwi = bmm(xr, gi) - bmm(xi, gr)
    dwr_ref[...] = jnp.transpose(dwr, (1, 2, 0)).astype(dwr_ref.dtype)
    dwi_ref[...] = jnp.transpose(dwi, (1, 2, 0)).astype(dwi_ref.dtype)


def _dense_call(kernel, a_specs, out_specs, out_shapes, grid, interpret, *args):
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=a_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=MOSAIC_PARAMS,
    )(*args)


def _x_spec(B, I, block_m):
    return pl.BlockSpec((B, I, block_m), lambda m: (0, 0, m))


def _dense_fwd_call(config, xr, xi, wr, wi):
    block_m, _block_m_bwd, interpret, out_dtype, cast_to = config
    B, I, M = xr.shape
    _, O, _ = wr.shape
    xr, xi, wr, wi = (_pad_modes(a, block_m) for a in (xr, xi, wr, wi))
    Mp = xr.shape[-1]
    out_re, out_im = _dense_call(
        functools.partial(_dense_fwd_kernel, cast_to=cast_to),
        [_x_spec(B, I, block_m)] * 2 + [_x_spec(I, O, block_m)] * 2,
        [_x_spec(B, O, block_m)] * 2,
        [jax.ShapeDtypeStruct((B, O, Mp), out_dtype)] * 2,
        (Mp // block_m,),
        interpret,
        xr, xi, wr, wi,
    )
    return out_re[..., :M], out_im[..., :M]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dense_op(config, xr, xi, wr, wi):
    return _dense_fwd_call(config, xr, xi, wr, wi)


def _dense_op_fwd(config, xr, xi, wr, wi):
    return _dense_fwd_call(config, xr, xi, wr, wi), (xr, xi, wr, wi)


def _dense_op_bwd(config, res, cts):
    xr, xi, wr, wi = res
    gr, gi = cts
    _block_m, block_m, interpret, _, cast_to = config
    B, I, M = xr.shape
    _, O, _ = wr.shape
    grp, gip = _pad_modes(gr, block_m), _pad_modes(gi, block_m)
    wrp, wip = _pad_modes(wr, block_m), _pad_modes(wi, block_m)
    xrp, xip = _pad_modes(xr, block_m), _pad_modes(xi, block_m)
    Mp = grp.shape[-1]
    grid = (Mp // block_m,)
    dxr, dxi = _dense_call(
        functools.partial(_dense_bwd_x_kernel, cast_to=cast_to),
        [_x_spec(B, O, block_m)] * 2 + [_x_spec(I, O, block_m)] * 2,
        [_x_spec(B, I, block_m)] * 2,
        [jax.ShapeDtypeStruct((B, I, Mp), xr.dtype)] * 2,
        grid, interpret, grp, gip, wrp, wip,
    )
    dwr, dwi = _dense_call(
        functools.partial(_dense_bwd_w_kernel, cast_to=cast_to),
        [_x_spec(B, I, block_m)] * 2 + [_x_spec(B, O, block_m)] * 2,
        [_x_spec(I, O, block_m)] * 2,
        [jax.ShapeDtypeStruct((I, O, Mp), wr.dtype)] * 2,
        grid, interpret, xrp, xip, grp, gip,
    )
    return (dxr[..., :M], dxi[..., :M], dwr[..., :M], dwi[..., :M])


_dense_op.defvjp(_dense_op_fwd, _dense_op_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_m_bwd", "interpret", "out_dtype", "cast_to"
    ),
)
def spectral_contract_pallas(
    xr: jnp.ndarray,
    xi: jnp.ndarray,
    wr: jnp.ndarray,
    wi: jnp.ndarray,
    *,
    block_m: int = 128,
    block_m_bwd: int | None = None,
    interpret: bool | None = None,
    out_dtype=None,
    cast_to=None,
) -> tuple:
    """Split-real complex contraction ``bim,iom->bom`` (differentiable).

    Args:
      xr/xi: (B, I, M) half (or f32) real/imag parts of the spectrum tile.
      wr/wi: (I, O, M) spectral weights.
      block_m: mode-tile size (VMEM working set scales linearly in it).
      block_m_bwd: mode-tile size for the two backward kernels (defaults
        to ``block_m``; the autotuner calibrates the directions
        independently because their working sets differ).
      interpret: run the kernel body in Python (CPU validation); None
        resolves from the backend, so on a TPU the kernel compiles to
        Mosaic unless the caller asks otherwise.
      cast_to: fused-quantise mode — pass the half storage dtype and feed
        f32 operands; each tile is rounded onto the storage grid in VMEM
        (same Thm 3.2 representation error as pre-casting in HBM, one
        fewer HBM round-trip).

    Returns (out_re, out_im): (B, O, M) at ``out_dtype`` (default: x dtype).
    Reverse-mode differentiation runs the two backward Pallas kernels
    (``dL/dx = g·w̄``, ``dL/dw = x̄·g``) on the same mode tiling.
    """
    B, I, M = xr.shape
    I2, O, M2 = wr.shape
    if I != I2 or M != M2:
        raise ValueError(
            f"spectral_contract_pallas: x {xr.shape} vs w {wr.shape} — "
            f"expected (B, I, M) and (I, O, M) with matching I and M"
        )
    out_dtype = jnp.dtype(out_dtype or xr.dtype)
    cast_to = jnp.dtype(cast_to) if cast_to is not None else None
    config = (block_m, block_m_bwd or block_m, resolve_interpret(interpret),
              out_dtype, cast_to)
    return _dense_op(config, xr, xi, wr, wi)


# ---------------------------------------------------------------------------
# CP-factorised kernels (TFNO): project -> mode-scale -> expand per tile
# ---------------------------------------------------------------------------


def _cp_fwd_stages(xr, xi, uir, uii, uor, uoi, wr, wi, acc):
    """The three factorised stages at the accumulator dtype; returns
    (tr, ti, ur, ui, our, oui) so the backward can reuse t and u."""

    def dg(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=acc)

    # rank-project: t[b,m,r] = Σ_i x[b,i,m] Ui[i,r]
    d_t = ((1,), (0,))
    tr = dg(xr, uir, d_t) - dg(xi, uii, d_t)
    ti = dg(xr, uii, d_t) + dg(xi, uir, d_t)
    # mode-scale: u[b,m,r] = t[b,m,r] · W[r,m]
    wrT = jnp.transpose(wr, (1, 0)).astype(acc)[None]
    wiT = jnp.transpose(wi, (1, 0)).astype(acc)[None]
    ur = tr * wrT - ti * wiT
    ui = tr * wiT + ti * wrT
    # rank-expand: o[b,m,o] = Σ_r u[b,m,r] Uo[o,r]
    d_o = ((2,), (1,))
    our = dg(ur, uor, d_o) - dg(ui, uoi, d_o)
    oui = dg(ur, uoi, d_o) + dg(ui, uor, d_o)
    return tr, ti, ur, ui, our, oui


def _cp_fwd_kernel(xr_ref, xi_ref, uir_ref, uii_ref, uor_ref, uoi_ref,
                   wr_ref, wi_ref, or_ref, oi_ref):
    """Refs: x (B,I,TM), Ui (I,R), Uo (O,R), W (R,TM) -> out (B,O,TM)."""
    acc = _acc_dtype(xr_ref.dtype)
    _, _, _, _, our, oui = _cp_fwd_stages(
        xr_ref[...], xi_ref[...], uir_ref[...], uii_ref[...],
        uor_ref[...], uoi_ref[...], wr_ref[...], wi_ref[...], acc,
    )
    or_ref[...] = jnp.transpose(our, (0, 2, 1)).astype(or_ref.dtype)
    oi_ref[...] = jnp.transpose(oui, (0, 2, 1)).astype(oi_ref.dtype)


def _cp_bwd_kernel(xr_ref, xi_ref, uir_ref, uii_ref, uor_ref, uoi_ref,
                   wr_ref, wi_ref, gr_ref, gi_ref,
                   dxr_ref, dxi_ref, duir_ref, duii_ref,
                   duor_ref, duoi_ref, dwr_ref, dwi_ref):
    """Full CP backward for one mode tile.

    Recomputes t,u (cheaper than storing rank-space residuals in HBM),
    then:  du = g·Ūo,  dUo += g·ū,  dt = du·W̄,  dW = Σ_b du·t̄,
           dx = dt·Ūi,  dUi += x̄·dt.
    The mode-independent dUi/dUo blocks revisit across the (sequential)
    grid and accumulate in place at f32.
    """
    xr, xi = xr_ref[...], xi_ref[...]
    uir, uii = uir_ref[...], uii_ref[...]
    uor, uoi = uor_ref[...], uoi_ref[...]
    wr, wi = wr_ref[...], wi_ref[...]
    gr, gi = gr_ref[...], gi_ref[...]
    acc = _acc_dtype(xr.dtype)

    def dg(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=acc)

    def dg_bm(a, b):
        # Σ_{b,m} a[b,n,m]·b[b,m,r] -> (n, r): a matmul per batch row
        # (one contracting dim, as Mosaic requires), summed over b
        prods = jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                                    preferred_element_type=acc)
        return jnp.sum(prods, axis=0)

    tr, ti, ur, ui, _, _ = _cp_fwd_stages(
        xr, xi, uir, uii, uor, uoi, wr, wi, acc)

    # du[b,m,r] = Σ_o g[b,o,m]·conj(Uo[o,r])
    d_du = ((1,), (0,))
    dur = dg(gr, uor, d_du) + dg(gi, uoi, d_du)
    dui = dg(gi, uor, d_du) - dg(gr, uoi, d_du)
    # dUo[o,r] = Σ_{b,m} g[b,o,m]·conj(u[b,m,r])   (accumulated over tiles)
    duor = dg_bm(gr, ur) + dg_bm(gi, ui)
    duoi = dg_bm(gi, ur) - dg_bm(gr, ui)
    # dt = du·conj(W)
    wrT = jnp.transpose(wr, (1, 0)).astype(acc)[None]
    wiT = jnp.transpose(wi, (1, 0)).astype(acc)[None]
    dtr = dur * wrT + dui * wiT
    dti = dui * wrT - dur * wiT
    # dW[r,m] = Σ_b du[b,m,r]·conj(t[b,m,r])   (per-tile block)
    dwr = jnp.sum(dur * tr + dui * ti, axis=0)
    dwi = jnp.sum(dui * tr - dur * ti, axis=0)
    dwr_ref[...] = jnp.transpose(dwr, (1, 0)).astype(dwr_ref.dtype)
    dwi_ref[...] = jnp.transpose(dwi, (1, 0)).astype(dwi_ref.dtype)
    # dx[b,i,m] = Σ_r dt[b,m,r]·conj(Ui[i,r])
    d_dx = ((2,), (1,))
    dxr = dg(dtr, uir, d_dx) + dg(dti, uii, d_dx)
    dxi = dg(dti, uir, d_dx) - dg(dtr, uii, d_dx)
    dxr_ref[...] = jnp.transpose(dxr, (0, 2, 1)).astype(dxr_ref.dtype)
    dxi_ref[...] = jnp.transpose(dxi, (0, 2, 1)).astype(dxi_ref.dtype)
    # dUi[i,r] = Σ_{b,m} conj(x[b,i,m])·dt[b,m,r]   (accumulated over tiles)
    duir = dg_bm(xr, dtr) + dg_bm(xi, dti)
    duii = dg_bm(xr, dti) - dg_bm(xi, dtr)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for ref in (duir_ref, duii_ref, duor_ref, duoi_ref):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    duir_ref[...] += duir.astype(duir_ref.dtype)
    duii_ref[...] += duii.astype(duii_ref.dtype)
    duor_ref[...] += duor.astype(duor_ref.dtype)
    duoi_ref[...] += duoi.astype(duoi_ref.dtype)


def _cp_specs(B, I, O, R, block_m):
    x = _x_spec(B, I, block_m)
    ui = pl.BlockSpec((I, R), lambda _m: (0, 0))
    uo = pl.BlockSpec((O, R), lambda _m: (0, 0))
    w = pl.BlockSpec((R, block_m), lambda m: (0, m))
    return x, ui, uo, w


def _cp_fwd_call(config, xr, xi, uir, uii, uor, uoi, wr, wi):
    block_m, _block_m_bwd, interpret, out_dtype = config
    B, I, M = xr.shape
    O, R = uor.shape
    xr, xi, wr, wi = (_pad_modes(a, block_m) for a in (xr, xi, wr, wi))
    Mp = xr.shape[-1]
    x_s, ui_s, uo_s, w_s = _cp_specs(B, I, O, R, block_m)
    out_re, out_im = pl.pallas_call(
        _cp_fwd_kernel,
        grid=(Mp // block_m,),
        in_specs=[x_s, x_s, ui_s, ui_s, uo_s, uo_s, w_s, w_s],
        out_specs=[_x_spec(B, O, block_m)] * 2,
        out_shape=[jax.ShapeDtypeStruct((B, O, Mp), out_dtype)] * 2,
        interpret=interpret,
        compiler_params=MOSAIC_PARAMS,
    )(xr, xi, uir, uii, uor, uoi, wr, wi)
    return out_re[..., :M], out_im[..., :M]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cp_op(config, xr, xi, uir, uii, uor, uoi, wr, wi):
    return _cp_fwd_call(config, xr, xi, uir, uii, uor, uoi, wr, wi)


def _cp_op_fwd(config, xr, xi, uir, uii, uor, uoi, wr, wi):
    out = _cp_fwd_call(config, xr, xi, uir, uii, uor, uoi, wr, wi)
    return out, (xr, xi, uir, uii, uor, uoi, wr, wi)


def _cp_op_bwd(config, res, cts):
    xr, xi, uir, uii, uor, uoi, wr, wi = res
    gr, gi = cts
    _block_m, block_m, interpret, _ = config
    B, I, M = xr.shape
    O, R = uor.shape
    acc = _acc_dtype(xr.dtype)
    xrp, xip, wrp, wip, grp, gip = (
        _pad_modes(a, block_m) for a in (xr, xi, wr, wi, gr, gi))
    Mp = xrp.shape[-1]
    x_s, ui_s, uo_s, w_s = _cp_specs(B, I, O, R, block_m)
    outs = pl.pallas_call(
        _cp_bwd_kernel,
        grid=(Mp // block_m,),
        in_specs=[x_s, x_s, ui_s, ui_s, uo_s, uo_s, w_s, w_s,
                  _x_spec(B, O, block_m), _x_spec(B, O, block_m)],
        out_specs=[x_s, x_s, ui_s, ui_s, uo_s, uo_s, w_s, w_s],
        out_shape=(
            [jax.ShapeDtypeStruct((B, I, Mp), xr.dtype)] * 2
            # factor grads accumulate across revisited blocks at the
            # accumulator dtype; cast back to the primal dtype below
            + [jax.ShapeDtypeStruct((I, R), acc)] * 2
            + [jax.ShapeDtypeStruct((O, R), acc)] * 2
            + [jax.ShapeDtypeStruct((R, Mp), wr.dtype)] * 2
        ),
        interpret=interpret,
        compiler_params=MOSAIC_PARAMS,
    )(xrp, xip, uir, uii, uor, uoi, wrp, wip, grp, gip)
    dxr, dxi, duir, duii, duor, duoi, dwr, dwi = outs
    return (
        dxr[..., :M], dxi[..., :M],
        duir.astype(uir.dtype), duii.astype(uii.dtype),
        duor.astype(uor.dtype), duoi.astype(uoi.dtype),
        dwr[..., :M], dwi[..., :M],
    )


_cp_op.defvjp(_cp_op_fwd, _cp_op_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_m_bwd", "interpret", "out_dtype"),
)
def spectral_contract_cp_pallas(
    xr: jnp.ndarray,
    xi: jnp.ndarray,
    uir: jnp.ndarray,
    uii: jnp.ndarray,
    uor: jnp.ndarray,
    uoi: jnp.ndarray,
    wr: jnp.ndarray,
    wi: jnp.ndarray,
    *,
    block_m: int = 128,
    block_m_bwd: int | None = None,
    interpret: bool | None = None,
    out_dtype=None,
) -> tuple:
    """CP-factorised split-real contraction (differentiable).

    ``out[b,o,m] = Σ_r (Σ_i x[b,i,m]·Ui[i,r]) · W[r,m] · Uo[o,r]``

    Args:
      xr/xi: (B, I, M) spectrum tile;  uir/uii: (I, R) input factor;
      uor/uoi: (O, R) output factor;   wr/wi: (R, M) combined mode factor
      (λ and the per-axis CP factors folded together by the caller).

    Returns (out_re, out_im): (B, O, M) at ``out_dtype`` (default x dtype).
    """
    B, I, M = xr.shape
    I2, R = uir.shape
    O, R2 = uor.shape
    R3, M2 = wr.shape
    if I != I2 or R != R2 or R != R3 or M != M2:
        raise ValueError(
            f"spectral_contract_cp_pallas: inconsistent factor shapes "
            f"x {xr.shape}, Ui {uir.shape}, Uo {uor.shape}, W {wr.shape}"
        )
    out_dtype = jnp.dtype(out_dtype or xr.dtype)
    config = (block_m, block_m_bwd or block_m, resolve_interpret(interpret),
              out_dtype)
    return _cp_op(config, xr, xi, uir, uii, uor, uoi, wr, wi)


# ---------------------------------------------------------------------------
# l-shared kernels (SFNO): weight shared over order m, tiled over degree l
# ---------------------------------------------------------------------------
#
#   out[b,o,l,m] = Σ_i x[b,i,l,m] · w[i,o,l]
#
# The spherical convolution theorem shares the weight across orders m, so
# materialising it as a dense (I, O, l, m) operand for the dense kernel
# would stream mmax× the weight bytes (and materialise an mmax× gradient
# before reduction).  These kernels instead tile over *degrees l* and ride
# m along as a free axis, one batch row per grid step (grid (L/TL, B),
# batch minor), so the VMEM working set does not grow with the batch.
# The kernels hold the weight degree-major, (L, I, O), so its tile
# (TL, I, O) keeps the full channel dims minor — the layout Mosaic tiles
# — whatever the degree tile; the public entry point takes and returns
# the (I, O, L) layout and transposes at the edge.


def _lshared_fwd_kernel(xr_ref, xi_ref, wr_ref, wi_ref, or_ref, oi_ref):
    """Refs: x (1, I, TL, M), w (TL, I, O) -> out (1, O, TL, M)."""
    xr, xi = xr_ref[0], xi_ref[0]
    wr, wi = wr_ref[...], wi_ref[...]
    acc = _acc_dtype(xr.dtype)

    def bmm(a, b):
        # contract I; batch over the degree tile -> (TL, M, O)
        return jax.lax.dot_general(
            a, b, (((0,), (1,)), ((1,), (0,))), preferred_element_type=acc)

    our = bmm(xr, wr) - bmm(xi, wi)
    oui = bmm(xr, wi) + bmm(xi, wr)
    or_ref[0] = jnp.transpose(our, (2, 0, 1)).astype(or_ref.dtype)
    oi_ref[0] = jnp.transpose(oui, (2, 0, 1)).astype(oi_ref.dtype)


def _lshared_bwd_x_kernel(gr_ref, gi_ref, wr_ref, wi_ref, dxr_ref, dxi_ref):
    """dx = g · conj(w): g (1, O, TL, M), w (TL, I, O) -> dx (1, I, TL, M)."""
    gr, gi = gr_ref[0], gi_ref[0]
    wr, wi = wr_ref[...], wi_ref[...]
    acc = _acc_dtype(gr.dtype)

    def bmm(a, b):
        # contract O; batch TL -> (TL, M, I)
        return jax.lax.dot_general(
            a, b, (((0,), (2,)), ((1,), (0,))), preferred_element_type=acc)

    dxr = bmm(gr, wr) + bmm(gi, wi)
    dxi = bmm(gi, wr) - bmm(gr, wi)
    dxr_ref[0] = jnp.transpose(dxr, (2, 0, 1)).astype(dxr_ref.dtype)
    dxi_ref[0] = jnp.transpose(dxi, (2, 0, 1)).astype(dxi_ref.dtype)


def _lshared_bwd_w_kernel(xr_ref, xi_ref, gr_ref, gi_ref, dwr_ref, dwi_ref):
    """dw = conj(x) · g summed over b AND m: -> dw (TL, I, O).  The m
    reduction happens in-tile, so the (I, O, l, m) intermediate the dense
    path would materialise never exists; the b reduction accumulates
    across the batch grid axis into the revisited f32 output block."""
    xr, xi = xr_ref[0], xi_ref[0]
    gr, gi = gr_ref[0], gi_ref[0]
    acc = _acc_dtype(xr.dtype)

    def bmm(a, b):
        # contract M; batch TL -> (TL, I, O)
        return jax.lax.dot_general(
            a, b, (((2,), (2,)), ((1,), (1,))), preferred_element_type=acc)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dwr_ref[...] = jnp.zeros(dwr_ref.shape, dwr_ref.dtype)
        dwi_ref[...] = jnp.zeros(dwi_ref.shape, dwi_ref.dtype)

    dwr_ref[...] += (bmm(xr, gr) + bmm(xi, gi)).astype(dwr_ref.dtype)
    dwi_ref[...] += (bmm(xr, gi) - bmm(xi, gr)).astype(dwi_ref.dtype)


def _pad_l(a: jnp.ndarray, block_l: int, axis: int) -> jnp.ndarray:
    pad = (-a.shape[axis]) % block_l
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _lshared_specs(I, O, Mm, block_l):
    x = pl.BlockSpec((1, I, block_l, Mm), lambda l, b: (b, 0, l, 0))
    g = pl.BlockSpec((1, O, block_l, Mm), lambda l, b: (b, 0, l, 0))
    w = pl.BlockSpec((block_l, I, O), lambda l, b: (l, 0, 0))
    return x, g, w


def _lshared_fwd_call(config, xr, xi, wr, wi):
    block_l, _block_l_bwd, interpret, out_dtype = config
    B, I, L, Mm = xr.shape
    O = wr.shape[2]
    xr, xi = _pad_l(xr, block_l, 2), _pad_l(xi, block_l, 2)
    wr, wi = _pad_l(wr, block_l, 0), _pad_l(wi, block_l, 0)
    Lp = xr.shape[2]
    x_s, g_s, w_s = _lshared_specs(I, O, Mm, block_l)
    out_re, out_im = pl.pallas_call(
        _lshared_fwd_kernel,
        grid=(Lp // block_l, B),
        in_specs=[x_s, x_s, w_s, w_s],
        out_specs=[g_s, g_s],
        out_shape=[jax.ShapeDtypeStruct((B, O, Lp, Mm), out_dtype)] * 2,
        interpret=interpret,
        compiler_params=MOSAIC_PARAMS,
    )(xr, xi, wr, wi)
    return out_re[:, :, :L], out_im[:, :, :L]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lshared_op(config, xr, xi, wr, wi):
    return _lshared_fwd_call(config, xr, xi, wr, wi)


def _lshared_op_fwd(config, xr, xi, wr, wi):
    return _lshared_fwd_call(config, xr, xi, wr, wi), (xr, xi, wr, wi)


def _lshared_op_bwd(config, res, cts):
    xr, xi, wr, wi = res
    gr, gi = cts
    _block_l, block_l, interpret, _ = config
    B, I, L, Mm = xr.shape
    O = wr.shape[2]
    acc = _acc_dtype(xr.dtype)
    xrp, xip = _pad_l(xr, block_l, 2), _pad_l(xi, block_l, 2)
    wrp, wip = _pad_l(wr, block_l, 0), _pad_l(wi, block_l, 0)
    grp, gip = _pad_l(gr, block_l, 2), _pad_l(gi, block_l, 2)
    Lp = xrp.shape[2]
    grid = (Lp // block_l, B)
    x_s, g_s, w_s = _lshared_specs(I, O, Mm, block_l)
    dxr, dxi = pl.pallas_call(
        _lshared_bwd_x_kernel,
        grid=grid,
        in_specs=[g_s, g_s, w_s, w_s],
        out_specs=[x_s, x_s],
        out_shape=[jax.ShapeDtypeStruct((B, I, Lp, Mm), xr.dtype)] * 2,
        interpret=interpret,
        compiler_params=MOSAIC_PARAMS,
    )(grp, gip, wrp, wip)
    dwr, dwi = pl.pallas_call(
        _lshared_bwd_w_kernel,
        grid=grid,
        in_specs=[x_s, x_s, g_s, g_s],
        out_specs=[w_s, w_s],
        # dw accumulates over the batch grid axis at the accumulator
        # dtype; cast back to the primal dtype below
        out_shape=[jax.ShapeDtypeStruct((Lp, I, O), acc)] * 2,
        interpret=interpret,
        compiler_params=MOSAIC_PARAMS,
    )(xrp, xip, grp, gip)
    return (dxr[:, :, :L], dxi[:, :, :L],
            dwr[:L].astype(wr.dtype), dwi[:L].astype(wi.dtype))


_lshared_op.defvjp(_lshared_op_fwd, _lshared_op_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("block_l", "block_l_bwd", "interpret", "out_dtype"),
)
def spectral_contract_lshared_pallas(
    xr: jnp.ndarray,
    xi: jnp.ndarray,
    wr: jnp.ndarray,
    wi: jnp.ndarray,
    *,
    block_l: int = 8,
    block_l_bwd: int | None = None,
    interpret: bool | None = None,
    out_dtype=None,
) -> tuple:
    """Split-real ``bilm,iol->bolm`` with the weight shared over m
    (differentiable; the SFNO spherical contraction).

    xr/xi: (B, I, L, M) spectrum; wr/wi: (I, O, L) per-degree weights,
    transposed here to the kernels' degree-major (L, I, O) layout.
    ``interpret``: None resolves from the backend (compiled on a TPU).
    Returns (out_re, out_im): (B, O, L, M) at ``out_dtype``.
    """
    B, I, L, Mm = xr.shape
    I2, O, L2 = wr.shape
    if I != I2 or L != L2:
        raise ValueError(
            f"spectral_contract_lshared_pallas: x {xr.shape} vs w "
            f"{wr.shape} — expected (B, I, L, M) and (I, O, L)"
        )
    out_dtype = jnp.dtype(out_dtype or xr.dtype)
    config = (block_l, block_l_bwd or block_l, resolve_interpret(interpret),
              out_dtype)
    return _lshared_op(config, xr, xi, jnp.transpose(wr, (2, 0, 1)),
                       jnp.transpose(wi, (2, 0, 1)))


# ---------------------------------------------------------------------------
# Fused spectral megakernel: rFFT -> contract -> irFFT in one Pallas grid
# ---------------------------------------------------------------------------
#
# The staged pipeline round-trips HBM three times per layer: rfftn writes
# the full spectrum, the boundary quantise writes the half copy, the
# contraction writes the truncated output which the scatter + irfftn read
# back.  This family runs the whole pipeline per *batch tile* with the
# spectral activations resident in VMEM throughout:
#
#   1. truncated DFT as matmuls: per axis k the factor F_k holds only the
#      retained mode rows — the low [0, m_k) and high [S_k-m_k, S_k)
#      frequency blocks for every axis but the last, the rfft rows
#      [0, m_d) for the last — so truncation and the 2^(d-1) corner
#      gather cost nothing: they are rows that simply do not exist.
#   2. the boundary quantise (Thm 3.2's representation error) applies to
#      the VMEM-resident spectrum: the half grid via the same ``astype``
#      rounding as ``_cast_tiles`` / the simulated fp8 grid via
#      ``simulate_fp8`` — bit-identical values to the staged boundary,
#      zero HBM-visible casts.
#   3. the mode contraction reuses the dense 4-real-matmul schedule
#      (rr−ii / ri+ir, f32 accumulation) against the corner-gathered
#      weight (I, O, Mh).
#   4. the inverse transform applies per-axis iDFT factors; the last axis
#      folds the hermitian weights (1 for DC/Nyquist, 2 elsewhere) into a
#      real-output cos/sin pair, exactly ``irfftn`` of the zero-scattered
#      spectrum.
#
# The custom VJP runs the transposed pipeline in one backward kernel:
# cotangent -> adjoint iDFT -> (dx via conj(w), dw via conj(xh)) ->
# adjoint DFT -> real part.  dw is mode-independent of the batch grid, so
# its output block revisits every grid step and accumulates in place
# (init-or-accumulate discipline, as the CP backward does).


def _fused_rows(spatial, modes):
    """Retained spectrum rows per axis: 2m for truncated full-FFT axes
    (low+high corner blocks), m for the last (rfft) axis."""
    return tuple(2 * m for m in modes[:-1]) + (modes[-1],)


def fused_exact(spatial, modes) -> bool:
    """Whether the truncated-DFT factorisation is exact for this shape:
    corner blocks must not overlap (2m_k <= S_k) and the last axis must
    retain no more than the rfft spectrum holds."""
    if len(spatial) != len(modes) or not modes:
        return False
    if any(2 * m > s for m, s in zip(modes[:-1], spatial[:-1])):
        return False
    return modes[-1] <= spatial[-1] // 2 + 1


def fused_layout_compiles(modes) -> bool:
    """Whether Mosaic lowers the megakernel's vector reshapes for these
    modes.  On a multi-axis grid the kernel reshapes the flattened
    retained modes to per-axis row blocks and back; Mosaic's layout pass
    refuses that ("infer-vector-layout: unsupported shape cast") unless
    the minor block — the last axis's retained modes — is whole 128-lane
    rows.  A 1-D grid needs no such reshape."""
    return len(modes) == 1 or modes[-1] % LANES == 0


def fused_supported(spatial, modes, *, compiled: bool | None = None) -> bool:
    """Whether the dispatch may run this shape on the megakernel: the
    factorisation is exact and, where the kernel compiles to Mosaic
    (``compiled``; None resolves from the backend), its layout lowers."""
    if not fused_exact(spatial, modes):
        return False
    if compiled is None:
        compiled = not resolve_interpret(None)
    return not compiled or fused_layout_compiles(modes)


def fused_factors(spatial, modes):
    """Precomputed DFT / inverse-DFT factor matrices (numpy, float64).

    Returns a flat tuple: per axis k the forward pair (re, im) of
    ``F_k[mu, t] = exp(-2*pi*i*f_mu*t/S_k)`` over the retained rows, then
    per axis the inverse pair — ``G_k[mu, t] = exp(+2*pi*i*f_mu*t/S_k)/S_k``
    for full-FFT axes and, for the last axis, the real-output pair
    ``C_re[mu, t] = w_mu*cos(2*pi*mu*t/S_d)/S_d``,
    ``C_im[mu, t] = -w_mu*sin(...)/S_d`` with hermitian weights w
    (1 at DC and Nyquist, 2 elsewhere) — so ``y = yh_re@C_re + yh_im@C_im``
    is exactly ``irfftn`` of the zero-scattered truncated spectrum."""
    import numpy as np

    ndim = len(modes)
    fwd, inv = [], []
    for k in range(ndim):
        S, m = int(spatial[k]), int(modes[k])
        last = k == ndim - 1
        if last:
            freqs = np.arange(m)
        else:
            freqs = np.concatenate([np.arange(m), np.arange(S - m, S)])
        ang = 2.0 * np.pi * np.outer(freqs, np.arange(S)) / S
        fwd.append((np.cos(ang), -np.sin(ang)))
        if not last:
            inv.append((np.cos(ang) / S, np.sin(ang) / S))
        else:
            w = np.full(m, 2.0)
            w[0] = 1.0
            if S % 2 == 0 and m - 1 == S // 2:
                w[m - 1] = 1.0  # Nyquist row is its own conjugate
            inv.append((w[:, None] * np.cos(ang) / S,
                        -w[:, None] * np.sin(ang) / S))
    return tuple(x for pair in fwd + inv for x in pair)


def _cplx_apply(ar, ai, fr, fi, axis, f_axis, conj=False):
    """Apply one (split-real) complex factor matrix along ``axis``:
    contract that axis of (ar, ai) with axis ``f_axis`` of the factor and
    put the factor's other axis back in its place.  ``ai=None`` encodes a
    real operand (the pipeline entry).  ``conj`` multiplies by the
    conjugated factor — the adjoint the backward pipeline applies."""

    def td(a, f):
        return jnp.tensordot(a, f, axes=[[axis], [f_axis]])

    if ai is None:
        br, bi = td(ar, fr), td(ar, fi)
        if conj:
            bi = -bi
    elif conj:
        br = td(ar, fr) + td(ai, fi)
        bi = td(ai, fr) - td(ar, fi)
    else:
        br = td(ar, fr) - td(ai, fi)
        bi = td(ar, fi) + td(ai, fr)
    return jnp.moveaxis(br, -1, axis), jnp.moveaxis(bi, -1, axis)


def _fused_quantize(xhr, xhi, cast_to, sim_fmt, acc):
    """The fft_in boundary quantisation on the VMEM-resident spectrum:
    the simulated fp8 grid (Appendix B.11) and/or the half storage grid —
    value-identical to the staged ``fft_in.quantize`` + operand cast."""
    if sim_fmt is not None:
        from repro.core.precision import simulate_fp8

        xhr = simulate_fp8(xhr.astype(jnp.float32), sim_fmt).astype(acc)
        xhi = simulate_fp8(xhi.astype(jnp.float32), sim_fmt).astype(acc)
    return _cast_tiles(cast_to, xhr, xhi)


def _fused_spectrum(x_ref, fwd, cast_to, sim_fmt):
    """x tile -> quantised, mode-flattened split-real spectrum."""
    x = x_ref[...]
    acc = _acc_dtype(x.dtype)
    ar, ai = x.astype(acc), None
    for k, (fr, fi) in enumerate(fwd):
        ar, ai = _cplx_apply(ar, ai, fr, fi, 2 + k, 1)
    lead = ar.shape[:2]
    xhr = ar.reshape(*lead, -1)
    xhi = ai.reshape(*lead, -1)
    xhr, xhi = _fused_quantize(xhr, xhi, cast_to, sim_fmt, acc)
    return xhr, xhi, ar.shape[2:], acc


def _split_factor_refs(fac_refs, ndim):
    vals = [f[...] for f in fac_refs]
    fwd = [(vals[2 * k], vals[2 * k + 1]) for k in range(ndim)]
    inv = [(vals[2 * ndim + 2 * k], vals[2 * ndim + 2 * k + 1])
           for k in range(ndim)]
    return fwd, inv


def _fused_fwd_kernel(*refs, ndim, cast_to=None, sim_fmt=None):
    """One batch-tile step of the fused pipeline.

    Refs: x (BB, I, *spatial) f32, wg re/im (I, O, Mh) f32, then the
    2*ndim forward + 2*ndim inverse factor matrices -> y (BB, O, *spatial).
    ``Mh`` is the flattened retained-row count (2^(ndim-1) * prod(modes)).
    """
    x_ref, wr_ref, wi_ref = refs[:3]
    fwd, inv = _split_factor_refs(refs[3:3 + 4 * ndim], ndim)
    y_ref = refs[-1]

    xhr, xhi, mode_shape, acc = _fused_spectrum(x_ref, fwd, cast_to, sim_fmt)
    wr, wi = _cast_tiles(cast_to, wr_ref[...], wi_ref[...])

    def bmm(a, b):
        # contract I; batch over flattened modes -> (Mh, BB, O)
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((2,), (2,))), preferred_element_type=acc)

    yhr = jnp.transpose(bmm(xhr, wr) - bmm(xhi, wi), (1, 2, 0)).astype(acc)
    yhi = jnp.transpose(bmm(xhr, wi) + bmm(xhi, wr), (1, 2, 0)).astype(acc)
    BB, O = yhr.shape[:2]
    br = yhr.reshape(BB, O, *mode_shape)
    bi = yhi.reshape(BB, O, *mode_shape)
    for k in range(ndim - 1):
        br, bi = _cplx_apply(br, bi, *inv[k], 2 + k, 0)
    cr, ci = inv[ndim - 1]
    ax = 2 + ndim - 1
    # real-output last axis: y = yh_re@C_re + yh_im@C_im (hermitian fold)
    y = (jnp.tensordot(br, cr, axes=[[ax], [0]])
         + jnp.tensordot(bi, ci, axes=[[ax], [0]]))
    y_ref[...] = jnp.moveaxis(y, -1, ax).astype(y_ref.dtype)


def _fused_bwd_kernel(*refs, ndim, cast_to=None, sim_fmt=None):
    """Transposed pipeline for one batch tile: cotangent -> adjoint iDFT
    -> contraction VJPs -> adjoint DFT -> real part.

    Refs: x, wg re/im, the 4*ndim factors, g (BB, O, *spatial) ->
    dx (BB, I, *spatial), dwg re/im (I, O, Mh).  The dw blocks revisit
    across the batch grid: zero-init on the first step, then accumulate.
    """
    x_ref, wr_ref, wi_ref = refs[:3]
    fwd, inv = _split_factor_refs(refs[3:3 + 4 * ndim], ndim)
    g_ref = refs[3 + 4 * ndim]
    dx_ref, dwr_ref, dwi_ref = refs[-3:]

    # recompute the quantised spectrum in-tile (cheaper than saving the
    # VMEM-resident intermediate to HBM, which would defeat the fusion)
    xhr, xhi, mode_shape, acc = _fused_spectrum(x_ref, fwd, cast_to, sim_fmt)
    wr, wi = _cast_tiles(cast_to, wr_ref[...], wi_ref[...])

    # adjoint of the inverse transform: gh = dL/dyh
    g = g_ref[...].astype(acc)
    cr, ci = inv[ndim - 1]
    ax = 2 + ndim - 1
    ghr = jnp.moveaxis(jnp.tensordot(g, cr, axes=[[ax], [1]]), -1, ax)
    ghi = jnp.moveaxis(jnp.tensordot(g, ci, axes=[[ax], [1]]), -1, ax)
    for k in reversed(range(ndim - 1)):
        ghr, ghi = _cplx_apply(ghr, ghi, *inv[k], 2 + k, 1, conj=True)
    BB = ghr.shape[0]
    ghr = ghr.reshape(BB, ghr.shape[1], -1)
    ghi = ghi.reshape(BB, ghi.shape[1], -1)
    # same storage grid as the forward tiles (the dense backward rounds
    # its g tiles identically) — and the matmul operand dtypes must agree
    ghr, ghi = _cast_tiles(cast_to, ghr, ghi)

    def bmm(a, b, dims):
        return jax.lax.dot_general(
            a, b, (dims, ((2,), (2,))), preferred_element_type=acc)

    # dxh = gh . conj(wg): contract O, batch modes -> (Mh, BB, I)
    d_x = ((1,), (1,))
    dxhr = jnp.transpose(bmm(ghr, wr, d_x) + bmm(ghi, wi, d_x), (1, 2, 0))
    dxhi = jnp.transpose(bmm(ghi, wr, d_x) - bmm(ghr, wi, d_x), (1, 2, 0))
    # dwg = conj(xh) . gh: contract BB, batch modes -> (Mh, I, O);
    # batch-independent, so accumulate across the grid
    d_w = ((0,), (0,))
    dwr = jnp.transpose(bmm(xhr, ghr, d_w) + bmm(xhi, ghi, d_w), (1, 2, 0))
    dwi = jnp.transpose(bmm(xhr, ghi, d_w) - bmm(xhi, ghr, d_w), (1, 2, 0))

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dwr_ref[...] = jnp.zeros(dwr_ref.shape, dwr_ref.dtype)
        dwi_ref[...] = jnp.zeros(dwi_ref.shape, dwi_ref.dtype)

    dwr_ref[...] += dwr.astype(dwr_ref.dtype)
    dwi_ref[...] += dwi.astype(dwi_ref.dtype)

    # adjoint of the forward DFT, then project to the real input space
    dar = dxhr.reshape(BB, dxhr.shape[1], *mode_shape).astype(acc)
    dai = dxhi.reshape(BB, dxhi.shape[1], *mode_shape).astype(acc)
    for k in reversed(range(ndim)):
        dar, dai = _cplx_apply(dar, dai, *fwd[k], 2 + k, 0, conj=True)
    dx_ref[...] = dar.astype(dx_ref.dtype)


def _pad_batch(a: jnp.ndarray, block_b: int) -> jnp.ndarray:
    pad = (-a.shape[0]) % block_b
    if not pad:
        return a
    return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))


def _fused_specs(B_block, I, O, Mh, spatial, factors):
    ndim = len(spatial)
    zeros = (0,) * ndim

    def batch_spec(ch):
        return pl.BlockSpec((B_block, ch, *spatial),
                            lambda b: (b, 0, *zeros))

    w_spec = pl.BlockSpec((I, O, Mh), lambda b: (0, 0, 0))
    f_specs = [pl.BlockSpec(f.shape, lambda b: (0, 0)) for f in factors]
    return batch_spec(I), batch_spec(O), w_spec, f_specs


def _fused_fwd_call(config, x, wgr, wgi):
    modes, block_b, _bb_bwd, interpret, out_dtype, cast_to, sim_fmt = config
    B, I = x.shape[:2]
    spatial = x.shape[2:]
    O, Mh = wgr.shape[1], wgr.shape[2]
    acc = _acc_dtype(x.dtype)
    factors = tuple(jnp.asarray(f, acc)
                    for f in fused_factors(spatial, modes))
    xp = _pad_batch(x, block_b)
    Bp = xp.shape[0]
    x_s, y_s, w_s, f_s = _fused_specs(block_b, I, O, Mh, spatial, factors)
    y = pl.pallas_call(
        functools.partial(_fused_fwd_kernel, ndim=len(modes),
                          cast_to=cast_to, sim_fmt=sim_fmt),
        grid=(Bp // block_b,),
        in_specs=[x_s, w_s, w_s, *f_s],
        out_specs=y_s,
        out_shape=jax.ShapeDtypeStruct((Bp, O, *spatial), out_dtype),
        interpret=interpret,
        compiler_params=MOSAIC_PARAMS,
    )(xp, wgr, wgi, *factors)
    return y[:B]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_op(config, x, wgr, wgi):
    return _fused_fwd_call(config, x, wgr, wgi)


def _fused_op_fwd(config, x, wgr, wgi):
    return _fused_fwd_call(config, x, wgr, wgi), (x, wgr, wgi)


def _fused_op_bwd(config, res, g):
    x, wgr, wgi = res
    modes, _bb, block_b, interpret, _out, cast_to, sim_fmt = config
    B, I = x.shape[:2]
    spatial = x.shape[2:]
    O, Mh = wgr.shape[1], wgr.shape[2]
    acc = _acc_dtype(x.dtype)
    factors = tuple(jnp.asarray(f, acc)
                    for f in fused_factors(spatial, modes))
    xp = _pad_batch(x, block_b)
    gp = _pad_batch(g.astype(acc), block_b)
    Bp = xp.shape[0]
    x_s, g_s, w_s, f_s = _fused_specs(block_b, I, O, Mh, spatial, factors)
    dx, dwr, dwi = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, ndim=len(modes),
                          cast_to=cast_to, sim_fmt=sim_fmt),
        grid=(Bp // block_b,),
        in_specs=[x_s, w_s, w_s, *f_s, g_s],
        out_specs=[x_s, w_s, w_s],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, I, *spatial), x.dtype),
            # dw accumulates across revisited blocks at the accumulator
            # dtype; cast back to the primal dtype below
            jax.ShapeDtypeStruct((I, O, Mh), acc),
            jax.ShapeDtypeStruct((I, O, Mh), acc),
        ],
        interpret=interpret,
        compiler_params=MOSAIC_PARAMS,
    )(xp, wgr, wgi, *factors, gp)
    return dx[:B], dwr.astype(wgr.dtype), dwi.astype(wgi.dtype)


_fused_op.defvjp(_fused_op_fwd, _fused_op_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("modes", "block_b", "block_b_bwd", "interpret",
                     "out_dtype", "cast_to", "sim_fmt"),
)
def spectral_fused_pallas(
    x: jnp.ndarray,
    wgr: jnp.ndarray,
    wgi: jnp.ndarray,
    *,
    modes: tuple,
    block_b: int = 1,
    block_b_bwd: int | None = None,
    interpret: bool | None = None,
    out_dtype=None,
    cast_to=None,
    sim_fmt: str | None = None,
) -> jnp.ndarray:
    """Fused rFFT -> quantise -> contract -> irFFT (differentiable).

    Args:
      x: (B, I, *spatial) real f32 physical-space input (post-stabiliser).
      wgr/wgi: (I, O, Mh) corner-gathered split-real spectral weights,
        flattened row-major over the retained rows per axis (2m for every
        truncated full-FFT axis — low block then high block — and m for
        the last, rfft, axis); ``kernels.ops.gather_corner_weights``
        builds this layout from the per-corner (nc, I, O, *modes) params.
      modes: retained modes per axis (static).
      block_b: batch-tile size — the grid walks ceil(B/block_b) steps
        with the whole spectral pipeline VMEM-resident per step.
      cast_to: half storage grid applied to the spectrum AND the weight
        tiles in VMEM (the staged ``fft_in.quantize`` + operand cast).
      sim_fmt: simulated-fp8 grid ("fp8_e4m3" / "fp8_e5m2") applied to
        the spectrum only, before ``cast_to`` (Appendix B.11 boundary).

    Returns y: (B, O, *spatial) real, at ``out_dtype`` (default x dtype).
    Reverse-mode differentiation runs the transposed pipeline in one
    backward Pallas kernel on the ``block_b_bwd`` batch tiling.
    """
    if x.ndim != 2 + len(modes):
        raise ValueError(
            f"spectral_fused_pallas: x {x.shape} vs modes {modes} — "
            f"expected (B, I, *spatial) with one spatial axis per mode")
    spatial = x.shape[2:]
    if not fused_exact(spatial, modes):
        raise ValueError(
            f"spectral_fused_pallas: modes {modes} do not fit spatial "
            f"{spatial} (need 2m <= S per truncated axis and "
            f"m <= S//2+1 on the rfft axis)")
    rows = _fused_rows(spatial, modes)
    Mh = 1
    for r in rows:
        Mh *= r
    if wgr.shape[-1] != Mh or wgr.shape != wgi.shape or wgr.ndim != 3:
        raise ValueError(
            f"spectral_fused_pallas: weight {wgr.shape} — expected "
            f"(I, O, {Mh}) corner-gathered rows for modes {modes}")
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    cast_to = jnp.dtype(cast_to) if cast_to is not None else None
    config = (tuple(int(m) for m in modes), int(block_b),
              int(block_b_bwd or block_b), resolve_interpret(interpret),
              out_dtype, cast_to, sim_fmt)
    return _fused_op(config, x, wgr, wgi)


# ---------------------------------------------------------------------------
# VMEM budgeting
# ---------------------------------------------------------------------------


# Mosaic's tiling rules for the staged families: the minor dim of every
# block is a multiple of 128 lanes or the whole (padded) axis, the
# second-minor a multiple of 8 sublanes or the whole axis.  The
# estimators below count what a compiled grid step holds: every pipelined
# block twice (Pallas double-buffers the HBM<->VMEM copies), the
# half-grid copies a fused storage cast makes of f32 operand tiles, and
# the f32 matmul results and their transposes.  They were checked
# against the TPU compiler's scoped-VMEM verdicts for v5e at the
# configs/fno_paper widths (tests/test_tpu_compile.py compiles them).
LANES = 128
SUBLANES = 8


def _cast_copies(elems: int, itemsize: int) -> int:
    # f32 operand tiles rounded onto a half grid in the tile prologue
    return elems * 2 * 2 if itemsize > 2 else 0


def vmem_bytes(B: int, I: int, O: int, block_m: int, itemsize: int = 2) -> int:
    """Forward VMEM per grid step of the dense kernel: double-buffered
    re/im x, w and out tiles, the cast copies, and six f32 (TM, B, O)
    products and transposes."""
    blocks = 2 * (B * I + I * O + B * O) * block_m * 2 * itemsize
    casts = _cast_copies((B * I + I * O) * block_m, itemsize)
    return blocks + casts + 6 * B * O * block_m * 4


def vmem_bytes_bwd(B: int, I: int, O: int, block_m: int,
                   itemsize: int = 2) -> int:
    """Backward VMEM per grid step: the larger of the dx kernel (g, w
    in; dx out; four f32 (TM, B, I) products live) and the dw kernel
    (x, g in; dw out; four f32 (TM, I, O) products live)."""
    blocks = 2 * (B * O + I * O + B * I) * block_m * 2 * itemsize
    bwd_x = blocks + _cast_copies((B * O + I * O) * block_m, itemsize) \
        + 4 * B * I * block_m * 4
    bwd_w = blocks + _cast_copies((B * I + B * O) * block_m, itemsize) \
        + 4 * I * O * block_m * 4
    return max(bwd_x, bwd_w)


def cp_vmem_bytes(B: int, I: int, O: int, R: int, block_m: int,
                  itemsize: int = 2) -> int:
    """CP kernel VMEM per grid step (the backward dominates: it holds
    the double-buffered x, g, W tiles and rank factors, six f32
    rank-space (B, TM, R) arrays — t, u, du, dt and their conjugate
    partners — and the f32 gradient blocks)."""
    tiles = 2 * (B * I + B * O + R) * block_m * 2 * itemsize   # x, g, W
    factors = 2 * (I * R + O * R) * 2 * itemsize               # Ui, Uo
    rankspace = 6 * B * R * block_m * 2 * 4
    grads = 2 * (I * R + O * R + R * block_m + B * I * block_m) * 2 * 4
    return tiles + factors + rankspace + grads


def lshared_vmem_bytes(B: int, I: int, O: int, Mm: int, block_l: int,
                       itemsize: int = 2) -> int:
    """l-shared (SFNO) kernel VMEM per grid step.  The grid streams one
    batch row per step, so ``B`` does not enter: double-buffered x, g
    and w tiles plus the f32 dw blocks, and six f32 (TL, M, max(I, O))
    products and transposes."""
    del B  # one batch row per grid step
    blocks = 2 * block_l * ((I + O) * Mm * 2 * itemsize
                            + I * O * 2 * itemsize + I * O * 2 * 4)
    return blocks + 6 * max(I, O) * Mm * block_l * 4


def _pad(n: int, to: int) -> int:
    return -(-int(n) // to) * to


def _fused_tile_elems(block_b: int, I: int, O: int, spatial, modes):
    """(x tile, w tile, y tile, factor, worst transform intermediate,
    per-product contraction footprint) element counts for one fused
    grid step."""
    rows = _fused_rows(spatial, modes)
    S = Mh = 1
    for s in spatial:
        S *= int(s)
    for r in rows:
        Mh *= int(r)
    fac = 4 * sum(int(r) * int(s) for r, s in zip(rows, spatial))
    # per-axis transform intermediates: spatial axes collapse to mode
    # rows one at a time, so the worst step holds the largest mixed shape
    # (split re+im) for the wider of the channel counts
    inter, cur = 0, 1
    tail = S
    for k in range(len(modes) + 1):
        inter = max(inter, cur * tail)
        if k < len(modes):
            cur *= int(rows[k])
            tail //= int(spatial[k])
    inter *= 2 * block_b * max(I, O)
    # the mode-batched contraction products, (Mh, BB, O) forward and
    # (Mh, I, O) for dw, each padded to whole (8, 128) vector tiles
    prod = Mh * max(_pad(block_b, SUBLANES) * _pad(max(I, O), LANES),
                    _pad(I, SUBLANES) * _pad(O, LANES))
    return (block_b * I * S, 2 * I * O * Mh, block_b * O * S, fac, inter,
            prod)


def fused_vmem_bytes(block_b: int, I: int, O: int, spatial, modes,
                     itemsize: int = 4) -> int:
    """Forward VMEM working set of one fused grid step: the x / weight /
    output tiles plus the DFT factors, the worst per-axis transform
    intermediate (split-real, accumulator dtype) and the four f32
    contraction products."""
    x_t, w_t, y_t, fac, inter, prod = _fused_tile_elems(
        block_b, I, O, spatial, modes)
    return (x_t + w_t + y_t) * itemsize + fac * 4 + inter * 4 \
        + 4 * prod * 4


def fused_vmem_bytes_bwd(block_b: int, I: int, O: int, spatial, modes,
                         itemsize: int = 4) -> int:
    """Backward working set: the forward tiles plus the cotangent tile,
    the dx tile and the two f32 dw accumulator blocks (the transposed
    pipeline recomputes the spectrum in-tile, so both transform
    intermediates are live), and the four f32 contraction products."""
    x_t, w_t, y_t, fac, inter, prod = _fused_tile_elems(
        block_b, I, O, spatial, modes)
    tiles = (x_t + w_t + 2 * y_t + x_t) * itemsize
    return tiles + w_t * 4 + fac * 4 + 2 * inter * 4 + 4 * prod * 4


def pick_block_b(B: int, I: int, O: int, spatial, modes, *,
                 itemsize: int = 4, budget: int = VMEM_BUDGET // 2,
                 train: bool = True) -> int:
    """Largest power-of-two batch tile whose fused working set fits in
    ``budget`` bytes of VMEM (1 is the heuristic's last resort — callers
    deciding fused-vs-staged should check ``fused_vmem_bytes(1, ...)``
    themselves)."""
    for bb in (8, 4, 2, 1):
        if bb > max(B, 1):
            continue
        need = fused_vmem_bytes(I=I, O=O, spatial=spatial, modes=modes,
                                block_b=bb, itemsize=itemsize)
        if train:
            need = max(need, fused_vmem_bytes_bwd(
                bb, I, O, spatial, modes, itemsize))
        if need <= budget:
            return bb
    return 1


def pick_block_l(B: int, I: int, O: int, L: int, Mm: int, *,
                 itemsize: int = 2, budget: int = VMEM_BUDGET) -> int:
    """Largest power-of-two degree tile fitting the l-shared kernel's
    working set under ``budget`` bytes of VMEM.  The degree axis is a
    block's second-minor dim, so the tile is a multiple of 8 sublanes or
    the whole axis."""
    if L <= SUBLANES:
        return L
    for bl in (256, 128, 64, 32, 16):
        if bl > L:
            continue
        if lshared_vmem_bytes(B, I, O, Mm, bl, itemsize) <= budget:
            return bl
    return SUBLANES


def pick_block_m(B: int, I: int, O: int, M: int, *, rank: int = 0,
                 itemsize: int = 2, budget: int = VMEM_BUDGET,
                 train: bool = True) -> int:
    """Largest mode tile whose fwd (and, for ``train``, bwd) working set
    fits in ``budget`` bytes of VMEM.  The mode axis is a block's minor
    dim, so the tile is a multiple of 128 lanes or the whole axis.
    ``rank > 0`` budgets the CP kernel instead of the dense one."""
    if M <= LANES:
        return M
    for bm in (512, 256):
        if bm > M:
            continue
        if rank:
            need = cp_vmem_bytes(B, I, O, rank, bm, itemsize)
        else:
            need = vmem_bytes(B, I, O, bm, itemsize)
            if train:
                need = max(need, vmem_bytes_bwd(B, I, O, bm, itemsize))
        if need <= budget:
            return bm
    return LANES
