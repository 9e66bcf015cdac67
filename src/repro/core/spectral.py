"""Mixed-precision Fourier convolution operator (paper Section 4.2, Fig. 2).

The FNO layer computes ``(K v)(x) = iFFT( R · T_K( FFT v ) )(x)``.  The paper
runs all three spectral stages — forward FFT, tensor contraction with the
learnable ``R``, inverse FFT — at half precision (Table 4 shows the
all-half setting wins on every metric), with a ``tanh`` pre-activation for
stability and a memory-greedy contraction order.

TPU adaptation (see DESIGN.md §2): XLA has no half-precision FFT on TPU, so
the transform itself runs in f32 while inputs/outputs are **quantised to the
half spectral dtype at the boundary** (``quantize_complex``).  This models
the representation error bounded by Theorem 3.2 — the quantity the paper's
theory actually analyses — and matches what the MXU pipeline does: bf16
storage, f32 accumulation.  The contraction genuinely executes at half
precision via split-real einsums (``core.contraction``), optionally through
the Pallas kernel (``repro.kernels``).

On a TPU the staged path computes only the retained modes: the forward
transform is a truncated DFT and the inverse starts from the retained
corners, both as separable matmuls on the MXU at f32 ``Precision.HIGHEST``
(``truncated_rfft`` / ``truncated_irfft``).  The TPU's FFT lowering would
compute the whole spectrum and transform a zero-filled one back, most of
a training step at the paper's widths.  Elsewhere, and where the corner
blocks overlap, the path runs ``rfftn`` / ``irfftn``.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .precision import ComplexPair
from repro.precision import FULL, PrecisionPolicy


# ---------------------------------------------------------------------------
# Weight initialisation (dense / CP / Tucker factorisations — TFNO)
# ---------------------------------------------------------------------------


def _n_corners(ndim: int) -> int:
    # rfftn halves the last axis only; every other truncated axis keeps the
    # low and high mode blocks => 2^(ndim-1) corner blocks.
    return 2 ** (ndim - 1)


def cp_rank(in_channels: int, out_channels: int, rank: float) -> int:
    """The CP rank a ``rank`` fraction resolves to — shared by the weight
    initialiser and the dry-run VMEM budgeter so they can never drift."""
    return max(1, int(rank * min(in_channels, out_channels) * 2))


def init_spectral_weights(
    key: jax.Array,
    in_channels: int,
    out_channels: int,
    modes: Sequence[int],
    factorization: str = "dense",
    rank: float = 0.5,
) -> dict:
    """Spectral weights R for one layer.

    dense:  complex (corners, in, out, *modes), stored split-real f32.
    cp:     Canonical-Polyadic factors (paper §4.6 uses CP for NS/Darcy):
            weight[i,o,m1..md] = Σ_r λ_r A_i[i,r] A_o[o,r] Π_k A_mk[m_k,r].
    tucker: core (r_i, r_o, r_m1..r_md) + factor matrices.
    """
    ndim = len(modes)
    nc = _n_corners(ndim)
    scale = 1.0 / (in_channels * out_channels)
    if factorization == "dense":
        shape = (nc, in_channels, out_channels, *modes)
        k1, k2 = jax.random.split(key)
        return {
            "w_re": scale * jax.random.normal(k1, shape, jnp.float32),
            "w_im": scale * jax.random.normal(k2, shape, jnp.float32),
        }
    if factorization == "cp":
        r = cp_rank(in_channels, out_channels, rank)
        keys = jax.random.split(key, 2 * (2 + ndim) + 2)
        params = {}
        params["lam_re"] = scale * jax.random.normal(keys[0], (nc, r), jnp.float32)
        params["lam_im"] = scale * jax.random.normal(keys[1], (nc, r), jnp.float32)
        dims = [in_channels, out_channels, *modes]
        names = ["i", "o"] + [f"m{k}" for k in range(ndim)]
        for idx, (nm, ddim) in enumerate(zip(names, dims, strict=True)):
            params[f"U_{nm}_re"] = jax.random.normal(
                keys[2 + 2 * idx], (nc, ddim, r), jnp.float32
            ) / math.sqrt(r)
            params[f"U_{nm}_im"] = jax.random.normal(
                keys[3 + 2 * idx], (nc, ddim, r), jnp.float32
            ) / math.sqrt(r)
        return params
    if factorization == "tucker":
        # ranks proportional to each dim
        dims = [in_channels, out_channels, *modes]
        ranks = [max(1, int(rank * d)) for d in dims]
        keys = jax.random.split(key, 2 + 2 * len(dims))
        params = {}
        params["core_re"] = scale * jax.random.normal(keys[0], (nc, *ranks), jnp.float32)
        params["core_im"] = scale * jax.random.normal(keys[1], (nc, *ranks), jnp.float32)
        names = ["i", "o"] + [f"m{k}" for k in range(len(modes))]
        for idx, (nm, ddim, rr) in enumerate(zip(names, dims, ranks, strict=True)):
            params[f"U_{nm}_re"] = jax.random.normal(
                keys[2 + 2 * idx], (nc, ddim, rr), jnp.float32
            ) / math.sqrt(rr)
            params[f"U_{nm}_im"] = jax.random.normal(
                keys[3 + 2 * idx], (nc, ddim, rr), jnp.float32
            ) / math.sqrt(rr)
        return params
    raise ValueError(f"unknown factorization {factorization!r}")


def spectral_param_count(params: dict) -> int:
    return sum(
        int(v.size) for k, v in params.items() if isinstance(v, jnp.ndarray)
    )


# ---------------------------------------------------------------------------
# Mode-corner slicing
# ---------------------------------------------------------------------------


def _corner_slices(modes: Sequence[int], spectrum_shape: Sequence[int]):
    """Slices selecting each retained corner of the (r)fft spectrum.

    For every axis but the last we keep [:m] and [-m:]; the last (rfft) axis
    keeps [:m] only.  Yields tuples of slices, one per corner, ordered so
    that corner index bits map to axes (bit k set => high block on axis k).
    """
    ndim = len(modes)
    nc = _n_corners(ndim)
    out = []
    for c in range(nc):
        sl = []
        for ax in range(ndim - 1):
            m = modes[ax]
            if (c >> ax) & 1:
                sl.append(slice(spectrum_shape[ax] - m, spectrum_shape[ax]))
            else:
                sl.append(slice(0, m))
        sl.append(slice(0, modes[-1]))
        out.append(tuple(sl))
    return out


_EINSUM_SPATIAL = "xyzuvw"


def _dense_expr(ndim: int) -> str:
    sp = _EINSUM_SPATIAL[:ndim]
    return f"bi{sp},io{sp}->bo{sp}"


def _cp_exprs(ndim: int) -> str:
    sp = _EINSUM_SPATIAL[:ndim]
    mode_terms = ",".join(f"{ch}r" for ch in sp)
    return f"bi{sp},r,ir,or,{mode_terms}->bo{sp}"


def _tucker_expr(ndim: int) -> str:
    sp = _EINSUM_SPATIAL[:ndim]
    caps = "RSABCD"  # rank index letters: R=in-rank, S=out-rank, then modes
    core = "RS" + caps[2 : 2 + ndim]
    mode_terms = ",".join(f"{ch}{caps[2+k]}" for k, ch in enumerate(sp))
    return f"bi{sp},{core},iR,oS,{mode_terms}->bo{sp}"


def _kind(params: dict) -> str:
    """Infer the factorisation kind from the parameter keys (the params
    pytree must stay array-only so it is a valid grad/optimizer target)."""
    if "w_re" in params:
        return "dense"
    if "lam_re" in params:
        return "cp"
    if "core_re" in params:
        return "tucker"
    raise ValueError(f"unrecognised spectral params: {sorted(params)}")


def _corner_weight_ops(params: dict, corner: int, ndim: int):
    """Return (expr_suffix_ops, expr) for one corner's contraction."""
    kind = _kind(params)
    if kind == "dense":
        w = jax.lax.complex(params["w_re"][corner], params["w_im"][corner])
        return [w], _dense_expr(ndim)
    if kind == "cp":
        ops = [jax.lax.complex(params["lam_re"][corner], params["lam_im"][corner])]
        for nm in ["i", "o"] + [f"m{k}" for k in range(ndim)]:
            ops.append(
                jax.lax.complex(params[f"U_{nm}_re"][corner], params[f"U_{nm}_im"][corner])
            )
        return ops, _cp_exprs(ndim)
    if kind == "tucker":
        ops = [jax.lax.complex(params["core_re"][corner], params["core_im"][corner])]
        for nm in ["i", "o"] + [f"m{k}" for k in range(ndim)]:
            ops.append(
                jax.lax.complex(params[f"U_{nm}_re"][corner], params[f"U_{nm}_im"][corner])
            )
        return ops, _tucker_expr(ndim)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Truncated transforms as DFT matmuls (the TPU path)
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def dft_transforms(spatial: Sequence[int], modes: Sequence[int]) -> bool:
    """Whether the staged path runs its transforms as truncated-DFT
    matmuls: on a TPU, and where the factorisation is exact (the corner
    blocks do not overlap, where the FFT path's scatter would overwrite
    what a sum of corners adds)."""
    from repro.kernels.spectral_contract import fused_exact

    return _on_tpu() and fused_exact(tuple(spatial), tuple(modes))


def _full_axis_order(spatial: Sequence[int], modes: Sequence[int]):
    """The full (non-rfft) axes in the order that costs the forward the
    fewest FLOPs.  Transforming axis k costs the current size times m_k
    and leaves the size shrunk by r_k = 2m_k/S_k, so by exchange of
    neighbours the cheapest order sorts by m_k / (1 - r_k); the inverse
    runs the reverse order."""
    def cost(k):
        r = 2 * modes[k] / spatial[k]
        return math.inf if r >= 1 else modes[k] / (1 - r)

    return sorted(range(len(modes) - 1), key=cost)


def _complex_axis(re, im, mr, mi, axis: int):
    """Apply the complex matrix ``mr + i·mi`` (rows out, columns in) along
    ``axis`` of ``re + i·im``: one real matmul of ``[re; im]`` against its
    real block form, whose output holds the real then the imaginary
    rows."""
    blk = np.block([[mr.T, mi.T], [-mi.T, mr.T]]).astype(np.float32)
    z = jnp.tensordot(jnp.concatenate([re, im], axis=axis), blk,
                      axes=[[axis], [0]], precision=jax.lax.Precision.HIGHEST)
    return jnp.split(jnp.moveaxis(z, -1, axis), 2, axis=axis)


def rfft_rows(x: jnp.ndarray, m: int) -> jnp.ndarray:
    """The first ``m`` orders of ``rfft`` along the last axis of the real
    ``x``, as one real matmul against ``[F_re^T | F_im^T]`` at f32
    ``Precision.HIGHEST``: shape (..., 2m), the real parts then the
    imaginary ones."""
    from repro.kernels.spectral_contract import fused_factors

    fr, fi = fused_factors((x.shape[-1],), (m,))[:2]
    f = np.concatenate([fr.T, fi.T], axis=1).astype(np.float32)
    return jnp.tensordot(x, f, axes=[[x.ndim - 1], [0]],
                         precision=jax.lax.Precision.HIGHEST)


def irfft_rows(z: jnp.ndarray, n: int) -> jnp.ndarray:
    """``irfft(·, n)`` along the last axis of the spectrum whose first
    ``m`` orders ``z`` (..., 2m) holds, real parts then imaginary ones
    (the layout ``rfft_rows`` returns), and zeros above: one real matmul
    against the real-output pair ``[C_re; C_im]`` with its hermitian
    weights, at f32 ``Precision.HIGHEST``."""
    from repro.kernels.spectral_contract import fused_factors

    cr, ci = fused_factors((n,), (z.shape[-1] // 2,))[2:]
    c = np.concatenate([cr, ci], axis=0).astype(np.float32)
    return jnp.tensordot(z, c, axes=[[z.ndim - 1], [0]],
                         precision=jax.lax.Precision.HIGHEST)


def truncated_rfft(x: jnp.ndarray, modes: Sequence[int]) -> jnp.ndarray:
    """The retained modes of ``rfftn(x)`` over the spatial axes of ``x``
    (batch, ch, *spatial), computed as DFT matmuls: complex64 of shape
    (batch, ch, 2m_0, ..., 2m_{d-2}, m_{d-1}).  Each full axis holds its
    low block ``[:m]`` then its high block ``[-m:]``, so
    ``_corner_slices(modes, result.shape[2:])`` cuts the corners of
    ``_corner_slices(modes, rfftn(x).shape[2:])``.  The last (rfft) axis,
    which shrinks most, goes first, as one real matmul against
    ``[F_re | F_im]``; every matmul runs at f32 ``Precision.HIGHEST``."""
    from repro.kernels.spectral_contract import fused_factors

    spatial = x.shape[2:]
    facs = fused_factors(spatial, modes)
    re, im = jnp.split(rfft_rows(x, modes[-1]), 2, axis=-1)
    for k in _full_axis_order(spatial, modes):
        re, im = _complex_axis(re, im, facs[2 * k], facs[2 * k + 1], 2 + k)
    return jax.lax.complex(re, im)


def truncated_irfft(yh: jnp.ndarray, spatial: Sequence[int],
                    modes: Sequence[int]) -> jnp.ndarray:
    """``irfftn`` over ``spatial`` of the spectrum that holds the stacked
    corners ``yh`` (batch, ch, 2m_0, ..., m_{d-1}; the layout
    ``truncated_rfft`` returns) and zeros elsewhere, computed as
    inverse-DFT matmuls from the corners alone: the full axes first
    (2m -> S), then the real-output ``C_re``/``C_im`` pair, with its
    hermitian weights, along the last axis.  f32 ``Precision.HIGHEST``."""
    from repro.kernels.spectral_contract import fused_factors

    ndim = len(modes)
    inv = fused_factors(spatial, modes)[2 * ndim:]
    re, im = jnp.real(yh), jnp.imag(yh)
    for k in reversed(_full_axis_order(spatial, modes)):
        re, im = _complex_axis(re, im, inv[2 * k].T, inv[2 * k + 1].T, 2 + k)
    return irfft_rows(jnp.concatenate([re, im], axis=-1), spatial[-1])


def _stack_corners(corners, ndim: int) -> jnp.ndarray:
    """Join the per-corner blocks (``_corner_slices`` order: bit k of the
    corner index picks the high block on axis k) into the stacked layout
    of ``truncated_rfft``, the highest bit's axis first."""
    for k in reversed(range(ndim - 1)):
        half = len(corners) // 2
        corners = [jnp.concatenate([lo, hi], axis=2 + k)
                   for lo, hi in zip(corners[:half], corners[half:],
                                     strict=True)]
    return corners[0]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def spectral_conv_apply(
    params: dict,
    x: jnp.ndarray,
    modes: Sequence[int],
    policy: PrecisionPolicy = FULL,
    use_pallas: Optional[bool] = None,
    site: str = "model/spectral",
    fuse_spectral: Optional[bool] = None,
) -> jnp.ndarray:
    """Apply the Fourier convolution to ``x`` of shape (batch, ch, *spatial).

    Pipeline (Fig. 2): [stabiliser] -> FFT -> quantise -> truncate ->
    contract (memory-greedy, split-real half) -> scatter -> dequantise ->
    iFFT.  Where ``dft_transforms`` holds (a TPU), FFT + truncate is
    ``truncated_rfft`` and scatter + iFFT is ``truncated_irfft``, under
    the same scopes.  Each stage resolves its precision through the rule
    table at ``{site}/fft_in``, ``{site}/contract`` and ``{site}/fft_out``
    — callers pass a per-layer prefix (``"fno/layer2/spectral"``) so
    layers can be addressed individually.  Under the ``full`` rule set every site
    resolves to f32/complex64 and this is the exact full-precision FNO
    reference.

    ``use_pallas``: tri-state.  ``None`` resolves via
    ``kernels.ops.resolve_use_pallas`` (on for TPU backends and under
    ``REPRO_USE_PALLAS=1``); when on, dense and CP contractions run the
    training-grade Pallas kernels (custom-VJP backward, same telemetry
    taps), while Tucker keeps the einsum path — its core tensor has no
    mode-major kernel layout.

    ``fuse_spectral``: tri-state (``kernels.ops.resolve_fuse_spectral``;
    kill switch ``REPRO_FUSE_SPECTRAL=0``).  When it resolves on — and
    the Pallas path is active, the layer is dense, and
    ``fused_spectral_viable`` admits the shape/policy (VMEM fit at the
    floor tile, no active autoprec collector) — the *whole* pipeline
    runs as the one-grid ``spectral_fused`` megakernel instead of
    rFFT/contract/irFFT round-tripping HBM between stages.
    """
    ndim = len(modes)
    spatial = x.shape[2:]
    assert len(spatial) == ndim, (x.shape, modes)
    in_dtype = x.dtype
    kind = _kind(params)
    if use_pallas is None or use_pallas:
        from repro.kernels.ops import resolve_use_pallas

        use_pallas = resolve_use_pallas(use_pallas)

    fft_in = policy.at(f"{site}/fft_in")
    ctr = policy.at(f"{site}/contract")
    fft_out = policy.at(f"{site}/fft_out")

    if use_pallas and kind == "dense":
        from repro.kernels import ops as kops

        if kops.resolve_fuse_spectral(fuse_spectral) and \
                kops.fused_spectral_viable(
                    fft_in, ctr, x.shape[0], x.shape[1],
                    _out_channels(params), spatial, modes):
            # the megakernel: one Pallas grid for the whole pipeline —
            # the spectrum lives in VMEM between the transform stages
            return kops.spectral_conv_fused(
                x, params["w_re"], params["w_im"], modes,
                policy=policy, site=site)

    # 1. stabiliser before the forward FFT (only active for half spectral)
    x = fft_in.stabilize(x)

    # 2. forward transform in f32 (TPU has no half FFT); boundary
    #    quantisation models the half (or simulated fp8) representation per
    #    Thm 3.2.  It is elementwise with no per-tensor scale, so on the DFT
    #    path, which computes only the retained modes, it rounds every value
    #    that reaches the output as the FFT path does.  named_scope: the
    #    device trace's operations keep the site, so the transform's time
    #    can be told from the rest of the step.
    dft = dft_transforms(spatial, modes)
    # counted at trace time, one per layer body traced (a layer scan
    # traces its body once), as repro_kernels_calls_total counts kernels
    from repro.obs import registry

    registry().counter("repro_spectral_transforms_total",
                       path="dft" if dft else "fft").inc()
    x = x.astype(jnp.float32)
    with jax.named_scope(f"{site}/fft_in"):
        if dft:
            xf = truncated_rfft(x, modes)
        else:
            xf = jnp.fft.rfftn(x, axes=tuple(range(2, 2 + ndim)))
    xf = fft_in.quantize(xf)

    spectrum_shape = xf.shape[2:]
    corners = _corner_slices(modes, spectrum_shape)

    ycs = []
    for c, sl in enumerate(corners):
        xc = xf[(slice(None), slice(None), *sl)]
        ops, expr = _corner_weight_ops(params, c, ndim)
        if use_pallas and kind == "dense":
            from repro.kernels import ops as kops

            yc = kops.spectral_contract(xc, ops[0], policy=ctr)
        elif use_pallas and kind == "cp":
            from repro.kernels import ops as kops

            yc = kops.spectral_contract_cp(
                xc, ops[0], ops[1], ops[2], ops[3:], policy=ctr)
        else:
            # Tucker (and any future factorisation without a kernel
            # layout) falls back to the memory-greedy einsum path —
            # explicitly, never by silently reinterpreting the params.
            yc = ctr.contract(expr, xc, *ops)
        if isinstance(yc, ComplexPair):
            yc = yc.to_complex()
        ycs.append(yc.astype(jnp.complex64))
    if not dft:
        out_f = jnp.zeros((x.shape[0], _out_channels(params),
                           *spectrum_shape), jnp.complex64)
        for yc, sl in zip(ycs, corners, strict=True):
            out_f = out_f.at[(slice(None), slice(None), *sl)].set(yc)

    # 3. inverse transform back to physical space.  named_scope: the
    # analyzer attributes the iFFT/storage-cast eqns to the fft_out site.
    with jax.named_scope(f"{site}/fft_out"):
        if dft:
            y = truncated_irfft(_stack_corners(ycs, ndim), spatial, modes)
        else:
            y = jnp.fft.irfftn(out_f, s=spatial,
                               axes=tuple(range(2, 2 + ndim)))
        from repro.autoprec.telemetry import fmt_of, tap

        tap(f"{site}/fft_out", y, fmt=fmt_of(fft_out))
        if fft_out.spectral_is_half:
            # iFFT output also lives at half precision in the paper's pipeline
            y = y.astype(fft_out.compute_dtype)
        return y.astype(in_dtype)


def _out_channels(params: dict) -> int:
    if _kind(params) == "dense":
        return params["w_re"].shape[2]
    return params["U_o_re"].shape[1]
