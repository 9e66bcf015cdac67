"""jit'd public wrappers around the Pallas kernels.

These are the entry points the model code calls.  They handle:
  * complex <-> split-real conversion at the policy's spectral dtype,
  * mode flattening / padding,
  * interpret-mode selection (CPU container validates kernels in interpret
    mode; on TPU the same call compiles to Mosaic),
  * the autoprec telemetry tap at the contract site — the same
    ``tap(site, activation, fmt)`` stream ``SitePrecision.contract``
    feeds on the einsum path, so the controller's demotion decisions see
    identical amax/overflow streams whichever path runs,
  * explicit rejection of inputs the kernels don't support (Tucker
    factors and rank-mismatched operands fall back to the einsum path in
    ``core/spectral.py``, never silently through this one).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.precision import ComplexPair
from repro.precision import FULL, PrecisionPolicy
from .spectral_contract import (
    LANES,
    VMEM_BUDGET,
    cp_vmem_bytes,
    lshared_vmem_bytes,
    pick_block_l,
    pick_block_m,
    resolve_interpret,
    spectral_contract_cp_pallas,
    spectral_contract_lshared_pallas,
    spectral_contract_pallas,
    vmem_bytes,
    vmem_bytes_bwd,
)
from .flash_attention import flash_attention as _flash
from .rmsnorm import rmsnorm as _rmsnorm


def _use_interpret() -> bool:
    return resolve_interpret(None)


def resolve_use_pallas(flag: Optional[bool] = None) -> bool:
    """Resolve a tri-state ``use_pallas`` setting.

    Explicit True/False wins; ``None`` means *auto*: on when the env var
    ``REPRO_USE_PALLAS`` is truthy (the tier-1 CI leg sets it to run the
    whole suite through the kernels in interpret mode), otherwise on
    exactly when the backend is a TPU (where the kernels compile to
    Mosaic; interpret mode elsewhere stays opt-in).
    """
    if flag is not None:
        return bool(flag)
    env = os.environ.get("REPRO_USE_PALLAS")
    if env is not None and env != "":
        return env.lower() not in ("0", "false", "no")
    return jax.default_backend() == "tpu"


def _site_of(policy, site: str):
    """Resolve a PrecisionPolicy at ``site``; pass SitePrecision through."""
    if isinstance(policy, PrecisionPolicy):
        return policy.at(site)
    return policy


#: tile-resolution outcomes since process start, counted at trace time
#: (one per compiled shape, not per step — jit caches the resolution).
_TILE_SOURCES = {"heuristic": 0, "calibrated": 0}


def _obs_kernel_call(family: str, shape: tuple, dtype) -> None:
    """Per-family traced-call counter + bytes-moved gauge in the obs
    registry.  Counted where tiles resolve — once per compiled shape,
    not per executed step (jit caches the wrapper's trace); the gauge
    carries the tune traffic model's HBM bytes for the last shape this
    family traced with."""
    from repro.obs import registry
    from repro.tune.measure import bytes_moved

    reg = registry()
    reg.counter("repro_kernels_calls_total", family=family).inc()
    reg.gauge("repro_kernels_bytes_moved", family=family).set(
        float(bytes_moved(family, shape, jnp.dtype(dtype).name)))


def _resolve_blocks(family: str, shape: tuple, dtype, heuristic):
    """Resolve (block_fwd, block_bwd, source) for one kernel launch.

    Consults the active ``repro.tune`` calibration cache first (explicit
    ``tune.cache.activate(...)`` or the ``REPRO_CALIBRATION_STATE`` env
    var); entries that are missing, stale (kernel-version or backend
    mismatch) or corrupt fall back to the static VMEM heuristic — tuning
    state can degrade the heuristic path's performance only, never its
    availability.
    """
    from repro.tune.cache import active_cache

    _obs_kernel_call(family, shape, dtype)
    cache = active_cache()
    if cache is not None:
        ent = cache.lookup(family, shape, jnp.dtype(dtype).name)
        if ent is not None:
            _TILE_SOURCES["calibrated"] += 1
            return int(ent["block_fwd"]), int(ent["block_bwd"]), "calibrated"
    _TILE_SOURCES["heuristic"] += 1
    return heuristic(), None, "heuristic"


def tile_resolution_stats() -> dict:
    """Where this process's kernel tiles came from: per-source counts
    plus the active calibration cache's path and hit/miss/stale
    counters (None when no cache is active).  Surfaced by
    ``OperatorEngine.stats()`` and the dry-run roofline report."""
    from repro.tune.cache import active_cache

    cache = active_cache()
    out = {
        "sources": dict(_TILE_SOURCES),
        "calibration_state": cache.path if cache is not None else None,
        "cache": dict(cache.counters) if cache is not None else None,
    }
    return out


def reset_tile_resolution_stats() -> None:
    """Zero the tile-source counters and the active calibration cache's
    hit/miss/stale counters (bench hygiene between warmup and
    measurement legs).  Registered with the obs registry below, so
    ``repro.obs.registry().reset()`` covers it too."""
    from repro.tune.cache import active_cache

    for k in _TILE_SOURCES:
        _TILE_SOURCES[k] = 0
    cache = active_cache()
    if cache is not None:
        for k in cache.counters:
            cache.counters[k] = 0


def _register_obs() -> None:
    from repro.obs import registry

    registry().register_external(
        "repro_kernels_tiles", tile_resolution_stats,
        reset_tile_resolution_stats)


_register_obs()


def _tap_contract(policy, x) -> None:
    # same telemetry stream as SitePrecision.contract on the einsum path:
    # the activation operand against the site's storage format
    from repro.autoprec.telemetry import fmt_of, tap

    tap(policy.site, x, fmt=fmt_of(policy))


def _to_pair(x, half) -> ComplexPair:
    if isinstance(x, ComplexPair):
        return x if x.dtype == half else x.astype(half)
    return ComplexPair.from_complex(x, half)


def _batch_tile(B: int, need) -> int:
    """Largest batch tile (B halved until it fits) whose VMEM working
    set ``need(tile)`` at the smallest legal mode tile fits the budget.
    The mode-tiled kernels hold the whole batch in every grid step, so a
    batch too large for VMEM runs as several kernel calls."""
    bb = B
    while bb > 1 and need(bb) > VMEM_BUDGET:
        bb = (bb + 1) // 2
    return bb


def _run_batched(kernel, bb: int, batched: tuple, replicated: tuple):
    """``kernel(*batched, *replicated) -> tuple`` of batch-leading
    outputs, over batch slices of at most ``bb`` rows (one call when the
    batch fits).

    Under a multi-device ambient mesh the call runs inside a
    ``shard_map``: Mosaic kernels cannot be partitioned by the compiler,
    so each device runs the kernel on its own batch shard (the leading
    dim over every mesh axis, else over the data axes, else replicated)
    against replicated weights, whose gradient the transpose sums across
    devices.
    """
    n = len(batched)

    def local(*args):
        xs, ws = args[:n], args[n:]
        B = xs[0].shape[0]
        if bb >= B:
            return tuple(kernel(*xs, *ws))
        parts = [kernel(*(x[i:i + bb] for x in xs), *ws)
                 for i in range(0, B, bb)]
        return tuple(jnp.concatenate(p, axis=0) for p in zip(*parts))

    from repro.dist import ambient_mesh, pick_spec

    mesh = ambient_mesh()
    if mesh is None or mesh.devices.size <= 1:
        return local(*batched, *replicated)
    from jax.sharding import PartitionSpec as P

    lead = pick_spec(batched[0].shape[:1], mesh, [(("dp", "tp"),), ("dp",),
                                                  ()])
    bspec = P(*lead)
    out_shapes = jax.eval_shape(local, *batched, *replicated)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(bspec,) * n + (P(),) * len(replicated),
        out_specs=tuple(bspec for _ in out_shapes),
        check_vma=False,
    )(*batched, *replicated)


def _fused_cast_fits(x_shape, O: int) -> bool:
    """Whether the fused storage cast's f32 operand tiles fit VMEM at
    the smallest mode tile Mosaic accepts.  Where they do not (wide
    layers at a large batch), the operands are rounded onto the half
    grid in HBM instead — the same rounding, one more HBM pass."""
    B, I, *modes = x_shape
    M = 1
    for m in modes:
        M *= m
    bm = min(M, LANES)
    return max(vmem_bytes(B, I, O, bm, 4),
               vmem_bytes_bwd(B, I, O, bm, 4)) <= VMEM_BUDGET


def spectral_contract(
    x, w, *, policy=FULL, block_m: Optional[int] = None,
    block_m_bwd: Optional[int] = None,
    site: str = "model/spectral/contract",
):
    """Dense spectral contraction ``bi<modes>,io<modes>->bo<modes>``.

    ``block_m=None`` (the production default) resolves the mode tile from
    the active calibration cache when one holds a validated entry for
    this (family, shape, dtype, backend, kernel-version) key, else via
    ``pick_block_m`` from the actual shapes and storage itemsize — the
    same estimate the dry-runs record, so their ``fits_vmem`` verdict
    describes the tiling that really executes.  ``block_m_bwd`` tiles the
    two backward kernels independently (default: the forward tile, or
    the calibrated backward tile when one resolved).

    Where the site quantises to half, ``x``/``w`` arrive as complex (not
    pre-cast ComplexPairs) and the f32 operand tiles fit VMEM
    (``_fused_cast_fits``), the half storage rounding runs inside the
    kernel's tile prologue (``cast_to``), so the half operand copies never
    round-trip through HBM; numerically it is the same Thm 3.2 rounding.

    ``x``: complex64 or ComplexPair, shape (B, I, *modes);
    ``w``: complex64 or ComplexPair (the layer's dense corner weight),
    shape (I, O, *modes).  Anything else — CP/Tucker factor dicts, rank
    mismatches — raises ``ValueError`` (the factorised paths are
    ``spectral_contract_cp`` and the einsum fallback in
    ``core/spectral.py``; nothing is silently reinterpreted here).
    ``policy``: an already-resolved ``SitePrecision`` handed down by the
    model (``policy.at("fno/layer2/spectral/contract")``), or a bare
    ``PrecisionPolicy`` — then resolved here at ``site``, which direct
    callers must set to the layer's real address for per-layer
    ``precision_rules`` overrides to reach this path.
    Returns the same kind as ``x`` (ComplexPair under a half rule).
    """
    policy = _site_of(policy, site)
    for name, a in (("x", x), ("w", w)):
        if not (isinstance(a, ComplexPair) or hasattr(a, "ndim")):
            raise ValueError(
                f"spectral_contract: {name} is {type(a).__name__}, not a "
                f"dense array/ComplexPair — factorised (CP/Tucker) weights "
                f"must go through spectral_contract_cp or the einsum path"
            )
    if len(x.shape) != len(w.shape) or len(x.shape) < 3:
        raise ValueError(
            f"spectral_contract is dense-only: expected x (B, I, *modes) "
            f"and w (I, O, *modes) of equal rank >= 3, got {x.shape} vs "
            f"{w.shape} — CP/Tucker factors take spectral_contract_cp or "
            f"the einsum fallback in core/spectral.py"
        )
    half = policy.spectral_dtype or jnp.float32
    was_pair = isinstance(x, ComplexPair)
    _tap_contract(policy, x)
    fused = (
        policy.spectral_is_half
        and not was_pair
        and not isinstance(w, ComplexPair)
        and _fused_cast_fits(x.shape, w.shape[1])
    )
    if fused:
        # fused-quantise path: split to f32 pairs without rounding here;
        # the kernel prologue rounds each tile onto the half grid in
        # VMEM (same representation error, one fewer HBM round-trip).
        xp = ComplexPair.from_complex(x, jnp.float32)
        wp = ComplexPair.from_complex(w, jnp.float32)
    else:
        xp = _to_pair(x, half)
        wp = _to_pair(w, half)

    B, I, *modes = xp.re.shape
    I2, O, *modes2 = wp.re.shape
    if tuple(modes) != tuple(modes2) or I != I2:
        raise ValueError(
            f"spectral_contract: x {xp.re.shape} and w {wp.re.shape} "
            f"disagree on channels or modes"
        )
    M = 1
    for m in modes:
        M *= m
    # the fused path streams f32 operand tiles, so its VMEM working set
    # (and its calibration entries) price at itemsize 4
    itemsize = 4 if fused else jnp.dtype(half).itemsize
    bm0 = min(M, LANES)
    bb = _batch_tile(B, lambda b: max(vmem_bytes(b, I, O, bm0, itemsize),
                                      vmem_bytes_bwd(b, I, O, bm0, itemsize)))
    if block_m is None:
        block_m, tuned_bwd, _src = _resolve_blocks(
            "dense-fused" if fused else "dense", (bb, I, O, M), half,
            lambda: pick_block_m(bb, I, O, M, itemsize=itemsize),
        )
        block_m_bwd = block_m_bwd or tuned_bwd
    interpret = _use_interpret()

    def kernel(xr, xi, wr, wi):
        return spectral_contract_pallas(
            xr, xi, wr, wi, block_m=block_m, block_m_bwd=block_m_bwd,
            interpret=interpret, out_dtype=half,
            cast_to=half if fused else None,
        )

    # named_scope: eqns traced under this site carry its address in
    # their name stack — repro.analyze attributes findings with it
    with jax.named_scope(policy.site):
        out_re, out_im = _run_batched(
            kernel, bb, (xp.re.reshape(B, I, M), xp.im.reshape(B, I, M)),
            (wp.re.reshape(I, O, M), wp.im.reshape(I, O, M)))
    pair = ComplexPair(
        out_re.reshape(B, O, *modes), out_im.reshape(B, O, *modes)
    )
    if was_pair and policy.spectral_is_half:
        return pair
    return pair.to_complex()


def cp_mode_factor(lam, mode_factors: Sequence) -> jnp.ndarray:
    """Fold λ (R,) and the per-axis CP factors (m_k, R) into the combined
    mode factor ``W[r, m] = λ_r Π_k U_mk[m_k, r]`` over the row-major
    flattened mode index (tiny, differentiable jnp — the kernels never
    materialise the dense (I, O, M) weight this factor replaces)."""
    w = lam[:, None]
    for f in mode_factors:
        w = (w[:, :, None] * jnp.transpose(f)[:, None, :]).reshape(
            w.shape[0], -1)
    return w


def spectral_contract_cp(
    x, lam, ui, uo, mode_factors: Sequence, *, policy=FULL,
    block_m: Optional[int] = None, block_m_bwd: Optional[int] = None,
    site: str = "model/spectral/contract",
):
    """CP-factorised spectral contraction (TFNO §4.6) on the Pallas path.

    ``x``: complex64 or ComplexPair (B, I, *modes); ``lam``: (R,) complex
    CP weights; ``ui``/``uo``: (I, R)/(O, R) channel factors;
    ``mode_factors``: one (m_k, R) complex factor per mode axis.
    Returns the same kind as ``x`` (ComplexPair under a half rule).
    """
    policy = _site_of(policy, site)
    half = policy.spectral_dtype or jnp.float32
    was_pair = isinstance(x, ComplexPair)
    _tap_contract(policy, x)
    xp = _to_pair(x, half)

    B, I, *modes = xp.re.shape
    if len(mode_factors) != len(modes):
        raise ValueError(
            f"spectral_contract_cp: {len(mode_factors)} mode factors for "
            f"{len(modes)}-d modes {tuple(modes)}"
        )
    M = 1
    for m in modes:
        M *= m
    w = cp_mode_factor(lam, mode_factors)  # (R, M) complex
    uip = _to_pair(ui, half)
    uop = _to_pair(uo, half)
    wp = _to_pair(w, half)
    O = uop.re.shape[0]
    R = uip.re.shape[1]
    itemsize = jnp.dtype(half).itemsize
    bm0 = min(M, LANES)
    bb = _batch_tile(B, lambda b: cp_vmem_bytes(b, I, O, R, bm0, itemsize))
    if block_m is None:
        block_m, tuned_bwd, _src = _resolve_blocks(
            "cp", (bb, I, O, R, M), half,
            lambda: pick_block_m(bb, I, O, M, rank=R, itemsize=itemsize),
        )
        block_m_bwd = block_m_bwd or tuned_bwd

    interpret = _use_interpret()

    def kernel(*args):
        return spectral_contract_cp_pallas(
            *args, block_m=block_m, block_m_bwd=block_m_bwd,
            interpret=interpret, out_dtype=half,
        )

    with jax.named_scope(policy.site):
        out_re, out_im = _run_batched(
            kernel, bb, (xp.re.reshape(B, I, M), xp.im.reshape(B, I, M)),
            (uip.re, uip.im, uop.re, uop.im, wp.re, wp.im))
    pair = ComplexPair(
        out_re.reshape(B, O, *modes), out_im.reshape(B, O, *modes)
    )
    if was_pair and policy.spectral_is_half:
        return pair
    return pair.to_complex()


def spectral_contract_lshared(
    x, w, *, policy=FULL, block_l: Optional[int] = None,
    block_l_bwd: Optional[int] = None,
    site: str = "model/spectral/contract",
):
    """Order-shared spherical contraction ``bilm,iol->bolm`` (SFNO).

    ``x``: complex64 or ComplexPair, shape (B, I, L, M) — the (degree,
    order) spherical spectrum; ``w``: complex64 or ComplexPair (I, O, L),
    shared across orders m per the spherical convolution theorem.  The
    kernel tiles over degrees and reduces m in-tile, so the dense
    (I, O, L, M) weight (and its gradient) is never materialised.
    Returns the same kind as ``x`` (ComplexPair under a half rule).
    """
    policy = _site_of(policy, site)
    if len(x.shape) != 4 or len(w.shape) != 3:
        raise ValueError(
            f"spectral_contract_lshared: expected x (B, I, L, M) and "
            f"w (I, O, L), got {x.shape} vs {w.shape}"
        )
    half = policy.spectral_dtype or jnp.float32
    was_pair = isinstance(x, ComplexPair)
    _tap_contract(policy, x)
    xp = _to_pair(x, half)
    wp = _to_pair(w, half)
    B, I, L, Mm = xp.re.shape
    O = wp.re.shape[1]
    if block_l is None:
        block_l, tuned_bwd, _src = _resolve_blocks(
            "lshared", (B, I, O, L, Mm), half,
            lambda: pick_block_l(B, I, O, L, Mm,
                                 itemsize=jnp.dtype(half).itemsize),
        )
        block_l_bwd = block_l_bwd or tuned_bwd
    interpret = _use_interpret()

    def kernel(*args):
        return spectral_contract_lshared_pallas(
            *args, block_l=block_l, block_l_bwd=block_l_bwd,
            interpret=interpret, out_dtype=half,
        )

    with jax.named_scope(policy.site):
        out_re, out_im = _run_batched(kernel, B, (xp.re, xp.im),
                                      (wp.re, wp.im))
    pair = ComplexPair(out_re, out_im)
    if was_pair and policy.spectral_is_half:
        return pair
    return pair.to_complex()


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128):
    """(B, H, S, D) attention; flattens heads into the grid batch axis."""
    from repro.obs import registry

    registry().counter("repro_kernels_calls_total",
                       family="flash_attention").inc()
    B, H, S, D = q.shape
    Sk = k.shape[2]
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    out = _flash(
        qf, kf, vf, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_use_interpret(),
    )
    return out.reshape(B, H, S, D)


def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256):
    """Rank-agnostic RMSNorm over the last axis."""
    from repro.obs import registry

    registry().counter("repro_kernels_calls_total", family="rmsnorm").inc()
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    out = _rmsnorm(flat, w, eps=eps, block_rows=block_rows, interpret=_use_interpret())
    return out.reshape(shape)


__all__ = [
    "spectral_contract", "spectral_contract_cp", "spectral_contract_lshared",
    "cp_mode_factor", "flash_attention", "rmsnorm", "resolve_use_pallas",
    "tile_resolution_stats", "reset_tile_resolution_stats",
    "vmem_bytes", "vmem_bytes_bwd", "cp_vmem_bytes", "lshared_vmem_bytes",
    "pick_block_m", "pick_block_l",
]
