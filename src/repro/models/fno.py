"""FNO / TFNO models (Li et al. 2021; Kossaifi et al. 2023) with the
mixed-precision spectral pipeline as a first-class feature.

Architecture (matches the neuraloperator reference):
  lifting MLP  ->  n_layers x [ SpectralConv + (1x1 conv skip) + GELU ]
               ->  projection MLP
Per-layer weights are stacked on a leading axis and the block loop is one
``lax.scan`` whose lowering depends on the entry point.  A differentiated
forward (``fno_apply``, the training path) unrolls it: a rolled scan under
``grad`` stacks every layer's residuals into ``[n_layers, ...]`` buffers,
written with ``dynamic_update_slice`` and read back by the backward
``while`` with ``dynamic_slice``, which XLA can neither fuse into their
consumers nor free early; unrolled, the backward sees each layer's
residuals whole.  A forward that nothing differentiates (``fno_infer``,
the serving path) keeps the rolled scan, so only one layer's temporaries
are live at a time.

Precision is site-addressed: dense (real) ops resolve ``fno/dense`` /
``fno/layer<i>/dense`` (the AMP set), the spectral pipeline resolves
``fno/layer<i>/spectral/{fft_in,contract,fft_out}``, and the output head
``fno/proj_out`` (f32 by default); parameters are f32 masters.  When a
``precision_rules(...)`` override makes layers heterogeneous (e.g. the
last layer pinned to full precision), the block loop runs as a Python
loop over the layers instead, so each layer can compile at its own
formats.
"""
from __future__ import annotations

import dataclasses
import operator
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import PrecisionPolicy, FULL
from repro.core.spectral import init_spectral_weights, spectral_conv_apply
from repro.dist.constrain import constrain_spatial


@dataclasses.dataclass(frozen=True)
class FNOConfig:
    in_channels: int = 3
    out_channels: int = 1
    hidden_channels: int = 64
    lifting_channels: int = 256
    projection_channels: int = 256
    n_layers: int = 4
    modes: Tuple[int, ...] = (16, 16)
    factorization: str = "dense"  # dense | cp | tucker  (TFNO = cp/tucker)
    rank: float = 0.5
    #: Tri-state: None = auto (Pallas kernels on TPU backends and under
    #: REPRO_USE_PALLAS=1, einsum elsewhere); True/False force it.
    use_pallas: Optional[bool] = None
    #: Tri-state: None = auto (the one-grid rFFT→contract→irFFT megakernel
    #: whenever the Pallas path is on and the dense layer shape/policy is
    #: viable; REPRO_FUSE_SPECTRAL=0 kills it); True/False force it.
    fuse_spectral: Optional[bool] = None
    positional_embedding: bool = True  # append normalised grid coords

    @property
    def ndim(self) -> int:
        return len(self.modes)


def _linear_init(key, d_in, d_out):
    scale = (1.0 / d_in) ** 0.5
    kw, kb = jax.random.split(key)
    return {
        "w": scale * jax.random.normal(kw, (d_in, d_out), jnp.float32),
        "b": jnp.zeros((d_out,), jnp.float32),
    }


def _linear(p, x, dtype):
    # channel-last contraction; x: (..., d_in)
    return (
        jnp.einsum("...i,io->...o", x.astype(dtype), p["w"].astype(dtype))
        + p["b"].astype(dtype)
    )


def init_fno(key: jax.Array, cfg: FNOConfig) -> dict:
    keys = jax.random.split(key, 6)
    in_ch = cfg.in_channels + (cfg.ndim if cfg.positional_embedding else 0)
    params = {
        "lift1": _linear_init(keys[0], in_ch, cfg.lifting_channels),
        "lift2": _linear_init(keys[1], cfg.lifting_channels, cfg.hidden_channels),
        "proj1": _linear_init(keys[2], cfg.hidden_channels, cfg.projection_channels),
        "proj2": _linear_init(keys[3], cfg.projection_channels, cfg.out_channels),
    }
    # stacked per-layer spectral weights: vmap the initialiser over layers
    layer_keys = jax.random.split(keys[4], cfg.n_layers)
    spect = [
        init_spectral_weights(
            k, cfg.hidden_channels, cfg.hidden_channels, cfg.modes,
            cfg.factorization, cfg.rank,
        )
        for k in layer_keys
    ]
    params["spectral"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *spect)
    skip_keys = jax.random.split(keys[5], cfg.n_layers)
    skips = [
        _linear_init(k, cfg.hidden_channels, cfg.hidden_channels) for k in skip_keys
    ]
    params["skips"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *skips)
    return params


def _positional_grid(spatial: Sequence[int], dtype) -> jnp.ndarray:
    axes = [jnp.linspace(0.0, 1.0, s, dtype=jnp.float32) for s in spatial]
    grids = jnp.meshgrid(*axes, indexing="ij")
    return jnp.stack(grids, axis=0).astype(dtype)  # (ndim, *spatial)


def _layer_sites(policy: PrecisionPolicy, model: str, layer: int):
    """The resolved precision of one block layer (dense + spectral stages)."""
    base = f"{model}/layer{layer}"
    return (
        policy.at(f"{base}/dense"),
        policy.at(f"{base}/spectral/fft_in"),
        policy.at(f"{base}/spectral/contract"),
        policy.at(f"{base}/spectral/fft_out"),
    )


def layers_uniform(policy: PrecisionPolicy, model: str, n_layers: int) -> bool:
    """True when every layer resolves to the same formats, so the block
    loop can run as one ``lax.scan``; per-layer ``precision_rules``
    overrides make this False and the caller loops over the layers."""
    first = _layer_sites(policy, model, 0)
    return all(_layer_sites(policy, model, l) == first for l in range(1, n_layers))


def apply_block_loop(block, h, stacked, policy: PrecisionPolicy, model: str,
                     n_layers: int, *, rolled: bool):
    """Run ``block(h, layer_params, layer_idx)`` over a stacked layer pytree.

    When every layer resolves to the same formats the layers run as one
    ``lax.scan``, whose body is traced once.  ``rolled`` picks its
    lowering by entry point: a forward alone (``rolled=True``) keeps the
    scan rolled, so one layer's temporaries are live at a time; a
    differentiated forward (``rolled=False``) unrolls it, so its
    residuals are not stacked into ``[n_layers, ...]`` buffers that the
    backward ``while`` slices one layer at a time.  Shared by the FNO and
    SFNO block loops.

    A Python loop over the layers when per-layer ``precision_rules`` make
    them heterogeneous, so each layer lowers at its own formats, and
    while an autoprec telemetry collector is in scope: taps inside a scan
    body would be invisible to the outer trace, and the controller needs
    each ``<model>/layer<i>/spectral/*`` site reported under its own
    address.

    Counted at trace time, one per loop traced, under the lowering taken:
    ``repro_block_loop_total{path="unrolled"|"scan"|"layers"}``.
    """
    from repro.autoprec.telemetry import telemetry_active
    from repro.obs import registry

    if layers_uniform(policy, model, n_layers) and not telemetry_active():
        registry().counter("repro_block_loop_total",
                           path="scan" if rolled else "unrolled").inc()
        h, _ = jax.lax.scan(lambda c, lp: (block(c, lp, 0), None), h, stacked,
                            unroll=not rolled)
        return h
    registry().counter("repro_block_loop_total", path="layers").inc()
    for l in range(n_layers):
        lp = jax.tree_util.tree_map(operator.itemgetter(l), stacked)
        h = block(h, lp, l)
    return h


def fno_apply(
    params: dict,
    x: jnp.ndarray,
    cfg: FNOConfig,
    policy: PrecisionPolicy = FULL,
) -> jnp.ndarray:
    """x: (batch, in_channels, *spatial) -> (batch, out_channels, *spatial).

    The entry point of training and of every differentiated caller: the
    block loop is unrolled (see ``apply_block_loop``)."""
    return _forward(params, x, cfg, policy, rolled=False)


def _forward(params: dict, x: jnp.ndarray, cfg: FNOConfig,
             policy: PrecisionPolicy, rolled: bool) -> jnp.ndarray:
    B = x.shape[0]
    spatial = x.shape[2:]
    cdt = policy.at("fno/dense").compute_dtype

    if cfg.positional_embedding:
        pos = _positional_grid(spatial, x.dtype)
        pos = jnp.broadcast_to(pos[None], (B, cfg.ndim, *spatial))
        x = jnp.concatenate([x, pos], axis=1)

    # lifting (channel-last for the MLPs)
    h = jnp.moveaxis(x, 1, -1)
    h = _linear(params["lift1"], h, cdt)
    h = jax.nn.gelu(h)
    h = _linear(params["lift2"], h, cdt)
    h = jnp.moveaxis(h, -1, 1)  # (B, hidden, *spatial)

    def block(h, layer_params, layer: int):
        # Full-DP layout: at FNO sizes (~2-50M params) the weights are tiny,
        # so shard batch over EVERY mesh axis and replicate weights — FFTs
        # and contractions become embarrassingly parallel and the only
        # collective left is the gradient all-reduce (§Perf iteration 5:
        # collective term 2.02s -> ~0.04s on tfno-ns).  The layout decision
        # (incl. the channels-over-tp fallback when the batch doesn't cover
        # the mesh) lives in repro.dist, not here.
        h = constrain_spatial(h)
        spect, skip = layer_params
        ldt = policy.at(f"fno/layer{layer}/dense").compute_dtype
        y = spectral_conv_apply(
            spect, h, cfg.modes, policy, use_pallas=cfg.use_pallas,
            fuse_spectral=cfg.fuse_spectral,
            site=f"fno/layer{layer}/spectral",
        ).astype(ldt)
        s = jnp.moveaxis(
            _linear(skip, jnp.moveaxis(h, 1, -1), ldt), -1, 1
        )
        return jax.nn.gelu(y + s)

    h = h.astype(cdt)
    h = apply_block_loop(block, h, (params["spectral"], params["skips"]),
                         policy, "fno", cfg.n_layers, rolled=rolled)

    # projection
    h = jnp.moveaxis(h, 1, -1)
    h = _linear(params["proj1"], h, cdt)
    h = jax.nn.gelu(h)
    h = _linear(params["proj2"], h, policy.at("fno/proj_out").compute_dtype)
    return jnp.moveaxis(h, -1, 1)


def fno_infer(
    params: dict,
    x: jnp.ndarray,
    cfg: FNOConfig,
    policy: PrecisionPolicy = FULL,
) -> jnp.ndarray:
    """Batched-inference entry point for serving.

    x: (batch, in_channels, *spatial) -> (batch, out_channels, *spatial),
    cast to the ``serve/operator`` site's transport dtype (f32 in the
    base table).  Every op in the forward is per-sample independent
    (batched GEMMs, FFTs, pointwise), so the operator engine's
    micro-batching is bit-identical to serving each field alone under
    the same precision policy.  Nothing differentiates it, so the block
    loop stays a rolled scan (see ``apply_block_loop``).
    """
    y = _forward(params, x, cfg, policy, rolled=True)
    return y.astype(policy.at("serve/operator").compute_dtype)


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
