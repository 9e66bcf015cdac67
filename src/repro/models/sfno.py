"""Spherical FNO (Bonev et al. 2023) on our own SHT substrate.

Block: SHT -> truncate to (lmax, mmax) -> per-degree channel contraction
``bilm,iol->bolm`` (weights shared over order m, per the spherical
convolution theorem) -> iSHT, plus a pointwise skip, GELU.

The Legendre transforms and the spectral contraction are GEMMs, so the
paper's mixed-precision pipeline applies verbatim: tanh pre-activation
before the SHT, half-precision storage of the spherical spectrum
(boundary-quantised), contraction at half with f32 accumulation.  Every
stage resolves its format through the precision rule table at the
``sfno/layer<i>/spectral/*`` sites — the stabilise->quantise sequence is
the shared site helpers, not an inline re-implementation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import PrecisionPolicy, FULL, ComplexPair
from repro.dist.constrain import constrain_spatial
from .fno import _linear, _linear_init, apply_block_loop
from .sht import longitude_dft, sht_forward, sht_inverse


@dataclasses.dataclass(frozen=True)
class SFNOConfig:
    in_channels: int = 3
    out_channels: int = 3
    hidden_channels: int = 64
    n_layers: int = 4
    nlat: int = 64
    nlon: int = 128
    lmax: int = 32
    mmax: int = 32
    lifting_channels: int = 128
    projection_channels: int = 128
    #: Tri-state like FNOConfig: None = auto (TPU / REPRO_USE_PALLAS=1).
    use_pallas: Optional[bool] = None


def init_sfno(key: jax.Array, cfg: SFNOConfig) -> dict:
    keys = jax.random.split(key, 5)
    params = {
        "lift1": _linear_init(keys[0], cfg.in_channels, cfg.lifting_channels),
        "lift2": _linear_init(keys[1], cfg.lifting_channels, cfg.hidden_channels),
        "proj1": _linear_init(keys[2], cfg.hidden_channels, cfg.projection_channels),
        "proj2": _linear_init(keys[3], cfg.projection_channels, cfg.out_channels),
    }
    h = cfg.hidden_channels
    scale = 1.0 / h
    lkeys = jax.random.split(keys[4], cfg.n_layers)
    ws, skips = [], []
    for lk in lkeys:
        k1, k2, k3 = jax.random.split(lk, 3)
        ws.append(
            {
                "w_re": scale * jax.random.normal(k1, (h, h, cfg.lmax), jnp.float32),
                "w_im": scale * jax.random.normal(k2, (h, h, cfg.lmax), jnp.float32),
            }
        )
        skips.append(_linear_init(k3, h, h))
    params["spectral"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ws)
    params["skips"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *skips)
    return params


def _spherical_conv(h, w, cfg: SFNOConfig, policy: PrecisionPolicy,
                    site: str = "sfno/layer0/spectral"):
    """h: (B, C, nlat, nlon) -> (B, C, nlat, nlon) via spherical spectrum."""
    fft_in = policy.at(f"{site}/fft_in")
    ctr = policy.at(f"{site}/contract")
    fft_out = policy.at(f"{site}/fft_out")
    # counted at trace time, one per spherical layer body traced (a layer
    # scan traces its body once), as core/spectral.py counts its layers
    from repro.obs import registry

    registry().counter("repro_sht_transforms_total",
                       path="dft" if longitude_dft(cfg.nlon, cfg.mmax)
                       else "fft").inc()
    h = fft_in.stabilize(h).astype(jnp.float32)
    # named_scope: the device trace's operations keep the site, so the
    # transform's time can be told from the rest of the step.  The
    # transform runs in f32; the spectrum is then boundary-quantised onto
    # the site's storage grid (Thm 3.2's representation error).
    with jax.named_scope(f"{site}/fft_in"):
        coeffs = sht_forward(h, cfg.lmax, cfg.mmax)  # (B,C,l,m)
    coeffs = fft_in.quantize(coeffs)
    wc = jax.lax.complex(w["w_re"], w["w_im"])  # (i, o, l)
    from repro.kernels.ops import resolve_use_pallas

    if resolve_use_pallas(cfg.use_pallas):
        from repro.kernels import ops as kops

        # the spherical weight is shared over order m (per the spherical
        # convolution theorem): the l-shared kernel tiles over degrees
        # and never materialises the dense (i, o, l, m) weight
        out = kops.spectral_contract_lshared(coeffs, wc, policy=ctr)
    else:
        out = ctr.contract("bilm,iol->bolm", coeffs, wc)
    if isinstance(out, ComplexPair):
        out = out.to_complex()
    # named_scope: repro.analyze attributes the inverse-transform and
    # storage-cast eqns to the fft_out site (as core/spectral.py does)
    with jax.named_scope(f"{site}/fft_out"):
        y = sht_inverse(out.astype(jnp.complex64), cfg.nlat, cfg.nlon)
        from repro.autoprec.telemetry import fmt_of, tap

        tap(f"{site}/fft_out", y, fmt=fmt_of(fft_out))
        if fft_out.spectral_is_half:
            y = y.astype(fft_out.compute_dtype)
        return y


def sfno_apply(
    params: dict, x: jnp.ndarray, cfg: SFNOConfig, policy: PrecisionPolicy = FULL
) -> jnp.ndarray:
    """x: (B, in_channels, nlat, nlon) -> (B, out_channels, nlat, nlon).

    The entry point of training and of every differentiated caller: the
    block loop is unrolled (see ``fno.apply_block_loop``)."""
    return _forward(params, x, cfg, policy, rolled=False)


def _forward(params: dict, x: jnp.ndarray, cfg: SFNOConfig,
             policy: PrecisionPolicy, rolled: bool) -> jnp.ndarray:
    cdt = policy.at("sfno/dense").compute_dtype
    h = jnp.moveaxis(x, 1, -1)
    h = _linear(params["lift1"], h, cdt)
    h = jax.nn.gelu(h)
    h = _linear(params["lift2"], h, cdt)
    h = jnp.moveaxis(h, -1, 1)

    def block(h, layer, layer_idx: int):
        h = constrain_spatial(h)
        w, skip = layer
        ldt = policy.at(f"sfno/layer{layer_idx}/dense").compute_dtype
        y = _spherical_conv(h, w, cfg, policy,
                            site=f"sfno/layer{layer_idx}/spectral").astype(ldt)
        s = jnp.moveaxis(_linear(skip, jnp.moveaxis(h, 1, -1), ldt), -1, 1)
        return jax.nn.gelu(y + s)

    h = h.astype(cdt)
    h = apply_block_loop(block, h, (params["spectral"], params["skips"]),
                         policy, "sfno", cfg.n_layers, rolled=rolled)

    h = jnp.moveaxis(h, 1, -1)
    h = _linear(params["proj1"], h, cdt)
    h = jax.nn.gelu(h)
    h = _linear(params["proj2"], h, policy.at("sfno/proj_out").compute_dtype)
    return jnp.moveaxis(h, -1, 1)


def sfno_infer(
    params: dict, x: jnp.ndarray, cfg: SFNOConfig, policy: PrecisionPolicy = FULL
) -> jnp.ndarray:
    """Batched-inference entry point for serving (see ``fno_infer``):
    (B, in_channels, nlat, nlon) -> (B, out_channels, nlat, nlon) at the
    ``serve/operator`` transport dtype.  The block loop stays a rolled
    scan."""
    y = _forward(params, x, cfg, policy, rolled=True)
    return y.astype(policy.at("serve/operator").compute_dtype)
