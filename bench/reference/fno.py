"""Plain FNO (Li et al. 2021): lifting MLP, ``n_layers`` Fourier layers
(rFFT, per-mode channel mixing on the retained corner blocks, irFFT, plus
a pointwise skip and GELU), projection MLP.  The optional ``tanh`` before
each forward FFT is the pre-activation of the mixed-precision method
(Tu et al., ICLR 2024, §4.3), part of the model wherever the cell's
precision states it.

Written in plain ``jax.numpy`` at float32 for ``jax.default_matmul_precision
("highest")``, with no kernels, batching tricks or precision policies.
``init_params`` makes the weights from the seed in the parameter layout
the program under test takes (per-layer weights stacked on a leading
axis, split-real spectral weights of shape (corners, in, out, *modes)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import gelu, linear, linear_init, seed_key, stack


def _corners(cfg) -> int:
    return 2 ** (len(cfg["modes"]) - 1)


def init_params(seed: int, cfg: dict) -> dict:
    """The weights, made on the device in one jitted call from ``seed``."""
    modes = tuple(cfg["modes"])
    h = cfg["hidden_channels"]
    in_ch = cfg["in_channels"] + len(modes)   # positional embedding

    def make(key):
        k = jax.random.split(key, 6)
        scale = 1.0 / (h * h)
        shape = (_corners(cfg), h, h, *modes)

        def spectral(kk):
            k1, k2 = jax.random.split(kk)
            return {"w_re": scale * jax.random.normal(k1, shape, jnp.float32),
                    "w_im": scale * jax.random.normal(k2, shape, jnp.float32)}

        n = cfg["n_layers"]
        return {
            "lift1": linear_init(k[0], in_ch, cfg["lifting_channels"]),
            "lift2": linear_init(k[1], cfg["lifting_channels"], h),
            "proj1": linear_init(k[2], h, cfg["projection_channels"]),
            "proj2": linear_init(k[3], cfg["projection_channels"],
                                 cfg["out_channels"]),
            "spectral": stack([spectral(kk)
                               for kk in jax.random.split(k[4], n)]),
            "skips": stack([linear_init(kk, h, h)
                            for kk in jax.random.split(k[5], n)]),
        }

    return jax.jit(make)(seed_key(seed))


def _corner_slices(modes, spectrum_shape):
    """Corner ``c`` keeps the high block of axis ``k`` when bit ``k`` is
    set, else the low block; the last (rFFT) axis keeps its low block."""
    out = []
    for c in range(2 ** (len(modes) - 1)):
        sl = []
        for ax in range(len(modes) - 1):
            m, s = modes[ax], spectrum_shape[ax]
            sl.append(slice(s - m, s) if (c >> ax) & 1 else slice(0, m))
        sl.append(slice(0, modes[-1]))
        out.append(tuple(sl))
    return out


def spectral_conv(w, h, modes, pre_fft_tanh: bool, q):
    """h: (B, C, *spatial) real -> (B, O, *spatial)."""
    ndim = len(modes)
    axes = tuple(range(2, 2 + ndim))
    spatial = h.shape[2:]
    if pre_fft_tanh:
        h = jnp.tanh(h)
    hf = q(jnp.fft.rfftn(q(h), axes=axes))
    out = jnp.zeros((h.shape[0], w["w_re"].shape[2], *hf.shape[2:]),
                    jnp.complex64)
    letters = "xyzuvw"[:ndim]
    for c, sl in enumerate(_corner_slices(modes, hf.shape[2:])):
        wc = q(jax.lax.complex(w["w_re"][c], w["w_im"][c]))
        xc = hf[(slice(None), slice(None), *sl)]
        yc = jnp.einsum(f"bi{letters},io{letters}->bo{letters}", xc, wc)
        out = out.at[(slice(None), slice(None), *sl)].set(q(yc))
    return jnp.fft.irfftn(out, s=spatial, axes=axes)


def forward(params, x, cfg, pre_fft_tanh: bool, q):
    """x: (B, in_channels, *spatial) -> (B, out_channels, *spatial)."""
    modes = tuple(cfg["modes"])
    B, spatial = x.shape[0], x.shape[2:]
    grids = jnp.meshgrid(*[jnp.linspace(0.0, 1.0, s) for s in spatial],
                         indexing="ij")
    pos = jnp.broadcast_to(jnp.stack(grids)[None], (B, len(spatial), *spatial))
    h = jnp.moveaxis(jnp.concatenate([x, pos], axis=1), 1, -1)
    h = linear(params["lift2"], gelu(linear(params["lift1"], h, q)), q)
    h = jnp.moveaxis(h, -1, 1)
    for layer in range(cfg["n_layers"]):
        w = jax.tree_util.tree_map(lambda a: a[layer], params["spectral"])
        skip = jax.tree_util.tree_map(lambda a: a[layer], params["skips"])
        y = spectral_conv(w, h, modes, pre_fft_tanh, q)
        s = jnp.moveaxis(linear(skip, jnp.moveaxis(h, 1, -1), q), -1, 1)
        h = gelu(y + s)
    h = jnp.moveaxis(h, 1, -1)
    h = linear(params["proj2"], gelu(linear(params["proj1"], h, q)), q)
    return jnp.moveaxis(h, -1, 1)
