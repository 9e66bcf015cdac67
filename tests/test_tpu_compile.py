"""Compile rehearsal for the TPU v5e: the main-path spectral kernels, the
FNO serving forward, and one whole FNO and one whole SFNO training step,
compiled with ``interpret=False`` for a described (not attached)
``v5e:2x2`` topology.

Nothing here runs on a chip: each test lowers against shapes placed on a
described device and asks the TPU compiler (Mosaic for the Pallas
kernels) to compile them.  That catches what interpret mode cannot —
unaligned block shapes, unsupported vector layouts and contractions,
VMEM and HBM over-use — at the published widths of
``configs/fno_paper``.

The topology is described inside a module-scoped fixture, never at
import, so every pytest-xdist worker collects the same tests and only
the worker that runs this file loads the TPU compiler library.
"""
from __future__ import annotations

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.fno_paper import FNO_DARCY, SFNO_SWE, TFNO_NS
from repro.kernels import spectral_contract as sc


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compilation cache off: a
    compile for a device that is not attached can be written to the
    cache but never read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


def _compile(fn, args, *, train: bool):
    """Compile ``fn`` (or its value_and_grad over every argument when
    ``train``) for the described chip; returns the compiled text."""
    step = fn
    if train:
        def loss(*a):
            out = fn(*a)
            return sum(jnp.sum(jnp.square(o.astype(jnp.float32)))
                       for o in jax.tree_util.tree_leaves(out))

        step = jax.value_and_grad(loss, argnums=tuple(range(len(args))))
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


PHASES = pytest.mark.parametrize("train", [False, True],
                                 ids=["fwd", "fwd+bwd"])


@PHASES
def test_dense_fno_darcy(one_chip, train):
    """Dense staged kernel at FNO_DARCY (B=16, I=O=64, modes 32x32, bf16
    storage) on the operands and tile the dispatch chooses: f32 operands
    with the storage cast in the tile prologue where their tiles fit
    VMEM, half operands otherwise."""
    from repro.kernels.ops import _fused_cast_fits

    B, h = 16, FNO_DARCY.hidden_channels
    M = math.prod(FNO_DARCY.modes)
    fused = _fused_cast_fits((B, h, *FNO_DARCY.modes), h)
    dt = jnp.float32 if fused else jnp.bfloat16
    bm = sc.pick_block_m(B, h, h, M, itemsize=jnp.dtype(dt).itemsize)
    args = [_sds((B, h, M), dt, one_chip)] * 2 + \
        [_sds((h, h, M), dt, one_chip)] * 2

    def fn(xr, xi, wr, wi):
        return sc.spectral_contract_pallas(
            xr, xi, wr, wi, block_m=bm, interpret=False,
            out_dtype=jnp.bfloat16,
            cast_to=jnp.bfloat16 if fused else None)

    _compile(fn, args, train=train)


@PHASES
def test_cp_tfno_ns(one_chip, train):
    """CP kernel at TFNO_NS: I=O=64, R=64, modes 42x42, bf16 storage."""
    from repro.core.spectral import cp_rank

    B, h = 16, TFNO_NS.hidden_channels
    R = cp_rank(h, h, TFNO_NS.rank)
    M = math.prod(TFNO_NS.modes)
    bm = sc.pick_block_m(B, h, h, M, rank=R, itemsize=2)
    bf = jnp.bfloat16
    args = ([_sds((B, h, M), bf, one_chip)] * 2
            + [_sds((h, R), bf, one_chip)] * 4
            + [_sds((R, M), bf, one_chip)] * 2)

    def fn(*a):
        return sc.spectral_contract_cp_pallas(*a, block_m=bm,
                                              interpret=False)

    _compile(fn, args, train=train)


@PHASES
def test_lshared_sfno_swe(one_chip, train):
    """l-shared kernel at SFNO_SWE: I=O=64, lmax = mmax = 128, bf16."""
    B, h = 4, SFNO_SWE.hidden_channels
    L, Mm = SFNO_SWE.lmax, SFNO_SWE.mmax
    bl = sc.pick_block_l(B, h, h, L, Mm, itemsize=2)
    bf = jnp.bfloat16
    args = ([_sds((B, h, L, Mm), bf, one_chip)] * 2
            + [_sds((h, h, L), bf, one_chip)] * 2)

    def fn(*a):
        return sc.spectral_contract_lshared_pallas(*a, block_l=bl,
                                                   interpret=False)

    _compile(fn, args, train=train)


@pytest.mark.parametrize("policy_name", ["mixed_fno_bf16", "full"])
def test_fno_infer_serving_forward(one_chip, monkeypatch, policy_name):
    """The serving cell's program: ``fno_infer`` at FNO_DARCY width on a
    full micro-batch (B=8, 128x128) with the Pallas path compiled and the
    TPU's truncated-DFT transforms, under the paper's mixed policy and
    under ``full``.  Nothing differentiates it, so the block loop stays
    one rolled scan: one ``while``, no ``fft``, Mosaic kernels, and the
    program fits one chip's HBM."""
    from repro.core import get_policy, spectral
    from repro.kernels import ops
    from repro.models import fno_infer, init_fno

    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    monkeypatch.setattr(spectral, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(FNO_DARCY, use_pallas=True)
    policy = get_policy(policy_name)
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: init_fno(jax.random.PRNGKey(0), cfg)))
    x = _sds((8, 1, 128, 128), jnp.float32, one_chip)

    lowered = jax.jit(lambda p, x: fno_infer(p, x, cfg, policy)).lower(
        params, x)
    text = lowered.as_text()
    assert "stablehlo.fft" not in text
    assert text.count("stablehlo.while") == 1
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, f"forward needs {used / 1e9:.1f} GB of HBM"


#: Ceilings on a training step's temporaries (``memory_analysis``), in
#: bytes.  With the block loop unrolled under ``grad`` the steps hold
#: about 1.4 GB (FNO_DARCY, batch 64, mixed), 3.1 GB (the same under
#: ``full``) and 3.2 GB (SFNO_SWE, batch 16); a rolled layer scan, which
#: stacks every layer's residuals, held about 7.3, 11.8 and 15.7 GB.
TEMP_CEILING = {("fno", "mixed_fno_bf16"): 3e9, ("fno", "full"): 6e9,
                ("sfno", "mixed_fno_bf16"): 5e9}


@pytest.mark.parametrize("policy_name", ["mixed_fno_bf16", "full"])
def test_fno_darcy_train_step(one_chip, monkeypatch, policy_name):
    """One whole FNO_DARCY training step at paper width and the benchmark
    cell's batch (B=64, 128x128) with the Pallas path forced on and
    compiled, under the paper's mixed policy and under ``full`` (f32
    operand tiles, the largest dense kernel working set): it must
    compile, hold Mosaic kernels, fit one chip's HBM, and keep its
    temporaries under ``TEMP_CEILING``.  The spectral transforms take the
    TPU's path, truncated-DFT matmuls, so no ``fft`` is left in the
    program."""
    from repro.core import get_policy, spectral
    from repro.kernels import ops
    from repro.models import fno_apply, init_fno
    from repro.optim import AdamW
    from repro.train.losses import relative_l2

    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    monkeypatch.setattr(spectral, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(FNO_DARCY, use_pallas=True)
    policy = get_policy(policy_name)
    opt = AdamW(lr=1e-3)
    B, res = 64, (128, 128)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(lambda: init_fno(jax.random.PRNGKey(0),
                                                   cfg)))
    opt_state = place(jax.eval_shape(opt.init, params))
    batch = {"x": _sds((B, 1, *res), jnp.float32, one_chip),
             "y": _sds((B, 1, *res), jnp.float32, one_chip)}

    def step(p, o, b):
        def loss_fn(p):
            return relative_l2(fno_apply(p, b["x"], cfg, policy), b["y"])

        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_o = opt.update(grads, o, p)
        return new_p, new_o, loss

    lowered = jax.jit(step).lower(params, opt_state, batch)
    assert "stablehlo.fft" not in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, f"step needs {used / 1e9:.1f} GB of HBM"
    ceiling = TEMP_CEILING[("fno", policy_name)]
    assert mem.temp_size_in_bytes < ceiling, (
        f"step holds {mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries")


def test_sfno_swe_train_step(one_chip, monkeypatch):
    """One whole SFNO_SWE training step at paper width and the benchmark
    cell's batch (B=16, 256x512 grid, lmax = mmax = 128) under the
    paper's mixed policy, with the l-shared kernel compiled: the
    longitude transforms take the TPU's path, truncated-DFT matmuls, so
    no ``fft`` is left, the step fits one chip's HBM, and its
    temporaries stay under ``TEMP_CEILING``."""
    from repro.configs.fno_paper import SFNO_SWE
    from repro.core import get_policy, spectral
    from repro.kernels import ops
    from repro.models import init_sfno, sfno_apply
    from repro.optim import AdamW
    from repro.train.losses import relative_l2

    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    monkeypatch.setattr(spectral, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(SFNO_SWE, use_pallas=True)
    policy = get_policy("mixed_fno_bf16")
    opt = AdamW(lr=1e-3, weight_decay=1e-4, grad_clip_norm=1.0)
    shape = (16, cfg.in_channels, cfg.nlat, cfg.nlon)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(lambda: init_sfno(jax.random.PRNGKey(0),
                                                    cfg)))
    opt_state = place(jax.eval_shape(opt.init, params))
    batch = {"x": _sds(shape, jnp.float32, one_chip),
             "y": _sds(shape, jnp.float32, one_chip)}

    def step(p, o, b):
        def loss_fn(p):
            return relative_l2(sfno_apply(p, b["x"], cfg, policy), b["y"])

        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_o = opt.update(grads, o, p)
        return new_p, new_o, loss

    lowered = jax.jit(step).lower(params, opt_state, batch)
    assert "stablehlo.fft" not in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, f"step needs {used / 1e9:.1f} GB of HBM"
    ceiling = TEMP_CEILING[("sfno", "mixed_fno_bf16")]
    assert mem.temp_size_in_bytes < ceiling, (
        f"step holds {mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries")
