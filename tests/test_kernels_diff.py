"""Differential harness: the Pallas spectral path vs the einsum reference.

This is the proof obligation for the training-grade kernel path
(DESIGN/paper mapping: the contraction is the precision-critical site of
Thm 3.2, so "numerically interchangeable" means *within the theorem's own
budget*, not bitwise):

  * forward: for every registry policy, random (B, I, O) including
    non-MXU-aligned channels, 1D/2D/3D modes, dense and CP factorisations,
    ``|pallas − einsum| ≤ n_stages · 4 ε M + c·ε_f32·M`` elementwise, where
    ``ε`` is the policy's storage grid spacing (``SitePrecision.eps``),
    ``M`` the contraction of operand magnitudes actually flowing through
    the site (the empirical sup bound of Thm 3.2), ``4εM`` is
    ``core.theory.prec_upper_bound``, one term per requantising stage of
    the memory-greedy einsum path, plus an f32 accumulation-order term;
  * backward: ``value_and_grad`` through ``spectral_conv_apply`` and a
    full FNO/TFNO train step (incl. the fp16 loss-scale interaction)
    matches the einsum path per policy, and the custom VJP passes an
    fp64 central-difference gradcheck on a tiny dense case;
  * edges: non-``block_m``-divisible mode counts exercise the kernel's
    padding path, Tucker params fall back to the einsum path, and
    non-dense operands are rejected loudly rather than silently.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    FULL,
    get_policy,
    init_spectral_weights,
    spectral_conv_apply,
)
from repro.core.precision import ComplexPair
from repro.core.spectral import _cp_exprs, _dense_expr, dft_transforms
from repro.kernels import ops, ref
from repro.kernels.spectral_contract import (
    pick_block_m,
    spectral_contract_pallas,
)
from repro.models import FNOConfig, fno_apply, init_fno
from repro.precision import POLICIES
from repro.train import Trainer, TrainerConfig, relative_l2

from helpers import (
    GRAD_TOLS,
    HALF_POLICY_NAMES,
    MODES_BY_NDIM,
    POLICY_NAMES,
    SPATIAL_BY_NDIM,
    assert_within_budget as _assert_within_budget,
    force_tpu_transforms,
    rand_complex as _randc,
    rel_err as _rel_err,
    spectral_mag,
)

jax.config.update("jax_platform_name", "cpu")


def _diff_dense(policy_name, B, I, O, modes, seed, block_m=8):
    policy = get_policy(policy_name)
    site = policy.at("fno/layer0/spectral/contract")
    rng = np.random.RandomState(seed)
    x = _randc(rng, (B, I, *modes))
    w = _randc(rng, (I, O, *modes))
    y_e = site.contract(_dense_expr(len(modes)), x, w)
    y_p = ops.spectral_contract(x, w, policy=site, block_m=block_m)
    mag = np.einsum(
        _dense_expr(len(modes)).replace(" ", ""), np.abs(x), np.abs(w))
    # two requantising stages: one per path's storage rounding of the result
    _assert_within_budget(
        y_p, y_e, site.eps, mag, stages=2,
        label=f"dense {policy_name} B{B} I{I} O{O} modes{modes}")


def _diff_cp(policy_name, B, I, O, R, modes, seed, block_m=8):
    policy = get_policy(policy_name)
    site = policy.at("fno/layer0/spectral/contract")
    ndim = len(modes)
    rng = np.random.RandomState(seed)
    x = _randc(rng, (B, I, *modes))
    lam = _randc(rng, (R,))
    ui = _randc(rng, (I, R))
    uo = _randc(rng, (O, R))
    factors = [_randc(rng, (m, R)) for m in modes]
    expr = _cp_exprs(ndim)
    y_e = site.contract(expr, x, lam, ui, uo, *factors)
    y_p = ops.spectral_contract_cp(x, lam, ui, uo, factors, policy=site,
                                   block_m=block_m)
    mag = np.einsum(expr.replace(" ", ""), np.abs(x), np.abs(lam),
                    np.abs(ui), np.abs(uo), *[np.abs(f) for f in factors])
    # the memory-greedy einsum path requantises after each of its
    # (n_operands − 1) pairwise steps; the kernel path rounds its three
    # factorised stages — budget one 4εM term per stage on either side
    _assert_within_budget(
        y_p, y_e, site.eps, mag, stages=(ndim + 3) + 3,
        label=f"cp {policy_name} B{B} I{I} O{O} R{R} modes{modes}")


def _diff_lshared(policy_name, B, I, O, L, Mm, seed, block_l=2):
    """The SFNO order-shared contraction ``bilm,iol->bolm``."""
    policy = get_policy(policy_name)
    site = policy.at("sfno/layer0/spectral/contract")
    rng = np.random.RandomState(seed)
    x = _randc(rng, (B, I, L, Mm))
    w = _randc(rng, (I, O, L))
    y_e = site.contract("bilm,iol->bolm", x, w)
    y_p = ops.spectral_contract_lshared(x, w, policy=site, block_l=block_l)
    mag = np.einsum("bilm,iol->bolm", np.abs(x), np.abs(w))
    _assert_within_budget(
        y_p, y_e, site.eps, mag, stages=2,
        label=f"lshared {policy_name} B{B} I{I} O{O} L{L} M{Mm}")


class TestDifferentialAllPolicies:
    """Full registry-policy × factorisation × dimensionality cross."""

    @pytest.mark.parametrize("policy_name", POLICY_NAMES)
    @pytest.mark.parametrize("ndim", [1, 2, 3])
    def test_dense(self, policy_name, ndim):
        _diff_dense(policy_name, B=2, I=3, O=4, modes=MODES_BY_NDIM[ndim],
                    seed=ndim)

    @pytest.mark.parametrize("policy_name", POLICY_NAMES)
    def test_lshared(self, policy_name):
        _diff_lshared(policy_name, B=2, I=3, O=4, L=5, Mm=4, seed=21)

    @pytest.mark.parametrize("policy_name", POLICY_NAMES)
    @pytest.mark.parametrize("ndim", [1, 2, 3])
    def test_cp(self, policy_name, ndim):
        _diff_cp(policy_name, B=2, I=3, O=4, R=3, modes=MODES_BY_NDIM[ndim],
                 seed=10 + ndim)

    @pytest.mark.slow
    @given(
        st.integers(min_value=1, max_value=3),
        st.integers(min_value=1, max_value=13),
        st.integers(min_value=1, max_value=13),
        st.integers(min_value=1, max_value=21),
        st.sampled_from(sorted(POLICIES)),
        st.sampled_from(["dense", "cp"]),
    )
    @settings(max_examples=12, deadline=None)
    def test_property_random_shapes(self, B, I, O, M, policy_name, kind):
        """Random non-MXU-aligned channels and mode counts (1D keeps the
        example budget affordable; the ndim cross above covers 2D/3D)."""
        seed = B * 10000 + I * 1000 + O * 100 + M
        if kind == "dense":
            _diff_dense(policy_name, B, I, O, (M,), seed)
        else:
            _diff_cp(policy_name, B, I, O, max(1, min(I, O)), (M,), seed)


class TestPaddingAndFallback:
    def test_block_m_padding_edge(self):
        """Modes not divisible by block_m exercise the zero-pad + slice
        path of all three dense kernels (fwd and both backward)."""
        rng = np.random.RandomState(3)
        x = _randc(rng, (2, 4, 13))   # M=13, block_m=8 -> pad to 16
        w = _randc(rng, (4, 5, 13))
        cr = jnp.asarray(rng.randn(2, 5, 13), jnp.float32)

        def loss(fn):
            def f(xr, xi, wr, wi):
                yr, yi = fn(xr, xi, wr, wi)
                return jnp.sum(yr * cr + yi * cr)
            return f

        args = tuple(jnp.asarray(a, jnp.float32)
                     for a in (x.real, x.imag, w.real, w.imag))
        pl_fn = loss(lambda *a: spectral_contract_pallas(
            *a, block_m=8, interpret=True))

        def ref_pair(xr, xi, wr, wi):
            y = ref.spectral_contract_ref(
                jax.lax.complex(xr, xi), jax.lax.complex(wr, wi))
            return jnp.real(y), jnp.imag(y)

        v1, g1 = jax.value_and_grad(pl_fn, argnums=(0, 1, 2, 3))(*args)
        v2, g2 = jax.value_and_grad(loss(ref_pair), argnums=(0, 1, 2, 3))(*args)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
        for a, b in zip(g1, g2, strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_ops_wrapper_padding_multimode(self):
        rng = np.random.RandomState(4)
        x = _randc(rng, (2, 3, 3, 5))  # M=15, block_m=4 -> pad to 16
        w = _randc(rng, (3, 4, 3, 5))
        got = ops.spectral_contract(x, w, policy=FULL, block_m=4)
        want = jnp.einsum("bixy,ioxy->boxy", x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_tucker_falls_back_to_einsum(self):
        rng = np.random.RandomState(5)
        params = init_spectral_weights(jax.random.PRNGKey(5), 4, 4, (3, 3),
                                       "tucker")
        x = jnp.asarray(rng.randn(2, 4, 8, 8), jnp.float32)
        a = spectral_conv_apply(params, x, (3, 3), FULL, use_pallas=False)
        b = spectral_conv_apply(params, x, (3, 3), FULL, use_pallas=True)
        assert jnp.array_equal(a, b), "tucker must take the identical einsum path"

    def test_non_dense_operands_raise(self):
        rng = np.random.RandomState(6)
        x = _randc(rng, (2, 4, 8))
        with pytest.raises(ValueError, match="dense-only"):
            ops.spectral_contract(x, _randc(rng, (4, 4)), policy=FULL)
        with pytest.raises(ValueError, match="ComplexPair"):
            ops.spectral_contract(x, {"U_i_re": np.zeros((4, 2))}, policy=FULL)
        with pytest.raises(ValueError, match="disagree"):
            ops.spectral_contract(x, _randc(rng, (5, 4, 8)), policy=FULL)
        with pytest.raises(ValueError, match="mode factors"):
            ops.spectral_contract_cp(
                x, _randc(rng, (3,)), _randc(rng, (4, 3)), _randc(rng, (4, 3)),
                [], policy=FULL)

    def test_resolve_use_pallas_env(self, monkeypatch):
        from repro.kernels.ops import resolve_use_pallas

        assert resolve_use_pallas(True) is True
        assert resolve_use_pallas(False) is False
        monkeypatch.setenv("REPRO_USE_PALLAS", "1")
        assert resolve_use_pallas(None) is True
        monkeypatch.setenv("REPRO_USE_PALLAS", "0")
        assert resolve_use_pallas(None) is False
        monkeypatch.delenv("REPRO_USE_PALLAS")
        assert resolve_use_pallas(None) == (jax.default_backend() == "tpu")

    def test_pick_block_m_respects_budget(self):
        from repro.kernels.spectral_contract import (
            VMEM_BUDGET, cp_vmem_bytes, vmem_bytes, vmem_bytes_bwd)

        # the estimators count double-buffered blocks and the f32
        # intermediates, so the pickers pack under the whole scoped limit;
        # mode tiles are whole 128-lane rows (Mosaic's minor-dim rule)
        bm = pick_block_m(32, 64, 64, 4096)
        assert bm in (128, 256, 512)
        need = max(vmem_bytes(32, 64, 64, bm), vmem_bytes_bwd(32, 64, 64, bm))
        assert need <= VMEM_BUDGET
        # the CP kernel's rank-space arrays grow with the batch: the ops
        # wrapper splits a batch whose smallest tile would not fit
        from repro.kernels.ops import _batch_tile

        bb = _batch_tile(64, lambda b: cp_vmem_bytes(b, 64, 64, 64, 128))
        assert bb < 64
        bm_cp = pick_block_m(bb, 64, 64, 4096, rank=64)
        assert bm_cp in (128, 256, 512)
        assert cp_vmem_bytes(bb, 64, 64, 64, bm_cp) <= VMEM_BUDGET


# ---------------------------------------------------------------------------
# Gradients
# ---------------------------------------------------------------------------


def _grad_leaves(g):
    return jax.tree_util.tree_leaves(g)


def _grad_parity(policy_name, factorization, modes, spatial, seed=11):
    policy = get_policy(policy_name)
    rng = np.random.RandomState(seed)
    params = init_spectral_weights(
        jax.random.PRNGKey(seed), 4, 4, modes, factorization)
    x = jnp.asarray(rng.randn(2, 4, *spatial), jnp.float32)

    def loss(p, use_pallas):
        y = spectral_conv_apply(p, x, modes, policy, use_pallas=use_pallas)
        return jnp.mean(y ** 2)

    l_e, g_e = jax.value_and_grad(loss)(params, False)
    l_p, g_p = jax.value_and_grad(loss)(params, True)
    tol = GRAD_TOLS[policy_name]
    assert abs(float(l_p) - float(l_e)) <= tol * (abs(float(l_e)) + 1e-6)
    for a, b in zip(_grad_leaves(g_p), _grad_leaves(g_e), strict=True):
        assert _rel_err(a, b) <= tol, (policy_name, factorization, modes)


class TestGradients:
    assert sorted(GRAD_TOLS) == POLICY_NAMES, "cover every registry policy"

    @pytest.mark.parametrize("policy_name", POLICY_NAMES)
    @pytest.mark.parametrize("factorization", ["dense", "cp"])
    def test_spectral_conv_value_and_grad_matches(self, policy_name,
                                                  factorization):
        _grad_parity(policy_name, factorization, (3, 3), (8, 8))

    @pytest.mark.parametrize("policy_name", ["full", "mixed_fno_bf16"])
    @pytest.mark.parametrize("factorization", ["dense", "cp"])
    @pytest.mark.parametrize("ndim", [1, 3])
    def test_spectral_conv_grads_1d_3d(self, policy_name, factorization,
                                       ndim):
        modes = MODES_BY_NDIM[ndim]
        spatial = tuple(2 * m + 2 for m in modes)
        _grad_parity(policy_name, factorization, modes, spatial, seed=ndim)

    @pytest.mark.parametrize("policy_name", ["full", "mixed_fno_bf16"])
    def test_lshared_grads_match_einsum(self, policy_name):
        """value_and_grad through the SFNO l-shared kernel vs the einsum
        path (both via the resolved contract site)."""
        policy = get_policy(policy_name)
        site = policy.at("sfno/layer0/spectral/contract")
        rng = np.random.RandomState(23)
        x = _randc(rng, (2, 3, 5, 4))
        w = _randc(rng, (3, 4, 5))

        def loss(w, use_pallas):
            if use_pallas:
                y = ops.spectral_contract_lshared(x, w, policy=site,
                                                  block_l=2)
            else:
                y = site.contract("bilm,iol->bolm", x, w)
            if isinstance(y, ComplexPair):
                return jnp.mean(y.abs2())
            return jnp.mean(jnp.abs(y) ** 2)

        l_e, g_e = jax.value_and_grad(loss, holomorphic=False)(w, False)
        l_p, g_p = jax.value_and_grad(loss, holomorphic=False)(w, True)
        tol = GRAD_TOLS[policy_name] * 10  # complex-cotangent casts add noise
        assert abs(float(l_p) - float(l_e)) <= tol * (abs(float(l_e)) + 1e-6)
        assert _rel_err(np.asarray(g_p), np.asarray(g_e)) <= tol

    @pytest.mark.slow
    @pytest.mark.parametrize("factorization", ["dense", "cp"])
    def test_train_step_parity_with_loss_scaling(self, factorization):
        """Full FNO/TFNO train steps through the Trainer, pallas vs
        einsum, under the fp16 policy whose ``train/loss_scale`` site is
        on — the loss-scale interaction rides through the custom VJP."""
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=2, modes=(4, 4), factorization=factorization)
        params = init_fno(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        batches = [
            {"a": jnp.asarray(rng.randn(4, 1, 12, 12), jnp.float32),
             "u": jnp.asarray(rng.randn(4, 1, 12, 12), jnp.float32)}
            for _ in range(3)
        ]

        def loss_fn(p, batch, policy, use_pallas=None):
            c = dataclasses.replace(cfg, use_pallas=use_pallas)
            return relative_l2(fno_apply(p, batch["a"], c, policy), batch["u"])

        from repro.core import PrecisionSchedule

        results = {}
        for up in (False, True):
            tr = Trainer(loss_fn, params, TrainerConfig(
                total_steps=3,
                schedule=PrecisionSchedule.constant("mixed_fno_fp16"),
                use_pallas=up,
            ))
            hist = tr.run(lambda step: batches[step])
            results[up] = (tr.params, tr.scale_state, hist)
        p_e, s_e, h_e = results[False]
        p_p, s_p, h_p = results[True]
        assert float(s_e.scale) == float(s_p.scale)
        # two independent half-storage roundings accumulated over 3 fp16
        # train steps; 2e-3 was borderline on some CPU backends (2.15e-3
        # observed), so the budget carries headroom over the observed peak
        for a, b in zip(_grad_leaves(p_p), _grad_leaves(p_e), strict=True):
            assert _rel_err(a, b) <= 3e-3
        for he, hp in zip(h_e, h_p, strict=True):
            assert abs(he["loss"] - hp["loss"]) <= 0.02 * (abs(he["loss"]) + 1e-6)

    def test_fd_gradcheck_fp64_dense(self):
        """fp64 central-difference check of the custom VJP itself (both
        backward kernels), on a tiny dense case in interpret mode."""
        jax.config.update("jax_enable_x64", True)
        try:
            rng = np.random.RandomState(2)
            shapes = [(1, 2, 3), (1, 2, 3), (2, 3, 3), (2, 3, 3)]
            args = [jnp.asarray(rng.randn(*s), jnp.float64) for s in shapes]
            cr = jnp.asarray(rng.randn(1, 3, 3), jnp.float64)
            ci = jnp.asarray(rng.randn(1, 3, 3), jnp.float64)

            def loss(xr, xi, wr, wi):
                yr, yi = spectral_contract_pallas(
                    xr, xi, wr, wi, block_m=8, interpret=True)
                return jnp.sum(yr * cr + yi * ci)

            grads = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
            h = 1e-6
            for k in range(4):
                g = np.asarray(grads[k])
                fd = np.zeros_like(g)
                flat = np.asarray(args[k]).copy()
                for idx in np.ndindex(g.shape):
                    plus = flat.copy(); plus[idx] += h
                    minus = flat.copy(); minus[idx] -= h
                    ap = list(args); ap[k] = jnp.asarray(plus)
                    am = list(args); am[k] = jnp.asarray(minus)
                    fd[idx] = (float(loss(*ap)) - float(loss(*am))) / (2 * h)
                np.testing.assert_allclose(g, fd, rtol=1e-6, atol=1e-7,
                                           err_msg=f"arg {k}")
        finally:
            jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# Fused quantize prologue (cast_to)
# ---------------------------------------------------------------------------


class TestFusedCastPrologue:
    """The in-kernel quantize fusion must be numerically *invisible*:
    same storage grid, same rounding, one fewer HBM round-trip.  The
    unfused path is the one ``spectral_contract`` takes where the f32
    operand tiles do not fit VMEM (``_fused_cast_fits`` False)."""

    def _xw(self, seed=17, B=2, I=3, O=4, modes=(3, 5)):
        rng = np.random.RandomState(seed)
        return _randc(rng, (B, I, *modes)), _randc(rng, (I, O, *modes))

    @staticmethod
    def _unfuse(monkeypatch):
        monkeypatch.setattr(ops, "_fused_cast_fits", lambda *a: False)

    @pytest.mark.parametrize("policy_name", HALF_POLICY_NAMES)
    def test_forward_bit_identical_to_unfused(self, policy_name,
                                              monkeypatch):
        """In-VMEM ``astype(half)`` is the same rounding as the HBM
        ``ComplexPair.from_complex`` pass it replaces — the fused forward
        is bitwise equal, not merely within budget."""
        site = get_policy(policy_name).at("fno/layer0/spectral/contract")
        x, w = self._xw()
        y_f = ops.spectral_contract(x, w, policy=site, block_m=4)
        self._unfuse(monkeypatch)
        y_u = ops.spectral_contract(x, w, policy=site, block_m=4)
        assert jnp.array_equal(jnp.asarray(y_f), jnp.asarray(y_u)), (
            policy_name)

    @pytest.mark.parametrize("policy_name", HALF_POLICY_NAMES)
    def test_fused_within_budget_vs_einsum(self, policy_name):
        site = get_policy(policy_name).at("fno/layer0/spectral/contract")
        x, w = self._xw(seed=18)
        assert ops._fused_cast_fits(x.shape, w.shape[1])
        y_e = site.contract(_dense_expr(2), x, w)
        y_p = ops.spectral_contract(x, w, policy=site, block_m=4)
        mag = np.einsum("bixy,ioxy->boxy", np.abs(x), np.abs(w))
        _assert_within_budget(y_p, y_e, site.eps, mag, stages=2,
                              label=f"dense-fused {policy_name}")

    @pytest.mark.parametrize("policy_name", HALF_POLICY_NAMES)
    def test_grads_match_unfused(self, policy_name, monkeypatch):
        """The fused backward writes dx/dw at f32 (the residuals'
        dtype); the unfused one rounds them to half — they may differ
        only by that final storage rounding."""
        site = get_policy(policy_name).at("fno/layer0/spectral/contract")
        x, w = self._xw(seed=19)

        def loss(x, w):
            y = ops.spectral_contract(x, w, policy=site, block_m=4)
            return jnp.sum(jnp.abs(jnp.asarray(y)) ** 2)

        l_f, g_f = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        self._unfuse(monkeypatch)
        l_u, g_u = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(float(l_f), float(l_u), rtol=1e-6)
        tol = max(8 * site.eps, 1e-4)
        for a, b in zip(g_f, g_u, strict=True):
            assert _rel_err(a, b) <= tol, policy_name

    def test_full_precision_site_never_fuses(self, monkeypatch):
        """No quantize rule means nothing to fuse: with or without room
        for the f32 tiles, the identical f32 path runs."""
        x, w = self._xw(seed=20)
        y_t = ops.spectral_contract(x, w, policy=FULL, block_m=4)
        self._unfuse(monkeypatch)
        y_f = ops.spectral_contract(x, w, policy=FULL, block_m=4)
        assert jnp.array_equal(y_t, y_f)

    def test_pair_inputs_skip_fusion(self, monkeypatch):
        """Operands already rounded to half pairs have no cast to fuse:
        room for the f32 tiles must make no difference to them."""
        site = get_policy("mixed_fno_bf16").at("fno/layer0/spectral/contract")
        x, w = self._xw(seed=21)
        xp = ComplexPair.from_complex(x, site.spectral_dtype)
        wp = ComplexPair.from_complex(w, site.spectral_dtype)
        y_t = ops.spectral_contract(xp, wp, policy=site, block_m=4)
        self._unfuse(monkeypatch)
        y_f = ops.spectral_contract(xp, wp, policy=site, block_m=4)
        assert jnp.array_equal(y_t.re, y_f.re)
        assert jnp.array_equal(y_t.im, y_f.im)


# ---------------------------------------------------------------------------
# The chip's spectral path: truncated-DFT transforms around the kernels
# ---------------------------------------------------------------------------


def _dense_layer(seed, I, O, modes):
    return init_spectral_weights(
        jax.random.PRNGKey(seed), I, O, modes, "dense")


def _diff_dft(policy_name, B, I, O, spatial, modes, seed):
    """The path a TPU takes (truncated-DFT transforms around the staged
    Pallas kernel) vs the einsum + FFT reference, under the composed
    Thm 3.2 budget: each path has (at most) four requantising stages —
    forward transform, quantise, contract, inverse transform — so the
    elementwise budget carries stages=8, one ``4 eps M`` term per stage
    of either side, with ``M`` the composed magnitude envelope of the
    whole pipeline (``helpers.spectral_mag``)."""
    policy = get_policy(policy_name)
    fft_in = policy.at("fno/layer0/spectral/fft_in")
    ctr = policy.at("fno/layer0/spectral/contract")
    rng = np.random.RandomState(seed)
    params = _dense_layer(seed, I, O, modes)
    x = jnp.asarray(rng.randn(B, I, *spatial), jnp.float32)

    y_s = spectral_conv_apply(params, x, modes, policy, use_pallas=False,
                              site="fno/layer0/spectral")
    with force_tpu_transforms():
        assert dft_transforms(spatial, modes), (
            "test shape must take the DFT path", spatial, modes)
        y_f = spectral_conv_apply(params, x, modes, policy, use_pallas=True,
                                  site="fno/layer0/spectral")
    assert y_f.shape == y_s.shape == (B, O, *spatial)
    assert y_f.dtype == y_s.dtype, (policy_name, y_f.dtype, y_s.dtype)

    xs = fft_in.stabilize(x)
    mag = spectral_mag(xs, params["w_re"], params["w_im"], spatial, modes)
    _assert_within_budget(
        np.asarray(y_f, np.float64), np.asarray(y_s, np.float64),
        ctr.eps, mag, stages=8,
        label=f"dft {policy_name} B{B} I{I} O{O} "
              f"spatial{spatial} modes{modes}")


class TestFusedMegakernel:
    """Differential proof for the TPU's spectral path: truncated-DFT
    matmuls around the staged dense kernel must stay within the composed
    Thm 3.2 budget against the einsum + FFT reference, for every registry
    policy, on odd / non-MXU-aligned grids."""

    @pytest.mark.parametrize("policy_name", POLICY_NAMES)
    @pytest.mark.parametrize("ndim", [1, 2, 3])
    def test_fused_vs_staged_all_policies(self, policy_name, ndim):
        _diff_dft(policy_name, B=2, I=3, O=4,
                    spatial=SPATIAL_BY_NDIM[ndim],
                    modes=MODES_BY_NDIM[ndim], seed=40 + ndim)

    def test_fused_matches_einsum_reference_full(self):
        """Against the pure jnp reference (no Pallas, no DFT), full
        precision: the truncated-DFT factorisation itself."""
        _diff_dft("full", B=1, I=2, O=2, spatial=(8, 16),
                    modes=(4, 5), seed=51)

    @pytest.mark.slow
    @given(
        st.integers(min_value=1, max_value=3),
        st.integers(min_value=1, max_value=5),
        st.integers(min_value=1, max_value=5),
        st.integers(min_value=5, max_value=14),
        st.integers(min_value=5, max_value=15),
        st.sampled_from(sorted(POLICIES)),
    )
    @settings(max_examples=10, deadline=None)
    def test_property_fuzzed_shapes(self, B, I, O, S0, S1, policy_name):
        """Hypothesis-fuzzed 2D shapes: odd spatial dims, mode counts off
        the kernel's tile, Nyquist-touching last-axis modes."""
        m0 = max(1, S0 // 2 - 1)           # corner blocks must not overlap
        m1 = S1 // 2 + 1                   # retain the full rfft extent
        seed = B * 100000 + I * 10000 + O * 1000 + S0 * 16 + S1
        _diff_dft(policy_name, B, I, O, (S0, S1), (m0, m1), seed)

    def test_fused_fp64_gradcheck(self):
        """fp64 central-difference check of the chip path's pieces
        composed: ``truncated_rfft`` -> the dense kernel's custom VJP per
        corner -> ``truncated_irfft``, on a tiny 2D case in interpret
        mode, sampling entries of each operand.  The factors are f32
        constants; the check is that the gradients differentiate the
        forward as computed."""
        from repro.core.spectral import (
            _corner_slices, _stack_corners, truncated_irfft, truncated_rfft)

        jax.config.update("jax_enable_x64", True)
        try:
            spatial, modes = (7, 8), (3, 3)
            rng = np.random.RandomState(7)
            shapes = [(1, 2, *spatial)] + [(2, 2, 3, *modes)] * 2
            args = [jnp.asarray(rng.randn(*s), jnp.float64) for s in shapes]
            c = jnp.asarray(rng.randn(1, 3, *spatial), jnp.float64)

            def loss(x, w_re, w_im):
                xf = truncated_rfft(x, modes)
                ycs = []
                for k, sl in enumerate(_corner_slices(modes, xf.shape[2:])):
                    xc = xf[(slice(None), slice(None), *sl)].reshape(1, 2, -1)
                    yr, yi = spectral_contract_pallas(
                        jnp.real(xc), jnp.imag(xc),
                        w_re[k].reshape(2, 3, -1), w_im[k].reshape(2, 3, -1),
                        block_m=8, interpret=True)
                    ycs.append(jax.lax.complex(yr, yi).reshape(1, 3, *modes))
                y = truncated_irfft(_stack_corners(ycs, 2), spatial, modes)
                return jnp.sum(y * c)

            grads = jax.grad(loss, argnums=(0, 1, 2))(*args)
            h = 1e-6
            for k in range(3):
                g = np.asarray(grads[k])
                flat = np.asarray(args[k], np.float64)
                idxs = [np.unravel_index(j, g.shape) for j in
                        rng.choice(g.size, size=min(8, g.size),
                                   replace=False)]
                for idx in idxs:
                    plus = flat.copy(); plus[idx] += h
                    minus = flat.copy(); minus[idx] -= h
                    ap = list(args); ap[k] = jnp.asarray(plus)
                    am = list(args); am[k] = jnp.asarray(minus)
                    fd = (float(loss(*ap)) - float(loss(*am))) / (2 * h)
                    np.testing.assert_allclose(
                        g[idx], fd, rtol=1e-5, atol=1e-6,
                        err_msg=f"arg {k} idx {idx}")
        finally:
            jax.config.update("jax_enable_x64", False)

    @pytest.mark.slow
    def test_train_step_parity_fused_vs_staged_fp16_loss_scale(self):
        """Full FNO train steps through the Trainer on the staged Pallas
        path, DFT transforms (a TPU's) vs FFT transforms, under the fp16
        policy whose ``train/loss_scale`` site is on — the loss-scale
        interaction rides through the DFT matmuls' gradients."""
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=2, modes=(4, 4), factorization="dense")
        params = init_fno(jax.random.PRNGKey(1), cfg)
        rng = np.random.RandomState(1)
        batches = [
            {"a": jnp.asarray(rng.randn(4, 1, 12, 12), jnp.float32),
             "u": jnp.asarray(rng.randn(4, 1, 12, 12), jnp.float32)}
            for _ in range(3)
        ]

        from repro.core import PrecisionSchedule

        def loss_fn(p, batch, policy, use_pallas=None):
            c = dataclasses.replace(cfg, use_pallas=use_pallas)
            return relative_l2(fno_apply(p, batch["a"], c, policy),
                               batch["u"])

        def train():
            tr = Trainer(loss_fn, params, TrainerConfig(
                total_steps=3,
                schedule=PrecisionSchedule.constant("mixed_fno_fp16"),
                use_pallas=True,
            ))
            hist = tr.run(lambda step: batches[step])
            return tr.params, tr.scale_state, hist

        p_s, s_s, h_s = train()
        with force_tpu_transforms():
            p_f, s_f, h_f = train()
        assert float(s_s.scale) == float(s_f.scale)
        # both paths round the spectrum onto the same fp16 grid but order
        # their f32 accumulations differently; 3 accumulated steps
        for a, b in zip(_grad_leaves(p_f), _grad_leaves(p_s), strict=True):
            assert _rel_err(a, b) <= 5e-3
        for hs, hf in zip(h_s, h_f, strict=True):
            assert abs(hs["loss"] - hf["loss"]) <= 0.02 * (abs(hs["loss"]) + 1e-6)


class TestCompiledLayout:
    """What the TPU path needs from the dispatch, checked on the CPU."""

    @pytest.mark.parametrize("policy_name", ["full", "mixed_fno_bf16"])
    @pytest.mark.parametrize("kind", ["dense", "cp"])
    def test_batch_split_matches_one_call(self, kind, policy_name,
                                          monkeypatch):
        """A batch too large for one kernel call's VMEM runs as several
        calls; values and gradients match one call (to f32 rounding at
        f32 storage; to the policy's storage rounding otherwise, since
        each call's weight gradient is stored at the storage dtype)."""
        policy = get_policy(policy_name)
        site = policy.at("fno/layer0/spectral/contract")
        rng = np.random.RandomState(31)
        B, I, O, modes, R = 6, 3, 4, (3, 5), 4
        M = 15
        x = _randc(rng, (B, I, *modes))
        if kind == "dense":
            ws = (_randc(rng, (I, O, *modes)),)

            def call(x, *ws):
                return ops.spectral_contract(x, ws[0], policy=site)

            # at a budget this small the fused cast's f32 tiles do not
            # fit, so half storage streams half operand tiles
            item = 2 if site.spectral_is_half else 4

            def need(b):
                return max(ops.vmem_bytes(b, I, O, M, item),
                           ops.vmem_bytes_bwd(b, I, O, M, item))
        else:
            ws = (_randc(rng, (R,)), _randc(rng, (I, R)), _randc(rng, (O, R)),
                  *[_randc(rng, (m, R)) for m in modes])
            item = jnp.dtype(site.spectral_dtype or jnp.float32).itemsize

            def call(x, *ws):
                return ops.spectral_contract_cp(x, ws[0], ws[1], ws[2],
                                                list(ws[3:]), policy=site)

            def need(b):
                return ops.cp_vmem_bytes(b, I, O, R, M, item)

        def loss(x, *ws):
            y = call(x, *ws)
            if isinstance(y, ComplexPair):
                return jnp.sum(y.abs2())
            return jnp.sum(jnp.abs(y) ** 2)

        grad = jax.value_and_grad(loss, argnums=tuple(range(1 + len(ws))))
        l1, g1 = grad(x, *ws)
        # a budget that fits two batch rows, not six: three calls of two
        monkeypatch.setattr(ops, "VMEM_BUDGET", need(2))
        assert ops._batch_tile(B, need) == 2
        fwd = str(jax.make_jaxpr(call)(x, *ws))
        assert fwd.count("name=spectral_contract") == 3
        l2, g2 = grad(x, *ws)
        tol = GRAD_TOLS[policy_name]
        assert abs(float(l2) - float(l1)) <= tol * abs(float(l1))
        for a, b in zip(g2, g1, strict=True):
            assert _rel_err(np.asarray(a), np.asarray(b)) <= tol

    def test_interpret_resolves_from_backend(self):
        from repro.kernels.spectral_contract import resolve_interpret

        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False
        assert resolve_interpret(None) == (jax.default_backend() != "tpu")

    @pytest.mark.parametrize("B,I,O,M,rank,itemsize", [
        (16, 64, 64, 1024, 0, 2), (16, 64, 64, 1764, 64, 2),
        (2, 8, 8, 40, 0, 4), (4, 16, 16, 100, 8, 2)])
    def test_mode_tiles_are_lane_legal(self, B, I, O, M, rank, itemsize):
        """Mosaic tiles the minor dim in 128 lanes: a mode tile is a
        multiple of 128 or the whole mode axis."""
        bm = pick_block_m(B, I, O, M, rank=rank, itemsize=itemsize)
        assert bm % 128 == 0 or bm == M

    @pytest.mark.parametrize("L", [3, 8, 12, 128])
    def test_degree_tiles_are_sublane_legal(self, L):
        from repro.kernels.spectral_contract import pick_block_l

        bl = pick_block_l(4, 64, 64, L, 128)
        assert bl % 8 == 0 or bl == L
