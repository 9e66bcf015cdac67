"""Tests for the mixed-precision Fourier convolution + theory + schedule."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FULL,
    MIXED_FNO_BF16,
    MIXED_FNO_FP16,
    PrecisionSchedule,
    get_policy,
    init_spectral_weights,
    spectral_conv_apply,
    theory,
)

jax.config.update("jax_platform_name", "cpu")


def _x(rng, shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


class TestSpectralConv:
    @pytest.mark.parametrize("ndim,spatial", [(1, (32,)), (2, (16, 16)), (3, (8, 8, 8))])
    def test_shapes(self, ndim, spatial):
        rng = np.random.RandomState(0)
        assert len(spatial) == ndim
        key = jax.random.PRNGKey(0)
        modes = tuple(max(2, s // 4) for s in spatial)
        params = init_spectral_weights(key, 4, 6, modes)
        x = _x(rng, (2, 4, *spatial))
        y = spectral_conv_apply(params, x, modes, FULL)
        assert y.shape == (2, 6, *spatial)
        assert np.isfinite(np.asarray(y)).all()

    @pytest.mark.parametrize("fact", ["dense", "cp", "tucker"])
    def test_factorizations_run(self, fact):
        rng = np.random.RandomState(1)
        key = jax.random.PRNGKey(1)
        params = init_spectral_weights(key, 4, 4, (4, 4), factorization=fact)
        x = _x(rng, (2, 4, 16, 16))
        y = spectral_conv_apply(params, x, (4, 4), FULL)
        assert y.shape == (2, 4, 16, 16)
        assert np.isfinite(np.asarray(y)).all()

    @pytest.mark.parametrize("policy_name", ["mixed_fno_fp16", "mixed_fno_bf16"])
    @pytest.mark.parametrize("fact", ["dense", "cp"])
    def test_half_close_to_full(self, policy_name, fact):
        """Precision error of the half pipeline is small relative to signal —
        the empirical counterpart of Thm 3.2."""
        rng = np.random.RandomState(2)
        key = jax.random.PRNGKey(2)
        params = init_spectral_weights(key, 8, 8, (6, 6), factorization=fact)
        x = _x(rng, (2, 8, 24, 24))
        y_full = np.asarray(spectral_conv_apply(params, x, (6, 6), FULL))
        y_half = np.asarray(
            spectral_conv_apply(params, x, (6, 6), get_policy(policy_name))
        ).astype(np.float32)
        # tanh stabiliser changes the function; compare against the full
        # pipeline with the same stabiliser applied.
        x_stab = jnp.tanh(x)
        y_ref = np.asarray(spectral_conv_apply(params, x_stab, (6, 6), FULL))
        rel = np.linalg.norm(y_half - y_ref) / (np.linalg.norm(y_ref) + 1e-9)
        assert rel < 0.05, rel

    def test_no_overflow_on_large_inputs_with_tanh(self):
        """The paper's headline failure mode: naive half FNO overflows. With
        the tanh stabiliser the half pipeline must stay finite even for
        inputs near the fp16 max."""
        rng = np.random.RandomState(3)
        key = jax.random.PRNGKey(3)
        params = init_spectral_weights(key, 4, 4, (4, 4))
        x = _x(rng, (1, 4, 16, 16)) * 3e4  # near fp16 max 65504
        y = spectral_conv_apply(params, x, (4, 4), MIXED_FNO_FP16)
        assert np.isfinite(np.asarray(y, dtype=np.float32)).all()

    def test_naive_half_overflows_without_stabilizer(self):
        """Counterpart: without the stabiliser, the fp16 FFT boundary
        overflows for large inputs (reproduces the NaN failure)."""
        from repro.precision import SiteRule

        rng = np.random.RandomState(4)
        key = jax.random.PRNGKey(4)
        params = init_spectral_weights(key, 4, 4, (4, 4))
        naive = MIXED_FNO_FP16.with_rules(
            ("*/spectral/*", SiteRule(stabilize=None)), name="naive_fp16"
        )
        x = _x(rng, (1, 4, 64, 64)) * 3e4
        y = spectral_conv_apply(params, x, (4, 4), naive)
        assert not np.isfinite(np.asarray(y, dtype=np.float32)).all()

    def test_grad_flows(self):
        rng = np.random.RandomState(5)
        key = jax.random.PRNGKey(5)
        params = init_spectral_weights(key, 4, 4, (4, 4))
        x = _x(rng, (2, 4, 16, 16))

        def loss(p):
            y = spectral_conv_apply(p, x, (4, 4), MIXED_FNO_BF16)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        g = jax.grad(loss)(params)
        flat = [v for k, v in g.items() if isinstance(v, jnp.ndarray)]
        assert all(np.isfinite(np.asarray(t)).all() for t in flat)
        assert any(np.abs(np.asarray(t)).max() > 0 for t in flat)

    def test_discretization_convergence(self):
        """FNO property: the same operator applied at finer resolution
        converges (zero-shot super-resolution, Table 1 setting).  We check
        the spectral conv of a band-limited function is resolution-stable."""
        key = jax.random.PRNGKey(6)
        params = init_spectral_weights(key, 1, 1, (4, 4))

        def field(n):
            t = np.linspace(0, 1, n, endpoint=False)
            xx, yy = np.meshgrid(t, t, indexing="ij")
            f = np.sin(2 * np.pi * xx) * np.cos(4 * np.pi * yy)
            return jnp.asarray(f[None, None], jnp.float32)

        outs = {}
        for n in (32, 64):
            y = np.asarray(spectral_conv_apply(params, field(n), (4, 4), FULL))
            outs[n] = y[0, 0, :: n // 32, :: n // 32]  # sample to common grid
        rel = np.linalg.norm(outs[64] - outs[32]) / np.linalg.norm(outs[32])
        assert rel < 0.05, rel


def _force_tpu_transforms(monkeypatch):
    """Make the backend predicate read "tpu", so the staged path takes the
    truncated-DFT transforms on the CPU (kernels stay interpreted)."""
    from repro.core import spectral

    monkeypatch.setattr(spectral, "_on_tpu", lambda: True)


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, custom_vjp, scan)
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    yield from _eqns(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _transforms_total(path):
    from repro.obs import registry

    key = f'repro_spectral_transforms_total{{path="{path}"}}'
    return registry().snapshot()["counters"].get(key, 0.0)


class TestDFTPath:
    """The staged path's truncated-DFT transforms (taken on a TPU) against
    its FFT path, layer by layer."""

    #: full: float32 transforms that differ only in rounding order (~1e-7
    #: of the signal).  mixed_fno_bf16: those differences move a value
    #: across a bfloat16 rounding boundary now and then, one bfloat16
    #: step (2^-8) on that value, a small share of the values.
    RTOL = {"full": 1e-5, "mixed_fno_bf16": 2.0 ** -8}

    @pytest.mark.parametrize("policy_name", ["full", "mixed_fno_bf16"])
    @pytest.mark.parametrize("fact,use_pallas", [
        ("dense", False), ("dense", True), ("cp", True)],
        ids=["dense-einsum", "dense-pallas", "cp-pallas"])
    def test_layer_matches_fft_path(self, monkeypatch, fact, use_pallas,
                                    policy_name):
        rng = np.random.RandomState(7)
        params = init_spectral_weights(jax.random.PRNGKey(7), 4, 6, (4, 5),
                                       factorization=fact)
        x = _x(rng, (2, 4, 16, 16))
        r = _x(rng, (2, 6, 16, 16))
        policy = get_policy(policy_name)

        def run(p):
            def loss(p):
                y = spectral_conv_apply(p, x, (4, 5), policy,
                                        use_pallas=use_pallas,
                                        fuse_spectral=False)
                return jnp.sum(y.astype(jnp.float32) * r), y

            (_, y), g = jax.value_and_grad(loss, has_aux=True)(p)
            return y, g

        y_fft, g_fft = run(params)
        _force_tpu_transforms(monkeypatch)
        y_dft, g_dft = run(params)
        tol = self.RTOL[policy_name]

        def rel(a, b):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            return np.linalg.norm(a - b) / np.linalg.norm(b)

        assert y_dft.dtype == y_fft.dtype
        assert rel(y_dft, y_fft) <= tol
        for k in g_fft:
            assert rel(g_dft[k], g_fft[k]) <= tol, k

    def test_jaxpr_has_no_fft_and_keeps_scopes(self, monkeypatch):
        """No ``fft`` primitive on the DFT path, and the transforms keep the
        ``{site}/fft_in`` / ``{site}/fft_out`` named scopes that the
        benchmark's ``fft_ms.train`` reads from the device trace."""
        params = init_spectral_weights(jax.random.PRNGKey(0), 4, 4, (4, 4))
        x = jnp.zeros((2, 4, 16, 16), jnp.float32)
        site = "fno/layer0/spectral"

        def census():
            # a new function each time: make_jaxpr caches by function
            def fwd_bwd(p, x):
                return jax.grad(lambda p: jnp.sum(spectral_conv_apply(
                    p, x, (4, 4), MIXED_FNO_BF16, use_pallas=False,
                    site=site)))(p)

            eqns = list(_eqns(jax.make_jaxpr(fwd_bwd)(params, x).jaxpr))
            prims = {e.primitive.name for e in eqns}
            scopes = {str(e.source_info.name_stack) for e in eqns
                      if e.primitive.name == "dot_general"}
            return prims, scopes

        prims, _ = census()
        assert "fft" in prims
        _force_tpu_transforms(monkeypatch)
        prims, scopes = census()
        assert "fft" not in prims
        assert any(f"{site}/fft_in" in s for s in scopes), scopes
        assert any(f"{site}/fft_out" in s for s in scopes), scopes

    def test_overlapping_corners_keep_fft_path(self, monkeypatch):
        """Where the low and high blocks overlap (2m > S) the factorisation
        is not exact: the FFT path runs, even on a TPU."""
        from repro.core.spectral import dft_transforms

        _force_tpu_transforms(monkeypatch)
        assert dft_transforms((16, 16), (8, 9))
        assert not dft_transforms((12, 12), (7, 4))
        params = init_spectral_weights(jax.random.PRNGKey(0), 2, 2, (7, 4))
        x = jnp.zeros((1, 2, 12, 12), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda x: spectral_conv_apply(
            params, x, (7, 4), FULL, use_pallas=False))(x)
        assert "fft" in {e.primitive.name for e in _eqns(jaxpr.jaxpr)}

    def test_transform_counter_per_layer_and_path(self, monkeypatch):
        """``repro_spectral_transforms_total`` counts one per layer traced,
        under the path it took."""
        params = init_spectral_weights(jax.random.PRNGKey(0), 2, 2, (4, 4))
        x = jnp.zeros((1, 2, 16, 16), jnp.float32)

        def trace(spatial_x, modes):
            jax.make_jaxpr(lambda x: spectral_conv_apply(
                params, x, modes, FULL, use_pallas=False))(spatial_x)

        before = {p: _transforms_total(p) for p in ("dft", "fft")}
        trace(x, (4, 4))
        assert _transforms_total("fft") == before["fft"] + 1
        _force_tpu_transforms(monkeypatch)
        trace(x, (4, 4))
        trace(x, (4, 4))
        assert _transforms_total("dft") == before["dft"] + 2
        over = init_spectral_weights(jax.random.PRNGKey(0), 2, 2, (7, 4))
        jax.make_jaxpr(lambda x: spectral_conv_apply(
            over, x, (7, 4), FULL, use_pallas=False))(
                jnp.zeros((1, 2, 12, 12), jnp.float32))
        assert _transforms_total("fft") == before["fft"] + 2
        assert _transforms_total("dft") == before["dft"] + 2


class TestTheory:
    def test_disc_error_within_bounds_1d(self):
        v = lambda x: np.prod(x, axis=-1)  # the lower-bound witness v(x)=x1..xd
        for m in (16, 32, 64):
            err = theory.disc_error(v, m=m, d=1, omega=1.0)
            ub = theory.disc_upper_bound(n=m, d=1, omega=1.0, L=1.0, M=1.0)
            assert err <= ub, (m, err, ub)

    def test_disc_error_decays_with_n(self):
        v = lambda x: np.sin(2 * np.pi * x[..., 0]) * np.prod(x, axis=-1)
        errs = [theory.disc_error(v, m=m, d=1, omega=1.0) for m in (8, 16, 32, 64)]
        assert errs[0] > errs[-1]

    def test_prec_error_bounded(self):
        v = lambda x: np.prod(x, axis=-1)
        for d in (1, 2):
            err = theory.prec_error(v, m=16, d=d, omega=1.0, dtype="float16")
            ub = theory.prec_upper_bound(eps=2.0 ** -11, M=1.0)
            assert err <= ub, (d, err, ub)

    def test_precision_smaller_than_discretization(self):
        """The paper's headline claim: Prec << Disc at realistic mesh sizes."""
        v = lambda x: np.sin(2 * np.pi * x[..., 0]) + 0.5 * np.prod(x, axis=-1)
        disc = theory.disc_error(v, m=64, d=2, omega=1.0)
        prec = theory.prec_error(v, m=64, d=2, omega=1.0, dtype="float16")
        assert prec < disc

    def test_crossover_mesh_size_3d_fp16(self):
        n_star = theory.crossover_mesh_size(eps=1e-4, d=3)
        assert n_star > 1e6  # the paper quotes ~1e6 for 3-D fp16


class TestSchedule:
    def test_paper_default_phases(self):
        s = PrecisionSchedule.paper_default("fp16")
        total = 100
        assert s.policy_at(0, total).name == "mixed_fno_fp16"
        assert s.policy_at(50, total).name == "amp_fp16"
        assert s.policy_at(99, total).name == "full"

    def test_boundaries_cover_run(self):
        s = PrecisionSchedule.paper_default("bf16")
        bs = s.phase_boundaries(1000)
        assert bs[0][0] == 0 and bs[-1][1] == 1000
        assert all(b[1] == nb[0] for b, nb in zip(bs, bs[1:], strict=False))

    def test_invalid_raises(self):
        with pytest.raises(ValueError):
            PrecisionSchedule(phases=((0.5, "full"), (0.4, "full")))
