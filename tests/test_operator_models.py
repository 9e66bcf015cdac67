"""Tests for the neural-operator model zoo (FNO/TFNO/SFNO/GINO/U-Net)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FULL, get_policy
from repro.models import (
    FNOConfig,
    GINOConfig,
    SFNOConfig,
    UNetConfig,
    fno_apply,
    fno_infer,
    gino_apply,
    init_fno,
    init_gino,
    init_sfno,
    init_unet,
    param_count,
    sfno_apply,
    sfno_infer,
    unet_apply,
)

jax.config.update("jax_platform_name", "cpu")


class TestFNO:
    @pytest.mark.parametrize("fact", ["dense", "cp", "tucker"])
    def test_forward_shapes(self, fact):
        cfg = FNOConfig(
            in_channels=3, out_channels=1, hidden_channels=16,
            lifting_channels=24, projection_channels=24, n_layers=2,
            modes=(4, 4), factorization=fact,
        )
        params = init_fno(jax.random.PRNGKey(0), cfg)
        x = jnp.ones((2, 3, 16, 16))
        y = fno_apply(params, x, cfg, FULL)
        assert y.shape == (2, 1, 16, 16)
        assert np.isfinite(np.asarray(y)).all()

    def test_mixed_policy_close_to_full(self):
        cfg = FNOConfig(
            in_channels=1, out_channels=1, hidden_channels=16,
            lifting_channels=16, projection_channels=16, n_layers=2, modes=(4, 4),
        )
        params = init_fno(jax.random.PRNGKey(1), cfg)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 1, 16, 16), jnp.float32)
        y_full = np.asarray(fno_apply(params, x, cfg, FULL))
        y_half = np.asarray(fno_apply(params, x, cfg, get_policy("mixed_fno_bf16")), np.float32)
        rel = np.linalg.norm(y_half - y_full) / (np.linalg.norm(y_full) + 1e-9)
        assert rel < 0.25, rel  # tanh + half storage changes the net slightly

    def test_train_step_reduces_loss(self):
        """End-to-end sanity: a few SGD steps reduce the fit loss."""
        cfg = FNOConfig(
            in_channels=1, out_channels=1, hidden_channels=12,
            lifting_channels=12, projection_channels=12, n_layers=2, modes=(4, 4),
        )
        params = init_fno(jax.random.PRNGKey(2), cfg)
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(4, 1, 16, 16), jnp.float32)
        t = jnp.asarray(rng.randn(4, 1, 16, 16), jnp.float32) * 0.1

        def loss_fn(p):
            y = fno_apply(p, x, cfg, FULL)
            return jnp.mean((y - t) ** 2)

        loss0 = float(loss_fn(params))
        g = jax.grad(loss_fn)
        for _ in range(5):
            grads = g(params)
            params = jax.tree_util.tree_map(lambda p, gr: p - 0.05 * gr, params, grads)
        assert float(loss_fn(params)) < loss0

    def test_resolution_invariance(self):
        """Same params run at 16x16 and 32x32 (discretisation convergence)."""
        cfg = FNOConfig(
            in_channels=1, out_channels=1, hidden_channels=8,
            lifting_channels=8, projection_channels=8, n_layers=1, modes=(4, 4),
        )
        params = init_fno(jax.random.PRNGKey(4), cfg)
        for n in (16, 32):
            y = fno_apply(params, jnp.ones((1, 1, n, n)), cfg, FULL)
            assert y.shape == (1, 1, n, n)

    def test_cp_fewer_params_than_dense(self):
        mk = lambda f: init_fno(
            jax.random.PRNGKey(0),
            FNOConfig(hidden_channels=32, n_layers=2, modes=(8, 8), factorization=f),
        )
        assert param_count(mk("cp")) < param_count(mk("dense"))


class TestSFNO:
    def test_forward_shapes(self):
        cfg = SFNOConfig(
            in_channels=3, out_channels=3, hidden_channels=8, n_layers=2,
            nlat=16, nlon=32, lmax=8, mmax=8,
            lifting_channels=8, projection_channels=8,
        )
        params = init_sfno(jax.random.PRNGKey(0), cfg)
        x = jnp.ones((2, 3, 16, 32))
        y = sfno_apply(params, x, cfg, FULL)
        assert y.shape == (2, 3, 16, 32)
        assert np.isfinite(np.asarray(y)).all()

    def test_mixed_policy_finite(self):
        cfg = SFNOConfig(
            in_channels=1, out_channels=1, hidden_channels=8, n_layers=1,
            nlat=16, nlon=32, lmax=8, mmax=8,
            lifting_channels=8, projection_channels=8,
        )
        params = init_sfno(jax.random.PRNGKey(1), cfg)
        x = jnp.asarray(np.random.RandomState(2).randn(1, 1, 16, 32) * 100, jnp.float32)
        y = sfno_apply(params, x, cfg, get_policy("mixed_fno_fp16"))
        assert np.isfinite(np.asarray(y, np.float32)).all()


def _smoke_model(model):
    """A 2-layer FNO or SFNO at smoke widths, with its input."""
    if model == "fno":
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=2, modes=(4, 4))
        return (cfg, init_fno(jax.random.PRNGKey(0), cfg),
                jnp.ones((2, 1, 16, 16)), fno_apply, fno_infer)
    cfg = SFNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                     n_layers=2, nlat=16, nlon=32, lmax=8, mmax=8,
                     lifting_channels=8, projection_channels=8)
    return (cfg, init_sfno(jax.random.PRNGKey(0), cfg),
            jnp.ones((2, 1, 16, 32)), sfno_apply, sfno_infer)


def _block_loops(path):
    from repro.obs import registry

    key = f'repro_block_loop_total{{path="{path}"}}'
    return registry().snapshot()["counters"].get(key, 0.0)


class TestBlockLoopLowering:
    """The block loop is lowered by entry point: unrolled under a
    differentiated forward (no residuals stacked across a ``while``),
    one rolled scan for the serving forward."""

    @pytest.mark.parametrize("model", ["fno", "sfno"])
    def test_training_step_stacks_no_residuals(self, model):
        cfg, params, x, apply, _ = _smoke_model(model)
        policy = get_policy("mixed_fno_bf16")

        def loss(p):
            return jnp.sum(apply(p, x, cfg, policy).astype(jnp.float32) ** 2)

        text = jax.jit(jax.value_and_grad(loss)).lower(params).as_text()
        assert "stablehlo.while" not in text
        assert "dynamic_update_slice" not in text
        assert "dynamic_slice" not in text

    @pytest.mark.parametrize("model", ["fno", "sfno"])
    def test_serving_forward_keeps_one_scan(self, model):
        cfg, params, x, _, infer = _smoke_model(model)
        policy = get_policy("mixed_fno_bf16")
        text = jax.jit(lambda p, v: infer(p, v, cfg, policy)).lower(
            params, x).as_text()
        assert text.count("stablehlo.while") == 1

    @pytest.mark.parametrize("model", ["fno", "sfno"])
    def test_block_loop_counter_names_the_lowering(self, model):
        """``repro_block_loop_total`` counts one loop per step traced,
        under the lowering it took."""
        from repro.autoprec import TraceCollector, collecting

        cfg, params, x, apply, infer = _smoke_model(model)
        policy = get_policy("mixed_fno_bf16")
        paths = ("unrolled", "scan", "layers")
        before = {p: _block_loops(p) for p in paths}
        jax.make_jaxpr(jax.value_and_grad(
            lambda p: jnp.sum(apply(p, x, cfg, policy))))(params)
        jax.make_jaxpr(lambda p: infer(p, x, cfg, policy))(params)
        with collecting(TraceCollector()):
            jax.make_jaxpr(lambda p: apply(p, x, cfg, policy))(params)
        assert {p: _block_loops(p) - before[p] for p in paths} == {
            "unrolled": 1, "scan": 1, "layers": 1}


class TestGINO:
    def _batch(self, B=2, N=64, G=4, k=4, Nq=32):
        rng = np.random.RandomState(0)
        return {
            "points": jnp.asarray(rng.rand(B, N, 3), jnp.float32),
            "feats": jnp.asarray(rng.randn(B, N, 1), jnp.float32),
            "enc_idx": jnp.asarray(rng.randint(0, N, (B, G ** 3, k))),
            "enc_mask": jnp.asarray(rng.rand(B, G ** 3, k) > 0.3, jnp.float32),
            "query": jnp.asarray(rng.rand(B, Nq, 3), jnp.float32),
            "dec_idx": jnp.asarray(rng.randint(0, G ** 3, (B, Nq, k))),
            "dec_mask": jnp.ones((B, Nq, k), jnp.float32),
        }

    def test_forward_shapes(self):
        from repro.models.fno import FNOConfig

        cfg = GINOConfig(
            hidden=8, latent_grid=4, k_neighbors=4,
            fno=FNOConfig(
                in_channels=8, out_channels=8, hidden_channels=8,
                lifting_channels=8, projection_channels=8, n_layers=1,
                modes=(2, 2, 2), positional_embedding=False,
            ),
        )
        params = init_gino(jax.random.PRNGKey(0), cfg)
        batch = self._batch(G=4, k=4)
        y = gino_apply(params, batch, cfg, FULL)
        assert y.shape == (2, 32, 1)
        assert np.isfinite(np.asarray(y)).all()


class TestUNet:
    def test_forward_shapes(self):
        cfg = UNetConfig(in_channels=3, out_channels=1, base_width=8, depth=2)
        params = init_unet(jax.random.PRNGKey(0), cfg)
        x = jnp.ones((2, 3, 32, 32))
        y = unet_apply(params, x, cfg, FULL)
        assert y.shape == (2, 1, 32, 32)
        assert np.isfinite(np.asarray(y)).all()

    def test_amp_policy(self):
        cfg = UNetConfig(in_channels=1, out_channels=1, base_width=8, depth=2)
        params = init_unet(jax.random.PRNGKey(1), cfg)
        x = jnp.ones((1, 1, 16, 16))
        y = unet_apply(params, x, cfg, get_policy("amp_bf16"))
        assert np.isfinite(np.asarray(y, np.float32)).all()
