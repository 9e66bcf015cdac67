"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in interpret mode on CPU (the TPU compile path is covered
by the dry-run, which lowers the same call sites for the production mesh).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ComplexPair, FULL, get_policy
from repro.kernels import ops, ref
from repro.kernels.spectral_contract import spectral_contract_pallas, vmem_bytes

from helpers import rand_complex

jax.config.update("jax_platform_name", "cpu")

# this module's sweeps predate the shared helper and pinned unit scale
_rand_complex = functools.partial(rand_complex, scale=1.0)


class TestSpectralContractKernel:
    @pytest.mark.parametrize(
        "B,I,O,M", [(1, 4, 4, 8), (2, 8, 16, 32), (3, 16, 8, 65), (2, 8, 8, 1)]
    )
    def test_shapes_f32(self, B, I, O, M):
        rng = np.random.RandomState(B * 100 + I)
        x = _rand_complex(rng, (B, I, M))
        w = _rand_complex(rng, (I, O, M))
        xr, xi = jnp.real(x), jnp.imag(x)
        wr, wi = jnp.real(w), jnp.imag(w)
        out_re, out_im = spectral_contract_pallas(
            xr, xi, wr, wi, block_m=16, interpret=True
        )
        want = ref.spectral_contract_ref(x, w)
        np.testing.assert_allclose(np.asarray(out_re), np.real(want), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(out_im), np.imag(want), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
    def test_half_dtypes(self, dtype):
        rng = np.random.RandomState(7)
        B, I, O, M = 2, 8, 8, 24
        x = _rand_complex(rng, (B, I, M), scale=0.5)
        w = _rand_complex(rng, (I, O, M), scale=0.2)
        xr = jnp.real(x).astype(dtype)
        xi = jnp.imag(x).astype(dtype)
        wr = jnp.real(w).astype(dtype)
        wi = jnp.imag(w).astype(dtype)
        out_re, out_im = spectral_contract_pallas(
            xr, xi, wr, wi, block_m=8, interpret=True
        )
        want = ref.spectral_contract_ref(x, w)
        got = np.asarray(out_re, np.float32) + 1j * np.asarray(out_im, np.float32)
        rel = np.abs(got - np.asarray(want)) / (np.abs(np.asarray(want)) + 1e-2)
        # storage-precision error only (accumulation is f32)
        tol = 2e-2 if dtype == jnp.float16 else 1e-1
        assert rel.mean() < tol

    def test_ops_wrapper_multimode(self):
        """The ops wrapper flattens (x, y) mode axes and restores them."""
        rng = np.random.RandomState(8)
        x = _rand_complex(rng, (2, 4, 6, 5))
        w = _rand_complex(rng, (4, 8, 6, 5))
        got = ops.spectral_contract(x, w, policy=FULL)
        want = jnp.einsum("bixy,ioxy->boxy", x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3)

    def test_ops_wrapper_half_policy_returns_pair(self):
        rng = np.random.RandomState(9)
        policy = get_policy("mixed_fno_bf16")
        x = ComplexPair.from_complex(_rand_complex(rng, (2, 4, 6, 5)), jnp.bfloat16)
        w = _rand_complex(rng, (4, 8, 6, 5))
        got = ops.spectral_contract(x, w, policy=policy)
        assert isinstance(got, ComplexPair)
        assert got.re.dtype == jnp.bfloat16
        assert got.shape == (2, 8, 6, 5)

    @pytest.mark.slow
    @given(
        st.integers(min_value=1, max_value=3),
        st.integers(min_value=1, max_value=12),
        st.integers(min_value=1, max_value=12),
        st.integers(min_value=1, max_value=40),
        st.sampled_from([4, 8, 16]),
    )
    @settings(max_examples=15, deadline=None)
    def test_property_any_shape(self, B, I, O, M, block_m):
        rng = np.random.RandomState(B * 1000 + I * 100 + O * 10 + M)
        x = _rand_complex(rng, (B, I, M))
        w = _rand_complex(rng, (I, O, M))
        out_re, out_im = spectral_contract_pallas(
            jnp.real(x), jnp.imag(x), jnp.real(w), jnp.imag(w),
            block_m=block_m, interpret=True,
        )
        want = ref.spectral_contract_ref(x, w)
        np.testing.assert_allclose(np.asarray(out_re), np.real(want), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(out_im), np.imag(want), rtol=1e-3, atol=1e-3)

    def test_vmem_budget_helper(self):
        # the default tile (one 128-lane row of modes) at a 32-row batch
        # of 64 channels fits the 16 MiB scoped VMEM limit, double
        # buffers and f32 products included
        assert vmem_bytes(32, 64, 64, 128) < 16 * 2 ** 20


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("S,D,causal", [(64, 32, True), (128, 64, False), (96, 32, True)])
    def test_matches_ref(self, S, D, causal):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(2, S, D), jnp.float32)
        k = jnp.asarray(rng.randn(2, S, D), jnp.float32)
        v = jnp.asarray(rng.randn(2, S, D), jnp.float32)
        from repro.kernels.flash_attention import flash_attention

        got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)

    def test_unaligned_lengths(self):
        """Padding paths: S=50, Sk=70 with 32-blocks."""
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 50, 32), jnp.float32)
        k = jnp.asarray(rng.randn(1, 70, 32), jnp.float32)
        v = jnp.asarray(rng.randn(1, 70, 32), jnp.float32)
        from repro.kernels.flash_attention import flash_attention

        got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)

    def test_bf16(self):
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(2, 64, 32), jnp.bfloat16)
        k = jnp.asarray(rng.randn(2, 64, 32), jnp.bfloat16)
        v = jnp.asarray(rng.randn(2, 64, 32), jnp.bfloat16)
        from repro.kernels.flash_attention import flash_attention

        got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
        )

    def test_ops_wrapper_heads(self):
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(2, 4, 64, 32), jnp.float32)
        k = jnp.asarray(rng.randn(2, 4, 64, 32), jnp.float32)
        v = jnp.asarray(rng.randn(2, 4, 64, 32), jnp.float32)
        got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        want = ref.flash_attention_ref(
            q.reshape(8, 64, 32), k.reshape(8, 64, 32), v.reshape(8, 64, 32), causal=True
        ).reshape(2, 4, 64, 32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


class TestRMSNormKernel:
    @pytest.mark.parametrize("N,D", [(8, 16), (300, 64), (1, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, N, D, dtype):
        rng = np.random.RandomState(N)
        x = jnp.asarray(rng.randn(N, D), dtype)
        w = jnp.asarray(rng.rand(D) + 0.5, dtype)
        from repro.kernels.rmsnorm import rmsnorm

        got = rmsnorm(x, w, block_rows=64, interpret=True)
        want = ref.rmsnorm_ref(x, w)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
        )

    def test_ops_wrapper_rank3(self):
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(2, 5, 16), jnp.float32)
        w = jnp.ones(16, jnp.float32)
        got = ops.rmsnorm(x, w)
        want = ref.rmsnorm_ref(x.reshape(-1, 16), w).reshape(2, 5, 16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


class TestKernelModelIntegration:
    def test_spectral_conv_pallas_path_matches_jnp(self):
        """spectral_conv_apply(use_pallas=True) == jnp contraction path."""
        import numpy as np
        from repro.core import FULL, init_spectral_weights, spectral_conv_apply

        rng = np.random.RandomState(11)
        key = jax.random.PRNGKey(11)
        params = init_spectral_weights(key, 4, 4, (4, 4))
        x = jnp.asarray(rng.randn(2, 4, 16, 16), jnp.float32)
        y_jnp = np.asarray(
            spectral_conv_apply(params, x, (4, 4), FULL, use_pallas=False))
        y_pl = np.asarray(spectral_conv_apply(params, x, (4, 4), FULL, use_pallas=True))
        np.testing.assert_allclose(y_pl, y_jnp, rtol=1e-3, atol=1e-4)

    def test_spectral_conv_pallas_half(self):
        import numpy as np
        from repro.core import get_policy, init_spectral_weights, spectral_conv_apply

        rng = np.random.RandomState(12)
        key = jax.random.PRNGKey(12)
        policy = get_policy("mixed_fno_bf16")
        params = init_spectral_weights(key, 8, 8, (4, 4))
        x = jnp.asarray(rng.randn(2, 8, 16, 16), jnp.float32)
        y_pl = np.asarray(
            spectral_conv_apply(params, x, (4, 4), policy, use_pallas=True), np.float32
        )
        y_jnp = np.asarray(
            spectral_conv_apply(params, x, (4, 4), policy, use_pallas=False),
            np.float32,
        )
        rel = np.linalg.norm(y_pl - y_jnp) / (np.linalg.norm(y_jnp) + 1e-9)
        assert rel < 0.05, rel


class TestBlockedAttentionJNP:
    """Pure-JAX blocked attention (models/lm/common.py) vs plain reference,
    including the MLA case where v's head dim differs from q/k's."""

    def test_matches_plain(self):
        from repro.models.lm.common import blocked_attention, plain_attention

        rng = np.random.RandomState(0)
        B, H, S, D = 2, 3, 96, 16
        q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        pos = jnp.arange(S)
        got = blocked_attention(q, k, v, pos, pos, 1 << 30, q_chunk=32, k_chunk=32)
        want = plain_attention(q, k, v, pos, pos, 1 << 30)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)

    def test_mla_distinct_v_dim(self):
        from repro.models.lm.common import blocked_attention, plain_attention

        rng = np.random.RandomState(1)
        B, H, S, D, Dv = 1, 2, 64, 24, 16
        q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, H, S, Dv), jnp.float32)
        pos = jnp.arange(S)
        got = blocked_attention(q, k, v, pos, pos, 1 << 30, q_chunk=16, k_chunk=16)
        want = plain_attention(q, k, v, pos, pos, 1 << 30)
        assert got.shape == (B, H, S, Dv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)

    def test_sliding_window(self):
        from repro.models.lm.common import blocked_attention, plain_attention

        rng = np.random.RandomState(2)
        B, H, S, D = 1, 2, 96, 16
        q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        pos = jnp.arange(S)
        got = blocked_attention(q, k, v, pos, pos, 24, q_chunk=32, k_chunk=32)
        want = plain_attention(q, k, v, pos, pos, 24)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)
