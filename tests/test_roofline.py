"""Roofline machinery tests: the cost_analysis loop-undercount finding and
the trip-count-aware HLO parser against analytically-known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_parse import parse_hlo, split_computations
from repro.launch.roofline import analyze_counts, model_flops

jax.config.update("jax_platform_name", "cpu")

L, N = 8, 128


def _scan_matmul_fn():
    def f(w, x):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return jnp.sum(h)
    return f


def _shapes():
    return (jax.ShapeDtypeStruct((L, N, N), jnp.float32),
            jax.ShapeDtypeStruct((N, N), jnp.float32))


class TestCostAnalysisUndercount:
    def test_loop_bodies_counted_once(self):
        """The finding that motivates the custom parser: XLA cost_analysis
        reports a scan of length L at ~1/L of the true FLOPs."""
        f = _scan_matmul_fn()
        compiled = jax.jit(f).lower(*_shapes()).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        true_flops = L * 2 * N ** 3
        ratio = cost["flops"] / true_flops
        assert ratio < 0.5, f"expected undercount, got ratio {ratio}"


class TestHLOParser:
    def test_forward_flops_exact(self):
        f = _scan_matmul_fn()
        compiled = jax.jit(f).lower(*_shapes()).compile()
        counts = parse_hlo(compiled.as_text())
        true_flops = L * 2 * N ** 3
        assert abs(counts.flops - true_flops) / true_flops < 0.05

    def test_grad_flops_about_3x(self):
        f = _scan_matmul_fn()
        fwd = parse_hlo(jax.jit(f).lower(*_shapes()).compile().as_text())
        bwd = parse_hlo(
            jax.jit(jax.grad(f, argnums=0)).lower(*_shapes()).compile().as_text())
        assert 2.0 < bwd.flops / fwd.flops < 4.5

    def test_collectives_counted_under_spmd(self):
        if jax.device_count() < 2:
            pytest.skip("needs >1 device (run under dryrun env)")
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((jax.device_count(),), ("data",))
        def f(x):
            return jnp.sum(x)
        xs = jax.ShapeDtypeStruct((jax.device_count() * 4, 8), jnp.float32)
        with mesh:
            comp = jax.jit(f, in_shardings=NamedSharding(mesh, P("data"))
                           ).lower(xs).compile()
        counts = parse_hlo(comp.as_text())
        assert counts.collective_bytes >= 0  # parses without error

    def test_split_computations_structure(self):
        f = _scan_matmul_fn()
        hlo = jax.jit(f).lower(*_shapes()).compile().as_text()
        comps = split_computations(hlo)
        assert any("while" in o.op for c in comps.values() for o in c.ops)

    def test_bytes_in_sane_range(self):
        """HBM-byte estimate must be within [1x, 30x] of the tensor data
        actually touched (loose envelope; catches unit errors)."""
        f = _scan_matmul_fn()
        counts = parse_hlo(jax.jit(f).lower(*_shapes()).compile().as_text())
        data_bytes = (L * N * N + N * N) * 4
        assert data_bytes <= counts.bytes <= 40 * data_bytes


class TestRooflineTerms:
    def test_analyze_counts_math(self):
        from repro.launch.hlo_parse import HLOCounts
        c = HLOCounts(flops=197e12, bytes=819e9, collective_by_kind={"all-reduce": 50e9})
        r = analyze_counts(c, 256)
        np.testing.assert_allclose(r.compute_s, 1.0)
        np.testing.assert_allclose(r.memory_s, 1.0)
        np.testing.assert_allclose(r.collective_s, 1.0)
        assert r.step_time_s == 1.0

    def test_model_flops_6nd(self):
        assert model_flops(1e9, 1e6) == 6e15


class TestPeakTableAndCompileCache:
    def test_peaks_keyed_by_device_kind(self):
        from repro.launch.roofline import HBM_BW, PEAKS

        v5e = PEAKS["TPU v5 lite"]
        assert v5e["hbm_bw"] == HBM_BW == 819e9
        assert v5e["bf16_flops"] == 197e12
        assert "cpu" not in PEAKS

    def test_compile_cache_env_dir_is_left_to_jax(self, monkeypatch,
                                                  tmp_path):
        from repro.launch import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert compile_cache.place(tmp_path) == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == before

    def test_compile_cache_defaults_to_fixed_repo_dir(self, monkeypatch,
                                                      tmp_path):
        from repro.launch import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            got = compile_cache.place(tmp_path)
            assert got == str(tmp_path.resolve() / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            # the same checkout always gets the same directory
            assert compile_cache.place(tmp_path) == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_cache_counter_counts_monitoring_events(self):
        from repro.launch import compile_cache

        with compile_cache.CacheCounter() as c:
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
            jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        assert c.counts == {"hits": 2, "misses": 1}
