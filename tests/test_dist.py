"""repro.dist API tests: rule-table resolution, fallback ordering,
off-mesh no-op behaviour, and FNO spec derivation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist import (
    axis_rules,
    constrain,
    constrain_bsd,
    constrain_spatial,
    dp_axes,
    fno_param_specs,
    logical_axis_size,
    pick_spec,
    replication_report,
    use_mesh,
)
from repro.models.fno import FNOConfig, init_fno

jax.config.update("jax_platform_name", "cpu")


def _fake_mesh(shape=(2, 4), axes=("data", "model")):
    """Abstract mesh over fake devices for spec-only tests."""
    devs = np.empty(shape, dtype=object)

    class _D:
        def __init__(self, i):
            self.id = i
            self.platform = "cpu"
            self.device_kind = "fake"

    for idx in range(int(np.prod(shape))):
        devs.reshape(-1)[idx] = _D(idx)
    try:
        return Mesh(devs, axes)
    except Exception:
        pytest.skip("cannot build fake mesh on this jax version")


class TestConstrainOffMesh:
    def test_no_mesh_is_identity(self):
        x = jnp.ones((4, 8))
        assert constrain(x, "dp", "tp") is x
        assert constrain_bsd(jnp.ones((2, 4, 8))) is not None

    def test_no_mesh_under_jit(self):
        @jax.jit
        def f(x):
            return constrain_spatial(x) * 2.0

        x = jnp.ones((2, 3, 8, 8))
        np.testing.assert_allclose(np.asarray(f(x)), 2.0 * np.ones(x.shape))

    def test_single_device_mesh_is_identity(self):
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        x = jnp.ones((4, 8))
        with use_mesh(mesh):
            assert constrain(x, "dp", "tp") is x

    def test_logical_axis_size_off_mesh(self):
        assert logical_axis_size("dp") == 1
        assert logical_axis_size("heads") == 1


class TestPickSpecFallback:
    def test_fallback_ordering(self):
        mesh = _fake_mesh((2, 4), ("data", "model"))
        # first divisible candidate wins, even if later ones also fit
        chain = [(("model",), None), ((("data",),) + (None,)), ()]
        assert pick_spec((16, 64), mesh, chain) == P("model", None)
        # 15 % model=4 fails -> falls to data (15 % 2 fails too) -> P()
        assert pick_spec((15, 64), mesh, chain) == P()
        # 6 % 4 fails but 6 % 2 passes -> second candidate
        assert pick_spec((6, 64), mesh, chain) == P("data", None)

    def test_logical_names_resolve(self):
        mesh = _fake_mesh((2, 4), ("data", "model"))
        assert pick_spec((8, 8), mesh, [("dp", "tp"), ()]) == P("data", "model")
        # "pod" absent from this mesh: adapted away, not a failure
        assert pick_spec((8,), mesh, [(("pod", "data"),), ()]) == P("data")

    def test_multi_pod_dp(self):
        mesh = _fake_mesh((2, 2, 4), ("pod", "data", "model"))
        assert dp_axes(mesh) == ("pod", "data")
        assert pick_spec((8, 4), mesh, [("dp", None), ()]) == P(("pod", "data"), None)

    def test_axis_rules_override(self):
        mesh = _fake_mesh((2, 4), ("data", "model"))
        with axis_rules(seq=("data",)):
            assert pick_spec((8, 8), mesh, [(None, "seq"), ()]) == P(None, "data")
        assert pick_spec((8, 8), mesh, [(None, "seq"), ()]) == P(None, "model")


class TestFnoParamSpecs:
    def test_small_fno_fully_replicates(self):
        mesh = _fake_mesh()
        cfg = FNOConfig()
        p_shape = jax.eval_shape(lambda k: init_fno(k, cfg), jax.random.PRNGKey(0))
        specs = fno_param_specs(p_shape, mesh)
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(spec_leaves) == len(jax.tree_util.tree_leaves(p_shape))
        assert all(s == P() for s in spec_leaves)
        rep = replication_report(p_shape, specs)
        assert rep["sharded_bytes"] == 0
        assert rep["replicated_bytes"] == rep["total_bytes"] > 0

    def test_big_spectral_leaf_shards_channels(self):
        mesh = _fake_mesh()
        # stacked dense spectral weights above the threshold:
        # (L, corners, in, out, m1, m2) -> out channels over model
        big = jax.ShapeDtypeStruct((4, 2, 64, 64, 128, 128), jnp.float32)
        tree = {"spectral": {"w_re": big},
                "lift1": {"w": jax.ShapeDtypeStruct((5, 256), jnp.float32)}}
        specs = fno_param_specs(tree, mesh, shard_threshold=1 << 20)
        assert specs["spectral"]["w_re"][0] is None  # scan axis untouched
        assert "model" in jax.tree_util.tree_leaves(
            [list(specs["spectral"]["w_re"])])
        assert specs["lift1"]["w"] == P()

    def test_replication_report_with_sharding(self):
        mesh = _fake_mesh()
        big = jax.ShapeDtypeStruct((4, 2, 64, 64, 128, 128), jnp.float32)
        tree = {"w": big}
        specs = fno_param_specs(tree, mesh, shard_threshold=1 << 20)
        rep = replication_report(tree, specs)
        assert rep["sharded_bytes"] > 0
        assert rep["n_sharded"] == 1


_SHARDED_SERVE_SCRIPT = """
import jax, numpy as np
from jax.sharding import Mesh
from repro.configs import get_config
from repro.models.lm import init_lm
from repro.serve import Request, ServeEngine

cfg = get_config("smollm-360m", smoke=True)
params = init_lm(jax.random.PRNGKey(0), cfg)
reqs = lambda: [Request(uid=i, prompt=[1, 2, 3], max_new_tokens=4)
                for i in range(6)]
plain = ServeEngine(params, cfg, n_slots=4, max_len=32)
d1, _ = plain.run_until_done(reqs())
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
sharded = ServeEngine(params, cfg, n_slots=4, max_len=32, mesh=mesh)
d2, _ = sharded.run_until_done(reqs())
g1 = {r.uid: r.generated for r in d1}
g2 = {r.uid: r.generated for r in d2}
assert g1 == g2, (g1, g2)
print("MATCH")
"""


class TestShardedServing:
    def test_sharded_engine_matches_unsharded(self):
        """ServeEngine(mesh=...) must generate bit-identical tokens to
        the unsharded engine.  Runs in a subprocess because the forced
        device count must be set before jax initialises."""
        import os
        import subprocess
        import sys

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORM_NAME"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        proc = subprocess.run(
            [sys.executable, "-c", _SHARDED_SERVE_SCRIPT],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "MATCH" in proc.stdout


_SHARDED_KERNELS_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import get_policy
from repro.dist import batch_specs, fno_param_specs, to_named, use_mesh
from repro.models import (FNOConfig, SFNOConfig, fno_apply, init_fno,
                          init_sfno, sfno_apply)
from repro.train.losses import relative_l2

policy = get_policy("full")
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
rng = np.random.RandomState(0)
cases = [
    (FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
               lifting_channels=8, projection_channels=8, n_layers=2,
               modes=(4, 4), use_pallas=True),
     init_fno, fno_apply, (1, 12, 12)),
    (FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
               lifting_channels=8, projection_channels=8, n_layers=2,
               modes=(4, 4), factorization="cp", use_pallas=True),
     init_fno, fno_apply, (1, 12, 12)),
    (SFNOConfig(in_channels=3, out_channels=3, hidden_channels=8,
                n_layers=2, nlat=8, nlon=16, lmax=4, mmax=4,
                lifting_channels=8, projection_channels=8, use_pallas=True),
     init_sfno, sfno_apply, (3, 8, 16)),
]
for cfg, init, apply, shape in cases:
    params = init(jax.random.PRNGKey(0), cfg)
    batch = {"x": jnp.asarray(rng.randn(8, *shape), jnp.float32),
             "y": jnp.asarray(rng.randn(8, *shape), jnp.float32)}

    def loss_fn(p, b):
        return relative_l2(apply(p, b["x"], cfg, policy), b["y"])

    grad = jax.value_and_grad(loss_fn)
    l1, g1 = jax.jit(grad)(params, batch)
    with use_mesh(mesh):
        p_named = to_named(mesh, fno_param_specs(params, mesh))
        b_named = to_named(mesh, batch_specs(batch, mesh))
        sharded = jax.jit(grad, in_shardings=(p_named, b_named))
        text = sharded.lower(params, batch).as_text()
        # the kernels run per device inside a shard_map
        assert "manual_computation" in text, "no shard_map around kernels"
        l4, g4 = sharded(jax.device_put(params, p_named),
                         jax.device_put(batch, b_named))
    assert abs(float(l4) - float(l1)) <= 1e-5 * abs(float(l1)), (l4, l1)
    for a, b in zip(jax.tree_util.tree_leaves(g4),
                    jax.tree_util.tree_leaves(g1), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
print("MATCH")
"""


class TestShardedKernels:
    def test_pallas_kernels_under_a_mesh_match_one_device(self):
        """Mosaic kernels cannot be partitioned by the compiler, so under
        an ambient mesh the kernel wrappers run per batch shard in a
        shard_map; loss and gradients (dense, CP and l-shared kernels)
        must match the unsharded step.  Subprocess: the forced device
        count must be set before jax initialises."""
        import os
        import subprocess
        import sys

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        proc = subprocess.run(
            [sys.executable, "-c", _SHARDED_KERNELS_SCRIPT],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "MATCH" in proc.stdout
