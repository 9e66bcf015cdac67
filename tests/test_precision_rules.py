"""Tests for the site-addressed precision API (repro.precision).

Covers: rule resolution / scoping, bit-identity of the rebuilt registry
rule sets against a reference implementation of the old flat-dataclass
pipeline, per-site overrides the old API could not express, the overlay
schedule, loss-scaling resolution, and the simulated fp8 rule sets.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ComplexPair,
    FULL,
    PrecisionSchedule,
    contract,
    get_policy,
    quantize_complex,
    simulate_fp8,
)
from repro.core.stabilizer import get_stabilizer
from repro.models import FNOConfig, fno_apply, init_fno
from repro.models.fno import _linear, layers_uniform
from repro.models.lm import init_lm, lm_forward
from repro.configs import get_config
from repro.optim import loss_scaling_required
from repro.precision import (
    FULL_PRECISION,
    SiteRule,
    describe,
    precision_rules,
)

jax.config.update("jax_platform_name", "cpu")

POLICY_NAMES = ["full", "amp_bf16", "mixed_fno_bf16", "mixed_fno_fp16"]


# ---------------------------------------------------------------------------
# Rule resolution
# ---------------------------------------------------------------------------


class TestResolution:
    def test_registry_resolves_like_old_dataclass(self):
        """The rebuilt rule sets resolve to exactly the formats the old
        flat policy fields carried."""
        expect = {
            # name: (compute, spectral, stabilizer, loss_scaling)
            "full": (jnp.float32, None, None, False),
            "amp_fp16": (jnp.float16, None, None, True),
            "amp_bf16": (jnp.bfloat16, None, None, False),
            "mixed_fno_fp16": (jnp.float16, jnp.float16, "tanh", True),
            "mixed_fno_bf16": (jnp.bfloat16, jnp.bfloat16, "tanh", False),
            "half_fno_only": (jnp.float32, jnp.float16, "tanh", True),
        }
        for name, (cdt, sdt, stab, ls) in expect.items():
            p = get_policy(name)
            assert p.compute_dtype == cdt, name
            assert p.spectral_dtype == sdt, name
            assert p.stabilizer == stab, name
            assert p.requires_loss_scaling is ls, name
            assert loss_scaling_required(p) is ls, name

    def test_sites_resolve_independently(self):
        p = get_policy("mixed_fno_bf16")
        # routers and output heads stay f32 even under the mixed rule set
        assert p.at("lm/router").compute_dtype == jnp.float32
        assert p.at("fno/proj_out").compute_dtype == jnp.float32
        assert p.at("params").compute_dtype == jnp.float32
        # spectral sites are addressable per layer
        s = p.at("fno/layer3/spectral/contract")
        assert s.spectral_dtype == jnp.bfloat16
        assert s.accum_dtype == jnp.float32
        # kv cache follows the rule set's compute dtype
        assert p.at("serve/kv_cache").compute_dtype == jnp.bfloat16
        assert get_policy("full").at("serve/kv_cache").compute_dtype == jnp.float32

    def test_precision_rules_scoping(self):
        p = get_policy("mixed_fno_bf16")
        assert p.at("fno/layer2/spectral/contract").spectral_is_half
        with precision_rules(("fno/layer2/*", FULL_PRECISION)):
            assert not p.at("fno/layer2/spectral/contract").spectral_is_half
            # other layers untouched
            assert p.at("fno/layer1/spectral/contract").spectral_is_half
            # nesting: innermost wins
            with precision_rules(
                ("fno/layer2/*", SiteRule(compute=jnp.float16, quantize="half"))
            ):
                assert (
                    p.at("fno/layer2/spectral/contract").spectral_dtype == jnp.float16
                )
            assert not p.at("fno/layer2/spectral/contract").spectral_is_half
        assert p.at("fno/layer2/spectral/contract").spectral_is_half

    def test_field_wise_merge(self):
        """An overlay overriding one field leaves the others resolved by
        the policy's own rules."""
        p = get_policy("mixed_fno_fp16").with_rules(
            ("*/spectral/*", SiteRule(stabilize="hard_clip"))
        )
        s = p.at("fno/layer0/spectral/fft_in")
        assert s.stabilizer == "hard_clip"
        assert s.spectral_dtype == jnp.float16  # untouched

    def test_describe_reports_canonical_sites(self):
        d = describe(get_policy("mixed_fno_fp16"))
        assert d["model/spectral/contract"]["compute"] == "float16"
        assert d["model/spectral/contract"]["quantize"] == "half"
        assert d["lm/router"]["compute"] == "float32"
        assert d["train/loss_scale"]["loss_scaling"] is True


# ---------------------------------------------------------------------------
# Bit-identity vs the old flat-dataclass pipeline
# ---------------------------------------------------------------------------


def _old_policy_view(policy):
    """The old flat dataclass, reconstructed from the policy's compat
    properties (these resolve through the rule table)."""
    return types.SimpleNamespace(
        compute_dtype=policy.compute_dtype,
        spectral_dtype=policy.spectral_dtype,
        accum_dtype=policy.accum_dtype,
        stabilizer=policy.stabilizer,
        spectral_is_half=policy.spectral_is_half,
    )


def _old_spectral_conv(params, x, modes, pol):
    """Reference: the seed's spectral_conv_apply, driven by flat fields."""
    from repro.core.spectral import _corner_slices, _corner_weight_ops, _out_channels

    ndim = len(modes)
    spatial = x.shape[2:]
    in_dtype = x.dtype
    if pol.spectral_is_half and pol.stabilizer:
        x = get_stabilizer(pol.stabilizer)(x)
    xf = jnp.fft.rfftn(x.astype(jnp.float32), axes=tuple(range(2, 2 + ndim)))
    if pol.spectral_is_half:
        xf = quantize_complex(xf, pol.spectral_dtype)
    corners = _corner_slices(modes, xf.shape[2:])
    out_f = jnp.zeros((x.shape[0], _out_channels(params), *xf.shape[2:]),
                      jnp.complex64)
    for c, sl in enumerate(corners):
        xc = xf[(slice(None), slice(None), *sl)]
        ops, expr = _corner_weight_ops(params, c, ndim)
        yc = contract(expr, xc, *ops, policy=pol)
        if isinstance(yc, ComplexPair):
            yc = yc.to_complex()
        out_f = out_f.at[(slice(None), slice(None), *sl)].set(
            yc.astype(jnp.complex64))
    y = jnp.fft.irfftn(out_f, s=spatial, axes=tuple(range(2, 2 + ndim)))
    if pol.spectral_is_half:
        y = y.astype(pol.spectral_dtype)
    return y.astype(in_dtype)


def _old_fno_apply(params, x, cfg, pol, spectral_pols=None):
    """Reference: the seed's fno_apply with flat-field casts.

    Mirrors the program's loop structure exactly: a ``lax.scan`` block
    loop, unrolled as ``fno_apply`` lowers it, when every layer shares one
    flat policy (XLA fuses rolled-scan and unrolled bodies differently
    under bf16, so structure matters for bitwise comparison), and a
    Python loop over the layers when ``spectral_pols`` gives a per-layer
    flat policy (cross-checking per-site overrides, where the new API
    loops over the layers too).
    """
    B, spatial = x.shape[0], x.shape[2:]
    cdt = pol.compute_dtype
    if cfg.positional_embedding:
        from repro.models.fno import _positional_grid

        pos = jnp.broadcast_to(_positional_grid(spatial, x.dtype)[None],
                               (B, cfg.ndim, *spatial))
        x = jnp.concatenate([x, pos], axis=1)
    h = jnp.moveaxis(x, 1, -1)
    h = _linear(params["lift1"], h, cdt)
    h = jax.nn.gelu(h)
    h = _linear(params["lift2"], h, cdt)
    h = jnp.moveaxis(h, -1, 1).astype(cdt)

    def block(h, spect, skip, lpol):
        ldt = lpol.compute_dtype
        y = _old_spectral_conv(spect, h, cfg.modes, lpol).astype(ldt)
        s = jnp.moveaxis(_linear(skip, jnp.moveaxis(h, 1, -1), ldt), -1, 1)
        return jax.nn.gelu(y + s)

    if spectral_pols is None:
        h, _ = jax.lax.scan(
            lambda c, lp: (block(c, lp[0], lp[1], pol), None),
            h, (params["spectral"], params["skips"]), unroll=True,
        )
    else:
        for l in range(cfg.n_layers):
            spect = {k: v[l] for k, v in params["spectral"].items()}
            skip = {k: v[l] for k, v in params["skips"].items()}
            h = block(h, spect, skip, spectral_pols[l])
    h = jnp.moveaxis(h, 1, -1)
    h = _linear(params["proj1"], h, cdt)
    h = jax.nn.gelu(h)
    h = _linear(params["proj2"], h, jnp.float32)
    return jnp.moveaxis(h, -1, 1)


class TestBitIdentity:
    @pytest.fixture(scope="class")
    def fno_setup(self):
        # pinned to the einsum path: the frozen flat-policy reference
        # below predates the Pallas kernels, and this test is about rule
        # resolution being bit-identical, not about the kernel backend
        # (tests/test_kernels_diff.py owns pallas-vs-einsum)
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=2, modes=(4, 4), use_pallas=False)
        params = init_fno(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 1, 16, 16),
                        jnp.float32)
        return cfg, params, x

    @pytest.mark.parametrize("name", POLICY_NAMES)
    def test_fno_forward_bit_identical(self, fno_setup, name):
        cfg, params, x = fno_setup
        policy = get_policy(name)
        got = np.asarray(fno_apply(params, x, cfg, policy), np.float32)
        want = np.asarray(
            _old_fno_apply(params, x, cfg, _old_policy_view(policy)), np.float32
        )
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("name", POLICY_NAMES)
    def test_lm_forward_identical_to_hand_built_rule_set(self, name):
        """A registry policy and the same rule set assembled by hand via
        with_rules produce identical logits — the registry really is just
        rules over the shared table."""
        cfg = get_config("smollm-360m", smoke=True)
        params = init_lm(jax.random.PRNGKey(1), cfg)
        toks = jnp.asarray(np.random.RandomState(1).randint(0, cfg.vocab, (1, 8)))
        policy = get_policy(name)
        rebuilt = FULL.with_rules(*policy.rules, name=f"rebuilt_{name}")
        la, _ = lm_forward(params, toks, cfg, policy)
        lb, _ = lm_forward(params, toks, cfg, rebuilt)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# Per-site overrides the flat dataclass could not express
# ---------------------------------------------------------------------------


class TestPerSiteOverride:
    def test_last_fno_layer_forced_full(self):
        """Pin the last FNO layer to full precision under the mixed rule
        set — inexpressible with the old whole-model policy — and check
        the result against a per-layer flat-policy reference."""
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=3, modes=(4, 4),
                        use_pallas=False)  # einsum-path reference below
        params = init_fno(jax.random.PRNGKey(2), cfg)
        x = jnp.asarray(np.random.RandomState(2).randn(2, 1, 16, 16),
                        jnp.float32)
        mixed = get_policy("mixed_fno_bf16")
        last = f"fno/layer{cfg.n_layers - 1}"

        y_mixed = np.asarray(fno_apply(params, x, cfg, mixed), np.float32)
        with precision_rules((f"{last}/*", FULL_PRECISION)):
            assert not layers_uniform(mixed, "fno", cfg.n_layers)
            y_over = np.asarray(fno_apply(params, x, cfg, mixed), np.float32)
        # outside the scope the layers are homogeneous again
        assert layers_uniform(mixed, "fno", cfg.n_layers)

        # reference: seed-style pipeline with a per-layer policy list
        mixed_flat = _old_policy_view(mixed)
        full_flat = _old_policy_view(get_policy("full"))
        # the override pins the layer's *dense* skip too, but lift/proj
        # stay at the mixed compute dtype
        full_flat.compute_dtype = jnp.float32
        pols = [mixed_flat, mixed_flat, full_flat]
        want = np.asarray(
            _old_fno_apply(params, x, cfg, mixed_flat, spectral_pols=pols),
            np.float32,
        )
        np.testing.assert_array_equal(y_over, want)
        assert not np.array_equal(y_over, y_mixed)

    def test_override_flips_loss_scaling(self):
        p = get_policy("mixed_fno_fp16")
        assert loss_scaling_required(p)
        with precision_rules(("train/loss_scale", SiteRule(loss_scaling=False))):
            assert not loss_scaling_required(p)

    def test_trainer_step_cache_keyed_by_override_scope(self):
        """A train step built under a precision_rules scope bakes those
        rules in at trace time; leaving the scope must rebuild the step
        rather than reuse the stale one (cache key includes the scope)."""
        from repro.train import Trainer, TrainerConfig, relative_l2

        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=1, modes=(4, 4))
        params = init_fno(jax.random.PRNGKey(4), cfg)
        rng = np.random.RandomState(4)
        batch = {"x": jnp.asarray(rng.randn(2, 1, 16, 16), jnp.float32),
                 "t": jnp.asarray(rng.randn(2, 1, 16, 16), jnp.float32)}

        def loss_fn(p, b, policy):
            return relative_l2(fno_apply(p, b["x"], cfg, policy), b["t"])

        sched = PrecisionSchedule.constant("mixed_fno_fp16")
        tr = Trainer(loss_fn, params, TrainerConfig(total_steps=4, schedule=sched))
        with precision_rules(("train/loss_scale", SiteRule(loss_scaling=False))):
            tr.run(lambda _s: batch, steps=1)
        assert tr.stats["recompiles"] == 1
        tr.run(lambda _s: batch)  # outside the scope: same name, new rules
        assert tr.stats["recompiles"] == 2


# ---------------------------------------------------------------------------
# Overlay schedule
# ---------------------------------------------------------------------------


class TestOverlaySchedule:
    def test_named_phases_return_registry_policies(self):
        s = PrecisionSchedule.paper_default("bf16")
        assert s.policy_at(0, 100).name == "mixed_fno_bf16"
        assert s.policy_at(99, 100).name == "full"

    def test_rule_overlay_phase(self):
        """A phase may be a raw rule overlay over the base — a partial-
        precision phase no whole-policy swap could express."""
        overlay = (
            ("*/spectral/contract", SiteRule(compute=jnp.bfloat16, quantize="half")),
        )
        s = PrecisionSchedule(phases=((0.5, overlay), (1.0, "full")))
        p0 = s.policy_at(0, 10)
        # only the contraction is half; the FFT boundary stays full
        assert p0.at("fno/layer0/spectral/contract").spectral_is_half
        assert not p0.at("fno/layer0/spectral/fft_in").spectral_is_half
        assert p0.name != "full"  # distinct step-cache key
        assert s.policy_at(9, 10).name == "full"

    def test_malformed_overlay_raises_early(self):
        with pytest.raises(TypeError):
            PrecisionSchedule(phases=((1.0, (("*/dense", "bf16"),)),))


# ---------------------------------------------------------------------------
# Simulated fp8 rule sets
# ---------------------------------------------------------------------------


class TestSimFP8:
    @pytest.mark.parametrize("name", ["sim_fp8_e4m3", "sim_fp8_e5m2"])
    def test_fft_in_quantizes_onto_fp8_grid(self, name):
        p = get_policy(name)
        site = p.at("fno/layer0/spectral/fft_in")
        rng = np.random.RandomState(0)
        c = jnp.asarray(rng.randn(32) + 1j * rng.randn(32), jnp.complex64)
        q = site.quantize(c)
        fmt = site.quantize_fmt
        # idempotent: the values already sit on the fp8 grid
        np.testing.assert_array_equal(
            np.asarray(simulate_fp8(jnp.real(q), fmt)), np.asarray(jnp.real(q))
        )
        # and it is a genuinely coarser grid than fp16
        assert np.abs(np.asarray(q) - np.asarray(c)).max() > 1e-3

    def test_fp8_fno_runs_finite(self):
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=1, modes=(4, 4))
        params = init_fno(jax.random.PRNGKey(3), cfg)
        x = jnp.asarray(np.random.RandomState(3).randn(2, 1, 16, 16),
                        jnp.float32)
        y = fno_apply(params, x, cfg, get_policy("sim_fp8_e5m2"))
        assert np.isfinite(np.asarray(y, np.float32)).all()
