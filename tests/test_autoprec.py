"""repro.autoprec tests: telemetry taps (eager, jitted, under grad and
microbatch scan), the bound-guided controller's demote/promote
hysteresis, auto-precision training (incl. loss-scale composition), the
serving engines' numerics counters/online control, and certification."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.autoprec import (
    AutoPrecisionController,
    SiteWindow,
    TelemetryAggregator,
    TraceCollector,
    collecting,
    group_of,
    tap,
    telemetry_active,
)
from repro.autoprec.telemetry import site_stats
from repro.core import PrecisionSchedule
from repro.core.precision import FORMAT_EPS
from repro.models import FNOConfig, fno_apply, init_fno
from repro.optim import init_loss_scale, update_loss_scale
from repro.precision import get_policy
from repro.train import Trainer, TrainerConfig, relative_l2

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


class TestSiteStats:
    def test_amax_and_counts(self):
        x = jnp.asarray([0.5, -2.0, 1e5, 1e-6, 0.0], jnp.float32)
        s = site_stats(x, fmt="float16", hist_stride=1)
        assert float(s.amax) == 1e5
        assert float(s.overflow) == 1.0       # 1e5 > 65504
        assert float(s.underflow) == 1.0      # 1e-6 below fp16 tiny, 0 exempt
        assert float(s.n) == 5.0
        assert float(s.hist.sum()) == 4.0     # non-zero values only

    def test_nonfinite_counts_as_overflow(self):
        x = jnp.asarray([1.0, jnp.inf, jnp.nan], jnp.float32)
        s = site_stats(x, fmt="float32", hist_stride=1)
        assert float(s.overflow) == 2.0

    def test_qerr_measures_quantisation(self):
        x = jnp.asarray(np.random.RandomState(0).randn(256), jnp.float32)
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
        s = site_stats(x, fmt="bfloat16", quantized=q, hist_stride=1)
        # measured error under the per-value representation bound eps*amax
        assert 0.0 < float(s.qerr) <= FORMAT_EPS["bfloat16"] * float(s.amax)

    def test_complex_split_real_components(self):
        c = jnp.asarray([1.0 + 2.0j, -3.0 + 0.5j], jnp.complex64)
        s = site_stats(c, hist_stride=1)
        assert float(s.n) == 4.0              # re+im components
        assert float(s.amax) == 3.0


class TestCollector:
    def test_tap_noop_without_collector(self):
        assert not telemetry_active()
        tap("some/site", jnp.ones(3))  # must not raise, records nothing

    def test_repeated_taps_merge(self):
        col = TraceCollector(hist_stride=1)
        with collecting(col):
            assert telemetry_active()
            tap("s", jnp.asarray([1.0]), fmt="float32")
            tap("s", jnp.asarray([5.0]), fmt="float32")
        snap = col.snapshot()
        assert float(snap["s"].amax) == 5.0
        assert float(snap["s"].n) == 2.0

    def test_jit_collection_matches_eager(self):
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=1, modes=(4, 4))
        params = init_fno(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 1, 16, 16),
                        jnp.float32)
        pol = get_policy("mixed_fno_bf16")

        def run(p, x):
            col = TraceCollector()
            with collecting(col):
                y = fno_apply(p, x, cfg, pol)
            return y, col.snapshot()

        y_e, snap_e = run(params, x)
        y_j, snap_j = jax.jit(run)(params, x)
        assert set(snap_e) == set(snap_j)
        for site in snap_e:
            # jit fuses the FFT differently; amax agrees to float noise
            np.testing.assert_allclose(float(snap_e[site].amax),
                                       float(snap_j[site].amax), rtol=1e-3)
        # the spectral sites of the one layer are all addressed
        assert "fno/layer0/spectral/fft_in" in snap_e
        assert "fno/layer0/spectral/contract" in snap_e
        assert "fno/layer0/spectral/fft_out" in snap_e

    def test_dft_transforms_keep_spectral_sites(self, monkeypatch):
        """On the truncated-DFT transforms a TPU takes, the collector still
        sees every spectral site of the layer; ``fft_in`` now taps the
        retained modes, the only quantised values that reach the output."""
        from repro.core import spectral

        monkeypatch.setattr(spectral, "_on_tpu", lambda: True)
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=1, modes=(4, 4))
        params = init_fno(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 1, 16, 16),
                        jnp.float32)
        col = TraceCollector()
        with collecting(col):
            fno_apply(params, x, cfg, get_policy("mixed_fno_bf16"))
        snap = col.snapshot()
        for stage in ("fft_in", "contract", "fft_out"):
            assert f"fno/layer0/spectral/{stage}" in snap
        # 2 x 8 channels x (2*4 x 4) retained modes, re and im
        assert float(snap["fno/layer0/spectral/fft_in"].n) == 2 * 8 * 32 * 2

    def test_aggregator_window_and_totals(self):
        agg = TelemetryAggregator()
        col = TraceCollector(hist_stride=1)
        with collecting(col):
            tap("s", jnp.asarray([2.0]), fmt="float32")
        agg.update(col.snapshot())
        agg.update(col.snapshot())
        assert agg.totals["s"].updates == 2
        w = agg.take_window()
        assert w["s"].updates == 2
        assert agg.window() == {}             # window resets, totals stay
        assert agg.totals["s"].updates == 2
        assert agg.counters()["sites"]["s"]["amax"] == 2.0

    def test_fraction_below(self):
        col = TraceCollector(hist_stride=1)
        with collecting(col):
            tap("s", jnp.asarray([1e-6] * 3 + [1.0] * 7), fmt="float32")
        agg = TelemetryAggregator()
        agg.update(col.snapshot())
        frac = agg.totals["s"].fraction_below(6.1e-5)  # fp16 tiny
        np.testing.assert_allclose(frac, 0.3, atol=0.01)


# ---------------------------------------------------------------------------
# controller
# ---------------------------------------------------------------------------


def _window(amax=10.0, overflow=0.0, n=1000.0):
    w = SiteWindow(updates=1, amax=amax, qerr=0.0, n=n,
                   overflow=overflow, underflow=0.0,
                   overflow_updates=int(overflow > 0))
    # all mass in a healthy exponent bucket
    w.hist[30] = n
    return w


class TestController:
    def test_demotes_after_patience(self):
        ctl = AutoPrecisionController(base="full", grid_points=1024,
                                      demote_patience=2, cooldown=0)
        assert not ctl.update({"fno/layer0/spectral/fft_in": _window()})
        assert ctl.update({"fno/layer0/spectral/fft_in": _window()})
        assert ctl.sites["fno/layer0/spectral"].fmt == "bfloat16"
        assert ctl.policy().name == "full+auto1"
        assert ctl.policy().at("fno/layer0/spectral/contract").spectral_is_half

    def test_budget_tightens_with_grid(self):
        # Thm 3.1: finer grids shrink the disc bound, so the eps ceiling
        # falls below bf16's eps and the controller must pick fp16
        ctl = AutoPrecisionController(base="full", demote_patience=1,
                                      cooldown=0)
        assert ctl.eps_budget(1024) > FORMAT_EPS["bfloat16"]
        assert ctl.eps_budget(262144) < FORMAT_EPS["bfloat16"]
        ctl.update({"fno/layer0/spectral/fft_in": _window()},
                   grid_points=262144)
        assert ctl.sites["fno/layer0/spectral"].fmt == "float16"
        # fp16-family decisions switch dynamic loss scaling on
        assert ctl.policy().at("train/loss_scale").loss_scaling

    def test_range_check_blocks_fp16(self):
        # amax*margin beyond fp16's 65504 => fp16 rejected; at a fine
        # grid where bf16 fails the eps budget, only f32 remains
        ctl = AutoPrecisionController(base="full", demote_patience=1,
                                      cooldown=0, range_margin=4.0)
        ctl.update({"fno/layer0/spectral/fft_in": _window(amax=30000.0)},
                   grid_points=262144)
        assert ctl.sites["fno/layer0/spectral"].fmt == "float32"

    def test_promotes_on_overflow_streak_with_cooldown(self):
        ctl = AutoPrecisionController(base="full", grid_points=1024,
                                      demote_patience=1, promote_streak=2,
                                      cooldown=2)
        site = "fno/layer0/spectral/fft_in"
        ctl.update({site: _window()})
        assert ctl.sites["fno/layer0/spectral"].fmt == "bfloat16"
        assert not ctl.update({site: _window(overflow=5.0)})  # streak 1
        assert ctl.update({site: _window(overflow=5.0)})      # promoted
        assert ctl.sites["fno/layer0/spectral"].fmt == "float32"
        # cooldown: a clean window cannot immediately re-demote
        assert not ctl.update({site: _window()})
        assert ctl.sites["fno/layer0/spectral"].fmt == "float32"

    def test_uncontrolled_sites_ignored(self):
        ctl = AutoPrecisionController(base="full", grid_points=1024,
                                      demote_patience=1, cooldown=0)
        ctl.update({"lm/dense": _window(), "serve/operator": _window()})
        assert ctl.sites == {}
        assert ctl.overlay() == ()

    def test_group_of(self):
        assert group_of("fno/layer3/spectral/fft_in") == "fno/layer3/spectral"
        assert group_of("sfno/layer0/spectral/contract") == "sfno/layer0/spectral"
        assert group_of("serve/kv_cache") == "serve/kv_cache"


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------


def _tiny_problem(n_layers=2, res=16):
    cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                    lifting_channels=8, projection_channels=8,
                    n_layers=n_layers, modes=(4, 4))
    params = init_fno(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 1, res, res), jnp.float32)
    t = jnp.asarray(rng.randn(4, 1, res, res) * 0.1, jnp.float32)

    def loss_fn(p, batch, policy):
        return relative_l2(fno_apply(p, batch["x"], cfg, policy), batch["t"])

    return cfg, params, loss_fn, {"x": x, "t": t}


class TestAutoPrecisionTraining:
    def test_auto_mode_demotes_and_recompiles_once_per_change(self):
        cfg, params, loss_fn, batch = _tiny_problem()
        ctl = AutoPrecisionController(base="full", grid_points=256,
                                      interval=3, demote_patience=1,
                                      cooldown=0)
        tr = Trainer(loss_fn, params,
                     TrainerConfig(total_steps=9, autoprec=ctl))
        hist = tr.run(lambda _s: batch)
        assert np.isfinite([h["loss"] for h in hist]).all()
        assert tr.stats["policy_changes"] == 1
        assert tr.stats["recompiles"] == 2    # full+auto0 and full+auto1
        assert hist[0]["policy"] == "full+auto0"
        assert hist[-1]["policy"] == "full+auto1"
        for i in range(cfg.n_layers):
            assert ctl.sites[f"fno/layer{i}/spectral"].fmt == "bfloat16"
        # telemetry saw every spectral tap site with zero overflows
        counters = tr.telemetry.counters()
        assert counters["overflow_total"] == 0
        assert len(counters["sites"]) == 3 * cfg.n_layers

    def test_schedule_auto_mode_builds_controller(self):
        _, params, loss_fn, batch = _tiny_problem(n_layers=1)
        tr = Trainer(loss_fn, params, TrainerConfig(
            total_steps=2, schedule=PrecisionSchedule.auto("full")))
        tr.run(lambda _s: batch)
        assert tr.controller is not None
        assert tr.controller.base.name == "full"

    def test_microbatch_scan_merges_telemetry(self):
        _, params, loss_fn, batch = _tiny_problem(n_layers=1)
        tr = Trainer(loss_fn, params, TrainerConfig(
            total_steps=2, microbatches=2, telemetry=True))
        tr.run(lambda _s: batch)
        w = tr.telemetry.totals["fno/layer0/spectral/fft_in"]
        # both microbatches' taps merged into each step's stats
        tr1 = Trainer(loss_fn, params, TrainerConfig(
            total_steps=2, microbatches=1, telemetry=True))
        tr1.run(lambda _s: batch)
        w1 = tr1.telemetry.totals["fno/layer0/spectral/fft_in"]
        np.testing.assert_allclose(w.n, w1.n)

    def test_static_training_unaffected(self):
        """No controller, no telemetry: the step signature/behaviour of
        plain schedules is unchanged (loss path identical)."""
        _, params, loss_fn, batch = _tiny_problem(n_layers=1)
        tr = Trainer(loss_fn, params, TrainerConfig(total_steps=3))
        hist = tr.run(lambda _s: batch)
        assert tr.telemetry is None
        assert hist[-1]["loss"] < hist[0]["loss"]


class TestLossScaleComposition:
    """Satellite regression: dynamic scale halves on overflow, recovers
    after the growth interval, and composes with controller-driven
    overlay changes."""

    def test_scale_halves_on_injected_overflow_and_training_recovers(self):
        _, params, loss_fn, batch = _tiny_problem(n_layers=1)
        bad = {"x": batch["x"].at[0, 0, 0, 0].set(jnp.inf), "t": batch["t"]}
        tr = Trainer(loss_fn, params, TrainerConfig(
            total_steps=6,
            schedule=PrecisionSchedule.constant("mixed_fno_fp16")))
        # the clean steps before the injection may themselves overflow
        # fp16 at the initial scale (a gradient larger than 65504 once
        # scaled) and back the scale off; count from the scale and skip
        # count the injected step actually starts from
        tr.run(lambda s: batch, steps=2)
        s0 = float(tr.scale_state.scale)
        k0 = tr.stats["skipped_steps"]
        tr.run(lambda s: bad if s == 2 else batch)
        assert not np.isfinite(tr.history[2]["loss"])
        assert tr.stats["skipped_steps"] == k0 + 1
        assert float(tr.scale_state.scale) == s0 * 0.5
        # subsequent steps trained through (finite losses, no divergence)
        assert np.isfinite([h["loss"] for h in tr.history[3:]]).all()

    def test_scale_regrows_after_growth_interval(self):
        s = init_loss_scale(1024.0)
        s = update_loss_scale(s, jnp.asarray(False))      # overflow: halve
        assert float(s.scale) == 512.0
        for _ in range(3):
            s = update_loss_scale(s, jnp.asarray(True), growth_interval=3)
        assert float(s.scale) == 1024.0                   # recovered

    def test_controller_overlay_change_preserves_scale_state(self):
        """A controller demotion to an fp16-family format flips loss
        scaling on mid-run via a recompile; the scale state must carry
        across the step swap instead of resetting."""
        _, params, loss_fn, batch = _tiny_problem(n_layers=1)
        ctl = AutoPrecisionController(
            base="full", grid_points=256, interval=2, demote_patience=1,
            cooldown=0, formats=("float16",))
        tr = Trainer(loss_fn, params, TrainerConfig(
            total_steps=8, autoprec=ctl))
        # age the scale state so a reset would be visible
        tr.scale_state = tr.scale_state._replace(
            scale=jnp.asarray(256.0, jnp.float32))
        hist = tr.run(lambda _s: batch)
        assert ctl.sites["fno/layer0/spectral"].fmt == "float16"
        assert tr.stats["policy_changes"] == 1
        # loss scaling became active (fp16 overlay) and the carried
        # scale kept evolving from 256, not from the 2^15 init
        assert ctl.policy().at("train/loss_scale").loss_scaling
        assert float(tr.scale_state.scale) <= 256.0
        assert tr.stats["skipped_steps"] == 0
        assert np.isfinite([h["loss"] for h in hist]).all()


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------


class TestOperatorEngineAutoprec:
    def _engine_parts(self):
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=1, modes=(4, 4))
        params = init_fno(jax.random.PRNGKey(0), cfg)
        return cfg, params

    def test_telemetry_counters_in_stats(self):
        from repro.serve import FieldRequest, OperatorEngine

        cfg, params = self._engine_parts()
        eng = OperatorEngine(params, cfg, telemetry=True, max_batch=2)
        rng = np.random.RandomState(0)
        for i in range(4):
            eng.submit(FieldRequest(
                uid=i, x=rng.randn(1, 16, 16).astype(np.float32)))
        eng.drain()
        numerics = eng.stats()["numerics"]
        assert numerics["overflow_total"] == 0
        assert "fno/layer0/spectral/fft_in" in numerics["sites"]

    def test_online_controller_retunes_policy(self):
        from repro.serve import FieldRequest, OperatorEngine

        cfg, params = self._engine_parts()
        ctl = AutoPrecisionController(base="full", demote_patience=1,
                                      cooldown=0)
        eng = OperatorEngine(params, cfg, autoprec=ctl, max_batch=2,
                             autoprec_every=2)
        rng = np.random.RandomState(0)
        fields = [rng.randn(1, 16, 16).astype(np.float32) for _ in range(8)]
        for i, x in enumerate(fields):
            eng.submit(FieldRequest(uid=i, x=x))
        done, _ = eng.drain()
        stats = eng.stats()
        assert stats["policy"] == "full+auto1"
        assert stats["autoprec"]["sites"]["fno/layer0/spectral"]["fmt"] == "bfloat16"
        # served fields remain close to the full-precision forward
        from repro.models import fno_infer
        from repro.precision import FULL

        ref = np.asarray(fno_infer(
            params, jnp.asarray(fields[-1])[None], cfg, FULL))[0]
        got = next(r.y for r in done if r.uid == len(fields) - 1)
        np.testing.assert_allclose(np.asarray(got), ref, atol=0.05)

    def test_lm_engine_numerics_counters(self):
        from repro.configs import get_config
        from repro.models.lm import init_lm
        from repro.serve import LMEngine, Request

        cfg = get_config("smollm-360m", smoke=True)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        eng = LMEngine(params, cfg, n_slots=2, max_len=32, telemetry=True)
        for i in range(2):
            eng.submit(Request(uid=i, prompt=[1, 2, 3], max_new_tokens=2))
        eng.drain()
        numerics = eng.stats()["numerics"]
        assert numerics["logits_nonfinite"] == 0
        assert numerics["rows_observed"] > 0
        assert numerics["logits_amax"] > 0.0


# ---------------------------------------------------------------------------
# certification
# ---------------------------------------------------------------------------


class TestPallasPathParity:
    """The Pallas contraction path feeds the same telemetry streams and
    drives the controller to the same demotion decisions as the einsum
    path (the tentpole contract of the training-grade kernel PR)."""

    def test_contract_taps_observed_and_decisions_match(self):
        import dataclasses

        from repro.autoprec.certify import (
            instrumented_apply, sample_inputs, tiny_fno)

        cfg, params = tiny_fno()
        x = sample_inputs("grf", 24, 2)
        decisions, amaxes = {}, {}
        for up in (False, True):
            c = dataclasses.replace(cfg, use_pallas=up)
            ctl = AutoPrecisionController(base="full", grid_points=24 ** 2)
            totals = {}
            for r in range(4):
                _, totals = instrumented_apply(ctl.policy(), c, params, x)
                ctl.update(totals, step=r)
            # every per-layer contract tap is observed on this path
            for layer in range(cfg.n_layers):
                site = f"fno/layer{layer}/spectral/contract"
                assert site in totals, (up, sorted(totals))
                assert totals[site].amax > 0.0
            decisions[up] = {
                g: s["fmt"] for g, s in ctl.describe()["sites"].items()}
            amaxes[up] = {s: w.amax for s, w in totals.items()}
        assert decisions[True] == decisions[False]
        # non-vacuous: the certify harness demotes its spectral groups
        assert any(f != "float32" for f in decisions[True].values())
        # and the measured ranges agree across paths (same stream, not
        # merely the same thresholded outcome)
        for site, a_e in amaxes[False].items():
            a_p = amaxes[True][site]
            assert abs(a_p - a_e) <= 0.05 * (abs(a_e) + 1e-9), site


class TestCertification:
    def test_mixed_bf16_certifies(self):
        from repro.autoprec.certify import certify_policy

        rep = certify_policy(get_policy("mixed_fno_bf16"),
                             resolution=16, batch=2)
        assert rep["all_within"]
        assert len(rep["demoted_sites"]) > 0
        for s in rep["demoted_sites"]:
            row = rep["sites"][s]
            assert row["qerr_measured"] <= row["prec_budget"]
            assert row["overflow"] == 0
        # the headline claim: precision error far below the disc bound
        assert rep["end_to_end"]["prec_fraction_of_disc"] < 0.5

    def test_controller_certifies(self):
        from repro.autoprec.certify import certify_controller

        ctl = AutoPrecisionController(base="full", grid_points=256,
                                      demote_patience=1, cooldown=0)
        rep = certify_controller(ctl, rounds=2, resolution=16, batch=2)
        assert rep["all_within"]
        assert rep["controller"]["version"] >= 1
        assert len(rep["demoted_sites"]) > 0

    def test_dryrun_overhead_helper(self):
        from repro.launch.dryrun import telemetry_overhead

        plain = SimpleNamespace(flops_per_device=100.0, bytes_per_device=50.0)
        instr = SimpleNamespace(flops_per_device=104.0, bytes_per_device=51.0)
        oh = telemetry_overhead(plain, instr)
        np.testing.assert_allclose(oh["flops_overhead"], 0.04)
        np.testing.assert_allclose(oh["bytes_overhead"], 0.02)
