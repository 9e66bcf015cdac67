"""repro.obs: trace ring, metrics registry, numerics events, exporters,
CLI, and the wiring into Trainer / serve engines.

The metrics-schema golden (``tests/golden/obs_metrics_keys.json``)
freezes the *series names* a canonical run publishes — key drift in any
``stats()`` surface (trainer, LM engine, scheduler) or in the numerics
vocabulary shows up here as a diff.  Set ``REPRO_REGEN_GOLDENS=1`` and
rerun to re-record after an intentional schema change.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.kernels import ops
from repro.models import FNOConfig, fno_apply, init_fno
from repro.obs import (
    KINDS,
    MAX_LABEL_SETS,
    autoprec_decision,
    chrome_trace,
    metric_names,
    numerics_event,
    prometheus_text,
    read_jsonl,
    registry,
    result_header,
    run_records,
    tile_cache_event,
    trace,
    validate_chrome_trace,
    write_jsonl,
    write_result,
)
from repro.obs.__main__ import main as obs_main
from repro.train import Trainer, TrainerConfig, relative_l2

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "obs_metrics_keys.json")


@pytest.fixture(autouse=True)
def _obs_clean():
    """Each test starts from a disabled trace and an empty registry;
    the kernels external (dropped by ``clear()``) is re-registered."""
    trace.disable()
    trace.clear()
    registry().clear()
    ops._register_obs()
    yield
    trace.disable()
    trace.clear()
    registry().clear()
    ops._register_obs()


# ---------------------------------------------------------------------------
# trace ring
# ---------------------------------------------------------------------------


class TestTrace:
    def test_off_is_shared_noop(self):
        s1, s2 = trace.span("a"), trace.span("b", k=1)
        assert s1 is s2  # the shared _NULL object: zero allocation off
        with s1:
            trace.event("x")
        assert trace.snapshot() == []

    def test_off_without_profiler_is_the_shared_null(self):
        assert not jax.profiler.TraceAnnotation.is_enabled()
        assert trace.span("train/step", step=0) is trace._NULL

    def test_profiler_session_gets_live_spans_ring_stays_empty(self, tmp_path):
        jax.profiler.start_trace(str(tmp_path))
        try:
            s = trace.span("serve/tick", tick=3)
            assert s is not trace._NULL
            with s:
                with trace.span("serve/operator/take") as inner:
                    assert inner.parent == "serve/tick"
        finally:
            jax.profiler.stop_trace()
        # the ring is obs's own sink: off unless enabled
        assert trace.snapshot() == []
        assert trace.span("serve/tick") is trace._NULL

    def test_span_nesting_records_depth_and_parent(self):
        trace.enable()
        with trace.span("outer"):
            with trace.span("inner", k=2):
                pass
        recs = trace.snapshot()
        # spans close inner-first
        inner, outer = recs
        assert inner["name"] == "inner" and inner["depth"] == 1
        assert inner["parent"] == "outer" and inner["attrs"] == {"k": 2}
        assert outer["name"] == "outer" and outer["depth"] == 0
        assert "parent" not in outer
        assert inner["ts_ns"] >= outer["ts_ns"]
        assert inner["dur_ns"] <= outer["dur_ns"]

    def test_ring_wraps_drop_oldest(self):
        trace.enable(capacity=8)
        for i in range(12):
            trace.event(f"e{i}")
        recs = trace.snapshot()
        assert [r["name"] for r in recs] == [f"e{i}" for i in range(4, 12)]
        assert trace.dropped() == 4

    def test_async_begin_end_and_event_kinds(self):
        trace.enable()
        trace.begin("request", 7, category="request", engine="lm")
        trace.event("mark", category="c", n=1)
        trace.end("request", 7, category="request")
        kinds = [r["kind"] for r in trace.snapshot()]
        assert kinds == ["b", "event", "e"]
        b = trace.snapshot()[0]
        assert b["id"] == 7 and b["category"] == "request"

    def test_clear_keeps_enabled_state(self):
        trace.enable()
        trace.event("x")
        trace.clear()
        assert trace.is_enabled() and trace.snapshot() == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_name_convention_enforced(self):
        with pytest.raises(ValueError, match="convention"):
            registry().counter("Bad-Name")
        with pytest.raises(ValueError, match="convention"):
            registry().gauge("nope")

    def test_counter_gauge_histogram_snapshot(self):
        registry().counter("repro_t_total", kind="a").inc()
        registry().counter("repro_t_total", kind="a").inc(2)
        registry().gauge("repro_t_g").set(3.5)
        h = registry().histogram("repro_t_ms", edges=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        h.observe(100.0)
        snap = registry().snapshot()
        assert snap["counters"]['repro_t_total{kind="a"}'] == 3.0
        assert snap["gauges"]["repro_t_g"] == 3.5
        hs = snap["histograms"]["repro_t_ms"]
        assert hs["counts"] == [1, 1, 1] and hs["count"] == 3

    def test_label_cardinality_capped(self):
        for i in range(MAX_LABEL_SETS):
            registry().counter("repro_t_total", k=str(i))
        with pytest.raises(ValueError, match="label sets"):
            registry().counter("repro_t_total", k="one-too-many")

    def test_histogram_redeclare_different_edges_raises(self):
        registry().histogram("repro_t_ms", edges=(1.0, 2.0))
        with pytest.raises(ValueError, match="different edges"):
            registry().histogram("repro_t_ms", edges=(1.0, 3.0))

    def test_publish_flattens_nested_stats(self):
        registry().publish("eng", {"ticks": 4, "memo": {"hits": 2},
                                   "name": "skipped-string"})
        g = registry().snapshot()["gauges"]
        assert g["repro_eng_ticks"] == 4.0
        assert g["repro_eng_memo_hits"] == 2.0
        assert not any("name" in k for k in g)

    def test_register_external_snapshot_and_reset(self):
        box = {"n": 5}
        registry().register_external(
            "repro_t_ext", lambda: dict(box),
            lambda: box.update(n=0))
        assert registry().snapshot()["external"]["repro_t_ext"] == {"n": 5}
        registry().reset()
        assert box["n"] == 0

    def test_reset_zeroes_instruments(self):
        registry().counter("repro_t_total").inc(9)
        registry().reset()
        assert registry().snapshot()["counters"]["repro_t_total"] == 0.0

    def test_kernels_external_registered(self):
        snap = registry().snapshot()
        assert "repro_kernels_tiles" in snap.get("external", {})

    def test_fused_family_counter_and_bytes_gauge(self):
        """One fused launch lands on the per-family traced-call counter
        and the bytes-moved gauge under the ``spectral_fused`` label."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.core import FULL, init_spectral_weights
        from repro.kernels import ops

        params = init_spectral_weights(
            jax.random.PRNGKey(0), 2, 2, (2, 3), "dense")
        x = jnp.asarray(np.random.RandomState(0).randn(1, 2, 6, 8),
                        jnp.float32)
        registry().reset()
        ops.spectral_conv_fused(x, params["w_re"], params["w_im"], (2, 3),
                                policy=FULL)
        snap = registry().snapshot()
        key = 'repro_kernels_calls_total{family="spectral_fused"}'
        assert snap["counters"].get(key, 0) >= 1, snap["counters"]
        gkey = 'repro_kernels_bytes_moved{family="spectral_fused"}'
        assert snap["gauges"].get(gkey, 0) > 0, snap["gauges"]


# ---------------------------------------------------------------------------
# numerics events
# ---------------------------------------------------------------------------


class TestNumerics:
    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="unknown numerics event"):
            numerics_event("not_a_kind")

    def test_counter_always_trace_only_when_enabled(self):
        numerics_event("oracle_reject", key="k")
        assert trace.snapshot() == []
        trace.enable()
        numerics_event("oracle_reject", site="s", key="k")
        c = registry().snapshot()["counters"]
        assert c['repro_numerics_events_total{kind="oracle_reject"}'] == 2.0
        (ev,) = trace.snapshot()
        assert ev["name"] == "numerics/oracle_reject"
        assert ev["category"] == "numerics"
        assert ev["attrs"] == {"key": "k", "site": "s"}

    def test_every_kind_lands_in_counter(self):
        for kind in KINDS:
            numerics_event(kind)
        c = registry().snapshot()["counters"]
        assert all(
            c[f'repro_numerics_events_total{{kind="{kind}"}}'] == 1.0
            for kind in KINDS)

    def test_forced_demote_emits_budgeted_event(self):
        from repro.autoprec import AutoPrecisionController

        trace.enable()
        ctl = AutoPrecisionController(base="full", grid_points=1024,
                                      demote_patience=1, cooldown=0)
        from tests.test_autoprec import _window

        assert ctl.update({"fno/layer0/spectral/fft_in": _window()})
        c = registry().snapshot()["counters"]
        assert c['repro_numerics_events_total{kind="autoprec_demote"}'] >= 1
        demotes = [r for r in trace.snapshot()
                   if r["name"] == "numerics/autoprec_demote"]
        attrs = demotes[0]["attrs"]
        # the acceptance criterion: the event carries the budget numbers
        assert attrs["to_fmt"] == "bfloat16"
        assert attrs["eps_budget"] > 0 and attrs["fmt_eps"] > 0
        assert attrs["site"] == "fno/layer0/spectral"

    def test_seeded_stale_cache_hit_emits_event(self):
        from repro.tune.cache import CalibrationCache, entry_key

        trace.enable()
        cache = CalibrationCache(entries={})
        cache.entries[entry_key("spectral_dense", (4, 8, 8), "float32")] = {
            "family": "spectral_dense", "block_fwd": 8, "block_bwd": 8,
            "validated": False,   # seeded stale: never oracle-validated
        }
        assert cache.lookup("spectral_dense", (4, 8, 8), "float32") is None
        assert cache.counters["stale"] == 1
        c = registry().snapshot()["counters"]
        assert c['repro_numerics_events_total{kind="tile_cache_stale"}'] == 1
        (ev,) = trace.snapshot()
        assert ev["name"] == "numerics/tile_cache_stale"
        assert ev["attrs"]["family"] == "spectral_dense"

    def test_autoprec_decision_promote_vs_demote(self):
        autoprec_decision("g", "bfloat16", "float32",
                          eps_budget=1e-3, amax=2.0)
        autoprec_decision("g", "float32", "float16",
                          eps_budget=1e-3, amax=2.0, fmt_eps=4.9e-4)
        c = registry().snapshot()["counters"]
        assert c['repro_numerics_events_total{kind="autoprec_promote"}'] == 1
        assert c['repro_numerics_events_total{kind="autoprec_demote"}'] == 1


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _sample_records():
    trace.enable()
    with trace.span("outer", step=1):
        with trace.span("inner"):
            pass
        trace.event("mark", category="numerics", site="s")
    trace.begin("request", 3, category="request")
    trace.end("request", 3, category="request")
    return trace.snapshot()


class TestExport:
    def test_chrome_trace_validates(self):
        doc = chrome_trace(_sample_records())
        assert validate_chrome_trace(doc) == []
        phs = [e["ph"] for e in doc["traceEvents"]]
        assert phs == ["M", "X", "i", "X", "b", "e"]
        x_inner = doc["traceEvents"][1]
        assert x_inner["name"] == "inner"
        assert x_inner["args"]["parent"] == "outer"
        # ns -> us conversion
        assert all(e.get("dur", 0) < 1e7 for e in doc["traceEvents"])

    def test_validate_catches_defects(self):
        errs = validate_chrome_trace({"traceEvents": [
            {"ph": "X", "name": "a", "ts": 0, "pid": 1, "tid": 1},
            {"ph": "b", "name": "r", "ts": 0, "pid": 1, "tid": 1,
             "id": "1", "cat": "c"},
            {"ph": "Z", "name": "?", "ts": 0, "pid": 1, "tid": 1},
        ]})
        assert any("missing dur" in e for e in errs)
        assert any("unmatched begin" in e for e in errs)
        assert any("unknown ph" in e for e in errs)

    def test_prometheus_text(self):
        registry().counter("repro_t_total", kind="a").inc(2)
        h = registry().histogram("repro_t_ms", edges=(1.0, 10.0))
        h.observe(0.5)
        h.observe(100.0)
        text = prometheus_text(registry().snapshot())
        assert "# TYPE repro_t_total counter" in text
        assert 'repro_t_total{kind="a"} 2' in text
        assert 'repro_t_ms_bucket{le="1"} 1' in text
        assert 'repro_t_ms_bucket{le="+Inf"} 2' in text
        assert "repro_t_ms_count 2" in text

    def test_result_header_fields(self):
        hdr = result_header(extra_field=7)
        assert hdr["schema_version"] == 1
        assert hdr["backend"] == jax.default_backend()
        assert hdr["jax_version"] == jax.__version__
        assert "timestamp_utc" in hdr and hdr["extra_field"] == 7
        assert isinstance(hdr["env"], dict)

    def test_write_result_and_atomicity(self, tmp_path):
        path = str(tmp_path / "sub" / "r.json")
        write_result(path, {"x": 1})
        doc = json.load(open(path))
        assert doc["x"] == 1 and doc["meta"]["schema_version"] == 1
        # no temp litter from the atomic protocol
        assert os.listdir(tmp_path / "sub") == ["r.json"]

    def test_jsonl_roundtrip_and_run_framing(self, tmp_path):
        recs = run_records(_sample_records(),
                           snapshot=registry().snapshot())
        path = str(tmp_path / "run.jsonl")
        write_jsonl(path, recs)
        back = read_jsonl(path)
        assert back[0]["kind"] == "meta"
        assert back[-1]["kind"] == "metrics"
        assert [r["kind"] for r in back[1:-1]] == [
            "span", "event", "span", "b", "e"]


# ---------------------------------------------------------------------------
# wiring: trainer spans + paged-serve tick spans
# ---------------------------------------------------------------------------


def _tiny_trainer(**cfg_kw):
    cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                    lifting_channels=8, projection_channels=8,
                    n_layers=1, modes=(4, 4))
    params = init_fno(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 1, 16, 16), jnp.float32)
    t = jnp.asarray(rng.randn(2, 1, 16, 16) * 0.1, jnp.float32)

    def loss_fn(p, batch, policy):
        return relative_l2(fno_apply(p, batch["x"], cfg, policy),
                           batch["t"])

    return Trainer(loss_fn, params,
                   TrainerConfig(total_steps=2, obs=True, **cfg_kw))


class TestTrainerWiring:
    def test_step_spans_nest_and_metrics_land(self):
        tr = _tiny_trainer()
        with trace.span("test/run"):
            tr.run(lambda _s: {
                "x": jnp.zeros((2, 1, 16, 16)),
                "t": jnp.zeros((2, 1, 16, 16))})
        spans = [r for r in trace.snapshot() if r["kind"] == "span"]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        assert len(by_name["train/step"]) == 2
        assert len(by_name["train/data"]) == 2
        # phase spans nest under the caller's span
        assert all(s["parent"] == "test/run" and s["depth"] == 1
                   for s in by_name["train/step"])
        assert by_name["train/step"][0]["attrs"]["step"] == 0
        snap = registry().snapshot()
        assert snap["counters"]["repro_train_steps_total"] == 2.0
        assert snap["histograms"]["repro_train_step_wall_ms"]["count"] == 2
        # publish_stats ran at end of run
        assert snap["gauges"]["repro_train_step"] == 2.0

    def test_step_hlo_carries_fft_and_optimizer_scopes(self):
        """The compiled step keeps the spectral layers' FFT scopes and the
        optimizer's in its operations' ``op_name``: what a device trace
        reads them from (the backward under ``transpose(...)``)."""
        tr = _tiny_trainer()
        batch = {"x": jnp.zeros((2, 1, 16, 16)),
                 "t": jnp.zeros((2, 1, 16, 16))}
        text = tr.lower(batch).compile().as_text()
        names = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in ("/spectral/fft_in", "/spectral/fft_out", "optim/adamw"):
            assert any(scope in n for n in names), scope
        assert any("transpose(" in n and "/spectral/fft_in" in n
                   for n in names)

    def test_obs_off_trainer_records_nothing(self):
        cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                        lifting_channels=8, projection_channels=8,
                        n_layers=1, modes=(4, 4))
        params = init_fno(jax.random.PRNGKey(0), cfg)
        tr = Trainer(
            lambda p, b, pol: relative_l2(
                fno_apply(p, b["x"], cfg, pol), b["t"]),
            params, TrainerConfig(total_steps=1))
        tr.run(lambda _s: {"x": jnp.zeros((2, 1, 16, 16)),
                           "t": jnp.zeros((2, 1, 16, 16))})
        assert trace.snapshot() == []
        assert "repro_train_steps_total" not in (
            registry().snapshot()["counters"])


def _paged_run():
    import dataclasses

    from repro.configs import get_config
    from repro.models.lm import init_lm
    from repro.serve import PagedLMEngine, Request

    cfg = get_config("smollm-360m", smoke=True)
    if cfg.moe_experts:
        cfg = dataclasses.replace(cfg, moe_experts=0, moe_shared=0, d_ff=32)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    engine = PagedLMEngine(params, cfg, n_slots=2, max_len=32, block_size=8)
    reqs = [Request(uid=u, prompt=[3, 1, 4, 1, 5, 9][: 4 + u % 2],
                    max_new_tokens=2) for u in range(3)]
    finished, _ = engine.run_until_done(reqs)
    return engine, finished


class TestServeWiring:
    def test_paged_tick_spans_and_request_tracks(self):
        trace.enable()
        engine, finished = _paged_run()
        assert all(r.status == "done" for r in finished)
        recs = trace.snapshot()
        ticks = [r for r in recs if r["name"] == "serve/tick"]
        assert ticks and all(
            r["attrs"]["engine"] == "lm_paged" for r in ticks)
        # prefill/decode phases nest inside the tick span
        phases = [r for r in recs
                  if r["name"] in ("serve/prefill", "serve/decode")]
        assert phases and all(p["parent"] == "serve/tick" and p["depth"] >= 1
                              for p in phases)
        # one async begin/end pair per request uid
        begins = {r["id"] for r in recs
                  if r["kind"] == "b" and r["name"] == "request"}
        ends = {r["id"] for r in recs
                if r["kind"] == "e" and r["name"] == "request"}
        assert begins == ends == {0, 1, 2}
        # the whole timeline exports to a valid Chrome trace
        assert validate_chrome_trace(chrome_trace(recs)) == []

    def test_stats_publish_and_reset_counters(self):
        engine, _ = _paged_run()
        stats = engine.stats()
        assert stats["completed"] == 3
        g = registry().snapshot()["gauges"]
        assert g["repro_serve_lm_paged_completed"] == 3.0
        engine.reset_counters()
        stats2 = engine.stats()
        assert stats2["completed"] == 0 and stats2["wall_s"] == 0.0
        # absolute tick count is preserved; occupancy uses the new window
        assert stats2["ticks"] == stats["ticks"]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


class TestCLI:
    def _run_file(self, tmp_path):
        _sample_records()
        numerics_event("autoprec_demote", site="g", eps_budget=1e-3)
        path = str(tmp_path / "run.jsonl")
        write_jsonl(path, run_records(trace.snapshot(),
                                      snapshot=registry().snapshot()))
        return path

    def test_summary(self, tmp_path, capsys):
        path = self._run_file(tmp_path)
        assert obs_main(["summary", path]) == 0
        out = capsys.readouterr().out
        assert "outer" in out and "inner" in out
        assert "numerics/autoprec_demote" in out
        assert "repro_numerics_events_total" in out

    def test_chrome_subcommand(self, tmp_path):
        path = self._run_file(tmp_path)
        out = str(tmp_path / "trace.json")
        assert obs_main(["chrome", path, out]) == 0
        doc = json.load(open(out))
        assert validate_chrome_trace(doc) == []

    def test_prom_subcommand(self, tmp_path):
        path = self._run_file(tmp_path)
        out = str(tmp_path / "metrics.prom")
        assert obs_main(["prom", path, out]) == 0
        assert "# TYPE repro_numerics_events_total counter" in open(out).read()


# ---------------------------------------------------------------------------
# metrics schema golden
# ---------------------------------------------------------------------------


#: kernel-call and spectral-transform series carry (family=... /
#: path=...) labels that depend on which compiled paths a leg traces
#: (REPRO_USE_PALLAS; the megakernel runs no staged transform) —
#: excluded from the schema golden so both CI legs pin the same key set.
_VOLATILE_PREFIXES = ("repro_kernels_calls_total",
                      "repro_kernels_bytes_moved",
                      "repro_spectral_transforms_total")


def _golden_names():
    for kind in KINDS:
        numerics_event(kind)
    tile_cache_event("miss", "spectral_dense", "k")
    tr = _tiny_trainer()
    tr.run(lambda _s: {"x": jnp.zeros((2, 1, 16, 16)),
                       "t": jnp.zeros((2, 1, 16, 16))})
    engine, _ = _paged_run()
    engine.stats()
    return [n for n in metric_names()
            if not n.startswith(_VOLATILE_PREFIXES)]


class TestMetricsSchemaGolden:
    def test_metric_names_match_golden(self):
        names = _golden_names()
        if os.environ.get("REPRO_REGEN_GOLDENS") == "1":
            with open(GOLDEN_PATH, "w") as fh:
                json.dump(names, fh, indent=2)
        with open(GOLDEN_PATH) as fh:
            golden = json.load(fh)
        assert names == golden, (
            "metrics snapshot schema drifted from the golden key set; "
            "if the stats-surface change is intentional, regenerate "
            "with REPRO_REGEN_GOLDENS=1")
