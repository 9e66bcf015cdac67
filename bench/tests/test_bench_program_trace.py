"""The program's spans in the profiler's trace, and the numbers read
from them (``harness.program_trace``).

On the CPU: one ``Trainer.run`` step and one ``OperatorEngine`` tick
under the profiler, each inside a benchmark span, reach the extracted
record with the program's span names, nested in time inside their
parent; and the arithmetic on a record made by hand.  On recorded TPU
v5e windows of both cells (``bench/tests/data``, extracted by
``bench/tools/span_record.py`` on the chip): each number against the
values read off the recording by hand.
"""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import program_trace as pt  # noqa: E402
from harness import trace  # noqa: E402

DATA = BENCH / "tests" / "data"


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and \
        child[1] + child[2] <= parent[1] + parent[2]


def test_program_spans_reach_the_profiler_trace():
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.models import FNOConfig, init_fno
    from repro.models.fno import fno_apply
    from repro.serve import FieldRequest, OperatorEngine
    from repro.train import Trainer, TrainerConfig, relative_l2

    cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                    lifting_channels=8, projection_channels=8,
                    n_layers=1, modes=(4, 4))
    params = init_fno(jax.random.PRNGKey(0), cfg)
    tr = Trainer(lambda p, b, pol: relative_l2(
        fno_apply(p, b["x"], cfg, pol), b["t"]), params,
        TrainerConfig(total_steps=2))
    batch = {"x": jnp.ones((2, 1, 16, 16)), "t": jnp.ones((2, 1, 16, 16))}
    tr.run(lambda _s: batch, steps=1)             # compiles outside
    eng = OperatorEngine(params, cfg, model="fno", max_batch=2)
    x = np.ones((1, 16, 16), np.float32)
    eng.submit(FieldRequest(uid=0, x=x))
    eng.drain()
    eng.submit(FieldRequest(uid=1, x=2 * x))
    with pt.capture() as rec:
        with TraceAnnotation(trace.WINDOW):
            with TraceAnnotation("bench/step"):
                tr.run(lambda _s: batch, steps=2)
            with TraceAnnotation("bench/tick"):
                eng.tick()
    spans = rec["program_spans"]
    names = [n for n, _, _ in spans]
    assert sorted(set(names)) == sorted([
        "train/data", "train/step", "train/dispatch", "train/sync",
        "serve/tick", "serve/operator/take", "serve/operator/stage",
        "serve/operator/batch", "serve/operator/dispatch",
        "serve/operator/fetch", "serve/operator/answer"])
    assert all(names.count(n) == 1 for n in names)
    by = {s[0]: s for s in spans}
    parents = {s[0]: s for s in rec["spans"]}
    for n, s in by.items():
        assert _inside(s, parents["bench/step" if n.startswith("train/")
                                  else "bench/tick"]), n
    for child, parent in [("train/dispatch", "train/step"),
                          ("train/sync", "train/step"),
                          ("serve/operator/take", "serve/tick"),
                          ("serve/operator/stage", "serve/tick"),
                          ("serve/operator/dispatch", "serve/operator/batch"),
                          ("serve/operator/fetch", "serve/operator/batch"),
                          ("serve/operator/answer", "serve/tick")]:
        assert _inside(by[child], by[parent]), child


def _record():
    """A window of 100 ns with two ticks; the device runs [10, 20),
    [30, 35) and [60, 70)."""
    return {
        "window_ns": [0.0, 100.0],
        "ops": [[0, "%fusion.1 fusion", 10.0, 10.0],
                [0, "%fft.2 fusion", 30.0, 5.0],
                [0, "%fusion.3 fusion", 60.0, 10.0]],
        "spans": [["bench/window", 0.0, 100.0], ["bench/tick", 5.0, 40.0],
                  ["bench/tick", 50.0, 49.0]],
        "program_spans": [["serve/tick", 6.0, 38.0],
                          ["serve/operator/take", 6.0, 4.0],
                          ["serve/operator/fetch", 22.0, 20.0],
                          ["serve/tick", 51.0, 47.0],
                          ["serve/operator/take", 51.0, 9.0],
                          ["serve/operator/fetch", 70.0, 25.0]],
        "op_scopes": {"%fft.2 fusion":
                      "jit(f)/fno/layer0/spectral/fft_in/fft"},
    }


def test_idle_split_by_innermost_span():
    rec = _record()
    segs = pt.idle_segments(rec)
    assert sum(s for _, s, _ in segs) == pytest.approx(75e-9)
    assert [a for a, _, _ in segs] == sorted(a for a, _, _ in segs)
    split = {k: v * 1e9 for k, v in pt.idle_split(rec).items()}
    assert split == pytest.approx({
        "none": 5 + 5 + 1,                       # [0,5) [45,50) [99,100)
        "bench/tick": 1 + 1 + 1 + 1,             # [5,6) [44,45) [50,51) [98,99)
        "serve/operator/take": 4 + 9,            # [6,10) [51,60)
        "serve/tick": 2 + 2 + 3,                 # [20,22) [42,44) [95,98)
        "serve/operator/fetch": 8 + 7 + 25,      # [22,30) [35,42) [70,95)
    })


def test_idle_named_by_program_span_before_benchmark_span():
    # the benchmark's batch function runs inside the trainer's train/data
    rec = {"window_ns": [0.0, 100.0], "ops": [],
           "spans": [["bench/window", 0.0, 100.0], ["bench/step", 0.0, 80.0],
                     ["bench/data", 10.0, 30.0]],
           "program_spans": [["train/data", 5.0, 40.0]]}
    assert {k: v * 1e9 for k, v in pt.idle_split(rec).items()} == \
        pytest.approx({"train/data": 40.0, "bench/step": 40.0, "none": 20.0})


def test_span_numbers_per_tick():
    rec = _record()
    assert pt.scheduler_ms(rec) == pytest.approx((4 + 9) / 2 * 1e-6)
    assert pt.engine_host_ms(rec) == pytest.approx(
        ((38 + 47) - (20 + 25)) / 2 * 1e-6)
    assert pt.stage_ms(rec) is None          # no stage span recorded
    assert pt.step_host_ms(rec) is None      # a serving record
    red = trace.Reduction(rec)
    assert pt.fft_ms(red, rec) is None       # no step, no fft_out scope
    assert pt.metrics(rec) == pytest.approx({
        "scheduler_ms.serve": 6.5e-6, "engine_host_ms.serve": 20e-6})


def test_scopes_from_hlo_key_like_the_trace():
    text = """
  %fusion.7 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %p), kind=kLoop, calls=%c, metadata={op_name="jit(step)/optim/adamw/mul"}
  ROOT %tuple.3 = (f32[4,8]{1,0}) tuple(%fusion.7)
  %param = f32[4]{0} parameter(0)
"""
    assert pt.scopes_from_hlo(text) == {
        "%fusion.7 fusion": "jit(step)/optim/adamw/mul"}


def _recording(cell: str) -> dict:
    with gzip.open(DATA / f"v5e_fno_darcy_{cell}.spans.json.gz", "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def train_rec():
    """Nine steps of ``fno_darcy.train`` (batch 64, ``mixed_fno_bf16``),
    with the compiled step's scopes."""
    return _recording("train")


@pytest.fixture(scope="module")
def serve_rec():
    """Two seconds of ``fno_darcy.serve_saturated``: 143 full ticks."""
    return _recording("serve_saturated")


def test_train_recording_span_numbers(train_rec):
    # per step: train/data 13,164,801 ns and train/dispatch 13,532,400 ns
    # over the 9 train/step spans
    assert len(pt.in_window(train_rec, pt.STEP)) == 9
    assert pt.step_host_ms(train_rec) == pytest.approx(
        (13164801 + 13532400) / 9 * 1e-6, rel=1e-9)
    assert pt.engine_host_ms(train_rec) is None


def test_train_recording_scope_numbers(train_rec):
    # 1,584 operation events under a */spectral/fft_in or fft_out scope
    # (1,080 fusions) and 207 under optim/adamw; none encloses another,
    # so their exclusive times are their durations inside the window
    red = trace.Reduction(train_rec)
    assert pt.fft_ms(red, train_rec) == pytest.approx(131.53380433, rel=1e-9)
    assert pt.optimizer_ms(red, train_rec) == pytest.approx(12.66636978,
                                                            rel=1e-9)
    scopes = train_rec["op_scopes"]
    fft = [n for _, n, _ in red.ops if pt.FFT.search(scopes.get(n, ""))]
    assert len(fft) == 1584
    # the recording's operations keep their scope for 201.5 of the
    # 225.5 ms a step is busy; the rest are copies and pads XLA added
    named = red.op_seconds(lambda n: n in scopes) / 9
    assert named == pytest.approx(0.20145801, rel=1e-6)


def test_train_recording_idle_split(train_rec):
    red = trace.Reduction(train_rec)
    split = pt.idle_split(train_rec)
    assert sum(split.values()) == pytest.approx(red.window_s - red.busy_s,
                                                rel=1e-9)
    # the device waits in train/sync for the batch's transfer, in
    # train/dispatch and in train/data (the benchmark's bench/data
    # inside it); little of the idle time lies outside the program
    ms = {k: v / 9 * 1e3 for k, v in split.items()}
    assert ms["train/sync"] == pytest.approx(2.1507359, rel=1e-6)
    assert ms["train/dispatch"] == pytest.approx(1.4807438, rel=1e-6)
    assert ms["train/data"] == pytest.approx(1.4627557, rel=1e-6)
    outside = sum(v for k, v in split.items() if not k.startswith(pt.PROGRAM))
    assert outside / sum(split.values()) == pytest.approx(0.0166206, rel=1e-4)


def test_serve_recording_span_numbers(serve_rec):
    # serve/tick 1,974,859,746 ns, serve/operator/fetch 1,113,957,292,
    # take 5,785,034 and stage 791,269,649 over the 143 ticks
    assert len(pt.in_window(serve_rec, pt.TICK)) == 143
    assert pt.engine_host_ms(serve_rec) == pytest.approx(
        (1974859746 - 1113957292) / 143 * 1e-6, rel=1e-9)
    assert pt.scheduler_ms(serve_rec) == pytest.approx(5785034 / 143 * 1e-6,
                                                       rel=1e-9)
    assert pt.stage_ms(serve_rec) == pytest.approx(791269649 / 143 * 1e-6,
                                                   rel=1e-9)
    assert pt.step_host_ms(serve_rec) is None
    red = trace.Reduction(serve_rec)
    assert pt.fft_ms(red, serve_rec) is None      # no scopes recorded


def test_serve_recording_idle_split(serve_rec):
    # nearly all of staging is idle device time; what falls in no
    # program span is the benchmark's generator and wait, between ticks
    red = trace.Reduction(serve_rec)
    split = pt.idle_split(serve_rec)
    assert sum(split.values()) == pytest.approx(red.window_s - red.busy_s,
                                                rel=1e-9)
    ms = {k: v / 143 * 1e3 for k, v in split.items()}
    assert ms["serve/operator/stage"] == pytest.approx(5.5239647, rel=1e-6)
    assert ms["serve/operator/fetch"] == pytest.approx(1.1148906, rel=1e-6)
    outside = sum(v for k, v in split.items() if not k.startswith(pt.PROGRAM))
    assert outside / sum(split.values()) == pytest.approx(0.0265813, rel=1e-4)
