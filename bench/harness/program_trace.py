"""The program's own spans and named scopes in a profiler trace, and the
per-layer numbers they give.

``harness.trace.extract`` keeps the device operations and the
benchmark's ``bench/*`` spans of a traced window.  Two things more make
the record read here:

  program_spans  the program's ``repro.obs`` spans, which
                 ``repro.obs.trace.span`` writes into the profiler's
                 trace as host events (``train/*``, ``serve/*``), as
                 [name, start_ns, duration_ns] inside the window
                 (``extract``);
  op_scopes      {operation name: ``op_name``} of a training step's
                 operations: the named-scope path XLA keeps from the
                 program's ``jax.named_scope``s (``.../fno/layer0/
                 spectral/fft_in/...``, ``.../optim/adamw/...``).  The
                 TPU trace names an operation by its HLO text without
                 this metadata, so it is read from the compiled step
                 (``step_scopes``), whose instruction names the trace
                 uses.

Every number below is computed from that record alone, so a recording
under ``bench/tests/data`` checks the arithmetic.  A span number counts
the spans whose midpoint lies in the window and divides by the
``train/step`` or ``serve/tick`` spans counted the same way; a number
returns None where the record holds nothing for it.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

from . import trace

PROGRAM = ("train/", "serve/")
STEP, TICK = "train/step", "serve/tick"
FFT = re.compile(r"/spectral/fft_(in|out)\b")
FFT_HALVES = (re.compile(r"/spectral/fft_in\b"),
              re.compile(r"/spectral/fft_out\b"))
OPTIMIZER = re.compile(r"\boptim/adamw\b")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def extract(log_dir: str) -> dict:
    """``harness.trace.extract``'s record of the window, with the
    program's spans added."""
    from jax.profiler import ProfileData

    rec = trace.extract(log_dir)
    t0, t1 = rec["window_ns"]
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    spans = [[e.name, float(e.start_ns), float(e.duration_ns)]
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PROGRAM)
             and e.start_ns + e.duration_ns > t0 and e.start_ns < t1]
    rec["program_spans"] = sorted(spans, key=lambda s: s[1])
    return rec


def scopes_from_hlo(text: str) -> Dict[str, str]:
    """{operation name: op_name} from a compiled module's HLO text,
    keyed as ``harness.trace.op_name`` keys the trace's operations."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        m = _OP_NAME.search(line)
        if m and line.startswith("%") and " = " in line:
            out[trace.op_name(line)] = m.group(1)
    return out


def step_scopes(sess) -> Dict[str, str]:
    """{operation name: op_name} of a training cell's compiled step
    (``harness.train.CellRun``): the step ``Trainer.run`` executes, for
    the trainer's current policy and the cell's batch."""
    batch = sess.batch_fn(sess.trainer.step)
    return scopes_from_hlo(sess.trainer.lower(batch).compile().as_text())


@contextlib.contextmanager
def capture():
    """``harness.trace.capture`` with this module's ``extract``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp(prefix="bench_program_trace_")
    out: dict = {}
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out.update(extract(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)


# -- span numbers -------------------------------------------------------------

def in_window(rec: dict, name: str) -> List[float]:
    """Durations (ns) of the program spans named ``name`` whose midpoint
    lies in the window."""
    t0, t1 = rec["window_ns"]
    return [d for n, s, d in rec.get("program_spans", ())
            if n == name and t0 <= s + 0.5 * d < t1]


def span_ms(rec: dict, name: str, per: str) -> Optional[float]:
    """Milliseconds of ``name`` spans per ``per`` span; None where the
    window holds either none of them."""
    spans, units = in_window(rec, name), in_window(rec, per)
    if not spans or not units:
        return None
    return sum(spans) / len(units) * 1e-6


def step_host_ms(rec: dict) -> Optional[float]:
    """Host work per training step that the device waits behind: the
    batch fetch (``train/data``) and the step's dispatch, where the
    batch's transfer starts (``train/dispatch``)."""
    data = span_ms(rec, "train/data", STEP)
    dispatch = span_ms(rec, "train/dispatch", STEP)
    return None if data is None or dispatch is None else data + dispatch


def engine_host_ms(rec: dict) -> Optional[float]:
    """The engine's host path per tick: ``serve/tick`` less the wait for
    the forward's outputs (``serve/operator/fetch``)."""
    tick = span_ms(rec, TICK, TICK)
    fetch = span_ms(rec, "serve/operator/fetch", TICK)
    return None if tick is None or fetch is None else tick - fetch


def scheduler_ms(rec: dict) -> Optional[float]:
    """``Scheduler.take`` per tick."""
    return span_ms(rec, "serve/operator/take", TICK)


def stage_ms(rec: dict) -> Optional[float]:
    """Per tick, staging the batch onto the device: memo partition, one
    transfer per field, stack and pad."""
    return span_ms(rec, "serve/operator/stage", TICK)


# -- scope numbers ------------------------------------------------------------

def _scope_ms(red: "trace.Reduction", rec: dict, pattern,
              need: Tuple = ()) -> Optional[float]:
    """Exclusive device milliseconds per ``train/step`` span of the
    operations whose scope matches ``pattern``; None unless the trace
    has a step and an operation under each pattern of ``need``."""
    steps = in_window(rec, STEP)
    scopes = rec.get("op_scopes", {})
    if not steps or not all(any(p.search(s) for s in scopes.values())
                            for p in need or (pattern,)):
        return None
    t = red.op_seconds(lambda n: bool(pattern.search(scopes.get(n, ""))))
    return t / len(steps) * 1e3


def fft_ms(red, rec: dict) -> Optional[float]:
    """Device milliseconds per step under the spectral layers' forward
    and inverse FFT scopes (``*/spectral/fft_in``, ``*/spectral/fft_out``,
    their backward included); None unless both scopes are in the trace."""
    return _scope_ms(red, rec, FFT, FFT_HALVES)


def optimizer_ms(red, rec: dict) -> Optional[float]:
    """Device milliseconds per step under ``optim/adamw``."""
    return _scope_ms(red, rec, OPTIMIZER)


#: the numbers by the names PERF.md gives them
SPAN_METRICS = {"step_host_ms.train": step_host_ms,
                "engine_host_ms.serve": engine_host_ms,
                "scheduler_ms.serve": scheduler_ms,
                "stage_ms.serve": stage_ms}
SCOPE_METRICS = {"fft_ms.train": fft_ms, "optimizer_ms.train": optimizer_ms}


def metrics(rec: dict, chips: int = 1) -> Dict[str, float]:
    """Every number the record holds, by name."""
    red = trace.Reduction(rec, chips)
    out = {k: f(rec) for k, f in SPAN_METRICS.items()}
    out.update({k: f(red, rec) for k, f in SCOPE_METRICS.items()})
    return {k: v for k, v in out.items() if v is not None}


# -- idle time by program span ------------------------------------------------

def _idle(rec: dict) -> List[Tuple[float, float]]:
    """Intervals of the window in which device 0 ran no operation."""
    t0, t1 = rec["window_ns"]
    busy = trace._merge([(max(s, t0), min(s + d, t1))
                         for dev, _, s, d in rec["ops"]
                         if dev == 0 and min(s + d, t1) > max(s, t0)])
    out, edge = [], t0
    for a, b in busy + [(t1, t1)]:
        if a > edge:
            out.append((edge, a))
        edge = max(edge, b)
    return out


def idle_segments(rec: dict) -> List[Tuple[float, float, str]]:
    """Device 0's idle time cut at every span boundary, each piece named
    by the innermost program span around it, else by the innermost
    benchmark span ("none" outside every span): (start_ns, seconds,
    name), in time order."""
    spans = [s for s in rec["spans"] if s[0] != trace.WINDOW] \
        + list(rec.get("program_spans", ()))
    # at one instant ends come before starts, and a longer span starts
    # before a shorter one; between two edges the innermost open span is
    # the one opened last, and a benchmark span names the time only
    # where no program span is open
    edges = sorted([(s, 1, -d, i) for i, (_, s, d) in enumerate(spans)]
                   + [(s + d, 0, 0, i) for i, (_, s, d) in enumerate(spans)])
    stack: List[int] = []
    cuts = []                       # (time, innermost name from there on)
    for t, is_start, _, i in edges:
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
        names = [spans[j][0] for j in stack]
        cuts.append((t, next((n for n in reversed(names)
                              if n.startswith(PROGRAM)),
                             names[-1] if names else "none")))
    out, k = [], 0
    for a, b in _idle(rec):
        while k < len(cuts) and cuts[k][0] <= a:
            k += 1
        name = cuts[k - 1][1] if k else "none"
        edge, j = a, k
        while j < len(cuts) and cuts[j][0] < b:
            t, after = cuts[j]
            if t > edge:
                out.append((edge, (t - edge) * 1e-9, name))
                edge = t
            name, j = after, j + 1
        if b > edge:
            out.append((edge, (b - edge) * 1e-9, name))
    return out


def idle_split(rec: dict) -> Dict[str, float]:
    """Idle seconds of device 0 by the innermost span they fall in."""
    out: Dict[str, float] = {}
    for _, s, name in idle_segments(rec):
        out[name] = out.get(name, 0.0) + s
    return out
