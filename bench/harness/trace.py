"""Profiler trace of the measured window, and its reduction to device
busy time, time per class of device operation, and idle gaps attributed
to the benchmark's own host spans.

``extract`` turns the profiler's ``.xplane.pb`` into a small JSON-able
record (device operations and ``bench/*`` host spans inside the window,
all on the profiler's one clock); ``Reduction`` computes from that
record alone, so the committed recording under ``bench/tests/data``
checks the arithmetic that every run uses.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Callable, Dict, List, Tuple

WINDOW = "bench/window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_LINE = "XLA Ops"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPCODE = re.compile(r"^\S+ = .*?\s([a-z][\w-]*)\(")


def op_name(hlo: str) -> str:
    """The TPU trace names each operation by its HLO instruction text;
    keep ``<instruction> <opcode>`` and, for a custom call, its target
    (``%spectral_contract_pallas.54 custom-call:tpu_custom_call``)."""
    head = hlo.split(" = ", 1)[0]
    m = _OPCODE.match(hlo)
    if not m:
        return head
    t = _TARGET.search(hlo)
    return f"{head} {m.group(1)}" + (f":{t.group(1)}" if t else "")


@contextlib.contextmanager
def capture():
    """Trace what runs inside; yields a dict that receives the extracted
    record when the block ends.  The raw trace lives in a temporary
    directory that is removed once read."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp(prefix="bench_trace_")
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


def extract(log_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, spans = [], []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == _OP_LINE:
                for e in line.events:
                    ops.append([int(m.group(1)), op_name(e.name),
                                float(e.start_ns), float(e.duration_ns)])
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith("bench/"):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
    win = [s for s in spans if s[0] == WINDOW]
    if not win:
        raise RuntimeError("the trace holds no bench/window span")
    t0, t1 = win[0][1], win[0][1] + win[0][2]
    ops = [o for o in ops if o[2] + o[3] > t0 and o[2] < t1]
    spans = [s for s in spans if s[1] + s[2] > t0 and s[1] < t1]
    return {"window_ns": [t0, t1], "ops": ops, "spans": spans}


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _self_times(ops):
    """Exclusive time of each operation of one device: a ``while`` or
    ``conditional`` encloses the operations of its body in the trace, so
    its own time is its span less theirs."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    own = [b - a for _, _, a, b in ops]
    stack: List[int] = []
    for i in order:
        a, b = ops[i][2], ops[i][3]
        while stack and ops[stack[-1]][3] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][3]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


class Reduction:
    """Sums over the traced window of one run on ``chips`` devices.
    Times per operation are exclusive (``_self_times``)."""

    def __init__(self, record: dict, chips: int = 1):
        self.t0, self.t1 = record["window_ns"]
        self.chips = chips
        clipped = [(d, name, max(s, self.t0), min(s + dur, self.t1))
                   for d, name, s, dur in record["ops"] if d < chips]
        self.ops = []
        for d in range(chips):
            mine = [o for o in clipped if o[0] == d]
            self.ops += [(d, o[1], t) for o, t in
                         zip(mine, _self_times(mine), strict=True)]
        self.spans = [(n, s, s + dur) for n, s, dur in record["spans"]
                      if n != WINDOW]
        self._busy = {d: _merge([(a, b) for dd, _, a, b in clipped
                                 if dd == d and b > a])
                      for d in range(chips)}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        tot = sum(b - a for iv in self._busy.values() for a, b in iv)
        return tot * 1e-9 / self.chips

    def op_seconds(self, pred: Callable[[str], bool] = lambda n: True
                   ) -> float:
        """Device seconds of the operations whose name satisfies
        ``pred``, averaged over the chips."""
        return sum(t for _, n, t in self.ops if pred(n)) * 1e-9 / self.chips

    def by_name(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for _, n, t in self.ops:
            out[n] = out.get(n, 0.0) + t * 1e-9 / self.chips
        return out

    def gaps(self) -> List[Tuple[str, float]]:
        """Idle intervals of device 0 in the window, each named by the
        innermost benchmark span around its midpoint ("none" outside
        every span), as (span, seconds)."""
        spans = sorted(self.spans, key=lambda s: s[1])
        starts = [s[1] for s in spans]
        longest = max((s[2] - s[1] for s in spans), default=0.0)
        out = []
        edge = self.t0
        for a, b in self._busy[0] + [(self.t1, self.t1)]:
            if a > edge:
                mid = 0.5 * (a + edge)
                around = []
                i = bisect.bisect_right(starts, mid) - 1
                while i >= 0 and spans[i][1] >= mid - longest:
                    if mid < spans[i][2]:
                        around.append(spans[i])
                    i -= 1
                name = (min(around, key=lambda s: s[2] - s[1])[0]
                        if around else "none")
                out.append((name, (a - edge) * 1e-9))
            edge = max(edge, b)
        return out

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.by_name().items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps(), key=lambda g: -g[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}
