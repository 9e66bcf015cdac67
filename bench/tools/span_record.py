#!/usr/bin/env python3
"""Trace one window of a cell with the program's own spans and scopes,
and print where the device's idle time falls among them.

    python bench/tools/span_record.py --workload fno_darcy.serve_saturated \
        --seed 3 --seconds 3 --out serve.spans.json.gz

Records what ``harness.program_trace`` reads (the benchmark's record
with the program's ``train/*`` and ``serve/*`` spans and, for a
training cell, each operation's named scope from the compiled step) and
prints one JSON line: the per-layer numbers ``program_trace.metrics``
reads, the device's idle time in milliseconds per step or tick under
the innermost program span it falls in (else the benchmark span), the
share of it in no program span, and the longest idle stretches with
their span.
"""
from __future__ import annotations

import argparse
import gzip
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import boot, program_trace, registry, trace  # noqa: E402


LONGEST = 10


def summary(rec: dict, chips: int) -> dict:
    red = trace.Reduction(rec, chips)
    per = program_trace.STEP if program_trace.in_window(
        rec, program_trace.STEP) else program_trace.TICK
    units = len(program_trace.in_window(rec, per))
    split = program_trace.idle_split(rec)
    idle = sum(split.values())
    outside = sum(v for k, v in split.items()
                  if not k.startswith(program_trace.PROGRAM))
    t0 = rec["window_ns"][0]
    gaps = sorted(program_trace.idle_segments(rec), key=lambda g: -g[1])
    return {
        "window_s": red.window_s, "busy_s": red.busy_s,
        "per": per, "units": units,
        "metrics": program_trace.metrics(rec, chips),
        "idle_ms_per_unit": {k: v / units * 1e3 for k, v in sorted(
            split.items(), key=lambda kv: -kv[1])} if units else {},
        "idle_outside_program_share": outside / idle if idle else 0.0,
        "longest_idle": [[(a - t0) * 1e-9, s, n]
                         for a, s, n in gaps[:LONGEST]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", help="write the record here (gzipped JSON)")
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    chips = cell["workload"]["chips"]
    boot.start(chips)
    kind = cell["traffic"]["kind"]
    run = importlib.import_module(f"harness.{kind}")
    sess = run.build(cell, args.seed, args.seconds)
    with program_trace.capture() as rec:
        window = run.measure(sess, args.seconds)
    if kind == "train":
        rec["op_scopes"] = program_trace.step_scopes(sess)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(args.out, "wt") as f:
            json.dump(rec, f)
    out = summary(rec, chips)
    out["window"] = {k: v for k, v in window.items() if k != "engine"}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
