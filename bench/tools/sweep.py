#!/usr/bin/env python3
"""Offered-load sweep of a serving cell, to find its knee once on the
chip: the highest rate the engine sustains without a growing backlog.
A serving cell's traffic file then fixes its rate at about four fifths
of it (tails judged) or above it (fields per second judged).

    python bench/tools/sweep.py --workload fno_darcy.serve_saturated \
        --rates 200,300,400,500 --seconds 8 --seed 5

Prints one JSON line per rate: answered fields per second against the
offered rate, latency percentiles of the answered requests from due
time, the requests still queued when the window closed, and the mean
batch fill.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import boot, registry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    base = registry.cell(args.workload)
    boot.start(base["workload"]["chips"])
    from harness import serve

    for rate in [float(r) for r in args.rates.split(",")]:
        cell = copy.deepcopy(base)
        cell["traffic"]["rate_per_s"] = rate
        sess = serve.build(cell, args.seed, args.seconds)
        w = serve.measure(sess, args.seconds)
        eng = w.pop("engine")
        w.update(rate_per_s=rate, avg_batch_fill=eng["avg_batch_fill"])
        print(json.dumps(w), flush=True)
        serve.release(sess)
    return 0


if __name__ == "__main__":
    sys.exit(main())
