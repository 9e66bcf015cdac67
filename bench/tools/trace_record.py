#!/usr/bin/env python3
"""Record the reduced trace of one traced window of a cell, for reading
by hand and for the recorded-trace test of the reduction.

    python bench/tools/trace_record.py --workload fno_darcy.train \
        --seed 3 --seconds 2 --out chiprun_out/fno_darcy.train.trace.json.gz

Writes the record ``harness.trace.extract`` makes (device operations and
``bench/*`` spans inside the window), gzipped JSON, and prints the
reduction's totals and its ten longest operation names.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import boot, registry, trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    chips = cell["workload"]["chips"]
    boot.start(chips)
    from harness import serve, train

    mod = train if cell["traffic"]["kind"] == "train" else serve
    sess = (mod.build(cell, args.seed) if mod is train
            else mod.build(cell, args.seed, args.seconds))
    with trace.capture() as record:
        window = mod.measure(sess, args.seconds)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(record, f)
    red = trace.Reduction(record, chips)
    print(json.dumps({"window": {k: v for k, v in window.items()
                                 if k != "engine"},
                      "busy_s": red.busy_s, "window_s": red.window_s,
                      "ops": len(record["ops"]),
                      "breakdown": red.breakdown()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
