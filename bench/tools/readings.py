#!/usr/bin/env python3
"""The readings the correctness limits are set from, on the chip, for
many seeds in one process (set-up is long; the benchmark's own runs do
not run this).

    python bench/tools/readings.py --workload fno_darcy.train \
        --seeds 11,12,13 --control-seeds 11,12,13 [--seconds 3]

Per seed it prints one JSON line:
  program   the program's numbers against the float32 reference (the
            lower readings);
  control   with --control-seeds: the reference computed with every
            contraction operand and spectrum stored as fp8 (e4m3), put
            in the program's place (the control's upper readings);
  faults    the faults a cell of this kind can have, planted in the
            reference put in the program's place: training, half of each
            batch left out (a state left unchanged reads 1 by
            construction and needs no run); serving, each sampled answer
            swapped for another request's.
Serving runs use a short window (``--seconds``) at the cell's rate.
With --dump, every leaf's (and leaf part's) norms of each side go to
one more JSON line per seed, for a look at which leaves the numbers
come from.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import boot, check, registry  # noqa: E402


def train_seed(cell, seed, control, dump):
    from harness import train

    sess = train.build(cell, seed)
    batches = sess.checked
    train.release(sess)
    ref = train.reference_steps(cell, seed, batches)
    out = {"program": check.training_readings(sess.prog, ref),
           "ref_losses": ref["losses"]}
    norms = {"program": sess.prog, "reference": ref}
    if control:
        low = train.reference_steps(cell, seed, batches, "fp8_e4m3")
        out["control"] = check.training_readings(low, ref)
        out["faults"] = {"half_batch": check.training_readings(
            train.reference_steps(cell, seed, batches, halve=True), ref)}
        norms["control"] = low
    if dump:
        print(json.dumps({"seed": seed, "norms": norms}), flush=True)
    return out


def serve_seed(cell, seed, control, seconds):
    from harness import serve

    sess = serve.build(cell, seed, seconds)
    window = serve.measure(sess, seconds)
    serve.release(sess)
    out = {"program": serve.readings(sess),
           "window": {k: v for k, v in window.items() if k != "engine"}}
    if control:
        x = serve.sample_inputs(sess)
        ref = serve.reference_for(sess, x)
        low = serve.reference_for(sess, x, "fp8_e4m3")
        out["control"] = serve.answer_gaps(low, ref)
        out["faults"] = {"answer_swapped": serve.answer_gaps(
            ref[1:] + ref[:1], ref)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--dump", action="store_true")
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    boot.start(cell["workload"]["chips"])
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        if cell["traffic"]["kind"] == "train":
            out = train_seed(cell, seed, seed in controls, args.dump)
        else:
            out = serve_seed(cell, seed, seed in controls, args.seconds)
        out.update(workload=args.workload, seed=seed,
                   seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
