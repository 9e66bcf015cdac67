"""Observability overhead guard: obs-on trainer steps must stay within
5% of obs-off.

The design promise of ``repro.obs`` is that the trace layer is free when
disabled and near-free when enabled (preallocated ring, one lock per
record, no allocation off the hot path).  This bench holds it to the
number: alternating obs-off / obs-on legs of an identical tiny-FNO
training run, per-step wall from the trainer's own history (the same
``t0..dt`` window in both modes — the spans sit inside it, so the obs-on
median carries their cost), best-of-medians across repeats to shed
scheduler noise.

    PYTHONPATH=src python -m benchmarks.bench_obs [--max-overhead 0.05]

Results land in ``benchmarks/results/obs_overhead.json``; exits nonzero
when the overhead budget is blown.
"""
from __future__ import annotations

import argparse
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import FNOConfig, fno_apply, init_fno
from repro.obs import registry, trace
from repro.train import Trainer, TrainerConfig, relative_l2

RESULTS = os.path.join(os.path.dirname(__file__), "results",
                       "obs_overhead.json")


def _problem():
    cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=8,
                    lifting_channels=8, projection_channels=8,
                    n_layers=2, modes=(4, 4))
    params = init_fno(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 1, 16, 16), jnp.float32)
    t = jnp.asarray(rng.randn(4, 1, 16, 16) * 0.1, jnp.float32)

    def loss_fn(p, batch, policy):
        return relative_l2(fno_apply(p, batch["x"], cfg, policy),
                           batch["t"])

    return params, loss_fn, {"x": x, "t": t}


def run_leg(obs_on: bool, steps: int, warmup: int) -> float:
    """Median post-warmup step wall (seconds) of one training leg."""
    params, loss_fn, batch = _problem()
    trainer = Trainer(loss_fn, params,
                      TrainerConfig(total_steps=steps, obs=obs_on))
    if not obs_on:
        trace.disable()
    hist = trainer.run(lambda _step: batch)
    trace.disable()
    trace.clear()
    return statistics.median(h["dt"] for h in hist[warmup:])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--warmup", type=int, default=10,
                    help="leading steps dropped from each leg's median "
                         "(compile + cache warm)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--max-overhead", type=float, default=0.05)
    args = ap.parse_args()

    # counters accumulated during the legs are bench-local noise
    registry().reset()

    med_off, med_on = [], []
    for r in range(args.repeats):
        med_off.append(run_leg(False, args.steps, args.warmup))
        med_on.append(run_leg(True, args.steps, args.warmup))
        print(f"repeat {r}: off={med_off[-1] * 1e3:.3f}ms "
              f"on={med_on[-1] * 1e3:.3f}ms")

    best_off, best_on = min(med_off), min(med_on)
    overhead = best_on / best_off - 1.0
    ok = overhead <= args.max_overhead

    report = {
        "steps": args.steps,
        "warmup": args.warmup,
        "repeats": args.repeats,
        "median_step_wall_s": {"obs_off": med_off, "obs_on": med_on},
        "best_median_s": {"obs_off": best_off, "obs_on": best_on},
        "overhead": round(overhead, 6),
        "max_overhead": args.max_overhead,
        "ok": ok,
    }
    from benchmarks.common import write_result

    write_result(RESULTS, report)
    print(f"obs overhead: {overhead * 100:+.2f}% "
          f"(budget {args.max_overhead * 100:.0f}%) -> "
          f"{'OK' if ok else 'OVER BUDGET'}")
    print(f"results -> {RESULTS}")
    return 0 if ok else 1


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    raise SystemExit(main())
