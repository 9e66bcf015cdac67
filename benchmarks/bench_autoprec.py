"""Auto-precision benchmark: steps-to-stable-policy, accuracy vs the
static registry policies, and telemetry overhead.

Trains the Darcy smoke problem three ways — static ``full``, static
``mixed_fno_bf16`` (the paper-faithful baseline) and ``auto``
(telemetry + bound-guided controller over a ``full`` base) — and records:

* **steps_to_stable**: the last training step at which the controller
  changed the overlay (afterwards the policy is converged);
* **final_loss** per run, plus the auto-vs-static ratio (acceptance:
  auto within 5% of ``mixed_fno_bf16``);
* **demoted_fraction**: how many spectral-contract sites the controller
  runs below fp32 (acceptance: >= half), and the overflow counters
  (acceptance: zero non-recovered overflows — every skipped step must be
  followed by recovery, and the counters record none here);
* **telemetry overhead**: median step wall-time with taps collected vs
  without, on the *same* static policy (acceptance: < 10%).

    PYTHONPATH=src python -m benchmarks.bench_autoprec [--steps 40]

Results land in ``benchmarks/results/autoprec.json``.
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro.autoprec import AutoPrecisionController
from repro.core import PrecisionSchedule
from repro.models import fno_apply
from repro.train import Trainer, TrainerConfig, relative_l2

from benchmarks.common import darcy_data, small_fno, write_result

RESULTS = os.path.join(os.path.dirname(__file__), "results", "autoprec.json")


def _trainer(loss_fn, params, steps, *, schedule=None, autoprec=None,
             telemetry=False):
    cfg = TrainerConfig(
        total_steps=steps,
        schedule=schedule or PrecisionSchedule.constant("full"),
        autoprec=autoprec,
        telemetry=telemetry,
    )
    return Trainer(loss_fn, params, cfg)


def _median_step_time(history, skip: int = 3) -> float:
    """Median per-step wall time, skipping compile-bearing early steps."""
    ts = [h["dt"] for h in history[skip:]] or [h["dt"] for h in history]
    return float(np.median(ts))


def run(steps: int = 40, resolution: int = 32, interval: int = 5) -> dict:
    cfg, params = small_fno(hidden=16, modes=(8, 8))
    cfg_small = cfg
    (a, u), _ = darcy_data(n=resolution, ntrain=16, ntest=8, maxiter=200)

    def loss_fn(p, batch, policy):
        return relative_l2(fno_apply(p, batch["x"], cfg_small, policy),
                           batch["t"])

    batch_fn = lambda _step: {"x": a, "t": u}  # noqa: E731

    runs = {}
    # -- static baselines -----------------------------------------------------
    for name in ("full", "mixed_fno_bf16"):
        tr = _trainer(loss_fn, params, steps,
                      schedule=PrecisionSchedule.constant(name))
        hist = tr.run(batch_fn)
        runs[name] = {
            "final_loss": hist[-1]["loss"],
            "median_step_s": _median_step_time(hist),
            "skipped_steps": tr.stats["skipped_steps"],
        }

    # -- auto mode -------------------------------------------------------------
    ctl = AutoPrecisionController(base="full", grid_points=resolution ** 2,
                                  interval=interval)
    tr = _trainer(loss_fn, params, steps, autoprec=ctl)
    hist = tr.run(batch_fn)
    changes = [h["step"] for i, h in enumerate(hist[1:], 1)
               if h["policy"] != hist[i - 1]["policy"]]
    contract_sites = [f"fno/layer{i}/spectral/contract"
                      for i in range(cfg_small.n_layers)]
    pol = ctl.policy()
    demoted = [s for s in contract_sites if pol.at(s).compute is not None]
    telem = tr.telemetry.counters()
    runs["auto"] = {
        "final_loss": hist[-1]["loss"],
        "median_step_s": _median_step_time(hist),
        "policy": pol.name,
        "policy_changes": tr.stats["policy_changes"],
        "steps_to_stable": changes[-1] if changes else 0,
        "recompiles": tr.stats["recompiles"],
        "skipped_steps": tr.stats["skipped_steps"],
        "demoted_contract_sites": demoted,
        "demoted_fraction": len(demoted) / len(contract_sites),
        "overflow_total": telem["overflow_total"],
        "decisions": {g: s["fmt"]
                      for g, s in ctl.describe()["sites"].items()},
    }

    # -- telemetry overhead: same static policy, taps on vs off ---------------
    base = _trainer(loss_fn, params, steps,
                    schedule=PrecisionSchedule.constant("mixed_fno_bf16"))
    h_off = base.run(batch_fn)
    instr = _trainer(loss_fn, params, steps,
                     schedule=PrecisionSchedule.constant("mixed_fno_bf16"),
                     telemetry=True)
    h_on = instr.run(batch_fn)
    t_off = _median_step_time(h_off)
    t_on = _median_step_time(h_on)
    overhead = t_on / t_off - 1.0

    rel = runs["auto"]["final_loss"] / runs["mixed_fno_bf16"]["final_loss"] - 1.0
    report = {
        "steps": steps,
        "resolution": resolution,
        "runs": runs,
        "auto_vs_bf16_loss_rel": rel,
        "telemetry_overhead": overhead,
        "acceptance": {
            "loss_within_5pct": bool(abs(rel) <= 0.05),
            "half_contract_sites_below_fp32":
                runs["auto"]["demoted_fraction"] >= 0.5,
            "zero_overflows": runs["auto"]["overflow_total"] == 0
                and runs["auto"]["skipped_steps"] == 0,
            "telemetry_overhead_lt_10pct": bool(overhead < 0.10),
        },
    }
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--resolution", type=int, default=32)
    ap.add_argument("--interval", type=int, default=5)
    args = ap.parse_args()

    jax.config.update("jax_platform_name", "cpu")
    report = run(args.steps, args.resolution, args.interval)
    write_result(RESULTS, report)

    print(f"\n== bench_autoprec (steps={args.steps}, "
          f"res={args.resolution}) ==")
    for name, r in report["runs"].items():
        print(f"  {name:<16s} final_loss={r['final_loss']:.4f} "
              f"step={r['median_step_s']*1e3:.1f}ms")
    a = report["runs"]["auto"]
    print(f"  auto policy={a['policy']} decisions={a['decisions']}")
    print(f"  steps_to_stable={a['steps_to_stable']} "
          f"demoted_fraction={a['demoted_fraction']:.2f}")
    print(f"  auto vs bf16 loss: {report['auto_vs_bf16_loss_rel']:+.2%}")
    print(f"  telemetry overhead: {report['telemetry_overhead']:+.2%}")
    print(f"  acceptance: {report['acceptance']}")
    print(f"results -> {RESULTS}")
    return 0 if all(report["acceptance"].values()) else 1


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    raise SystemExit(main())
