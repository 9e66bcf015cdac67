"""End-to-end driver: train a mixed-precision FNO on Darcy flow.

Generates the dataset with the in-repo CG solver, trains with the paper's
precision schedule (25% mixed / 50% AMP / 25% full), dynamic loss scaling
where fp16 is involved, checkpoints/restarts, and evaluates zero-shot
super-resolution — the full Table 1 protocol at CPU scale.

    PYTHONPATH=src python examples/train_darcy.py [--steps 60] [--n 32]
"""
import argparse
import tempfile

import jax
import numpy as np

from repro.core import FULL, PrecisionSchedule, get_policy
from repro.data import sample_darcy_batch
from repro.models import FNOConfig, fno_apply, init_fno
from repro.optim import AdamW
from repro.precision import FULL_PRECISION, precision_rules
from repro.train import Trainer, TrainerConfig, relative_l2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--half", default="bf16", choices=["bf16", "fp16"])
    ap.add_argument("--auto-precision", action="store_true",
                    help="replace the static 25/50/25 schedule with the "
                         "telemetry-driven controller: per-site formats "
                         "follow runtime amax/overflow counters plus the "
                         "Thm 3.1/3.2 budgets")
    ap.add_argument("--calibration-state", default=None,
                    help="repro.tune calibration-state JSON: spectral "
                         "tile resolution serves validated tuned tiles "
                         "instead of the static heuristic (default: "
                         "$REPRO_CALIBRATION_STATE if set)")
    ap.add_argument("--obs-trace", default=None, metavar="OUT_JSONL",
                    help="enable repro.obs and write the run timeline + "
                         "metrics snapshot as JSONL, plus a Perfetto-"
                         "loadable <stem>.trace.json and a Prometheus "
                         "<stem>.prom next to it; inspect with "
                         "`python -m repro.obs summary OUT_JSONL`")
    args = ap.parse_args()

    print("generating Darcy data (CG solver)...")
    a_tr, u_tr = sample_darcy_batch(jax.random.PRNGKey(0), args.n, 64, maxiter=400)
    a_te, u_te = sample_darcy_batch(jax.random.PRNGKey(1), args.n, 16, maxiter=400)
    a_hi, u_hi = sample_darcy_batch(jax.random.PRNGKey(2), args.n * 2, 8, maxiter=800)

    cfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=24,
                    lifting_channels=24, projection_channels=24,
                    n_layers=3, modes=(8, 8))
    params = init_fno(jax.random.PRNGKey(3), cfg)

    def loss_fn(p, batch, policy):
        pred = fno_apply(p, batch["a"], cfg, policy)
        return relative_l2(pred, batch["u"])

    def batch_fn(step):
        idx = np.random.RandomState(step).randint(0, a_tr.shape[0], 16)
        return {"a": a_tr[idx], "u": u_tr[idx]}

    with tempfile.TemporaryDirectory() as ckpt_dir:
        if args.auto_precision:
            # auto mode: telemetry taps measure per-site numerics inside
            # the jitted step; the controller demotes spectral sites only
            # while the Thm 3.2 budget stays inside the discretisation
            # error at this grid, and promotes back on overflow streaks
            from repro.autoprec import AutoPrecisionController

            autoprec = AutoPrecisionController(
                base="full", grid_points=args.n ** 2, interval=5)
            schedule = PrecisionSchedule.auto("full", grid_points=args.n ** 2)
        else:
            autoprec = None
            schedule = PrecisionSchedule.paper_default(args.half)
        tcfg = TrainerConfig(
            total_steps=args.steps,
            schedule=schedule, autoprec=autoprec,
            optimizer=AdamW(lr=2e-3, weight_decay=1e-5),
            ckpt_dir=ckpt_dir, ckpt_every=20,
            calibration_state=args.calibration_state,
            obs=args.obs_trace is not None,
        )
        trainer = Trainer(loss_fn, params, tcfg)
        trainer.install_preemption_handler()
        if args.auto_precision:
            print(f"training {args.steps} steps with bound-guided "
                  f"auto-precision (base=full)...")
        else:
            print(f"training {args.steps} steps with the paper schedule "
                  f"(25% mixed / 50% AMP / 25% full, half={args.half})...")
        hist = trainer.run(batch_fn)
        for h in hist[:: max(1, len(hist) // 8)]:
            print(f"  step {h['step']:4d} policy={h['policy']:<16s} loss={h['loss']:.4f}")
        if trainer.controller is not None:
            decisions = trainer.controller.describe()
            print("auto-precision decisions:",
                  {g: s["fmt"] for g, s in decisions["sites"].items()})
            counters = trainer.telemetry.counters()
            print(f"telemetry: {counters['steps']} steps, "
                  f"overflows={counters['overflow_total']:.0f}")

        # restart check
        t2 = Trainer(loss_fn, params, tcfg)
        assert t2.restore(), "checkpoint restore failed"
        print(f"restart OK from step {t2.step} (stats: {trainer.stats})")

        p_final = trainer.params
        e_test = float(relative_l2(fno_apply(p_final, a_te, cfg, FULL), u_te))
        e_super = float(relative_l2(fno_apply(p_final, a_hi, cfg, FULL), u_hi))
        print(f"test rel-L2 @ {args.n}x{args.n}:      {e_test:.4f}")
        print(f"zero-shot super-res @ {2*args.n}x{2*args.n}: {e_super:.4f}")

        # Per-site override: evaluate the paper's mixed pipeline with the
        # LAST FNO layer pinned to full precision — a per-layer precision
        # experiment the flat policy API could not express.  The scoped
        # rule takes precedence over the policy's own "*/spectral/*" rule.
        mixed = get_policy(f"mixed_fno_{args.half}")
        e_mixed = float(relative_l2(fno_apply(p_final, a_te, cfg, mixed), u_te))
        with precision_rules((f"fno/layer{cfg.n_layers - 1}/*", FULL_PRECISION)):
            e_lastfull = float(
                relative_l2(fno_apply(p_final, a_te, cfg, mixed), u_te))
        print(f"mixed eval rel-L2:                 {e_mixed:.4f}")
        print(f"mixed, last layer full (override): {e_lastfull:.4f}")

    if args.obs_trace:
        import os

        from repro.obs import (registry, run_records, trace,
                               write_chrome_trace, write_jsonl,
                               write_prometheus)

        recs = trace.snapshot()
        snap = registry().snapshot()
        write_jsonl(args.obs_trace,
                    run_records(recs, snapshot=snap,
                                run="train_darcy", steps=args.steps,
                                auto_precision=args.auto_precision))
        stem = os.path.splitext(args.obs_trace)[0]
        write_chrome_trace(stem + ".trace.json", recs)
        write_prometheus(stem + ".prom", snap)
        print(f"obs: {len(recs)} trace records -> {args.obs_trace} "
              f"(+ {stem}.trace.json, {stem}.prom); "
              f"render with `python -m repro.obs summary {args.obs_trace}`")


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
