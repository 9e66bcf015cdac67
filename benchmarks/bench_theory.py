"""Fig. 7 reproduction: measured discretisation/precision error vs the
Thm 3.1 / Thm 3.2 closed-form bounds on Darcy-like random fields.

This is the benchmark ``repro.core.theory``'s docstring promises.  It
reuses the certification harness (:mod:`repro.autoprec.certify`): a
smooth random Fourier field with *analytic* sup-norm and Lipschitz
bounds stands in for the paper's Darcy fields, so the bounds are
evaluated with their true constants rather than estimates.

Per mesh size ``m`` (n = m^d lattice points):
  * measured disc error (Eq. 1, reference integral on an 8x finer grid)
    against ``c2 √d (M|ω|+L) n^{-1/d}`` (upper) and the ``n^{-2/d}``
    lower rate;
  * measured precision error (Eq. 2) per format — fp16 via the real
    numpy cast, bf16/fp8 via the (a0, ε, T)-system quantiser — against
    ``4 ε M``, which is mesh-independent: the paper's crossover argument
    in one table.

    PYTHONPATH=src python -m benchmarks.bench_theory [--d 2]

Results land in ``benchmarks/results/theory_fig7.json``.
"""
from __future__ import annotations

import argparse
import os

from repro.autoprec.certify import theory_rows
from repro.core import theory

RESULTS = os.path.join(os.path.dirname(__file__), "results",
                       "theory_fig7.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=2)
    ap.add_argument("--omega", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--m", type=int, nargs="*", default=[6, 10, 16, 24])
    args = ap.parse_args()

    rows = theory_rows(seed=args.seed, d=args.d,
                       m_values=tuple(args.m), omega=args.omega)

    print(f"== bench_theory (d={args.d}, omega={args.omega}) ==")
    print(f"{'m':>4s} {'n':>7s} {'disc meas':>11s} {'disc upper':>11s} "
          f"{'fp16 prec':>11s} {'4εM fp16':>11s} {'bf16 prec':>11s}")
    violations = 0
    for r in rows:
        fp16, bf16 = r["prec"]["float16"], r["prec"]["bfloat16"]
        print(f"{r['m']:>4d} {r['n']:>7d} {r['disc_measured']:>11.3e} "
              f"{r['disc_upper']:>11.3e} {fp16['measured']:>11.3e} "
              f"{fp16['upper']:>11.3e} {bf16['measured']:>11.3e}")
        if r["disc_measured"] > r["disc_upper"]:
            violations += 1
        for p in r["prec"].values():
            if p["measured"] > p["upper"]:
                violations += 1

    # the paper's asymptotic claim: disc error shrinks with n, prec
    # error does not — beyond the crossover, half precision is "free".
    # Measured per-m errors can wiggle, so check the sweep endpoints.
    disc_monotone = rows[-1]["disc_measured"] < rows[0]["disc_measured"]
    crossover = theory.crossover_mesh_size(
        eps=2.0 ** -11, d=args.d, omega=args.omega)
    report = {
        "d": args.d,
        "omega": args.omega,
        "rows": rows,
        "bound_violations": violations,
        "disc_shrinks_with_n": disc_monotone,
        "crossover_mesh_size_fp16": crossover,
    }
    from benchmarks.common import write_result

    write_result(RESULTS, report)
    print(f"bound violations: {violations}  "
          f"(crossover n* for fp16, d={args.d}: {crossover:.3e})")
    print(f"results -> {RESULTS}")
    return 1 if violations else 0


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    raise SystemExit(main())
