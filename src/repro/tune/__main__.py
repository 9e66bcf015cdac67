"""``python -m repro.tune`` — tune / validate / report.

  tune      enumerate legal tiles per (family, shape, dtype) key, time
            each candidate on the backend, gate the fastest through the
            einsum oracle, persist winners to the calibration state
            (atomic write).  ``--smoke`` shrinks shapes and candidate
            counts so CI exercises the full loop in interpret mode.
  validate  re-run the oracle gate over every entry of an existing
            state file; exit 1 if any entry fails (``--prune`` rewrites
            the file without the failures).  ``--perturb X`` injects a
            scaled violation first — the self-check proving the gate
            rejects wrong kernels rather than passing vacuously.
  report    human-readable table: tiles, walls, GB/s, roofline
            fraction, validation + staleness per entry.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax

from . import cache as cache_mod
from . import oracle, space
from .cache import CalibrationCache, CalibrationError
from .measure import measure
from repro.kernels.spectral_contract import KERNEL_VERSION, resolve_interpret

DEFAULT_STATE = os.path.join("benchmarks", "results",
                             "calibration_state.json")

#: production tuning keys: the bench_kernels cases plus the SFNO
#: l-shared family, at the registry policies' half storage dtype
DEFAULT_KEYS = (
    ("dense", (4, 32, 32, 144), "bfloat16"),
    ("dense-fused", (4, 32, 32, 144), "bfloat16"),
    ("dense", (2, 16, 16, 216), "bfloat16"),
    ("cp", (4, 32, 32, 16, 144), "bfloat16"),
    ("lshared", (2, 8, 8, 12, 9), "bfloat16"),
    # the fused megakernel at bench_kernels' fused-2d case:
    # (B, I, O, *spatial, *modes)
    ("spectral_fused", (4, 16, 16, 24, 24, 6, 6), "bfloat16"),
)

#: CI smoke keys: tiny shapes, every family still covered
SMOKE_KEYS = (
    ("dense", (2, 8, 8, 40), "bfloat16"),
    ("dense-fused", (2, 8, 8, 40), "bfloat16"),
    ("cp", (2, 8, 8, 4, 40), "bfloat16"),
    ("lshared", (2, 8, 8, 12, 9), "bfloat16"),
    ("spectral_fused", (2, 4, 4, 12, 9, 3, 3), "bfloat16"),
)


def _state_path(args) -> str:
    return (args.state or os.environ.get(cache_mod.ENV_VAR)
            or DEFAULT_STATE)


def _entry_from(cand: space.Candidate, perf: dict, verdict: dict,
                backend: str) -> dict:
    return {
        "family": cand.family,
        "shape": list(cand.shape),
        "dtype": cand.dtype,
        "backend": backend,
        "kernel_version": KERNEL_VERSION,
        "block_fwd": cand.block_fwd,
        "block_bwd": cand.block_bwd,
        "wall_us": round(perf["wall_us"], 2),
        "bytes_moved": perf["bytes_moved"],
        "gbps": perf["gbps"],
        "roofline_fraction": perf["roofline_fraction"],
        "interpret": perf["interpret"],
        "validated": True,
        "max_err": verdict["max_err"],
        "budget_min": verdict["budget_min"],
    }


def cmd_tune(args) -> int:
    interpret = resolve_interpret(args.interpret)
    backend = jax.default_backend()
    keys = SMOKE_KEYS if args.smoke else DEFAULT_KEYS
    limit = args.limit if args.limit is not None else (4 if args.smoke
                                                      else None)
    iters = args.iters if args.iters is not None else (1 if args.smoke
                                                      else 5)
    path = _state_path(args)
    try:
        state = cache_mod.load(path)
    except CalibrationError:
        state = CalibrationCache(entries={}, backend=backend)
    state.kernel_version = KERNEL_VERSION
    state.backend = backend

    n_admitted = 0
    for family, shape, dtype in keys:
        cands = space.candidates(family, shape, dtype, limit=limit)
        timed = []
        for c in cands:
            perf = measure(c, interpret=interpret, iters=iters,
                           warmup=args.warmup, seed=args.seed)
            timed.append((perf["wall_us"], c, perf))
            print(f"  {family} {tuple(shape)} {dtype} "
                  f"fwd={c.block_fwd} bwd={c.block_bwd}: "
                  f"{perf['wall_us']:.1f} us  {perf['gbps']:.2f} GB/s")
        timed.sort(key=lambda t: t[0])
        # admission: fastest candidate that survives the oracle gate.
        # A candidate failing the Thm 3.2 budget is never written — a
        # mistuned-but-wrong kernel is unrepresentable in the cache.
        admitted = None
        for wall, c, perf in timed:
            verdict = oracle.check(c, interpret=interpret, seed=args.seed)
            if verdict["passed"]:
                admitted = (c, perf, verdict)
                break
            print(f"  REFUSED fwd={c.block_fwd} bwd={c.block_bwd}: "
                  f"max_err {verdict['max_err']:.3e} exceeds budget "
                  f"(worst excess {verdict['worst_excess']:.3e})")
        if admitted is None:
            print(f"  {family} {tuple(shape)} {dtype}: no candidate "
                  f"passed the oracle — key left uncalibrated",
                  file=sys.stderr)
            continue
        c, perf, verdict = admitted
        state.put(_entry_from(c, perf, verdict, backend))
        n_admitted += 1
        print(f"  ADMIT {family} {tuple(shape)} {dtype}: "
              f"fwd={c.block_fwd} bwd={c.block_bwd} "
              f"({perf['wall_us']:.1f} us, max_err "
              f"{verdict['max_err']:.3e} <= budget)")
    out = cache_mod.save(state, path)
    print(f"wrote {n_admitted} calibrated entr"
          f"{'y' if n_admitted == 1 else 'ies'} -> {out}")
    return 0 if n_admitted else 1


def _cand_of(ent: dict) -> space.Candidate:
    return space.Candidate(
        family=ent["family"], shape=tuple(ent["shape"]),
        dtype=ent["dtype"], block_fwd=int(ent["block_fwd"]),
        block_bwd=int(ent["block_bwd"]))


def cmd_validate(args) -> int:
    path = _state_path(args)
    try:
        state = cache_mod.load(path)
    except CalibrationError as e:
        print(f"validate: {e}", file=sys.stderr)
        return 2
    interpret = resolve_interpret(args.interpret)
    failures, stale, checked = [], [], 0
    for key, ent in sorted(state.entries.items()):
        if not cache_mod._entry_ok(ent):
            failures.append((key, "corrupt entry (structural)"))
            continue
        if ent.get("kernel_version") != KERNEL_VERSION:
            stale.append((key, f"kernel_version {ent.get('kernel_version')}"
                               f" != {KERNEL_VERSION}"))
            continue
        verdict = oracle.check(_cand_of(ent), interpret=interpret,
                               seed=args.seed, perturb=args.perturb)
        checked += 1
        if not verdict["passed"]:
            failures.append(
                (key, f"max_err {verdict['max_err']:.3e} exceeds the "
                      f"Thm 3.2 budget (worst excess "
                      f"{verdict['worst_excess']:.3e})"))
    for key, why in stale:
        print(f"STALE  {key}: {why} (entry is never served)")
    for key, why in failures:
        print(f"REJECT {key}: {why}")
    print(f"validate: {checked} checked, {len(failures)} rejected, "
          f"{len(stale)} stale")
    if failures and args.prune:
        for key, _ in failures:
            state.entries.pop(key, None)
        cache_mod.save(state, path)
        print(f"pruned {len(failures)} entries -> {path}")
    return 1 if failures else 0


def _pct(fraction) -> str:
    return "n/a" if fraction is None else f"{100 * fraction:.1f}%"


def cmd_report(args) -> int:
    path = _state_path(args)
    try:
        state = cache_mod.load(path)
    except CalibrationError as e:
        print(f"report: {e}", file=sys.stderr)
        return 2
    backend = jax.default_backend()
    print(f"calibration state: {path}")
    print(f"  format {cache_mod.FORMAT_VERSION}, tuned at kernel_version "
          f"{state.kernel_version} on backend {state.backend!r} "
          f"(current: {KERNEL_VERSION} on {backend!r})")
    hdr = (f"{'key':<42} {'fwd':>4} {'bwd':>4} {'wall_us':>9} "
           f"{'GB/s':>8} {'roof%':>6} {'ok':>3}")
    print(hdr)
    print("-" * len(hdr))
    for key, ent in sorted(state.entries.items()):
        live = (cache_mod._entry_ok(ent)
                and ent.get("validated", False)
                and ent.get("kernel_version") == KERNEL_VERSION
                and ent.get("backend") == backend)
        flag = "ok" if live else "--"
        print(f"{key:<42} {ent.get('block_fwd', '?'):>4} "
              f"{ent.get('block_bwd', '?'):>4} "
              f"{ent.get('wall_us', float('nan')):>9.1f} "
              f"{ent.get('gbps', float('nan')):>8.2f} "
              f"{_pct(ent.get('roofline_fraction')):>6} "
              f"{flag:>3}")
    if args.json:
        print(json.dumps(state.to_json(), indent=2, sort_keys=True))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m repro.tune",
                                description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--state", default=None,
                        help=f"calibration-state path (default: "
                             f"${cache_mod.ENV_VAR} or {DEFAULT_STATE})")
        sp.add_argument("--seed", type=int, default=0)
        g = sp.add_mutually_exclusive_group()
        g.add_argument("--interpret", dest="interpret",
                       action="store_true", default=None,
                       help="force interpret mode (default: auto — "
                            "interpret off TPU)")
        g.add_argument("--no-interpret", dest="interpret",
                       action="store_false")

    t = sub.add_parser("tune", help="search, time, gate, persist")
    common(t)
    t.add_argument("--smoke", action="store_true",
                   help="tiny shapes + capped candidates (CI)")
    t.add_argument("--iters", type=int, default=None,
                   help="timing samples per candidate (median)")
    t.add_argument("--warmup", type=int, default=1)
    t.add_argument("--limit", type=int, default=None,
                   help="cap candidates per key")
    t.set_defaults(fn=cmd_tune)

    v = sub.add_parser("validate", help="re-run the oracle over a state")
    common(v)
    v.add_argument("--perturb", type=float, default=0.0,
                   help="inject a scaled budget violation (self-check; "
                        ">1 must reject every entry)")
    v.add_argument("--prune", action="store_true",
                   help="rewrite the state without rejected entries")
    v.set_defaults(fn=cmd_validate)

    r = sub.add_parser("report", help="print the state as a table")
    common(r)
    r.add_argument("--json", action="store_true")
    r.set_defaults(fn=cmd_report)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
