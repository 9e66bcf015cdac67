#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python bench/run.py --workload fno_darcy.train --seed 7 --seconds 20 \
        --trace 0

One process, one cell, in this order: check that JAX sees a TPU whose
``device_kind`` is in ``bench/peaks.json`` (else exit 3, no result);
point JAX's persistent compilation cache into the checkout; build the
weights on the device and the cell's data from ``--seed``; warm up the
cell's own shapes; measure for ``--seconds``; read the peak device
memory; free the program's state and run the correctness comparison;
print one JSON result as the last line of standard output.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
traces the window with the profiler and reports its per-layer metrics.
Every run prints each number compared beside its limit, as the last
lines of standard error and under ``check`` in the result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class CompileCounter:
    """Counts the programs lowered and compiled while registered."""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.lowered = self.compiled = 0

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self._LOWER:
            self.lowered += 1
        elif event == self._COMPILE:
            self.compiled += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


def log(msg: str) -> None:
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def measure_cell(cell: dict, seed: int, seconds: float, traced: bool,
                 chips: int, peaks: dict) -> dict:
    """Set-up, window, peak memory, comparison: everything after the
    device check.  Returns the pieces of the result line.

    The traffic file's ``kind`` names the harness module that runs the
    cell (``bench/harness/<kind>.py``): ``build``, ``measure`` (which
    reports its end-to-end values under ``metrics``, by name),
    ``release``, ``readings`` and, optionally, ``kernel_calls``."""
    from harness import check, device, layers, registry, trace

    kind = cell["traffic"]["kind"]
    run = importlib.import_module(f"harness.{kind}")
    with CompileCounter() as comp:
        sess = run.build(cell, seed, seconds)
        setup_s = time.perf_counter() - _T_START
        lowered0, compiled0 = comp.lowered, comp.compiled
        record = None
        if traced:
            with trace.capture() as record:
                window = run.measure(sess, seconds)
        else:
            window = run.measure(sess, seconds)
        in_window = (comp.lowered - lowered0, comp.compiled - compiled0)
    stats = device.memory_stats(chips)
    calls = None
    if traced and hasattr(run, "kernel_calls"):
        calls = run.kernel_calls(sess)
    run.release(sess)
    t_ref = time.perf_counter()
    readings = run.readings(sess)
    ref_s = time.perf_counter() - t_ref
    correct, checked = check.judge(readings, cell["limits"])
    # a step that went non-finite, or an answer that is, is wrong
    correct = correct and window["failed"] == 0
    out = {"setup_s": setup_s, "window": window, "memory_stats": stats,
           "memory_peak_bytes": device.memory_peak_bytes(stats),
           "hbm_peak_bytes": device.hbm_peak_bytes(stats),
           "in_window": in_window, "readings": readings, "correct": correct,
           "checked": checked, "reference_s": ref_s, "kind": kind,
           "flops_per_sample": 3.0 * registry.load_module(
               "models", cell["config"]["family"]).forward_flops(
                   cell["config"])}
    out["values"] = {"setup_s": setup_s,
                     "peak_hbm_gib": out["hbm_peak_bytes"] / 2 ** 30,
                     **window["metrics"]}
    if traced:
        red = trace.Reduction(record, chips)
        out["record"] = record
        out["reduction"] = red
        out["per_layer"] = layers.Run(kind, red, window,
                                      out["flops_per_sample"], peaks, calls)
    return out


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(BENCH))
    from harness import boot, device, registry

    if not boot.program_present():
        print(f"bench: no program under {ROOT / 'src'}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    bench = registry.manifest()
    cell = registry.cell(args.workload, bench)
    chips = cell["workload"]["chips"]
    try:
        dev = boot.start(chips)
    except device.NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 3

    from harness import layers
    from repro.launch.compile_cache import CacheCounter

    with CacheCounter() as cache:
        r = measure_cell(cell, args.seed, args.seconds, bool(args.trace),
                         chips, dev["peaks"])
    w = r["window"]
    log(f"cell {args.workload} seed {args.seed}: set-up {r['setup_s']:.3f} s; "
        f"window {w['window_s']:.3f} s; compile cache: "
        f"{cache.counts['hits']} hits, {cache.counts['misses']} misses; "
        f"in the window {r['in_window'][0]} programs lowered, "
        f"{r['in_window'][1]} compiled; reference {r['reference_s']:.1f} s")
    log("window: " + json.dumps({k: v for k, v in w.items()
                                 if k != "engine"}))
    if "engine" in w:
        log("engine: " + json.dumps(w["engine"], default=str))
    log("readings: " + json.dumps(r["readings"]))
    log("memory: " + json.dumps(r["memory_stats"]))

    device_out = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"],
                  "memory_peak_bytes": r["memory_peak_bytes"]}
    result = {"correct": r["correct"], "attempted": w["attempted"],
              "failed": w["failed"]}
    if args.trace:
        red = r["reduction"]
        metrics = layers.read(registry.per_layer(args.workload, bench),
                              r["per_layer"])
        device_out.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
        if r["per_layer"].kernel_calls is not None:
            from harness import kernels

            calls = r["per_layer"].kernel_calls
            log("kernel calls per step: " + json.dumps(
                {**kernels.price(calls),
                 "least": kernels.least_seconds(calls, dev["peaks"])}))
    else:
        metrics = {m["name"]: {"value": r["values"].get(m["name"]),
                               "unit": m["unit"]}
                   for m in registry.end_to_end(args.workload, bench)}
    for k, v in metrics.items():
        if v["value"] is None or not math.isfinite(v["value"]):
            print(f"bench: {k} has no finite value", file=sys.stderr,
                  flush=True)
            v["value"] = None
    result["metrics"] = metrics
    result["device"] = device_out
    result["check"] = r["checked"]
    for name, c in r["checked"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
