"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Usage:
    PYTHONPATH=src python -m benchmarks.run [--only substring]
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from . import bench_paper_tables as bp

    print("name,us_per_call,derived")
    failures = 0
    for fn in bp.ALL:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{fn.__name__},0.0,ERROR")
    # roofline summary (reads dry-run artifacts if present)
    try:
        from .roofline_report import rows
        for r in rows():
            print("roofline_" + r[0] + "_" + r[1] + ",0.0," + " ".join(map(str, r[2:])))
    except Exception:
        traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
