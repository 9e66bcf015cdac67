"""Render the roofline table from benchmarks/results/dryrun.json."""
from __future__ import annotations

import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun.json")


def rows(mesh: str = "16x16"):
    if not os.path.exists(RESULTS):
        return []
    with open(RESULTS) as f:
        data = json.load(f)
    out = []
    for r in sorted(data, key=lambda r: (r["arch"], r["shape"])):
        if r["mesh"] != mesh:
            continue
        if r.get("status") == "skipped":
            out.append((r["arch"], r["shape"], "SKIP", r.get("reason", "")))
            continue
        if r.get("status") != "ok":
            out.append((r["arch"], r["shape"], "FAIL", r.get("error", "")[:60]))
            continue
        roof = r["roofline"]
        mf = r.get("model_flops_6nd")
        useful = r.get("useful_flops_ratio")
        out.append((
            r["arch"], r["shape"], roof["dominant"],
            f"compute={roof['compute_s']:.3g}s memory={roof['memory_s']:.3g}s "
            f"collective={roof['collective_s']:.3g}s"
            + (f" useful6ND={useful:.2f}" if useful else ""),
        ))
    return out


def main():
    print("arch,shape,dominant,terms")
    for r in rows():
        print(",".join(str(x) for x in r))


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
