"""PDE-inference-as-a-service demo: micro-batched mixed-precision FNO
serving through the same Engine API as the LM demo.

Submits Darcy-style coefficient fields at two resolutions; the
``OperatorEngine`` buckets them by grid, pads each micro-batch to a
fixed width (one compiled kernel per resolution), and runs the batched
``fno_infer`` under the requested precision rule set.  Batched outputs
are verified bit-identical against a solo run — micro-batching is a
pure throughput knob:

    PYTHONPATH=src python examples/serve_darcy.py --policy mixed_fno_bf16
"""
import argparse
import json

import jax
import numpy as np

from repro.configs.fno_paper import FNO_DARCY_SMOKE
from repro.core import get_policy
from repro.data import grf_2d
from repro.models import init_fno
from repro.serve import FieldRequest, OperatorEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="mixed_fno_bf16")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--scheduler", default="fcfs", choices=["fcfs", "spf"])
    args = ap.parse_args()

    cfg = FNO_DARCY_SMOKE
    policy = get_policy(args.policy)
    params = init_fno(jax.random.PRNGKey(0), cfg)
    engine = OperatorEngine(params, cfg, model="fno", policy=policy,
                            max_batch=args.max_batch,
                            scheduler=args.scheduler)

    key = jax.random.PRNGKey(1)
    reqs = []
    for i in range(args.requests):
        key, k = jax.random.split(key)
        n = 16 if i % 2 else 32   # two resolution buckets
        a = np.asarray(grf_2d(k, n, batch=1))          # (1, n, n) coeff field
        reqs.append(FieldRequest(uid=i, x=a))
    for r in reqs:
        engine.submit(r)
    done, ticks = engine.drain()
    stats = engine.stats()
    print(f"policy={args.policy} max_batch={args.max_batch}: served "
          f"{stats['fields_served']} fields in {ticks} ticks "
          f"({stats['fields_per_s']} fields/s on CPU); "
          f"buckets={stats['buckets']}")

    # micro-batching is bit-exact: replay one request through a fresh engine
    probe = done[0]
    solo = OperatorEngine(params, cfg, model="fno", policy=policy,
                          max_batch=args.max_batch)
    sr = FieldRequest(uid=0, x=probe.x)
    solo.submit(sr)
    solo.drain()
    assert np.array_equal(sr.y, probe.y), "batched != solo"
    print("batched == solo: bit-identical")
    print("stats:", json.dumps(stats, indent=1))


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
