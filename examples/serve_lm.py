"""Batched LM serving demo: the v2 Engine API over the slot engine.

Loads a reduced config from the architecture pool (selectable with
``--arch``; any of the 10 assigned ids), submits a stream of requests
through the scheduler (FCFS or shortest-prompt-first), and drives
chunked-prefill decoding with per-request sampling:

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-370m
    PYTHONPATH=src python examples/serve_lm.py --scheduler spf \\
        --temperature 0.8 --top-p 0.9 --prefill-chunk 16

``--paged`` swaps in the paged KV-block engine (``--block-size`` rows
per block, prefix sharing on); ``--slo-deadline-ms`` drives the run
through the async frontend with a per-request deadline and prints the
SLO accounting:

    PYTHONPATH=src python examples/serve_lm.py --paged --block-size 8 \\
        --slo-deadline-ms 250
"""
import argparse
import asyncio
import json

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.models.lm import init_lm
from repro.serve import (
    AsyncServeFrontend,
    LMEngine,
    PagedLMEngine,
    Request,
    SamplingParams,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--scheduler", default="fcfs", choices=["fcfs", "spf"])
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens consumed per tick "
                         "(default: auto — 8 dense, 1 MoE)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="serve over the paged KV-block cache "
                         "(COW + prefix sharing)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV rows per block (paged engine only)")
    ap.add_argument("--slo-deadline-ms", type=float, default=None,
                    help="drive requests through the async frontend with "
                         "this per-request deadline and report SLO metrics")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    if cfg.encoder_decoder:
        raise SystemExit("enc-dec serving demo: use whisper_decode_step directly")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    if args.paged:
        engine = PagedLMEngine(params, cfg, n_slots=args.slots, max_len=64,
                               scheduler=args.scheduler,
                               prefill_chunk=args.prefill_chunk,
                               seed=args.seed, block_size=args.block_size)
    else:
        engine = LMEngine(params, cfg, n_slots=args.slots, max_len=64,
                          scheduler=args.scheduler,
                          prefill_chunk=args.prefill_chunk, seed=args.seed)

    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p)
    rng = np.random.RandomState(0)
    reqs = [
        Request(uid=i, prompt=list(rng.randint(1, cfg.vocab, rng.randint(3, 8))),
                max_new_tokens=8, sampling=sampling)
        for i in range(args.requests)
    ]
    if args.slo_deadline_ms is not None:
        front = AsyncServeFrontend(engine)

        async def run_async():
            return await asyncio.gather(*[
                front.submit_async(r, deadline_ms=args.slo_deadline_ms)
                for r in reqs])

        done = asyncio.run(run_async())
        ticks = engine.stats()["ticks"]
        print("slo:", json.dumps(front.metrics(), indent=1))
    else:
        for r in reqs:
            engine.submit(r)
        done, ticks = engine.drain()
    stats = engine.stats()
    print(f"arch={args.arch} slots={args.slots} scheduler={args.scheduler} "
          f"chunk={engine.prefill_chunk}: served {stats['completed']} requests "
          f"in {ticks} ticks ({stats['wall_s']:.2f}s; "
          f"{stats['tokens_per_s']} tok/s on CPU)")
    for r in done[:3]:
        print(f"  req {r.uid}: prompt={r.prompt} -> generated={r.generated}")
    print("stats:", json.dumps(stats, indent=1))


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
