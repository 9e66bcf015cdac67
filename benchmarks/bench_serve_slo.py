"""SLO replay harness for the paged serving stack (CORTEX-style).

Drives a timed request trace — Poisson arrivals over shared prompt
templates, or a replayed ``--trace`` file — through the async frontend
against BOTH the paged and the dense LM engine, and records the full
latency distribution (p50/p90/p99, mean, max), jitter (latency stddev),
deadline-miss rate, and the paged-only wins: prefix-hit rate, prefill
tokens skipped, and peak physical blocks vs the dense per-slot backing.

    PYTHONPATH=src python -m benchmarks.bench_serve_slo --smoke
    PYTHONPATH=src python -m benchmarks.bench_serve_slo \
        --save-trace /tmp/trace.json
    PYTHONPATH=src python -m benchmarks.bench_serve_slo \
        --trace /tmp/trace.json

Arrivals are wall-clock: the replay sleeps each request until its trace
timestamp before submitting, so the engine sees the trace's actual
burstiness.  Greedy generations are asserted identical between the two
engines, so every recorded delta is scheduling/memory, not numerics.
Results land in ``benchmarks/results/serve_slo.json``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os

import jax
import numpy as np

from repro.configs import get_config
from repro.models.lm import init_lm
from repro.serve import AsyncServeFrontend, LMEngine, PagedLMEngine, Request

RESULTS = os.path.join(os.path.dirname(__file__), "results",
                       "serve_slo.json")


def make_trace(n_requests: int, rate_hz: float, n_templates: int,
               template_len: int, suffix_len: int, max_new: int,
               deadline_ms: float, vocab: int, seed: int = 0) -> dict:
    """Poisson arrivals over a small pool of shared prompt templates.

    Real serving traffic repeats system prompts / few-shot headers; the
    template pool models that, so the paged engine's prefix index has
    something to hit while the dense engine re-prefills every time.
    """
    rng = np.random.RandomState(seed)
    templates = [list(map(int, rng.randint(1, vocab, template_len)))
                 for _ in range(n_templates)]
    t, items = 0.0, []
    for uid in range(n_requests):
        t += float(rng.exponential(1.0 / rate_hz))
        prompt = (templates[int(rng.randint(n_templates))]
                  + list(map(int, rng.randint(1, vocab, suffix_len))))
        items.append({"t": round(t, 6), "uid": uid, "prompt": prompt,
                      "max_new_tokens": max_new,
                      "deadline_ms": deadline_ms})
    return {"rate_hz": rate_hz, "n_templates": n_templates,
            "template_len": template_len, "items": items}


async def _replay(front: AsyncServeFrontend, trace: dict) -> dict:
    """Submit every trace item at its wall-clock arrival offset."""
    loop = asyncio.get_running_loop()
    t0 = loop.time()

    async def one(item):
        delay = item["t"] - (loop.time() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        req = Request(uid=item["uid"], prompt=list(item["prompt"]),
                      max_new_tokens=item["max_new_tokens"])
        return await front.submit_async(req,
                                        deadline_ms=item["deadline_ms"])
    done = await asyncio.gather(*[one(it) for it in trace["items"]])
    return {r.uid: list(r.generated) for r in done}


def run_engine(kind: str, trace: dict, cfg, params, n_slots: int,
               max_len: int, prefill_chunk: int, block_size: int) -> dict:
    if kind == "paged":
        engine = PagedLMEngine(params, cfg, n_slots=n_slots, max_len=max_len,
                               prefill_chunk=prefill_chunk,
                               block_size=block_size)
    else:
        engine = LMEngine(params, cfg, n_slots=n_slots, max_len=max_len,
                          prefill_chunk=prefill_chunk)
    # compile outside the timed replay (jit warmup would otherwise land
    # entirely on the first request's latency)
    warm, _ = engine.run_until_done(
        [Request(uid=-1, prompt=[1] * (prefill_chunk + 1),
                 max_new_tokens=2)])
    assert all(r.done for r in warm)
    front = AsyncServeFrontend(engine)
    generations = asyncio.run(_replay(front, trace))
    stats = engine.stats()
    row = {
        "slo": front.metrics(),
        "prefill_tokens": stats["prompt_tokens"],
        "tokens_generated": stats["tokens_generated"],
        "ticks": stats["ticks"],
    }
    if kind == "paged":
        paged = stats["paged"]
        row["blocks"] = {
            "block_size": paged["block_size"],
            "peak_live_blocks": paged["peak_live_blocks"],
            "dense_equivalent_blocks": n_slots * paged["blocks_per_slot"],
            "cow_copies": paged["cow_copies"],
            "fragmentation": paged["fragmentation"],
        }
        row["prefix"] = paged["prefix"]
    return row, generations


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--n-requests", type=int, default=24)
    ap.add_argument("--rate-hz", type=float, default=8.0)
    ap.add_argument("--n-templates", type=int, default=3)
    ap.add_argument("--template-len", type=int, default=24)
    ap.add_argument("--suffix-len", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--deadline-ms", type=float, default=2000.0)
    ap.add_argument("--n-slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", help="replay this trace JSON instead of "
                    "generating Poisson arrivals")
    ap.add_argument("--save-trace", help="write the generated trace here "
                    "(for later --trace replay) and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized run (few requests, short prompts)")
    ap.add_argument("--out", default=RESULTS)
    args = ap.parse_args(argv)

    if args.smoke:
        args.n_requests, args.rate_hz = 8, 50.0
        args.template_len, args.suffix_len, args.max_new = 16, 2, 3
        args.max_len, args.deadline_ms = 32, 5000.0

    cfg = get_config(args.arch, smoke=True)
    if args.trace:
        with open(args.trace) as f:
            trace = json.load(f)
    else:
        trace = make_trace(args.n_requests, args.rate_hz, args.n_templates,
                           args.template_len, args.suffix_len, args.max_new,
                           args.deadline_ms, vocab=cfg.vocab,
                           seed=args.seed)
    if args.save_trace:
        with open(args.save_trace, "w") as f:
            json.dump(trace, f)
        print(f"wrote {len(trace['items'])} arrivals -> {args.save_trace}")
        return trace

    params = init_lm(jax.random.PRNGKey(0), cfg)
    run = lambda kind: run_engine(  # noqa: E731
        kind, trace, cfg, params, args.n_slots, args.max_len,
        args.prefill_chunk, args.block_size)
    paged_row, paged_gen = run("paged")
    dense_row, dense_gen = run("dense")
    assert paged_gen == dense_gen, \
        "paged generations diverged from dense — numerics bug"

    out = {
        "arch": args.arch,
        "trace": {"n_requests": len(trace["items"]),
                  "rate_hz": trace.get("rate_hz"),
                  "n_templates": trace.get("n_templates"),
                  "deadline_ms": args.deadline_ms,
                  "replayed_from": args.trace},
        "engine": {"n_slots": args.n_slots, "max_len": args.max_len,
                   "prefill_chunk": args.prefill_chunk,
                   "block_size": args.block_size},
        "paged": paged_row,
        "dense": dense_row,
        "comparison": {
            "bit_identical_generations": True,
            "prefill_tokens_saved": (dense_row["prefill_tokens"]
                                     - paged_row["prefill_tokens"]),
            # block writes the prefix index turned into shared references
            "blocks_saved": (paged_row["prefix"]["tokens_reused"]
                             // args.block_size),
            "prefix_hit_rate": paged_row["prefix"]["hit_rate"],
        },
    }
    from benchmarks.common import write_result

    write_result(args.out, out)
    c = out["comparison"]
    print(f"p50 {paged_row['slo']['latency_ms']['p50']}ms  "
          f"p99 {paged_row['slo']['latency_ms']['p99']}ms  "
          f"jitter {paged_row['slo']['jitter_ms']}ms  "
          f"miss {paged_row['slo']['deadline_miss_rate']}")
    print(f"prefix hit rate {c['prefix_hit_rate']}  "
          f"prefill tokens saved {c['prefill_tokens_saved']}  "
          f"blocks saved {c['blocks_saved']}")
    print(f"wrote {args.out}")
    return out


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
