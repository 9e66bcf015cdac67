"""Serve throughput: one-token-per-tick vs chunked batched prefill.

The old engine teacher-forced one prompt token per engine tick; the v2
``LMEngine`` consumes up to ``prefill_chunk`` pending tokens per tick
through the fused ``lm_prefill_chunk`` step.  This cell drives identical
request streams through both settings at the assigned LM configs (smoke
shapes — CPU container) and records ticks + wall time + tokens/s:

    PYTHONPATH=src python -m benchmarks.bench_serve

Results land in ``benchmarks/results/serve_prefill.json``; greedy
generations are asserted identical across chunk settings, so the
recorded speedup is numerics-free.
"""
from __future__ import annotations

import os

import jax
import numpy as np

from repro.configs import get_config
from repro.models.lm import init_lm
from repro.serve import LMEngine, Request

RESULTS = os.path.join(os.path.dirname(__file__), "results",
                       "serve_prefill.json")

ARCHS = ("smollm-360m", "mamba2-370m")
CHUNKS = (1, 8)
N_REQUESTS = 8
N_SLOTS = 2
PROMPT_LEN = 24
MAX_NEW = 4
MAX_LEN = 64


def _requests(vocab: int):
    rng = np.random.RandomState(0)
    return [
        Request(uid=i, prompt=list(rng.randint(1, vocab, PROMPT_LEN)),
                max_new_tokens=MAX_NEW)
        for i in range(N_REQUESTS)
    ]


def bench_arch(arch: str) -> dict:
    cfg = get_config(arch, smoke=True)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rows = {}
    generations = {}
    for chunk in CHUNKS:
        engine = LMEngine(params, cfg, n_slots=N_SLOTS, max_len=MAX_LEN,
                          prefill_chunk=chunk)
        # warm both compiled steps, then zero every counter the recorded
        # row reads so warmup traffic never contaminates the measurement
        warm, _ = engine.run_until_done(
            [Request(uid=-1, prompt=[1] * (chunk + 1), max_new_tokens=2)])
        assert all(r.done for r in warm)
        engine.reset_counters()
        done, ticks = engine.run_until_done(_requests(cfg.vocab))
        assert len(done) == N_REQUESTS and all(r.done for r in done)
        generations[chunk] = {r.uid: list(r.generated) for r in done}
        s = engine.stats()
        rows[str(chunk)] = {
            "ticks": ticks,
            "wall_s": s["wall_s"],
            "prompt_tokens": s["prompt_tokens"],
            "tokens_generated": s["tokens_generated"],
            "tokens_per_s": s["tokens_per_s"],
            "prefill_ticks": s["prefill_ticks"],
            "decode_ticks": s["decode_ticks"],
        }
    # chunking must not change greedy generations
    assert generations[CHUNKS[0]] == generations[CHUNKS[-1]], generations
    base, best = rows[str(CHUNKS[0])], rows[str(CHUNKS[-1])]
    return {
        "arch": arch,
        "n_requests": N_REQUESTS,
        "n_slots": N_SLOTS,
        "prompt_len": PROMPT_LEN,
        "max_new_tokens": MAX_NEW,
        "by_chunk": rows,
        "tick_speedup": round(base["ticks"] / best["ticks"], 2),
        "wall_speedup": round(base["wall_s"] / best["wall_s"], 2)
        if best["wall_s"] else None,
    }


def main():
    from benchmarks.common import write_result

    recs = [bench_arch(a) for a in ARCHS]
    write_result(RESULTS, {"records": recs})
    print("arch,chunk,ticks,wall_s,tokens_per_s")
    for r in recs:
        for chunk, row in r["by_chunk"].items():
            print(f"{r['arch']},{chunk},{row['ticks']},{row['wall_s']},"
                  f"{row['tokens_per_s']}")
        print(f"# {r['arch']}: {r['tick_speedup']}x fewer ticks, "
              f"{r['wall_speedup']}x wall-clock")
    print(f"-> {RESULTS}")


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
