"""A serving cell: open-loop requests through ``OperatorEngine``.

Requests arrive on a seeded schedule (``traffic.arrivals``), each one
input field of one grid from a pool per grid made on the host
(``traffic.requests``).  One thread submits every request that is due,
ticks the engine while work waits, and sleeps until the next due time
otherwise.  The window closes after ``seconds``: the fields answered in
it, over its length, are the throughput, and what is still queued then
is late, not failed.  Each request's latency runs from when it was due,
not from when it was submitted.  A sample of the answered requests,
drawn from the seed as they finish, is kept for the comparison.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List

import numpy as np

from . import registry
from .traffic import arrivals, requests, rng


@dataclasses.dataclass
class CellRun:
    cell: dict
    seed: int
    engine: object
    fields: List[np.ndarray]      # the host pool of input fields, per grid
    due: np.ndarray               # due times of the window's requests
    grid_of: np.ndarray           # grid index of each request
    field_of: np.ndarray          # pool index of each request
    draws: np.ndarray             # uniform draws of the answer sample
    sample: list = dataclasses.field(default_factory=list)  # (uid, answer)


def build(cell: dict, seed: int, seconds: float) -> CellRun:
    from repro.core import get_policy
    from repro.serve import FieldRequest, OperatorEngine

    cfg, traffic = cell["config"], cell["traffic"]
    fam = registry.load_module("models", cfg["family"])
    data = registry.load_module("datasets", cfg["data"])
    params = fam.reference.init_params(seed, cfg)
    mix = traffic.get("grids") or [[None, 1.0]]
    grids = [tuple(g) if g else None for g, _ in mix]
    fields = [data.serving_fields(rng(seed, 3 + 100 * k), cfg,
                                  traffic["pool"], g)
              for k, g in enumerate(grids)]
    eng = OperatorEngine(params, fam.program_config(cfg),
                         model=fam.ENGINE_MODEL,
                         policy=get_policy(traffic["precision"]),
                         **traffic["engine"])
    due = arrivals(traffic["rate_per_s"], seconds, seed,
                   traffic.get("rate_phases"))
    grid_of, field_of = requests(
        len(due), traffic["pool"], seed, [s for _, s in mix],
        traffic.get("repeat_share", 0.0), traffic.get("repeat_lag", 64))
    # warm-up: each grid the traffic uses, at every fill a tick can have
    # (the engine stacks and pads on the device per fill count); negated
    # pool fields, which the traffic never sends, so no memo holds them
    for pool in fields:
        for fill in range(1, eng.max_batch + 1):
            for i in range(fill):
                eng.submit(FieldRequest(uid=-1 - i, x=-pool[i]))
            eng.drain()
    eng.reset_counters()
    return CellRun(cell, seed, eng, fields, due, grid_of, field_of,
                   rng(seed, 4).random(len(due)))


def measure(sess: CellRun, seconds: float) -> dict:
    from jax.profiler import TraceAnnotation

    from repro.serve import FieldRequest

    eng, due = sess.engine, sess.due
    k = sess.cell["traffic"]["check_sample"]
    n = len(due)
    lat: List[float] = []
    late = np.zeros(n)
    nxt = answered = bad = 0
    t0 = time.perf_counter()
    with TraceAnnotation("bench/window"):
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            if nxt < n and due[nxt] <= now:
                with TraceAnnotation("bench/generator"):
                    while nxt < n and due[nxt] <= now:
                        eng.submit(FieldRequest(uid=nxt, x=sess.fields[
                            sess.grid_of[nxt]][sess.field_of[nxt]]))
                        late[nxt] = now - due[nxt]
                        nxt += 1
            if eng.scheduler.depth:
                with TraceAnnotation("bench/tick"):
                    finished = eng.tick()
                t = time.perf_counter() - t0
                for r in finished:
                    lat.append(t - due[r.uid])
                    bad += not np.isfinite(r.y).all()
                    # reservoir sampling: each answered request is in the
                    # sample with the same chance, k / answered
                    if answered < k:
                        sess.sample.append((r.uid, r.y))
                    else:
                        j = int(sess.draws[answered] * (answered + 1))
                        if j < k:
                            sess.sample[j] = (r.uid, r.y)
                    answered += 1
            else:
                with TraceAnnotation("bench/wait"):
                    until = due[nxt] if nxt < n else seconds
                    gap = min(until, seconds) - (time.perf_counter() - t0)
                    if gap > 2e-3:
                        time.sleep(gap - 1e-3)
    wall = time.perf_counter() - t0
    lat_ms = np.asarray(lat) * 1e3 if lat else np.full(1, np.nan)
    return {
        "attempted": nxt,
        "failed": int(bad),
        "metrics": {"serve_fields_per_s": answered / wall},
        "answered": answered,
        "queued_at_close": nxt - answered,
        "window_s": wall,
        "answered_p50_ms": float(np.percentile(lat_ms, 50)),
        "answered_p95_ms": float(np.percentile(lat_ms, 95)),
        "generator_late_p95_ms": float(np.percentile(late[:nxt], 95) * 1e3)
        if nxt else 0.0,
        "generator_late_max_ms": float(late[:nxt].max() * 1e3)
        if nxt else 0.0,
        "engine": eng.stats(),
    }


def release(sess: CellRun) -> None:
    sess.engine = None
    gc.collect()


def reference_answers(cell: dict, seed: int, x: np.ndarray,
                      rounding: str = "float32") -> np.ndarray:
    import jax

    from reference import common

    cfg, traffic = cell["config"], cell["traffic"]
    fam = registry.load_module("models", cfg["family"])
    q = common.ROUNDINGS[rounding]
    rows = cfg["reference_rows"]
    with jax.default_matmul_precision("highest"):
        p = fam.reference.init_params(seed, cfg)
        fwd = jax.jit(lambda p, v: fam.reference.forward(
            p, v, cfg, traffic["pre_fft_tanh"], q))
        return np.concatenate([np.asarray(fwd(p, x[i:i + rows]))
                               for i in range(0, len(x), rows)])


def answer_gaps(answers: List[np.ndarray], ref: List[np.ndarray]) -> dict:
    """Each sampled answer's L2 distance from the reference's, over the
    larger of that reference field's norm and the median field's (an
    answer whose field is nearly zero would otherwise read its rounding
    as a large relative error): the worst and the median of them."""
    d = np.array([np.linalg.norm((np.asarray(a, np.float64) - b).ravel())
                  for a, b in zip(answers, ref, strict=True)])
    norms = np.array([np.linalg.norm(np.asarray(b, np.float64).ravel())
                      for b in ref])
    gaps = d / np.maximum(norms, np.median(norms))
    return {"field_gap": float(gaps.max()),
            "field_gap_median": float(np.median(gaps))}


def sample_inputs(sess: CellRun) -> List[np.ndarray]:
    return [sess.fields[sess.grid_of[u]][sess.field_of[u]]
            for u, _ in sess.sample]


def reference_for(sess: CellRun, x: List[np.ndarray],
                  rounding: str = "float32") -> List[np.ndarray]:
    """The reference's answer to each input, run grid by grid."""
    out: List[np.ndarray] = [None] * len(x)
    for shape in {v.shape for v in x}:
        at = [i for i, v in enumerate(x) if v.shape == shape]
        ys = reference_answers(sess.cell, sess.seed,
                               np.stack([x[i] for i in at]), rounding)
        for i, y in zip(at, ys, strict=True):
            out[i] = y
    return out


def readings(sess: CellRun) -> dict:
    """The sampled answers against the float32 reference; a window that
    answered nothing makes the numbers infinite."""
    if not sess.sample:
        return {"field_gap": float("inf"), "field_gap_median": float("inf")}
    ref = reference_for(sess, sample_inputs(sess))
    return answer_gaps([y for _, y in sess.sample], ref)
