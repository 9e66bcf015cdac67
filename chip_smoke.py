#!/usr/bin/env python3
"""Run the paper's main path once on a TPU and check what comes out.

Mixed-precision FNO training through ``repro.train.Trainer`` at the
published width of ``configs/fno_paper.FNO_DARCY`` (hidden 64, lifting
and projection 256, 4 layers, modes 32x32) on a 128x128 grid at batch
16, under ``mixed_fno_bf16`` and under ``full``, with the Pallas kernels
compiled (``use_pallas`` left to its auto default).  Then:

* a plain f32 reference (einsum path, highest matmul precision) against
  the default-precision ``full`` forward and the paper's Thm 3.2
  certification of ``mixed_fno_bf16`` at that width;
* a few 128x128 fields served through ``serve.OperatorEngine`` and
  compared with per-field ``fno_apply``;
* one training step each of ``TFNO_NS`` (CP kernel, 128x128) and
  ``SFNO_SWE`` (l-shared kernel, 256x512).

``--chips 4`` runs only the sharded FNO training step on a 4-device
mesh from ``repro.dist`` against the same step unsharded on device 0,
under ``full`` and under ``mixed_fno_bf16``.

Weights are random (from ``--seed``); data is generated on the device.
Step walls printed here are a first chip run, not a benchmark.  The last
line of standard output is one JSON object naming the device; it is
printed only when every phase passed.  Without a TPU the script exits
non-zero before any phase runs.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips of one host

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
STEPS = 5
NOTE = "first chip run, not a measurement"


def _require_repo() -> None:
    src = ROOT / "src"
    if not (src / "repro" / "kernels").is_dir():
        sys.exit(f"chip_smoke: no repro package under {src}; "
                 f"run this script from a checkout of the repository")
    sys.path.insert(0, str(src))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: check failed: {what}")
    print(f"  ok: {what}", flush=True)


def rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


# ---------------------------------------------------------------------------
# Phases (each takes its configuration, so a rehearsal can run them small)
# ---------------------------------------------------------------------------


def make_trainer(model: str, cfg, params, policy_name: str, steps: int):
    from repro.core import PrecisionSchedule
    from repro.models import fno_apply, sfno_apply
    from repro.optim import AdamW
    from repro.train import Trainer, TrainerConfig
    from repro.train.losses import relative_l2

    apply = fno_apply if model == "fno" else sfno_apply

    def loss_fn(p, batch, policy):
        return relative_l2(apply(p, batch["x"], cfg, policy), batch["y"])

    return Trainer(loss_fn, params, TrainerConfig(
        total_steps=steps,
        schedule=PrecisionSchedule.constant(policy_name),
        optimizer=AdamW(lr=1e-3)))


def train_phase(label: str, model: str, cfg, params, batch,
                policy_name: str, steps: int, expect_kernels: bool):
    """``steps`` Trainer steps on ``batch``; checks the compiled step
    holds Mosaic kernels and every loss is finite.  Returns the losses."""
    import math

    tr = make_trainer(model, cfg, params, policy_name, steps)
    text = tr.lower(batch).as_text()
    if expect_kernels:
        check("tpu_custom_call" in text,
              f"{label}/{policy_name}: the lowered train step holds "
              f"compiled Pallas kernels (tpu_custom_call)")
    hist = tr.run(lambda _s: batch)
    for h in hist:
        print(f"  {label} {policy_name} step {h['step']}: loss "
              f"{h['loss']:.6f}  wall {h['dt']:.4f} s ({NOTE})", flush=True)
    losses = [h["loss"] for h in hist]
    check(len(losses) == steps and all(math.isfinite(v) for v in losses),
          f"{label}/{policy_name}: {steps} finite losses")
    return losses


def reference_phase(cfg, params, x) -> dict:
    """The default-precision forwards against a plain f32 reference, and
    the Thm 3.2 certification of mixed_fno_bf16 at this width."""
    import jax

    from repro.autoprec.certify import certify_policy
    from repro.core import get_policy
    from repro.models import fno_apply
    from repro.precision import FULL

    ref_cfg = dataclasses.replace(cfg, use_pallas=False)
    with jax.default_matmul_precision("highest"):
        y_ref = jax.jit(lambda p, v: fno_apply(p, v, ref_cfg, FULL))(
            params, x)
    out = {}
    for name in ("full", "mixed_fno_bf16"):
        pol = get_policy(name)
        y = jax.jit(lambda p, v, pol=pol: fno_apply(p, v, cfg, pol))(
            params, x)
        out[f"{name}_rel_l2_vs_highest"] = rel_l2(y, y_ref)
        print(f"  {name} (default matmul precision) vs f32 highest "
              f"reference: rel-L2 {out[f'{name}_rel_l2_vs_highest']:.3e}",
              flush=True)
    rep = certify_policy(get_policy("mixed_fno_bf16"), cfg, params, x)
    e2e = rep["end_to_end"]
    print(f"  certify mixed_fno_bf16: prec_fraction_of_disc "
          f"{e2e['prec_fraction_of_disc']:.3e}, prec_rel_l2 "
          f"{e2e['prec_rel_l2']:.3e}, disc_upper_bound "
          f"{e2e['disc_upper_bound']:.3e}, sites within Thm 3.2: "
          f"{rep['all_within']}", flush=True)
    frac = e2e["prec_fraction_of_disc"]
    check(frac is not None and frac <= 1.0,
          "mixed_fno_bf16 precision error within the Thm 3.1 "
          "discretisation bound (prec_fraction_of_disc <= 1)")
    out["prec_fraction_of_disc"] = frac
    out["all_within"] = rep["all_within"]
    return out


def serve_phase(cfg, params, fields, max_batch: int) -> dict:
    """Fields through OperatorEngine under mixed_fno_bf16, each compared
    with a per-field fno_infer."""
    import jax
    import numpy as np

    from repro.core import get_policy
    from repro.models import fno_infer
    from repro.serve import FieldRequest, OperatorEngine

    pol = get_policy("mixed_fno_bf16")
    eng = OperatorEngine(params, cfg, model="fno", policy=pol,
                         max_batch=max_batch)
    reqs = [FieldRequest(uid=i, x=np.asarray(f)) for i, f in
            enumerate(fields)]
    for r in reqs:
        eng.submit(r)
    done, ticks = eng.drain()
    check(len(done) == len(reqs) and all(r.done for r in reqs),
          f"OperatorEngine answered {len(reqs)} requests in {ticks} ticks")
    solo = jax.jit(lambda p, v: fno_infer(p, v, eng.cfg, pol))
    worst, identical = 0.0, 0
    for r in reqs:
        y1 = np.asarray(solo(params, np.asarray(r.x)[None]))[0]
        worst = max(worst, rel_l2(r.y, y1))
        identical += int(np.array_equal(r.y, y1))
    print(f"  engine vs per-field fno_infer: worst rel-L2 {worst:.3e}, "
          f"{identical}/{len(reqs)} bit-identical", flush=True)
    check(worst <= 1e-3, "engine answers match per-field fno_infer "
          "(rel-L2 <= 1e-3)")
    return {"engine_worst_rel_l2": worst, "bit_identical": identical}


#: Sharded-vs-unsharded tolerances per policy: (loss, gradient rel-L2,
#: updated-params diff norm over update norm); None reports the number
#: without holding it.  Under ``full`` every partial sum is f32, so the
#: two differ by f32 rounding of the reduction order.  Under
#: ``mixed_fno_bf16`` the bias gradients are sums over batch and grid
#: taken in bf16, with heavy cancellation: a different reduction order
#: moves them by tens of percent, so there only the loss is held.
FOUR_CHIP_TOLS = {"full": (1e-5, 1e-4, 1e-3),
                  "mixed_fno_bf16": (1e-5, None, None)}


def four_chip_phase(cfg, policy_name: str, batch, params, mesh,
                    device0) -> dict:
    """The sharded FNO train step (repro.dist specs on ``mesh``) against
    the same step unsharded on ``device0``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import get_policy
    from repro.dist import (batch_specs, fno_param_specs, to_named,
                            use_mesh)
    from repro.launch.steps import opt_specs
    from repro.models import fno_apply
    from repro.optim import AdamW
    from repro.train.losses import relative_l2

    policy = get_policy(policy_name)
    opt = AdamW(lr=1e-3)
    tol_loss, tol_grad, tol_params = FOUR_CHIP_TOLS[policy_name]

    def step(p, o, b):
        def loss_fn(q):
            return relative_l2(fno_apply(q, b["x"], cfg, policy), b["y"])

        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_o = opt.update(grads, o, p)
        return new_p, new_o, loss, grads

    opt_state = opt.init(params)
    p_specs = fno_param_specs(params, mesh)
    p_named = to_named(mesh, p_specs)
    o_named = to_named(mesh, opt_specs(opt_state, p_specs))
    b_named = to_named(mesh, batch_specs(batch, mesh))
    sharded = jax.jit(step, in_shardings=(p_named, o_named, b_named),
                      out_shardings=(p_named, o_named,
                                     NamedSharding(mesh, P()), p_named))
    with use_mesh(mesh):
        args = (jax.device_put(params, p_named),
                jax.device_put(opt_state, o_named),
                jax.device_put(batch, b_named))
        t0 = time.perf_counter()
        p4, _, loss4, g4 = jax.block_until_ready(sharded(*args))
        dt4 = time.perf_counter() - t0
    n_dev = {d for leaf in jax.tree_util.tree_leaves(p4)
             for d in leaf.sharding.device_set}
    split = [jax.tree_util.keystr(k) for k, leaf in
             jax.tree_util.tree_leaves_with_path(p4)
             if leaf.addressable_shards[0].data.shape != leaf.shape]
    print(f"  {policy_name}: sharded step on {len(n_dev)} devices: loss "
          f"{float(loss4):.6f}  wall {dt4:.3f} s incl. compile ({NOTE}); "
          f"params split across devices: {split}", flush=True)
    check(len(n_dev) == mesh.devices.size,
          f"{policy_name}: updated params lie on {mesh.devices.size} "
          f"devices")

    single = jax.jit(step)
    args1 = jax.device_put((params, opt_state, batch), device0)
    p1, _, loss1, g1 = jax.block_until_ready(single(*args1))
    print(f"  {policy_name}: unsharded step on one device: loss "
          f"{float(loss1):.6f}", flush=True)

    def flat(tree):
        return jnp.concatenate([jnp.ravel(jax.device_put(v, device0))
                                for v in jax.tree_util.tree_leaves(tree)])

    p0, f1, f4 = flat(params), flat(p1), flat(p4)
    loss_rel = abs(float(loss4) - float(loss1)) / abs(float(loss1))
    grad_rel = rel_l2(flat(g4), flat(g1))
    max_abs = float(jnp.max(jnp.abs(f4 - f1)))
    upd_rel = float(jnp.linalg.norm(f4 - f1) /
                    (jnp.linalg.norm(f1 - p0) + 1e-30))
    n_diff = int(jnp.sum(f4 != f1))
    print(f"  {policy_name}: sharded vs unsharded: loss rel diff "
          f"{loss_rel:.3e}; gradient rel-L2 {grad_rel:.3e}; params max "
          f"abs diff {max_abs:.3e}, diff norm / update norm {upd_rel:.3e}, "
          f"{n_diff} of {f1.size} elements differ", flush=True)
    check(loss_rel <= tol_loss, f"{policy_name}: sharded and unsharded "
          f"losses agree (rel diff <= {tol_loss:g})")
    if tol_grad is not None:
        check(grad_rel <= tol_grad, f"{policy_name}: sharded and "
              f"unsharded gradients agree (rel-L2 <= {tol_grad:g})")
    if tol_params is not None:
        check(upd_rel <= tol_params, f"{policy_name}: sharded and "
              f"unsharded updated params agree (diff norm <= "
              f"{tol_params:g} of the update norm)")
    return {"loss_rel_diff": loss_rel, "grad_rel_l2": grad_rel,
            "param_max_abs_diff": max_abs, "param_diff_over_update": upd_rel,
            "elements_differ": n_diff}


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def run_one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs.fno_paper import FNO_DARCY, SFNO_SWE, TFNO_NS
    from repro.data import (sample_darcy_batch, sample_ns_batch,
                            sample_swe_batch)
    from repro.kernels import ops
    from repro.models import init_fno, init_sfno

    check(ops.resolve_use_pallas(None) and not ops._use_interpret(),
          "Pallas path on and compiled (not interpreted) by default")
    key = jax.random.PRNGKey(seed)
    k_data, k_init, k_ns, k_swe, k_tinit, k_sinit = jax.random.split(key, 6)

    print("== FNO_DARCY training, 128x128, batch 16 ==", flush=True)
    t0 = time.perf_counter()
    a, u = jax.block_until_ready(sample_darcy_batch(k_data, 128, 16))
    print(f"  Darcy data: {a.shape} in {time.perf_counter() - t0:.2f} s",
          flush=True)
    batch = {"x": a, "y": u}
    params = init_fno(k_init, FNO_DARCY)
    for policy_name in ("mixed_fno_bf16", "full"):
        train_phase("fno_darcy", "fno", FNO_DARCY, params, batch,
                    policy_name, STEPS, expect_kernels=True)

    print("== reference and certification at FNO_DARCY width ==",
          flush=True)
    reference_phase(FNO_DARCY, params, a[:4])

    print("== OperatorEngine, 128x128 fields ==", flush=True)
    serve_phase(FNO_DARCY, params, np.asarray(a[:6]), max_batch=4)

    print("== TFNO_NS one step, 128x128, batch 8 (CP kernel) ==",
          flush=True)
    f, w = sample_ns_batch(k_ns, 128, 8)
    train_phase("tfno_ns", "fno", TFNO_NS, init_fno(k_tinit, TFNO_NS),
                {"x": f, "y": w}, "mixed_fno_bf16", 1, expect_kernels=True)

    print("== SFNO_SWE one step, 256x512, batch 4 (l-shared kernel) ==",
          flush=True)
    x, y = sample_swe_batch(k_swe, SFNO_SWE.nlat, SFNO_SWE.nlon, 4)
    train_phase("sfno_swe", "sfno", SFNO_SWE, init_sfno(k_sinit, SFNO_SWE),
                {"x": x, "y": y}, "mixed_fno_bf16", 1, expect_kernels=True)


def run_four_chips(seed: int) -> None:
    import jax

    from repro.configs.fno_paper import FNO_DARCY
    from repro.data import sample_darcy_batch
    from repro.launch.mesh import make_debug_mesh
    from repro.models import init_fno

    check(len(jax.devices()) >= 4, "four devices present")
    print("== FNO_DARCY train step: 4-device mesh vs one device ==",
          flush=True)
    key = jax.random.PRNGKey(seed)
    k_data, k_init = jax.random.split(key)
    a, u = sample_darcy_batch(k_data, 128, 16)
    params = init_fno(k_init, FNO_DARCY)
    mesh = make_debug_mesh(4)
    for policy_name in FOUR_CHIP_TOLS:
        four_chip_phase(FNO_DARCY, policy_name, {"x": a, "y": u}, params,
                        mesh, jax.devices()[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _require_repo()
    from repro.launch import compile_cache

    cache_dir = compile_cache.place(ROOT)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {json.dumps(device)}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    with compile_cache.CacheCounter() as cache:
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    print(f"compile cache {cache_dir}: {cache.counts['hits']} hits, "
          f"{cache.counts['misses']} misses; total wall "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
