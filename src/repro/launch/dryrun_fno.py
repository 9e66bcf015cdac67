"""Dry-run for the paper's own FNO-family configs at pod scale.

These rows extend the 40-cell LM table with the cells most representative
of the paper's technique, lowered under BOTH the paper-faithful mixed
policy (`mixed_fno_bf16`) and the full-precision baseline — the §Perf
hillclimb compares and optimises them.

  tfno-ns   train 128x128,  global batch 1024 (CP-factorised weights)
  tfno-ns-hr train 512x512, global batch 64   (the paper's super-res goal)
  sfno-swe  train 256x512,  global batch 128
"""
import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.fno_paper import FNO_DARCY, SFNO_SWE, TFNO_NS
from repro.core import get_policy
from repro.precision import describe
from repro.dist import use_mesh
from repro.dist.sharding import fno_param_specs, pick_spec, to_named
from repro.launch.dryrun import save_result
from repro.launch.steps import opt_specs as _opt_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze_counts, parse_hlo, spectral_kernel_vmem
from repro.models import fno_apply, init_fno, init_sfno, sfno_apply
from repro.optim import AdamW
from repro.train.losses import relative_l2

FNO_CELLS = {
    "tfno-ns": dict(kind="fno", cfg=TFNO_NS, res=(128, 128), batch=1024),
    "tfno-ns-hr": dict(kind="fno", cfg=TFNO_NS, res=(512, 512), batch=64),
    "fno-darcy": dict(kind="fno", cfg=FNO_DARCY, res=(128, 128), batch=1024),
    "sfno-swe": dict(kind="sfno", cfg=SFNO_SWE, res=(256, 512), batch=128),
}


def run_fno_cell(name: str, multi_pod: bool, policy_name: str,
                 verbose: bool = True, telemetry: bool = False) -> dict:
    spec = FNO_CELLS[name]
    cfg = spec["cfg"]
    policy = get_policy(policy_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": name, "shape": f"train_{spec['res'][0]}x{spec['res'][1]}_b{spec['batch']}",
           "mesh": mesh_name, "kind": "train", "policy": policy_name,
           "policy_sites": describe(policy)}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    B = spec["batch"]
    res = spec["res"]

    if spec["kind"] == "fno":
        init_fn = lambda k: init_fno(k, cfg)
        apply_fn = lambda p, x: fno_apply(p, x, cfg, policy)
        in_ch = cfg.in_channels
    else:
        init_fn = lambda k: init_sfno(k, cfg)
        apply_fn = lambda p, x: sfno_apply(p, x, cfg, policy)
        in_ch = cfg.in_channels

    p_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    opt_shape = jax.eval_shape(opt.init, p_shape)
    batch = {
        "x": jax.ShapeDtypeStruct((B, in_ch, *res), jnp.float32),
        "y": jax.ShapeDtypeStruct((B, cfg.out_channels, *res), jnp.float32),
    }

    def train_step(params, opt_state, b):
        def loss_fn(p):
            pred = apply_fn(p, b["x"])
            return relative_l2(pred, b["y"])
        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_p, new_o = opt.update(grads, opt_state, params)
        return new_p, new_o, loss

    param_specs = fno_param_specs(p_shape, mesh)
    p_named = to_named(mesh, param_specs)
    opt_named = to_named(mesh, _opt_specs(opt_shape, param_specs))
    # full-DP input layout: batch over every mesh axis when divisible
    # (matches constrain_spatial in the model — §Perf iteration 5)
    bspecs = jax.tree_util.tree_map(
        lambda v: pick_spec(v.shape, mesh, [
            ("all",) + (None,) * (len(v.shape) - 1),
            ("dp",) + (None,) * (len(v.shape) - 1),
            (),
        ]),
        batch,
    )
    b_named = to_named(mesh, bspecs)
    with use_mesh(mesh):
        lowered = jax.jit(
            train_step,
            in_shardings=(p_named, opt_named, b_named),
            out_shardings=(p_named, opt_named, NamedSharding(mesh, P())),
        ).lower(p_shape, opt_shape, batch)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    counts = parse_hlo(compiled.as_text())
    n_dev = mesh.devices.size
    roof = analyze_counts(counts, n_dev)
    # Pallas spectral-contraction tiling estimate for this cell: the
    # full-DP layout leaves B/n_dev fields per device; dense FNO corners
    # contract hidden->hidden over the retained modes, the SFNO over the
    # (lmax, mmax) spherical spectrum, and CP factorisations budget the
    # factorised kernel at the layer's CP rank.
    h = cfg.hidden_channels
    rank = 0
    if getattr(cfg, "factorization", "dense") == "cp":
        from repro.core.spectral import cp_rank

        rank = cp_rank(h, h, cfg.rank)
    kmodes = cfg.modes if spec["kind"] == "fno" else (cfg.lmax, cfg.mmax)
    itemsize = 2 if policy.spectral_is_half else 4
    kdtype = (jnp.dtype(policy.spectral_dtype).name
              if policy.spectral_is_half else "float32")
    rec["spectral_kernel"] = spectral_kernel_vmem(
        max(1, B // n_dev), h, h, kmodes, rank=rank,
        l_shared=spec["kind"] == "sfno", itemsize=itemsize, dtype=kdtype)
    rec.update({
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "n_devices": n_dev,
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "collective_bytes_by_kind": counts.collective_by_kind,
        "roofline": roof.to_dict(),
    })
    if telemetry:
        # also lower the autoprec-instrumented twin of the train step —
        # numerics taps collected as a functional carry — and record its
        # relative flops/bytes cost next to the plain roofline
        from repro.autoprec import TraceCollector, collecting
        from repro.launch.dryrun import telemetry_overhead

        t1 = time.time()

        def train_step_telem(params, opt_state, b):
            def loss_fn(p):
                col = TraceCollector()
                with collecting(col):
                    pred = apply_fn(p, b["x"])
                    loss = relative_l2(pred, b["y"])
                return loss, col.snapshot()
            (loss, telem), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_p, new_o = opt.update(grads, opt_state, params)
            return new_p, new_o, loss, telem

        with use_mesh(mesh):
            t_compiled = jax.jit(
                train_step_telem,
                in_shardings=(p_named, opt_named, b_named),
                out_shardings=(p_named, opt_named,
                               NamedSharding(mesh, P()), None),
            ).lower(p_shape, opt_shape, batch).compile()
        t_counts = parse_hlo(t_compiled.as_text())
        t_roof = analyze_counts(t_counts, n_dev)
        rec["telemetry"] = {
            "compile_s": round(time.time() - t1, 1),
            "roofline": t_roof.to_dict(),
            "overhead": telemetry_overhead(roof, t_roof),
        }
    if verbose:
        print(f"== {name} ({policy_name}) on {mesh_name} ==")
        print("memory:", rec["memory_analysis"])
        print("roofline:", json.dumps(rec["roofline"], indent=2))
        if "telemetry" in rec:
            print("telemetry overhead:", rec["telemetry"]["overhead"])
    return rec


def main():
    # the simulated pod: set before the first JAX backend use, here and
    # not at import, so importing this module never changes a process
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None, choices=list(FNO_CELLS) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--policy", default="mixed_fno_bf16")
    ap.add_argument("--telemetry", action="store_true",
                    help="also lower the autoprec-instrumented step and "
                         "record the telemetry overhead")
    args = ap.parse_args()
    cells = [args.cell] if args.cell else list(FNO_CELLS)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for c in cells:
        for mp in meshes:
            try:
                rec = run_fno_cell(c, mp, args.policy,
                                   telemetry=args.telemetry)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": c, "shape": "train", "mesh": "2x16x16" if mp else "16x16",
                       "policy": args.policy, "status": "FAILED",
                       "error": f"{type(e).__name__}: {e}"}
                failures.append(rec)
            save_result(rec)
    if failures:
        raise SystemExit(1)
    print("fno cells passed")


if __name__ == "__main__":
    main()
