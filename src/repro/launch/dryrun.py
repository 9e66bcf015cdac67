"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape) cell, on the single-pod 16×16 mesh
and the 2×16×16 multi-pod mesh:

    with mesh:
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…).lower(…)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

Results append to benchmarks/results/dryrun.json so interrupted sweeps
resume.  Failures here (sharding mismatch, OOM at compile, unsupported
collective) are bugs in the system — not acceptable skips.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import os
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, cell_is_runnable, get_config
from repro.dist import use_mesh
from repro.dist.sharding import lm_param_specs, replication_report
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze_counts, model_flops, parse_hlo
from repro.launch.steps import build_prefill_chunk_step, build_step, bundle_shardings

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "results", "dryrun.json")


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             policy_name: str = "amp_bf16", verbose: bool = True,
             prefill_chunk: int = 0, telemetry: bool = False) -> dict:
    from repro.core import get_policy
    from repro.precision import describe

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "policy": policy_name,
           # resolved site table: the record says exactly which sites this
           # cell lowered at which formats, not just a policy name
           "policy_sites": describe(get_policy(policy_name))}

    ok, reason = cell_is_runnable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    bundle = build_step(cfg, shape, get_policy(policy_name))
    param_specs = lm_param_specs(bundle.params_shape, mesh)
    in_sh, out_sh = bundle_shardings(bundle, cfg, mesh, param_specs)

    if shape.kind == "train":
        lower_args = (bundle.params_shape, bundle.extra_state_shape["opt_state"],
                      bundle.inputs["batch"])
    elif shape.kind == "prefill":
        lower_args = (bundle.params_shape, bundle.inputs["batch"])
    else:  # decode
        lower_args = (bundle.params_shape, bundle.inputs["cache"],
                      bundle.inputs["tokens"])

    with use_mesh(mesh):
        jitted = jax.jit(bundle.step_fn, in_shardings=in_sh, out_shardings=out_sh)
        compiled = jitted.lower(*lower_args).compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    counts = parse_hlo(hlo)   # trip-count-aware FLOPs/bytes/collectives
    n_dev = mesh.devices.size
    roof = analyze_counts(counts, n_dev)

    # MODEL_FLOPS (6·N·D) vs compiled useful-compute ratio
    if shape.kind == "train":
        tokens = shape.global_batch * (cfg.max_dec_len if cfg.encoder_decoder
                                       else shape.seq_len)
        mf = model_flops(cfg.active_params_approx(), tokens)  # 6ND = fwd+bwd
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = 2.0 * cfg.active_params_approx() * tokens
    else:
        tokens = shape.global_batch  # one token per slot
        mf = 2.0 * cfg.active_params_approx() * tokens

    global_flops = roof.flops_per_device * n_dev
    rec.update({
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "n_devices": n_dev,
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost_analysis_raw": {k: cost.get(k) for k in
                          ("flops", "bytes accessed", "transcendentals")
                          if k in cost},
        "collective_bytes_by_kind": counts.collective_by_kind,
        "roofline": roof.to_dict(),
        "model_flops_6nd": mf,
        "useful_flops_ratio": (mf / global_flops) if global_flops else None,
        "replication": replication_report(bundle.params_shape, param_specs),
    })
    if shape.kind == "train" and telemetry:
        # lower the autoprec-instrumented twin of the train step (taps
        # collected as a functional carry) and record its relative cost
        from repro.launch.steps import build_train_step

        t1 = time.time()
        tb = build_train_step(cfg, shape, get_policy(policy_name),
                              telemetry=True)
        t_in, t_out = bundle_shardings(tb, cfg, mesh, param_specs)
        with use_mesh(mesh):
            t_compiled = jax.jit(tb.step_fn, in_shardings=t_in,
                                 out_shardings=t_out).lower(
                tb.params_shape, tb.extra_state_shape["opt_state"],
                tb.inputs["batch"]).compile()
        t_roof = analyze_counts(parse_hlo(t_compiled.as_text()), n_dev)
        rec["telemetry"] = {
            "compile_s": round(time.time() - t1, 1),
            "roofline": t_roof.to_dict(),
            "overhead": telemetry_overhead(roof, t_roof),
        }

    if shape.kind == "decode" and prefill_chunk > 0 and not cfg.encoder_decoder:
        # also lower the serve engine's chunked-prefill step against the
        # same cache, so the record shows what chunking buys: the chunk
        # step moves K tokens of weights-reads per tick instead of 1.
        t1 = time.time()
        cb = build_prefill_chunk_step(cfg, shape, get_policy(policy_name),
                                      chunk=prefill_chunk)
        c_in, c_out = bundle_shardings(cb, cfg, mesh, param_specs)
        with use_mesh(mesh):
            c_compiled = jax.jit(cb.step_fn, in_shardings=c_in,
                                 out_shardings=c_out).lower(
                cb.params_shape, cb.inputs["cache"], cb.inputs["tokens"],
                cb.inputs["n_valid"]).compile()
        c_counts = parse_hlo(c_compiled.as_text())
        rec["prefill_chunk"] = {
            "chunk": prefill_chunk,
            "compile_s": round(time.time() - t1, 1),
            "roofline": analyze_counts(c_counts, n_dev).to_dict(),
            "collective_bytes_by_kind": c_counts.collective_by_kind,
        }

    if verbose:
        print(f"== {bundle.description} on {mesh_name} ==")
        print("memory_analysis:", rec["memory_analysis"])
        print("cost_analysis (raw, loop bodies once):", rec["cost_analysis_raw"])
        print("collectives:", counts.collective_by_kind)
        print("roofline:", json.dumps(rec["roofline"], indent=2))
        if "telemetry" in rec:
            print("telemetry overhead:", rec["telemetry"]["overhead"])
        if "prefill_chunk" in rec:
            print("prefill_chunk roofline:",
                  json.dumps(rec["prefill_chunk"]["roofline"], indent=2))
    return rec


def telemetry_overhead(plain, instrumented) -> dict:
    """Relative cost of a telemetry-instrumented step vs its plain twin
    (per-device flops/bytes from the compiled rooflines).  Both dry-runs
    record this so the autoprec overhead budget (<10% of step cost) is
    visible at lowering time, before a single real step runs."""

    def rel(a, b):
        return round(b / a - 1.0, 6) if a else None

    return {
        "flops_overhead": rel(plain.flops_per_device,
                              instrumented.flops_per_device),
        "bytes_overhead": rel(plain.bytes_per_device,
                              instrumented.bytes_per_device),
    }


def load_results(path=RESULTS):
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return []


def save_result(rec: dict, path=RESULTS):
    from repro.obs import result_header, write_json_atomic

    results = load_results(path)
    results = [r for r in results
               if not (r["arch"] == rec["arch"] and r["shape"] == rec["shape"]
                       and r["mesh"] == rec["mesh"] and r.get("policy") == rec.get("policy"))]
    # the file stays a flat record list (roofline_report iterates it);
    # the shared metadata header rides on each appended record instead
    rec = {**rec, "meta": result_header()}
    results.append(rec)
    write_json_atomic(path, results)


def main():
    # the simulated pod: set before the first JAX backend use, here and
    # not at import, so importing this module never changes a process
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--policy", default="amp_bf16")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="also lower the chunked-prefill serve step for "
                         "decode cells at this chunk size (0 = off)")
    ap.add_argument("--telemetry", action="store_true",
                    help="also lower the autoprec-instrumented train step "
                         "for train cells and record the telemetry overhead")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    done = {(r["arch"], r["shape"], r["mesh"], r.get("policy")) for r in load_results()
            if r.get("status") in ("ok", "skipped")} if args.skip_done else set()

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                if (arch, shape, mesh_name, args.policy) in done:
                    print(f"-- {arch} {shape} {mesh_name}: already done")
                    continue
                try:
                    rec = run_cell(arch, shape, mp, args.policy,
                                   prefill_chunk=args.prefill_chunk,
                                   telemetry=args.telemetry)
                except Exception as e:  # a failure here is a bug
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "policy": args.policy,
                           "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
                    failures.append(rec)
                save_result(rec)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f["arch"], f["shape"], f["mesh"], f["error"][:120])
        raise SystemExit(1)
    print("\nall requested cells passed")


if __name__ == "__main__":
    main()
