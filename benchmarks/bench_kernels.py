"""Kernel differential benchmark: the Pallas spectral path vs the einsum
reference, forward AND backward, wall-clock + compiled peak memory.

This is the measurement half of the training-grade kernel PR: for dense
and CP-factorised contractions it times ``value_and_grad`` through both
paths and records the compiled step's ``temp_size_in_bytes`` (the CPU
container's analogue of the paper's GPU peak-memory numbers; on TPU the
same harness prices the Mosaic kernels).  On CPU the Pallas kernels run
in *interpret mode*, so their wall numbers measure the harness, not the
hardware — the JSON records ``interpret`` so readers don't compare
apples to Mosaic.

    PYTHONPATH=src python -m benchmarks.bench_kernels [--policy mixed_fno_bf16]

Results land in ``benchmarks/results/kernels.json`` (uploaded by the CI
bench-smoke job).
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn, write_result
from repro.core import get_policy
from repro.core.spectral import _cp_exprs, _dense_expr
from repro.kernels import ops
from repro.kernels.spectral_contract import (
    cp_vmem_bytes, pick_block_m, vmem_bytes, vmem_bytes_bwd)
from repro.launch.roofline import PEAKS
from repro.tune.measure import bytes_moved

RESULTS = os.path.join(os.path.dirname(__file__), "results", "kernels.json")

CASES = {
    # name: (B, I, O, modes) — small enough for CI, big enough that the
    # contraction dominates the traced graph
    "dense-2d": (4, 32, 32, (12, 12)),
    "dense-3d": (2, 16, 16, (6, 6, 6)),
    "cp-2d": (4, 32, 32, (12, 12)),
}

FUSED_CASES = {
    # name: (B, I, O, spatial, modes) — the whole spectral layer, not
    # just the contraction.  fused-2d matches the tune CLI's default
    # spectral_fused key so a calibration state from `tune` covers it.
    "fused-2d": (4, 16, 16, (24, 24), (6, 6)),
    "fused-1d": (4, 16, 16, (48,), (9,)),
}


def _randc(rng, shape, scale=0.5):
    return jnp.asarray(
        scale * (rng.randn(*shape) + 1j * rng.randn(*shape)), jnp.complex64)


def _hbm_share(gbps: float):
    """Achieved bytes/s over the device's published HBM peak — only for
    compiled kernels on a device the peak table knows, else None."""
    peaks = (None if ops._use_interpret()
             else PEAKS.get(jax.devices()[0].device_kind))
    return round(gbps * 1e9 / peaks["hbm_bw"], 6) if peaks else None


def _temp_bytes(fn, *args) -> int:
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    return int(getattr(mem, "temp_size_in_bytes", 0) or 0)


def bench_case(name: str, policy_name: str, seed: int = 0,
               tuned_leg: bool = False) -> dict:
    B, I, O, modes = CASES[name]
    kind = name.split("-")[0]
    ndim = len(modes)
    policy = get_policy(policy_name)
    site = policy.at("fno/layer0/spectral/contract")
    rng = np.random.RandomState(seed)
    x = _randc(rng, (B, I, *modes))
    M = int(np.prod(modes))
    # the same tile the production wrapper auto-picks (block_m=None):
    # dense vs CP working-set model, at the policy's storage itemsize
    half = site.spectral_dtype or jnp.float32
    itemsize = jnp.dtype(half).itemsize
    R = I
    if kind == "dense":
        block_m = pick_block_m(B, I, O, M, itemsize=itemsize)
    else:
        block_m = pick_block_m(B, I, O, M, rank=R, itemsize=itemsize)

    if kind == "dense":
        w = _randc(rng, (I, O, *modes))
        operands = (w,)
        expr = _dense_expr(ndim)

        def pallas_loss_at(block):
            def loss(x, *ws):
                y = ops.spectral_contract(x, ws[0], policy=site,
                                          block_m=block)
                return _abs2(y)
            return loss

        vmem = {"fwd": vmem_bytes(B, I, O, block_m),
                "bwd": vmem_bytes_bwd(B, I, O, block_m)}
        traffic_shape = (B, I, O, M)
    else:
        operands = (_randc(rng, (R,)), _randc(rng, (I, R)),
                    _randc(rng, (O, R)),
                    *[_randc(rng, (m, R)) for m in modes])
        expr = _cp_exprs(ndim)

        def pallas_loss_at(block):
            def loss(x, *ws):
                y = ops.spectral_contract_cp(x, ws[0], ws[1], ws[2],
                                             list(ws[3:]), policy=site,
                                             block_m=block)
                return _abs2(y)
            return loss

        vmem = {"fwd": cp_vmem_bytes(B, I, O, R, block_m),
                "bwd": cp_vmem_bytes(B, I, O, R, block_m)}
        traffic_shape = (B, I, O, R, M)
    pallas_loss = pallas_loss_at(block_m)

    def _abs2(y):
        if hasattr(y, "abs2"):
            return jnp.sum(y.abs2())
        return jnp.sum(jnp.abs(y) ** 2)

    def einsum_loss(x, *ws):
        return _abs2(site.contract(expr, x, *ws))

    row = {
        "case": name, "policy": policy_name,
        "B": B, "I": I, "O": O, "modes": list(modes),
        "block_m": block_m, "vmem_bytes": vmem,
        "interpret": jax.default_backend() != "tpu",
    }
    # HBM traffic model for one fwd+bwd step (repro.tune's bytes-moved
    # model at the policy's storage itemsize) — normalises walls into
    # achieved GB/s and a roofline-bandwidth fraction per row
    dtype_name = jnp.dtype(half).name
    moved = bytes_moved(kind, traffic_shape, dtype_name)
    row["bytes_moved"] = moved

    legs = [("einsum", einsum_loss), ("pallas", pallas_loss)]
    if tuned_leg:
        # tuned leg: block_m=None routes tile resolution through the
        # active calibration cache (heuristic fallback per miss).  Reset
        # the trace-time tile counters first so row["tiles"] reports
        # this case's resolutions, not the process's accumulated total.
        legs.append(("pallas_tuned", pallas_loss_at(None)))
        ops.reset_tile_resolution_stats()
    for label, loss in legs:
        fwd = jax.jit(loss)
        bwd = jax.jit(jax.value_and_grad(loss, argnums=(0,)))
        entry = {
            "fwd_us": time_fn(fwd, x, *operands),
            "fwd_bwd_us": time_fn(bwd, x, *operands),
            "fwd_temp_bytes": _temp_bytes(loss, x, *operands),
            "fwd_bwd_temp_bytes": _temp_bytes(
                jax.value_and_grad(loss, argnums=(0,)), x, *operands),
        }
        if label != "einsum":
            gbps = moved / (entry["fwd_bwd_us"] * 1e-6) / 1e9
            entry["gbps"] = round(gbps, 3)
            entry["roofline_fraction"] = _hbm_share(gbps)
        row[label] = entry
    row["pallas_over_einsum_wall"] = round(
        row["pallas"]["fwd_bwd_us"] / max(row["einsum"]["fwd_bwd_us"], 1e-9), 3)
    if tuned_leg:
        row["tiles"] = ops.tile_resolution_stats()
        row["tuned_over_heuristic_wall"] = round(
            row["pallas_tuned"]["fwd_bwd_us"]
            / max(row["pallas"]["fwd_bwd_us"], 1e-9), 3)
    return row


def bench_fused_case(name: str, policy_name: str, seed: int = 0) -> dict:
    """The spectral megakernel vs the 3-stage path, whole-layer legs:
    ``einsum`` (no Pallas anywhere), ``staged`` (Pallas contraction,
    HBM-resident spectrum) and ``fused`` (one grid, spectrum in VMEM).
    Walls + compiled temp bytes per leg, plus the tune traffic model's
    HBM bytes for both pipelines — the fused pipeline must move strictly
    fewer bytes at every benchmarked shape."""
    from repro.core.spectral import init_spectral_weights, spectral_conv_apply
    from repro.kernels.spectral_contract import pick_block_b

    B, I, O, spatial, modes = FUSED_CASES[name]
    policy = get_policy(policy_name)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, I, *spatial), jnp.float32)
    params = init_spectral_weights(jax.random.PRNGKey(seed), I, O, modes,
                                   "dense")
    block_b = pick_block_b(B, I, O, spatial, modes)

    def loss_at(use_pallas, fuse):
        def loss(x, params):
            y = spectral_conv_apply(
                params, x, modes, policy, use_pallas=use_pallas,
                fuse_spectral=fuse, site="fno/layer0/spectral")
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return loss

    traffic_shape = (B, I, O, *spatial, *modes)
    moved = {
        "fused": bytes_moved("spectral_fused", traffic_shape, "float32"),
        "staged": bytes_moved("spectral_staged", traffic_shape, "float32"),
    }
    assert moved["fused"] < moved["staged"], (
        "the megakernel must move strictly fewer HBM bytes", name, moved)

    row = {
        "case": name, "policy": policy_name,
        "B": B, "I": I, "O": O, "spatial": list(spatial),
        "modes": list(modes), "block_b": block_b,
        "bytes_moved": moved,
        "interpret": jax.default_backend() != "tpu",
    }
    legs = [("einsum", loss_at(False, False)),
            ("staged", loss_at(True, False)),
            ("fused", loss_at(True, True))]
    for label, loss in legs:
        fwd = jax.jit(loss)
        bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        entry = {
            "fwd_us": time_fn(fwd, x, params),
            "fwd_bwd_us": time_fn(bwd, x, params),
            "fwd_temp_bytes": _temp_bytes(loss, x, params),
            "fwd_bwd_temp_bytes": _temp_bytes(
                jax.value_and_grad(loss, argnums=(0, 1)), x, params),
        }
        if label != "einsum":
            traffic = moved["fused"] if label == "fused" else moved["staged"]
            gbps = traffic / (entry["fwd_bwd_us"] * 1e-6) / 1e9
            entry["gbps"] = round(gbps, 3)
            entry["roofline_fraction"] = _hbm_share(gbps)
        row[label] = entry
    row["fused_over_staged_wall"] = round(
        row["fused"]["fwd_bwd_us"] / max(row["staged"]["fwd_bwd_us"], 1e-9), 3)
    row["fused_over_staged_hbm_bytes"] = round(
        moved["fused"] / moved["staged"], 4)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", nargs="*",
                    default=["full", "mixed_fno_bf16"])
    ap.add_argument("--case", nargs="*",
                    default=sorted(CASES) + sorted(FUSED_CASES))
    ap.add_argument("--calibration-state", default=None,
                    help="activate a repro.tune state and add a tuned-"
                         "tiles comparison leg per row")
    args = ap.parse_args()

    tuned_leg = args.calibration_state is not None
    if tuned_leg:
        from repro.tune.cache import activate

        activate(args.calibration_state)

    rows = []
    print(f"== bench_kernels (backend={jax.default_backend()}) ==")
    print(f"{'case':>10s} {'policy':>16s} {'einsum f+b us':>14s} "
          f"{'pallas f+b us':>14s} {'ratio':>7s} {'GB/s':>7s} "
          f"{'temp MiB e/p':>14s}")
    for case in args.case:
        for pol in args.policy:
            if case in FUSED_CASES:
                row = bench_fused_case(case, pol)
                rows.append(row)
                print(f"{case:>10s} {pol:>16s} "
                      f"{row['staged']['fwd_bwd_us']:>14.0f} "
                      f"{row['fused']['fwd_bwd_us']:>14.0f} "
                      f"{row['fused_over_staged_wall']:>7.2f} "
                      f"{row['fused']['gbps']:>7.2f} "
                      f"{row['staged']['fwd_bwd_temp_bytes'] / 2**20:>6.1f}/"
                      f"{row['fused']['fwd_bwd_temp_bytes'] / 2**20:<6.1f}"
                      f"  (hbm bytes x"
                      f"{row['fused_over_staged_hbm_bytes']:.2f})")
                continue
            row = bench_case(case, pol, tuned_leg=tuned_leg)
            rows.append(row)
            print(f"{case:>10s} {pol:>16s} "
                  f"{row['einsum']['fwd_bwd_us']:>14.0f} "
                  f"{row['pallas']['fwd_bwd_us']:>14.0f} "
                  f"{row['pallas_over_einsum_wall']:>7.2f} "
                  f"{row['pallas']['gbps']:>7.2f} "
                  f"{row['einsum']['fwd_bwd_temp_bytes'] / 2**20:>6.1f}/"
                  f"{row['pallas']['fwd_bwd_temp_bytes'] / 2**20:<6.1f}")
            if tuned_leg:
                print(f"{'':>10s} {'(tuned tiles)':>16s} {'':>14s} "
                      f"{row['pallas_tuned']['fwd_bwd_us']:>14.0f} "
                      f"{row['tuned_over_heuristic_wall']:>7.2f} "
                      f"{row['pallas_tuned']['gbps']:>7.2f}")

    report = {"backend": jax.default_backend(),
              "calibration_state": args.calibration_state, "rows": rows}
    write_result(RESULTS, report)
    print(f"results -> {RESULTS}")


if __name__ == "__main__":
    from pathlib import Path

    from repro.launch import compile_cache

    compile_cache.place(Path(__file__).resolve().parents[1])
    main()
