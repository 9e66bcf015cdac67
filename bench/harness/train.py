"""A training cell: ``Trainer.run`` on an epoch-shuffled feed.

Set-up builds one ``Trainer`` (the compiled step and its state) from the
seed's weights and drives it through its first ``check_steps`` steps, on
the same feed and call the window uses; those steps compile the step and
are what the reference checks.  The window then keeps calling
``Trainer.run`` one step at a time until ``seconds`` have passed: every
sample completed over all the time of the window, with the host's batch
fetch, the transfer and the per-step loss sync inside it.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, List, Optional

import numpy as np

from . import check, registry
from .traffic import EpochFeed


@dataclasses.dataclass
class CellRun:
    cell: dict
    seed: int
    trainer: object
    batch_fn: Callable
    batch: int
    checked: List[dict]          # the first steps' batches, on the host
    prog: dict                   # losses, grad and change norms per leaf
    grad_fn: Callable = None     # the step's loss gradient, for the census
    grad_args: tuple = ()        # of its kernel calls (shapes only)


def leaf_norms(tree, axes: Optional[dict] = None) -> dict:
    """{leaf path: float32 L2 norm}, computed on the device.  A leaf
    under a top-level key of ``axes`` is also normed per index of that
    axis, keyed ``<leaf path>#<index>`` (``harness/check.py``)."""
    import jax
    import jax.numpy as jnp

    axes = axes or {}
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(tree)]
    split = [next((a for k, a in axes.items() if p.startswith(f"['{k}']")),
                  None) for p in paths]

    def per_index(x, axis):
        x = jnp.moveaxis(x, axis, 0)
        return jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))

    def norms(t):
        xs = [x.astype(jnp.float32) for x in jax.tree_util.tree_leaves(t)]
        parts = [per_index(x, a) for x, a in zip(xs, split, strict=True)
                 if a is not None]
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x))) for x in xs]), parts

    whole, parts = jax.jit(norms)(tree)
    out = dict(zip(paths, np.asarray(whole, np.float64).tolist(), strict=True))
    cut = [p for p, a in zip(paths, split, strict=True) if a is not None]
    for p, v in zip(cut, parts, strict=True):
        out.update({f"{p}#{i}": float(x) for i, x in
                    enumerate(np.asarray(v, np.float64))})
    return out


def change_norms(new, old, axes: Optional[dict] = None) -> dict:
    import jax

    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, new, old),
                      axes)


def build(cell: dict, seed: int, seconds: float = 0.0) -> CellRun:
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core import PrecisionSchedule, get_policy
    from repro.optim import AdamW
    from repro.train import Trainer, TrainerConfig
    from repro.train.losses import relative_l2

    cfg, traffic = cell["config"], cell["traffic"]
    fam = registry.load_module("models", cfg["family"])
    data = registry.load_module("datasets", cfg["data"])
    pcfg = fam.program_config(cfg)
    B = cfg["assumed"]["batch"]
    pool = data.training_pool(seed, cfg, cfg["assumed"]["dataset_samples"])
    feed = EpochFeed(len(pool["x"]), B, seed)

    def batch_fn(step):
        with TraceAnnotation("bench/data"):
            idx = feed.indices(step)
            return {k: v[idx] for k, v in pool.items()}

    def loss_fn(p, b, policy):
        return relative_l2(fam.apply(p, b["x"], pcfg, policy), b["y"])

    opt = traffic["optimizer"]
    params = fam.reference.init_params(seed, cfg)
    tr = Trainer(loss_fn, params, TrainerConfig(
        total_steps=1 << 40,
        schedule=PrecisionSchedule.constant(traffic["precision"]),
        optimizer=AdamW(**opt)))
    del params
    K = traffic["check_steps"]
    checked = [{k: np.asarray(v) for k, v in batch_fn(s).items()}
               for s in range(K)]
    tr.run(batch_fn, steps=1)
    axes = getattr(fam, "SUBLEAF_AXES", None)
    grad = {k: v / (1.0 - opt["b1"])
            for k, v in leaf_norms(tr.opt_state.mu, axes).items()}
    tr.run(batch_fn, steps=K)
    change = change_norms(tr.params, fam.reference.init_params(seed, cfg),
                          axes)
    prog = {"losses": [h["loss"] for h in tr.history], "grad": grad,
            "change": change}
    policy = get_policy(traffic["precision"])
    sess = CellRun(cell, seed, tr, batch_fn, B, checked, prog)
    sess.grad_fn = jax.value_and_grad(lambda p, b: loss_fn(p, b, policy))
    sess.grad_args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype),
        (tr.params, checked[0]))
    return sess


def measure(sess: CellRun, seconds: float) -> dict:
    from jax.profiler import TraceAnnotation

    tr = sess.trainer
    s0, skipped0 = tr.step, tr.stats["skipped_steps"]
    t0 = time.perf_counter()
    with TraceAnnotation("bench/window"):
        while time.perf_counter() - t0 < seconds:
            with TraceAnnotation("bench/step"):
                tr.run(sess.batch_fn, steps=tr.step + 1)
    wall = time.perf_counter() - t0
    steps = tr.step - s0
    return {"attempted": steps, "window_s": wall, "steps": steps,
            "samples": steps * sess.batch,
            "failed": tr.stats["skipped_steps"] - skipped0,
            "metrics": {"train_samples_per_s": steps * sess.batch / wall}}


def kernel_calls(sess: CellRun) -> list:
    """The Pallas calls of one step, priced from their shapes."""
    from . import kernels

    return kernels.step_calls(sess.grad_fn, *sess.grad_args)


def release(sess: CellRun) -> None:
    """Free the program's device state before the reference runs."""
    sess.trainer = None
    sess.batch_fn = None
    sess.grad_fn = None
    gc.collect()


def reference_steps(cell: dict, seed: int, batches: List[dict],
                    rounding: str = "float32", halve: bool = False) -> dict:
    """The plain reference through the checked steps: losses, the first
    clipped gradient's leaf norms, each leaf's change.  Gradients are
    summed over blocks of ``reference_rows`` rows so that the float32
    reference fits beside nothing else.  ``halve`` plants the fault of a
    step that leaves out half of its batch."""
    import jax
    import jax.numpy as jnp

    from reference import common

    cfg, traffic = cell["config"], cell["traffic"]
    fam = registry.load_module("models", cfg["family"])
    hp = common.AdamWHyper(**{
        k if k != "grad_clip_norm" else "clip_norm": v
        for k, v in traffic["optimizer"].items()})
    q = common.ROUNDINGS[rounding]
    tanh = traffic["pre_fft_tanh"]
    rows = cfg["reference_rows"]
    axes = getattr(fam, "SUBLEAF_AXES", None)

    with jax.default_matmul_precision("highest"):
        p0 = fam.reference.init_params(seed, cfg)

        def block_loss(p, x, y, total):
            pred = fam.reference.forward(p, x, cfg, tanh, q)
            return jnp.sum(common.relative_l2_per_sample(pred, y)) / total

        grad_fn = jax.jit(jax.value_and_grad(block_loss),
                          static_argnums=(3,))
        update = jax.jit(lambda g, s, p: common.adamw_update(
            hp, common.clip_grads(g, hp.clip_norm), s, p))
        clip = jax.jit(lambda g: common.clip_grads(g, hp.clip_norm))
        p, st = p0, common.adamw_init(p0)
        losses, grad = [], None
        for b in batches:
            x, y = b["x"], b["y"]
            if halve:
                x, y = x[: len(x) // 2], y[: len(y) // 2]
            n = len(x)
            loss, g = 0.0, None
            for i in range(0, n, rows):
                lv, gi = grad_fn(p, x[i:i + rows], y[i:i + rows], n)
                loss += float(lv)
                g = gi if g is None else jax.tree_util.tree_map(
                    jnp.add, g, gi)
            if grad is None:
                grad = leaf_norms(clip(g), axes)
            p, st = update(g, st, p)
            losses.append(loss)
        change = change_norms(p, p0, axes)
    return {"losses": losses, "grad": grad, "change": change}


def readings(sess: CellRun) -> dict:
    """The program's first steps against the float32 reference."""
    ref = reference_steps(sess.cell, sess.seed, sess.checked)
    return check.training_readings(sess.prog, ref)
