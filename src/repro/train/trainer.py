"""The training loop: precision schedule, fault tolerance, stragglers.

One Trainer drives any model via a user-supplied
``loss_fn(params, batch, policy) -> scalar``.

Features (DESIGN.md §4):
  * **precision schedule** (paper §4.4): each schedule phase owns its own
    jitted train step (dtype changes require recompiles — at most 2/run);
  * **dynamic loss scaling + skip-step** for fp16 phases: non-finite
    gradients skip the update and halve the scale (lax.cond, fully jitted);
  * **checkpoint/restart**: async atomic checkpoints every ``ckpt_every``;
    ``Trainer.restore()`` resumes bit-compatible (data pipeline is
    stateless so only (params, opt, scale, step) need storage);
  * **preemption**: SIGTERM sets a flag; the loop checkpoints at the next
    step boundary and exits cleanly;
  * **straggler monitor**: EWMA of step wall-time; steps slower than
    ``straggler_factor``× the EWMA are counted and surfaced through
    ``Trainer.stats`` (on multi-host this hook feeds the re-scheduler);
  * **grad accumulation** via ``lax.scan`` over microbatches.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import PrecisionPolicy, PrecisionSchedule
from repro.optim import (
    AdamW,
    all_finite,
    init_loss_scale,
    loss_scaling_required,
    scale_loss,
    unscale_grads,
    update_loss_scale,
)
from . import checkpoint as ckpt_lib


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    schedule: PrecisionSchedule = dataclasses.field(
        default_factory=lambda: PrecisionSchedule.constant("full")
    )
    optimizer: AdamW = dataclasses.field(default_factory=AdamW)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep_last_k: int = 3
    microbatches: int = 1
    straggler_factor: float = 3.0
    log_every: int = 10
    #: Auto-precision: an ``repro.autoprec.AutoPrecisionController`` that
    #: supersedes the static schedule — per-site formats follow runtime
    #: telemetry plus the Thm 3.1/3.2 budgets.  Also created implicitly
    #: by ``PrecisionSchedule.auto(...)``.
    autoprec: Optional[Any] = None
    #: Collect numerics telemetry (amax / overflow / underflow taps as a
    #: functional carry of the jitted step) without a controller.
    telemetry: bool = False
    #: Tri-state Pallas toggle threaded into the step builder: None =
    #: auto (TPU backends / REPRO_USE_PALLAS=1).  Resolved once at
    #: Trainer construction and passed to ``loss_fn`` when its signature
    #: accepts a ``use_pallas`` keyword (incl. ``**kwargs``) —
    #: model-agnostic loss closures that bake the flag into their config
    #: simply ignore it.
    use_pallas: Optional[bool] = None
    #: Calibration-state JSON (``repro.tune``): activated at Trainer
    #: construction so spectral tile resolution serves validated tuned
    #: tiles instead of the static heuristic.  None = keep whatever is
    #: already active (explicit ``activate()`` or
    #: ``$REPRO_CALIBRATION_STATE``).
    calibration_state: Optional[str] = None
    #: Observability (``repro.obs``): turn on the global trace ring (if
    #: not already on) and record per-step phase spans (``train/data``,
    #: ``train/step`` with ``train/dispatch`` and ``train/sync``,
    #: ``train/telemetry``, ``train/controller``), loss-scale numerics
    #: events, and step-wall histograms in the metrics registry.  The
    #: span calls are free no-ops when this is off and no profiler
    #: session is active; what they cost on the chip is in PERF.md.
    obs: bool = False


class Trainer:
    def __init__(
        self,
        loss_fn: Callable[[Any, Dict, PrecisionPolicy], jnp.ndarray],
        params: Any,
        config: TrainerConfig,
    ):
        self.loss_fn = loss_fn
        # own the parameter buffers: the jitted step donates them
        # (donate_argnums), which would delete a caller-shared pytree.
        self.params = jax.tree_util.tree_map(jnp.copy, params)
        self.cfg = config
        self.opt_state = config.optimizer.init(params)
        self.scale_state = init_loss_scale()
        self.step = 0
        self.history: list = []
        self.stats = {"straggler_steps": 0, "skipped_steps": 0,
                      "recompiles": 0, "policy_changes": 0}
        # auto-precision: an explicit controller wins; a schedule in
        # ``auto`` mode gets a default controller over its base policy
        self.controller = config.autoprec
        if (getattr(config.schedule, "mode", "static") == "auto"
                and self.controller is None):
            from repro.autoprec import AutoPrecisionController

            self.controller = AutoPrecisionController(
                base=config.schedule.base,
                grid_points=getattr(config.schedule, "grid_points", None))
        self._collect = bool(config.telemetry or self.controller is not None)
        self.telemetry = None
        if self._collect:
            from repro.autoprec import TelemetryAggregator

            self.telemetry = TelemetryAggregator()
        from repro.kernels.ops import resolve_use_pallas

        self._use_pallas = resolve_use_pallas(config.use_pallas)
        if config.calibration_state is not None:
            from repro.tune.cache import activate

            activate(config.calibration_state)
        import inspect

        params_sig = inspect.signature(loss_fn).parameters
        self._loss_takes_pallas = "use_pallas" in params_sig or any(
            p.kind is inspect.Parameter.VAR_KEYWORD
            for p in params_sig.values()
        )
        self._obs = bool(config.obs)
        if self._obs:
            from repro.obs import trace as obs_trace

            if not obs_trace.is_enabled():
                obs_trace.enable()
        self._last_scale: Optional[float] = None
        self._steps_cache: Dict[Any, Callable] = {}
        self._preempted = False
        self._ckptr = (
            ckpt_lib.AsyncCheckpointer(config.ckpt_dir, config.keep_last_k)
            if config.ckpt_dir
            else None
        )

    # -- fault tolerance ----------------------------------------------------
    def install_preemption_handler(self, signum=signal.SIGTERM):
        signal.signal(signum, lambda *_: self._on_preempt())

    def _on_preempt(self):
        self._preempted = True

    def save(self):
        if self._ckptr is None:
            return
        state = {
            "params": self.params,
            "opt": self.opt_state,
            "scale": self.scale_state,
            "step": jnp.asarray(self.step),
        }
        self._ckptr.save(self.step, state)

    def restore(self) -> bool:
        if self.cfg.ckpt_dir is None or ckpt_lib.latest_step(self.cfg.ckpt_dir) is None:
            return False
        target = {
            "params": self.params,
            "opt": self.opt_state,
            "scale": self.scale_state,
            "step": jnp.asarray(self.step),
        }
        state, _ = ckpt_lib.restore(self.cfg.ckpt_dir, target)
        self.params = state["params"]
        self.opt_state = state["opt"]
        self.scale_state = state["scale"]
        self.step = int(state["step"])
        return True

    # -- compiled step per policy --------------------------------------------
    def _build_step(self, policy: PrecisionPolicy) -> Callable:
        opt = self.cfg.optimizer
        nmicro = self.cfg.microbatches
        collect = self._collect
        # decided by the resolved rule table (train/loss_scale site), so a
        # precision_rules override can flip it per run without a new policy
        use_scaling = loss_scaling_required(policy)
        if self._loss_takes_pallas:
            base_loss_fn, up = self.loss_fn, self._use_pallas

            def loss_fn(p, b, pol):
                return base_loss_fn(p, b, pol, use_pallas=up)
        else:
            loss_fn = self.loss_fn

        def micro_grads(params, batch, scale_state):
            # The telemetry collector lives *inside* the differentiated
            # function: taps record tracers of the loss trace and the
            # snapshot rides out through has_aux, so collection works
            # under grad and per-iteration inside the microbatch scan.
            def scaled_loss(p, b):
                if collect:
                    from repro.autoprec import TraceCollector, collecting

                    col = TraceCollector()
                    with collecting(col):
                        loss = loss_fn(p, b, policy)
                    telem = col.snapshot()
                else:
                    loss = loss_fn(p, b, policy)
                    telem = {}
                return (scale_loss(loss, scale_state) if use_scaling
                        else loss), telem

            grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)
            if nmicro == 1:
                (loss, telem), grads = grad_fn(params, batch)
                return loss, grads, telem
            # split the leading batch axis into microbatches and scan
            def resplit(x):
                return x.reshape(nmicro, x.shape[0] // nmicro, *x.shape[1:])

            mb = jax.tree_util.tree_map(resplit, batch)

            def body(carry, b):
                acc_loss, acc_g = carry
                (loss, telem), g = grad_fn(params, b)
                acc_g = jax.tree_util.tree_map(jnp.add, acc_g, g)
                return (acc_loss + loss, acc_g), telem

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss, grads), telems = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_g), mb
            )
            if collect:
                from repro.autoprec import merge_stacked

                telems = merge_stacked(telems)
            inv = 1.0 / nmicro
            return loss * inv, jax.tree_util.tree_map(lambda g: g * inv, grads), telems

        def train_step(params, opt_state, scale_state, batch):
            loss, grads, telem = micro_grads(params, batch, scale_state)
            if use_scaling:
                grads = unscale_grads(grads, scale_state)
                loss = loss / scale_state.scale
            finite = all_finite(grads)

            def do_update(_):
                return opt.update(grads, opt_state, params)

            def skip(_):
                return params, opt_state

            new_params, new_opt = jax.lax.cond(finite, do_update, skip, None)
            new_scale = (
                update_loss_scale(scale_state, finite) if use_scaling else scale_state
            )
            return new_params, new_opt, new_scale, loss, finite, telem

        return jax.jit(train_step, donate_argnums=(0, 1))

    def _step_fn(self, policy: PrecisionPolicy) -> Callable:
        # key by the policy's own rules and the active precision_rules
        # scope, not just the name: a step bakes the rules in at trace
        # time, and with_rules overlays may share the parent's name
        from repro.precision import current_overrides

        key = (policy.name, policy.rules, current_overrides())
        if key not in self._steps_cache:
            self._steps_cache[key] = self._build_step(policy)
            self.stats["recompiles"] += 1
        return self._steps_cache[key]

    def lower(self, batch: Dict, policy: Optional[PrecisionPolicy] = None):
        """Lower (without compiling) the train step ``run`` executes for
        ``policy`` (default: the schedule's policy at the current step);
        its text shows which kernels the step holds."""
        if policy is None:
            policy = self.cfg.schedule.policy_at(self.step,
                                                 self.cfg.total_steps)
        return self._step_fn(policy).lower(
            self.params, self.opt_state, self.scale_state, batch)

    # -- observability --------------------------------------------------------
    def _obs_step_end(self, dt: float) -> None:
        """Per-step metrics + loss-scale numerics events (obs mode only;
        the extra ``float(scale)`` sync is why this is gated)."""
        from repro.obs import loss_scale_event, registry

        registry().histogram("repro_train_step_wall_ms").observe(dt * 1e3)
        registry().counter("repro_train_steps_total").inc()
        new_scale = float(self.scale_state.scale)
        if self._last_scale is not None and new_scale != self._last_scale:
            kind = ("loss_scale_halved" if new_scale < self._last_scale
                    else "loss_scale_grown")
            loss_scale_event(kind, new_scale, step=self.step)
        self._last_scale = new_scale

    def publish_stats(self) -> Dict:
        """Publish ``self.stats`` into the obs registry as
        ``repro_train_*`` gauges and return the dict (the registry
        snapshot is the machine-readable export source)."""
        from repro.obs import registry

        registry().publish("train", self.stats)
        registry().gauge("repro_train_step").set(float(self.step))
        return self.stats

    # -- the loop -------------------------------------------------------------
    def run(self, batch_fn: Callable[[int], Dict], steps: Optional[int] = None):
        """batch_fn(step) -> batch pytree (stateless pipeline contract)."""
        from repro.obs import trace as obs_trace

        total = steps if steps is not None else self.cfg.total_steps
        ewma = None
        while self.step < total and not self._preempted:
            if self.controller is not None:
                # auto mode: the controller's overlay decides the formats;
                # a version bump resolves to a new (name, rules) key and
                # the step cache recompiles exactly once per change
                policy = self.controller.policy()
            else:
                policy = self.cfg.schedule.policy_at(self.step, self.cfg.total_steps)
            fn = self._step_fn(policy)
            with obs_trace.span("train/data", step=self.step):
                batch = batch_fn(self.step)
            t0 = time.perf_counter()
            # the span brackets the host call plus the float(loss) sync,
            # so its duration carries the device wall of the step:
            # train/dispatch is the host's call (the batch's transfer
            # starts there), train/sync the wait on the device
            with obs_trace.span("train/step", step=self.step,
                                policy=policy.name):
                with obs_trace.span("train/dispatch"):
                    self.params, self.opt_state, self.scale_state, loss, finite, telem = fn(
                        self.params, self.opt_state, self.scale_state, batch
                    )
                with obs_trace.span("train/sync"):
                    loss = float(loss)
                    finite = bool(finite)
            dt = time.perf_counter() - t0
            if not finite:
                self.stats["skipped_steps"] += 1
            if ewma is not None and dt > self.cfg.straggler_factor * ewma:
                self.stats["straggler_steps"] += 1
            ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
            if self.telemetry is not None:
                with obs_trace.span("train/telemetry", step=self.step):
                    self.telemetry.update(telem)
            if self._obs:
                self._obs_step_end(dt)
            self.history.append({"step": self.step, "loss": loss, "policy": policy.name, "dt": dt})
            self.step += 1
            if (self.controller is not None
                    and self.step % self.controller.config.interval == 0):
                with obs_trace.span("train/controller", step=self.step):
                    if self.controller.update(self.telemetry.take_window(),
                                              step=self.step):
                        self.stats["policy_changes"] += 1
            if self._ckptr is not None and self.step % self.cfg.ckpt_every == 0:
                self.save()
        if self._preempted and self._ckptr is not None:
            self.save()
        if self._ckptr is not None:
            self._ckptr.wait()
        if self._obs:
            self.publish_stats()
        return self.history
