"""Operator serving: micro-batched FNO/SFNO field inference under the
same :class:`~repro.serve.engine.Engine` protocol as the LM engine.

This is PDE-inference-as-a-service — the deployment story the paper's
precision bounds actually pay for: each request carries one input field
``(C, *spatial)``; the engine groups the waiting queue into
*resolution buckets* (FNO weights are resolution-agnostic, but a fused
step needs one static spatial shape), admits up to ``max_batch``
same-resolution requests per tick through the scheduler policy, and
runs one jitted batched ``fno_infer`` / ``sfno_infer`` per bucket shape.

Because every op in the operator forward is per-sample independent
(batched GEMMs, FFTs, pointwise), micro-batching is *bit-identical* to
serving each field alone under the same precision policy — the property
the acceptance test pins down — so batching is purely a throughput knob.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PrecisionPolicy, FULL
from repro.models import fno_infer, sfno_infer
from repro.obs import trace as obs_trace

from .engine import EngineBase
from .paged.prefix import content_key
from .scheduler import Scheduler


@dataclasses.dataclass(eq=False)
class FieldRequest:
    """One operator-inference request: a single input field.  Identity
    semantics (``eq=False``): value comparison over the ndarray payload
    is both meaningless and ambiguous."""

    uid: int
    x: Any                        # (C, *spatial) array-like
    y: Optional[np.ndarray] = None
    status: str = "new"           # new | queued | running | done | failed
    error: Optional[str] = None
    submit_tick: int = -1
    start_tick: int = -1
    finish_tick: int = -1

    @property
    def done(self) -> bool:
        return self.status == "done"

    @property
    def resolution(self) -> Tuple[int, ...]:
        return tuple(np.shape(self.x)[1:])


class OperatorEngine(EngineBase):
    """Micro-batching engine over ``fno_infer`` / ``sfno_infer``.

    ``n_slots`` (the Engine protocol's slot pool) is the micro-batch
    width: each tick fills up to ``n_slots`` same-resolution requests
    into one fused batched forward.
    """

    kind = "operator"

    def __init__(
        self,
        params,
        cfg,
        model: str = "fno",
        policy: PrecisionPolicy = FULL,
        max_batch: int = 8,
        scheduler: str = "fcfs",
        telemetry: bool = False,
        autoprec=None,
        autoprec_every: int = 4,
        use_pallas: Optional[bool] = None,
        memo_window: int = 0,
        calibration_state: Optional[str] = None,
    ):
        if model not in ("fno", "sfno"):
            raise ValueError(f"model must be 'fno' or 'sfno', got {model!r}")
        # tuned spectral tiles: an explicit state path beats the
        # $REPRO_CALIBRATION_STATE env default; either way kernel tile
        # resolution (repro.kernels.ops) consults the calibration cache
        # and falls back to the static heuristic per miss
        self.calibration_state = calibration_state
        if calibration_state is not None:
            from repro.tune.cache import activate

            activate(calibration_state)
        super().__init__(
            Scheduler(
                scheduler,
                capacity_check=self._capacity_check,
                # spf for fields = smallest-grid-first
                cost=lambda r: float(np.prod(r.resolution, dtype=np.int64)),
            ),
            max_batch,
        )
        self.params = params
        # serving-side Pallas toggle: an explicit engine argument beats
        # the config's tri-state; the resolved flag is baked into every
        # per-resolution compiled step below
        from repro.kernels.ops import resolve_use_pallas

        self.use_pallas = resolve_use_pallas(
            use_pallas if use_pallas is not None else cfg.use_pallas)
        self.cfg = dataclasses.replace(cfg, use_pallas=self.use_pallas)
        self.model = model
        # online auto-precision: the controller owns the policy; its
        # telemetry comes from the same taps the trainer collects
        self.controller = autoprec
        self.policy = autoprec.policy() if autoprec is not None else policy
        self.max_batch = max_batch
        self.autoprec_every = autoprec_every
        self._telemetry_on = bool(telemetry or autoprec is not None)
        self._telem = None
        self._window_max_points = 0
        if self._telemetry_on:
            from repro.autoprec import TelemetryAggregator

            self._telem = TelemetryAggregator()
        self._infer = fno_infer if model == "fno" else sfno_infer
        self._steps: Dict[Tuple[int, ...], Any] = {}   # resolution -> jitted
        # content-hash memo: identical input fields (by value, under the
        # active policy) reuse the computed output instead of re-running
        # the forward.  Sound because inference is a pure function of
        # (params, field, policy) and micro-batching is per-sample exact
        # — a memoised answer is bit-identical to a recompute.  LRU over
        # the last ``memo_window`` distinct fields; 0 disables.
        self.memo_window = memo_window
        self._memo: OrderedDict[str, np.ndarray] = OrderedDict()
        self._memo_hits = 0
        self._memo_misses = 0
        self._memo_evictions = 0
        self._n_fields = 0
        self._n_points = 0
        self._n_batches = 0
        self._bucket_counts: Dict[str, int] = {}

    # -- admission -------------------------------------------------------------
    def _capacity_check(self, req: FieldRequest) -> Tuple[bool, str]:
        shape = tuple(np.shape(req.x))
        if len(shape) < 2:
            return False, f"field must be (channels, *spatial), got shape {shape}"
        if shape[0] != self.cfg.in_channels:
            return False, (
                f"field has {shape[0]} channels but the {self.model} config "
                f"expects {self.cfg.in_channels}"
            )
        if self.model == "sfno":
            want = (self.cfg.nlat, self.cfg.nlon)
            if shape[1:] != want:
                return False, (
                    f"sfno grid is fixed at {want}, got {shape[1:]}"
                )
        elif len(shape) - 1 != self.cfg.ndim:
            return False, (
                f"{self.cfg.ndim}-d FNO got a {len(shape) - 1}-d field"
            )
        return True, ""

    def _step_for(self, resolution: Tuple[int, ...]):
        fn = self._steps.get(resolution)
        if fn is None:
            policy = self.policy
            if self._telemetry_on:
                from repro.autoprec import TraceCollector, collecting

                def run(p, x):
                    col = TraceCollector()
                    with collecting(col):
                        y = self._infer(p, x, self.cfg, policy)
                    return y, col.snapshot()
            else:
                def run(p, x):
                    return self._infer(p, x, self.cfg, policy), {}
            fn = jax.jit(run)
            self._steps[resolution] = fn
        return fn

    # -- one engine tick -------------------------------------------------------
    def _busy(self) -> bool:
        return False  # fields finish within their tick; no carried state

    def _memo_partition(self, batch: List[FieldRequest]
                        ) -> Tuple[Optional[List[str]], List[int]]:
        """Split a bucket batch into memoised fields and the indices that
        still need compute.  In-batch duplicates collapse onto the first
        occurrence; only that one enters the device batch."""
        if self.memo_window <= 0:
            return None, list(range(len(batch)))
        keys = [content_key(np.asarray(r.x, np.float32)) for r in batch]
        compute: List[int] = []
        pending = set()
        for j, k in enumerate(keys):
            if k in self._memo:
                self._memo.move_to_end(k)
                self._memo_hits += 1
            elif k in pending:
                self._memo_hits += 1
            else:
                pending.add(k)
                self._memo_misses += 1
                compute.append(j)
        return keys, compute

    def _tick_impl(self) -> List[FieldRequest]:
        # phase spans inside serve/tick: take, stage, batch (dispatch,
        # fetch), answer; the engine's host path is the tick less fetch
        with obs_trace.span("serve/operator/take"):
            batch = self.scheduler.take(
                self.max_batch, self._ticks,
                bucket_key=lambda r: r.resolution)
        self._occupancy_sum += len(batch) / self.max_batch
        if not batch:
            return []
        res = batch[0].resolution
        with obs_trace.span("serve/operator/stage"):
            keys, compute = self._memo_partition(batch)
            if compute:
                xb = jnp.stack([jnp.asarray(batch[j].x, jnp.float32)
                                for j in compute])
                if len(compute) < self.max_batch:
                    # pad to the fixed micro-batch width: one compiled
                    # kernel per resolution (no recompiles as occupancy
                    # fluctuates), and the per-sample outputs stay
                    # independent of batch fill — a solo request and a
                    # full batch produce bit-identical fields.
                    pad = self.max_batch - len(compute)
                    xb = jnp.concatenate(
                        [xb, jnp.zeros((pad, *xb.shape[1:]), xb.dtype)])
        computed: Dict[str, np.ndarray] = {}
        if compute:
            with obs_trace.span("serve/operator/batch",
                                resolution="x".join(map(str, res)),
                                fill=len(compute)):
                with obs_trace.span("serve/operator/dispatch"):
                    yb, telem = self._step_for(res)(self.params, xb)
                with obs_trace.span("serve/operator/fetch"):
                    yb = np.asarray(yb)[:len(compute)]
            self._n_batches += 1
            if self._telem is not None:
                self._telem.update(telem)
                self._window_max_points = max(
                    self._window_max_points, int(np.prod(res, dtype=np.int64)))
            if (self.controller is not None
                    and self._n_batches % self.autoprec_every == 0):
                # budget against the finest grid the window saw: with mixed
                # resolution buckets, the Thm 3.1 bound of the finest field
                # is the binding one (coarser fields only have more headroom)
                changed = self.controller.update(
                    self._telem.take_window(),
                    grid_points=self._window_max_points or None)
                self._window_max_points = 0
                if changed:
                    # new overlay => new formats: drop the compiled buckets
                    # so the next tick traces under the updated policy —
                    # and the memo, whose entries were computed under the
                    # old formats
                    self.policy = self.controller.policy()
                    self._steps.clear()
                    self._memo.clear()
        with obs_trace.span("serve/operator/answer"):
            if compute:
                computed = {str(j) if keys is None else keys[j]: yb[pos]
                            for pos, j in enumerate(compute)}
            key = "x".join(map(str, res))
            self._bucket_counts[key] = (self._bucket_counts.get(key, 0)
                                        + len(batch))
            self._n_fields += len(batch)
            self._n_points += int(np.prod(res, dtype=np.int64)) * len(batch)
            finished = []
            for j, r in enumerate(batch):
                if keys is None:
                    r.y = computed[str(j)]
                else:
                    r.y = computed.get(keys[j], self._memo.get(keys[j]))
                finished.append(r)
            if keys is not None:
                # admit this tick's fresh results, then LRU-trim — after
                # the batch is answered, so an admission never evicts a
                # key a later request in the same tick still needs
                self._memo.update(computed)
                while len(self._memo) > self.memo_window:
                    self._memo.popitem(last=False)
                    self._memo_evictions += 1
        return finished

    def _extra_stats(self) -> Dict[str, Any]:
        out = {
            "model": self.model,
            "max_batch": self.max_batch,
            "policy": self.policy.name,
            "use_pallas": self.use_pallas,
            "fields_served": self._n_fields,
            "batches": self._n_batches,
            "avg_batch_fill": round(
                self._n_fields / (self._n_batches * self.max_batch), 4)
            if self._n_batches else 0.0,
            "buckets": dict(self._bucket_counts),
            "fields_per_s": round(self._n_fields / self._wall_s, 2)
            if self._wall_s else None,
            "points_per_s": round(self._n_points / self._wall_s, 2)
            if self._wall_s else None,
        }
        if self.memo_window > 0:
            seen = self._memo_hits + self._memo_misses
            out["memo"] = {
                "window": self.memo_window,
                "entries": len(self._memo),
                "hits": self._memo_hits,
                "misses": self._memo_misses,
                "hit_rate": round(self._memo_hits / seen, 4) if seen else 0.0,
                "evictions": self._memo_evictions,
            }
        if self._telem is not None:
            out["numerics"] = self._telem.counters()
        if self.controller is not None:
            out["autoprec"] = self.controller.describe()
        from repro.kernels.ops import tile_resolution_stats

        out["tiles"] = tile_resolution_stats()
        return out

    def _reset_extra_counters(self) -> None:
        """Memo + throughput counter hygiene (exposed through the obs
        registry's reset path; bench scripts call this between legs)."""
        self._memo_hits = 0
        self._memo_misses = 0
        self._memo_evictions = 0
        self._n_fields = 0
        self._n_points = 0
        self._n_batches = 0
        self._bucket_counts = {}
