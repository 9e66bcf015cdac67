"""Dtype-flow pass: static precision lint over traced jaxprs.

Traces model forwards (and full Trainer steps) with ``jax.make_jaxpr``
on abstract inputs — nothing executes — then walks every eqn, recursing
into sub-jaxprs (pjit, scan, custom-VJP, Pallas kernels), and checks the
paper's statically-decidable failure modes:

  half-accum-contract (error)   a half-precision ``dot_general`` whose
      accumulation dtype is not f32 *inside a spectral-contract scope* —
      the invariant Theorem 3.2's error model assumes (half storage,
      full accumulation) and the one the MXU gives for free.
  half-accum (warning)          the same outside spectral scopes (the
      dense AMP set accepts half accumulation the way torch.autocast
      does, but it is worth seeing).
  half-accum-reduce             ``reduce_sum``/``reduce_prod`` carried
      out at a half dtype (error inside contract scopes, else warning).
  fp16-overflow-risk (warning)  ``exp`` / ``x**n`` / norm-like reduces
      on an fp16 value with no intervening bounded op (stabiliser,
      tanh, clamp) — the §3 overflow mode.  fp16 only: bf16 keeps the
      f32 exponent range.
  round-trip-cast (warning)     ``f32 → half → f32`` with no compute
      between — wasted HBM bandwidth, unless it is the Thm 3.2 boundary
      quantiser (suppressed by site in ``analyze.toml``).
  fp32-resident (error)         a ``*/spectral/contract`` scope whose
      policy demotes storage to half but whose eqns never touch the
      half dtype — the declared precision does not hold in the lowered
      program (the §4 memory-efficiency failure).

Site attribution rides on ``jax.named_scope``: the precision helpers
(``SitePrecision.stabilize/quantize/contract``) and the Pallas wrappers
push their site address (slashes and all) onto the trace-time name
stack, and ``eqn.source_info.name_stack`` carries it here.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.precision.rules import site_matches

from .findings import ERROR, WARNING, Finding

_HALF = (jnp.dtype(jnp.float16), jnp.dtype(jnp.bfloat16))
_F16 = jnp.dtype(jnp.float16)

#: Primitives whose output is bounded O(1) regardless of input — they
#: clear the fp16 overflow taint (this is exactly what the paper's
#: pre-FFT stabilisers are: tanh / clamp families).
_BOUNDED_PRIMS = {
    "tanh", "erf", "erfc", "logistic", "sin", "cos", "sign", "clamp",
    "eq", "ne", "lt", "le", "gt", "ge", "is_finite",
}
#: Shape/layout/identity primitives through which boundedness flows.
_TRANSPARENT_PRIMS = {
    "reshape", "transpose", "broadcast_in_dim", "slice", "squeeze",
    "expand_dims", "rev", "copy", "convert_element_type", "neg", "abs",
    "real", "imag", "conj", "complex", "reduce_max", "reduce_min",
    "stop_gradient", "dynamic_slice", "gather", "pad", "concatenate",
    "select_n", "max", "min",
}
#: Products of bounded values stay bounded (sums too, up to a constant
#: factor irrelevant at fp16 range scale).
_COMBINING_PRIMS = {"mul", "add", "sub", "div"}
#: Primitives that can push a finite fp16 value past 65504.
_OVERFLOW_PRIMS = {"exp", "exp2", "expm1", "cosh", "sinh"}

_SITE_PATH_RE = re.compile(r"[A-Za-z0-9_]+(?:/[A-Za-z0-9_]+)+")


def _dtype_of(v) -> Optional[jnp.dtype]:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return jnp.dtype(dt) if dt is not None else None


def eqn_site(eqn, inherited: Optional[str]) -> Optional[str]:
    """The innermost precision-site address on the eqn's name stack.

    Transform frames stringify as ``jvp(...)`` / ``transpose(...)`` and
    einsum appends a spec scope (``ij,jk->ik``); plain slash-paths are
    exactly the site strings our ``named_scope`` wiring pushed."""
    s = str(eqn.source_info.name_stack)
    paths = _SITE_PATH_RE.findall(s)
    return paths[-1] if paths else inherited


def _sub_jaxprs(eqn):
    """Yield every sub-jaxpr in an eqn's params (pjit/scan/custom-VJP/
    Pallas/cond all stash them under different keys and shapes)."""
    for v in eqn.params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, jcore.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, jcore.Jaxpr):
                    yield item


class _Walk:
    """One recursive walk over a closed jaxpr, accumulating findings."""

    def __init__(self, policy, where: str):
        self.policy = policy
        self.where = where
        self.findings: List[Finding] = []
        #: contract-scope site -> set of dtypes seen on its eqns
        self.site_dtypes: Dict[str, set] = {}

    # -- finding helpers ----------------------------------------------------
    def _emit(self, check: str, severity: str, site: Optional[str],
              detail: str) -> None:
        self.findings.append(Finding(
            pass_name="dataflow", check=check, severity=severity,
            site=site, where=self.where, detail=detail,
        ))

    def _contract_severity(self, site: Optional[str]) -> str:
        if site is not None and site_matches("*/spectral/contract", site):
            return ERROR
        return WARNING

    # -- the walk ------------------------------------------------------------
    def walk(self, jaxpr: jcore.Jaxpr, inherited_site: Optional[str],
             bounded_in: Optional[Sequence[bool]] = None) -> None:
        bounded: Dict[Any, bool] = {}
        if bounded_in is not None and len(bounded_in) == len(jaxpr.invars):
            for var, b in zip(jaxpr.invars, bounded_in, strict=False):
                bounded[var] = b

        def is_bounded(v) -> bool:
            if isinstance(v, jcore.Literal):
                return True
            return bounded.get(v, False)

        producers: Dict[Any, Any] = {}
        consumers: Dict[Any, list] = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                producers[ov] = eqn
            for iv in eqn.invars:
                if not isinstance(iv, jcore.Literal):
                    consumers.setdefault(iv, []).append(eqn)

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            site = eqn_site(eqn, inherited_site)
            in_dts = [_dtype_of(v) for v in eqn.invars]
            out_dts = [_dtype_of(v) for v in eqn.outvars]

            # record dtypes seen per contract scope for fp32-resident
            if site is not None and site.endswith("/contract"):
                seen = self.site_dtypes.setdefault(site, set())
                seen.update(dt for dt in in_dts + out_dts if dt is not None)

            # 1. half accumulation on contractions
            if prim in ("dot_general", "conv_general_dilated"):
                pref = eqn.params.get("preferred_element_type")
                acc = jnp.dtype(pref) if pref is not None else out_dts[0]
                if acc in _HALF:
                    self._emit(
                        "half-accum-contract"
                        if self._contract_severity(site) == ERROR
                        else "half-accum",
                        self._contract_severity(site), site,
                        f"{prim} accumulates at {acc.name} "
                        f"(inputs {[d.name for d in in_dts if d]}); set "
                        f"preferred_element_type=float32",
                    )
            if prim in ("reduce_sum", "reduce_prod", "cumsum"):
                if in_dts and in_dts[0] in _HALF and out_dts[0] in _HALF:
                    self._emit(
                        "half-accum-reduce", self._contract_severity(site),
                        site,
                        f"{prim} carried out at {out_dts[0].name}",
                    )

            # 2. fp16 overflow-prone primitives on unbounded values
            risky = prim in _OVERFLOW_PRIMS or (
                prim == "integer_pow" and eqn.params.get("y", 1) >= 2
            )
            if risky and out_dts[0] == _F16:
                if any(dt == _F16 and not is_bounded(v)
                       for v, dt in zip(eqn.invars, in_dts, strict=True)):
                    self._emit(
                        "fp16-overflow-risk", WARNING, site,
                        f"{prim} on an unstabilized float16 value "
                        f"(no bounded op between source and use)",
                    )

            # 3. round-trip casts: f32 -> half -> f32, no compute between
            if (prim == "convert_element_type"
                    and in_dts and in_dts[0] is not None
                    and jnp.dtype(in_dts[0]) == jnp.dtype(jnp.float32)
                    and out_dts[0] in _HALF):
                outv = eqn.outvars[0]
                cons = consumers.get(outv, [])
                if cons and all(
                    c.primitive.name == "convert_element_type"
                    and _dtype_of(c.outvars[0]) == jnp.dtype(jnp.float32)
                    for c in cons
                ):
                    self._emit(
                        "round-trip-cast", WARNING, site,
                        f"float32 -> {out_dts[0].name} -> float32 with no "
                        f"compute between (wasted HBM round trip)",
                    )

            # -- propagate boundedness and recurse ---------------------------
            if prim in _BOUNDED_PRIMS:
                out_b = True
            elif prim in _TRANSPARENT_PRIMS:
                ins = [v for v in eqn.invars]
                out_b = bool(ins) and all(is_bounded(v) for v in ins)
            elif prim in _COMBINING_PRIMS:
                out_b = all(is_bounded(v) for v in eqn.invars)
            else:
                out_b = False
            for ov in eqn.outvars:
                bounded[ov] = out_b

            sub_bounded = [is_bounded(v) for v in eqn.invars]
            for sub in _sub_jaxprs(eqn):
                self.walk(
                    sub, site,
                    sub_bounded if len(sub.invars) == len(sub_bounded)
                    else None,
                )

    # -- post-walk checks ----------------------------------------------------
    def finish(self) -> List[Finding]:
        for site, dtypes in sorted(self.site_dtypes.items()):
            sp = self.policy.at(site)
            demoted = sp.spectral_dtype is not None
            if not demoted:
                continue
            half = jnp.dtype(sp.spectral_dtype)
            if half not in dtypes:
                self._emit(
                    "fp32-resident", ERROR, site,
                    f"policy {self.policy.name!r} demotes this site to "
                    f"{half.name} but no eqn under its scope touches that "
                    f"dtype — the declared precision does not hold",
                )
        return self.findings


def analyze_closed_jaxpr(closed: jcore.ClosedJaxpr, policy,
                         where: str) -> List[Finding]:
    w = _Walk(policy, where)
    w.walk(closed.jaxpr, None)
    return w.finish()


def trace_findings(fn, abstract_args: Sequence, policy,
                   where: str) -> List[Finding]:
    """``make_jaxpr`` the callable on abstract inputs and lint the trace."""
    closed = jax.make_jaxpr(fn)(*abstract_args)
    return analyze_closed_jaxpr(closed, policy, where)


# ---------------------------------------------------------------------------
# Model / trainer tracing
# ---------------------------------------------------------------------------


def tiny_model(model: str):
    """(config, params, abstract input) for a representative-but-cheap
    instance of each operator family.  TFNO = the CP-factorised FNO."""
    if model in ("fno", "tfno"):
        from repro.models import FNOConfig, init_fno

        cfg = FNOConfig(
            in_channels=1, out_channels=1, hidden_channels=8,
            lifting_channels=8, projection_channels=8, n_layers=2,
            modes=(4, 4),
            factorization="cp" if model == "tfno" else "dense",
        )
        params = init_fno(jax.random.PRNGKey(0), cfg)
        x = jax.ShapeDtypeStruct((2, 1, 16, 16), jnp.float32)
        return cfg, params, x
    if model == "sfno":
        from repro.models import SFNOConfig, init_sfno

        cfg = SFNOConfig(
            in_channels=1, out_channels=1, hidden_channels=8,
            lifting_channels=8, projection_channels=8, n_layers=2,
            nlat=8, nlon=16, lmax=4, mmax=4,
        )
        params = init_sfno(jax.random.PRNGKey(0), cfg)
        x = jax.ShapeDtypeStruct((2, 1, 8, 16), jnp.float32)
        return cfg, params, x
    raise ValueError(f"unknown model {model!r}; have fno | tfno | sfno")


def model_findings(model: str, policy, use_pallas: bool) -> List[Finding]:
    """Lint one model forward under one policy/kernel-path combination."""
    cfg, params, x = tiny_model(model)
    if model == "sfno":
        from repro.models import sfno_apply as apply_fn
    else:
        from repro.models import fno_apply as apply_fn
    import dataclasses as _dc

    cfg = _dc.replace(cfg, use_pallas=use_pallas)
    where = f"{model}/{policy.name}" + ("+pallas" if use_pallas else "")
    return trace_findings(
        lambda p, xx: apply_fn(p, xx, cfg, policy), (params, x),
        policy, where,
    )


def trainer_findings(policy, use_pallas: bool = False) -> List[Finding]:
    """Lint a full Trainer step (fwd + bwd + optimizer + loss scaling)."""
    from repro.models import FNOConfig, fno_apply, init_fno
    from repro.train import Trainer, TrainerConfig, relative_l2

    cfg = FNOConfig(
        in_channels=1, out_channels=1, hidden_channels=8,
        lifting_channels=8, projection_channels=8, n_layers=1,
        modes=(4, 4), use_pallas=use_pallas,
    )
    params = init_fno(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, batch, pol):
        return relative_l2(fno_apply(p, batch["x"], cfg, pol), batch["t"])

    tr = Trainer(loss_fn, params, TrainerConfig(total_steps=1))
    step = tr._build_step(policy)
    batch = {
        "x": jax.ShapeDtypeStruct((2, 1, 16, 16), jnp.float32),
        "t": jax.ShapeDtypeStruct((2, 1, 16, 16), jnp.float32),
    }
    where = f"trainer/{policy.name}" + ("+pallas" if use_pallas else "")
    return trace_findings(
        step, (tr.params, tr.opt_state, tr.scale_state, batch),
        policy, where,
    )


# ---------------------------------------------------------------------------
# Golden dtype traces (snapshot-test helper)
# ---------------------------------------------------------------------------

_TRACE_PRIMS = ("convert_element_type", "dot_general", "fft", "pallas_call",
                "integer_pow", "tanh")


def _trace_entries(jaxpr: jcore.Jaxpr, inherited: Optional[str],
                   out: List[str]) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        site = eqn_site(eqn, inherited)
        if prim in _TRACE_PRIMS:
            ins = ",".join(
                d.name for d in (_dtype_of(v) for v in eqn.invars)
                if d is not None
            )
            outs = ",".join(
                d.name for d in (_dtype_of(v) for v in eqn.outvars)
                if d is not None
            )
            entry = f"{prim}:{ins}->{outs}"
            if prim == "dot_general":
                pref = eqn.params.get("preferred_element_type")
                entry += f"@acc={jnp.dtype(pref).name if pref else outs}"
            if site:
                entry += f"@{site}"
            out.append(entry)
        for sub in _sub_jaxprs(eqn):
            _trace_entries(sub, site, out)


def dtype_trace(policy, use_pallas: bool = False,
                factorization: str = "dense",
                fuse_spectral: Optional[bool] = False) -> List[str]:
    """The exact cast/contract/FFT dtype sequence of one FNO spectral
    layer under ``policy`` — the golden-snapshot surface: a policy or
    model refactor that silently changes numerics changes this list.

    ``fuse_spectral`` defaults to *False* (not auto) so the staged
    traces stay pinned to the staged pipeline whatever the environment;
    pass ``True`` to snapshot the fused megakernel's dispatch."""
    from repro.core.spectral import init_spectral_weights, spectral_conv_apply

    params = init_spectral_weights(
        jax.random.PRNGKey(0), 4, 4, (4, 4), factorization)
    x = jax.ShapeDtypeStruct((2, 4, 16, 16), jnp.float32)
    closed = jax.make_jaxpr(
        lambda p, xx: spectral_conv_apply(
            p, xx, (4, 4), policy, use_pallas=use_pallas,
            site="model/spectral", fuse_spectral=fuse_spectral,
        )
    )(params, x)
    out: List[str] = []
    _trace_entries(closed.jaxpr, None, out)
    return out
