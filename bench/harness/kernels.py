"""The Pallas kernel calls of a jitted step, found in its jaxpr, and their
operations and bytes from the call shapes.

Each kernel family is a file ``bench/kernels/<family>.py`` that names the
kernel functions it covers (``PREFIX``) and prices one call from its
operand and result shapes (``flops``, ``hbm_bytes``).  A call no family
claims is reported by name and priced at nothing, so its time counts
against the roofline share rather than inflating it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

from . import registry


@dataclasses.dataclass(frozen=True)
class Aval:
    shape: tuple
    dtype: str
    itemsize: int


def size(a: Aval) -> int:
    return math.prod(a.shape)


def io_bytes(ins, outs) -> float:
    """Every operand read once and every result written once: the least
    HBM traffic of a call."""
    return float(sum(size(a) * a.itemsize for a in list(ins) + list(outs)))


@dataclasses.dataclass
class Call:
    name: str
    ins: List[Aval]
    outs: List[Aval]
    count: int


def _aval(v) -> Aval:
    a = v.aval
    return Aval(tuple(a.shape), str(a.dtype), a.dtype.itemsize)


def _sub_jaxprs(value):
    items = value if isinstance(value, (list, tuple)) else [value]
    for s in items:
        if hasattr(s, "eqns"):
            yield s
        elif hasattr(getattr(s, "jaxpr", None), "eqns"):
            yield s.jaxpr


def pallas_calls(jaxpr, count: int = 1, out=None) -> List[Call]:
    """Every ``pallas_call`` reachable from ``jaxpr``, with how many times
    one execution runs it (scan bodies multiply by their length; of the
    branches of a ``cond``, the one with the most calls is counted)."""
    out = [] if out is None else out
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            info = getattr(e.params["jaxpr"], "debug_info", None)
            name = getattr(info, "func_name", None) or "pallas_call"
            out.append(Call(name, [_aval(v) for v in e.invars],
                            [_aval(v) for v in e.outvars], count))
            continue
        mult = count * (e.params.get("length", 1)
                        if e.primitive.name == "scan" else 1)
        if "branches" in e.params:
            options = [pallas_calls(b.jaxpr, mult) for b in
                       e.params["branches"]]
            out.extend(max(options, key=len))
            continue
        for v in e.params.values():
            for sub in _sub_jaxprs(v):
                pallas_calls(sub, mult, out)
    return out


def step_calls(fn, *args) -> List[Call]:
    """The kernel calls of one execution of ``fn(*args)`` (traced from
    shapes only: nothing runs)."""
    import jax

    return pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)


def families() -> Dict[str, object]:
    return {name: registry.load_module("kernels", name)
            for name in registry.names("kernels", ".py")}


def price(calls: List[Call], fams=None) -> dict:
    """Totals over one step's calls, FLOPs and bytes, overall and per
    family, and the names of calls no family prices."""
    fams = families() if fams is None else fams
    out = {"flops": 0.0, "bytes": 0.0, "by_family": {}, "unpriced": []}
    for c in calls:
        fam = next((n for n, m in fams.items()
                    if c.name.startswith(m.PREFIX)), None)
        if fam is None:
            out["unpriced"].append(c.name)
            continue
        m = fams[fam]
        f = m.flops(c.name, c.ins, c.outs) * c.count
        b = m.hbm_bytes(c.name, c.ins, c.outs) * c.count
        d = out["by_family"].setdefault(fam, {"calls": 0, "flops": 0.0,
                                              "bytes": 0.0})
        d["calls"] += c.count
        d["flops"] += f
        d["bytes"] += b
        out["flops"] += f
        out["bytes"] += b
    return out


def least_seconds(calls: List[Call], peaks: dict, fams=None) -> dict:
    """Sum over calls of max(FLOPs / peak FLOP/s, bytes / peak bytes/s),
    and which bound holds for most of that time."""
    fams = families() if fams is None else fams
    compute = memory = 0.0
    for c in calls:
        fam = next((m for m in fams.values()
                    if c.name.startswith(m.PREFIX)), None)
        if fam is None:
            continue
        t_f = fam.flops(c.name, c.ins, c.outs) / peaks["bf16_flops"]
        t_b = fam.hbm_bytes(c.name, c.ins, c.outs) / peaks["hbm_bytes_per_s"]
        if t_f >= t_b:
            compute += t_f * c.count
        else:
            memory += t_b * c.count
    return {"seconds": compute + memory,
            "bound": "compute" if compute >= memory else "memory",
            "compute_s": compute, "memory_s": memory}
