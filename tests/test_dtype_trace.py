"""Golden dtype-trace snapshots per registry policy.

``repro.analyze.dtype_trace`` records the exact cast / contraction /
FFT / kernel dtype sequence of one FNO spectral layer as traced under
each policy.  The sequences live in ``tests/golden/dtype_traces.json``;
a refactor that silently changes where a cast lands, which dtype a
contraction accumulates in, or whether the Pallas path quantises its
operands shows up here as a diff — set ``REPRO_REGEN_GOLDENS=1`` and
rerun to re-record after an *intentional* numerics change.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analyze import dtype_trace
from repro.precision.policy import POLICIES, get_policy

from helpers import force_tpu_transforms

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "dtype_traces.json")

_KEYS = [name + suffix
         for name in sorted(POLICIES)
         for suffix in ("", "+pallas", "+dft")]


def _load_goldens():
    with open(GOLDEN_PATH) as fh:
        return json.load(fh)


def _compute(key):
    name, _, suffix = key.partition("+")
    # "+pallas" is the Pallas pipeline over rfftn/irfftn (the CPU's);
    # "+dft" the same kernels between truncated-DFT matmuls (a TPU's)
    if suffix == "dft":
        with force_tpu_transforms():
            return dtype_trace(get_policy(name), use_pallas=True)
    return dtype_trace(get_policy(name), use_pallas=suffix == "pallas")


@pytest.fixture(scope="module")
def goldens():
    if os.environ.get("REPRO_REGEN_GOLDENS") == "1":
        gold = {key: _compute(key) for key in _KEYS}
        with open(GOLDEN_PATH, "w") as fh:
            json.dump(gold, fh, indent=2, sort_keys=True)
    return _load_goldens()


class TestGoldenTraces:
    def test_golden_file_covers_every_policy(self, goldens):
        assert sorted(goldens) == sorted(_KEYS)

    @pytest.mark.parametrize("key", _KEYS)
    def test_trace_matches_golden(self, goldens, key):
        assert _compute(key) == goldens[key], (
            f"dtype sequence for {key!r} drifted from the golden "
            f"snapshot; if the numerics change is intentional, "
            f"regenerate with REPRO_REGEN_GOLDENS=1")


class TestTraceInvariants:
    """Policy-level properties that must hold regardless of the exact
    golden sequence (these survive jax version bumps that reorder or
    rename eqns, where the snapshots would need regeneration)."""

    @pytest.mark.parametrize(
        "name", sorted(POLICIES) + [n + "+dft" for n in sorted(POLICIES)])
    def test_no_half_accumulation_on_pallas_path(self, name):
        """Every matmul accumulates at f32 or wider: the kernels' and, on
        a TPU's path ("+dft"), the truncated-DFT transforms'."""
        trace = _compute(name if "+" in name else name + "+pallas")
        for entry in trace:
            if entry.startswith("dot_general:"):
                acc = entry.split("@acc=")[1].split("@")[0]
                assert acc not in ("float16", "bfloat16"), entry

    def test_half_policy_touches_half_dtype(self):
        # mixed_fno_fp16 stores the spectrum at f16: the trace must
        # actually contain the half dtype (the fp32-resident check's
        # dynamic counterpart)
        trace = dtype_trace(get_policy("mixed_fno_fp16"), use_pallas=True)
        assert any("float16" in e for e in trace), trace

    def test_full_policy_is_all_f32(self):
        trace = dtype_trace(get_policy("full"))
        for entry in trace:
            assert "float16" not in entry and "bfloat16" not in entry, entry

    @pytest.mark.parametrize("name", sorted(POLICIES))
    def test_fused_path_spectrum_never_leaves_vmem(self, name):
        """A TPU's path computes only the retained modes: no ``fft``
        primitive, and no half array shaped like the full rfft spectrum
        outside the kernels — the boundary quantisation rounds the
        truncated spectrum only.  (The FFT path quantises the whole
        (16, 9) spectrum of the 16x16 layer before truncating it.)"""
        from repro.core.spectral import (init_spectral_weights,
                                         spectral_conv_apply)

        params = init_spectral_weights(jax.random.PRNGKey(0), 4, 4, (4, 4))
        x = jax.ShapeDtypeStruct((2, 4, 16, 16), jnp.float32)
        with force_tpu_transforms():
            assert not any(e.startswith("fft:") for e in _compute(
                name + "+dft"))
            closed = jax.make_jaxpr(lambda p, xx: spectral_conv_apply(
                p, xx, (4, 4), get_policy(name), use_pallas=True))(params, x)
        half = {jnp.dtype(jnp.float16), jnp.dtype(jnp.bfloat16)}
        for aval in _outside_kernels(closed.jaxpr):
            assert not (aval.dtype in half and aval.shape[-2:] == (16, 9)), (
                f"full-spectrum half array outside the kernels: {aval}")


def _outside_kernels(jaxpr):
    """Avals of every value a jaxpr and its sub-jaxprs compute, not
    descending into ``pallas_call`` bodies (what lives in VMEM)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        if eqn.primitive.name == "pallas_call":
            continue
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    yield from _outside_kernels(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _outside_kernels(sub)
