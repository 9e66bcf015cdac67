"""A training cell's comparison catches the faults it is there to catch.

At a size a CPU test holds, each run skips the harness's look for a chip
and drives the rest of a benchmark run (set-up, window, comparison with
the reference) with the timed path broken underneath: ``correct`` comes
out false, through a number that the sound run at the same size keeps
within its limit.  The control, the reference with every contraction
operand, spectrum and their cotangents stored as fp8 put in the
program's place, fails the limits at the cell's size on the chip; at
the size a test holds its error is smaller, so the test checks that it
departs from the reference many times farther than the sound program.
The chip readings the limits were set from are in PERF.md.
"""
from __future__ import annotations

import pytest
from faults_common import (SEED, caught_by, run_cell, small,  # noqa: E402
                           workloads)

from harness import check  # noqa: E402

TRAIN = workloads("train")
_SOUND: dict = {}


def sound(workload: str) -> dict:
    if workload not in _SOUND:
        _SOUND[workload] = run_cell(small(workload))["readings"]
    return _SOUND[workload]


@pytest.mark.parametrize("workload", TRAIN)
def test_state_left_unchanged(workload, monkeypatch):
    """The optimizer step returns its state unchanged."""
    from repro.optim import AdamW

    base = sound(workload)
    monkeypatch.setattr(AdamW, "update",
                        lambda self, g, s, p, lr_scale=1.0: (p, s))
    r = run_cell(small(workload))
    assert r["readings"]["change_gap"] == pytest.approx(1.0)
    assert not r["correct"]
    assert caught_by(r["readings"], base, small(workload)["limits"])


@pytest.mark.parametrize("workload", TRAIN)
def test_half_batch_left_out(workload, monkeypatch):
    """The loss is the mean over the first half of the batch alone."""
    from repro.train import losses

    base = sound(workload)
    whole = losses.relative_l2
    monkeypatch.setattr(losses, "relative_l2", lambda p, t, eps=1e-8: whole(
        p[: len(p) // 2], t[: len(t) // 2], eps))
    r = run_cell(small(workload))
    assert not r["correct"]
    assert caught_by(r["readings"], base, small(workload)["limits"])


@pytest.mark.parametrize("workload", TRAIN)
def test_control_fails(workload):
    """At the test size the control's loss departs from the reference's
    several times farther than the sound program's (at the cell's size
    that number fails it), and its backward pass yields gradients."""
    from harness import train

    cell = small(workload)
    sess = train.build(cell, SEED)
    batches = sess.checked
    train.release(sess)
    ref = train.reference_steps(cell, SEED, batches)
    low = check.training_readings(
        train.reference_steps(cell, SEED, batches, "fp8_e4m3"), ref)
    assert low["loss_gap"] > 4 * sound(workload)["loss_gap"]
    assert low["grad_gap"] < 0.5


def test_control_rounds_gradients_on_their_own_scale():
    """The control's fp8 storage rounds each cotangent with a scale of
    its own: a gradient far below the forward's scale is rounded onto
    e4m3's grid (3 mantissa bits), not flushed to zero."""
    import jax
    import jax.numpy as jnp

    from reference.common import fp8_rounding

    x = jnp.linspace(-3.0, 3.0, 64)
    w = 1e-7 * jnp.linspace(0.5, 1.5, 64)
    g = jax.grad(lambda v: jnp.sum(fp8_rounding(v) * w))(x)
    rel = jnp.abs(g - w) / w
    assert bool(jnp.all(g != 0))
    assert 0 < float(rel.max()) <= 2.0 ** -4


def test_change_gap_reads_parts_and_leaves_out_still_ones():
    """A leaf a family names in ``SUBLEAF_AXES`` is read per index of its
    axis, and a part whose reference gradient is nought to rounding is
    left out, by the same rule as a still leaf."""
    import jax.numpy as jnp

    from harness import train

    w = jnp.stack([jnp.ones((2, 2)), 1e-9 * jnp.ones((2, 2))], axis=-1)
    norms = train.leaf_norms({"a": jnp.ones((2, 3)), "spectral": {"w": w}},
                             {"spectral": -1})
    assert norms["['spectral']['w']#0"] == pytest.approx(2.0)
    assert norms["['spectral']['w']"] == pytest.approx(2.0)
    assert check.units(norms) == ["['a']", "['spectral']['w']#0",
                                  "['spectral']['w']#1"]
    assert check.moving(norms) == ["['a']", "['spectral']['w']#0"]
