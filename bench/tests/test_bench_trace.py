"""The trace reduction on a recorded TPU v5e trace: nine steps of
``fno_darcy.train`` (batch 64, ``mixed_fno_bf16``), extracted by
``harness.trace.extract`` on the chip.  The expected numbers were read
off the recording by hand: the union of the operation intervals, and the
durations of the Mosaic custom calls, which nest inside the layer loop's
``while`` and enclose nothing themselves."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import layers, trace  # noqa: E402

DATA = BENCH / "tests" / "data" / "v5e_fno_darcy_train.trace.json.gz"


@pytest.fixture(scope="module")
def red():
    with gzip.open(DATA, "rt") as f:
        return trace.Reduction(json.load(f))


def test_window_and_busy(red):
    assert red.window_s == pytest.approx(2.074591776, abs=1e-9)
    assert red.busy_s == pytest.approx(2.029249231, abs=1e-9)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.0218561, rel=1e-5)


def test_exclusive_times_add_up_to_busy(red):
    # the loop's while encloses its body: exclusive times do not double count
    assert red.op_seconds() == pytest.approx(red.busy_s, rel=1e-9)


def test_kernel_time(red):
    # 216 Mosaic calls: 9 steps x 4 layers x 2 corners x 3 kernels
    calls = [n for _, n, _ in red.ops if layers.is_kernel(n)]
    assert len(calls) == 216
    assert red.op_seconds(layers.is_kernel) == pytest.approx(0.0265122,
                                                             abs=1e-9)


def test_idle_gaps_named_by_span(red):
    gaps = red.gaps()
    assert sum(g for _, g in gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    longest = red.breakdown()["idle_gaps"][0]
    assert longest[0] == "bench/step"
    assert longest[1] == pytest.approx(0.005851469, abs=1e-9)


def test_op_names_compacted():
    hlo = ('%spectral_contract_pallas.54 = (bf16[64,64,1024]{2,1,0}) '
           'custom-call(bf16[64,64,1024]{2,1,0} %copy.522), '
           'custom_call_target="tpu_custom_call"')
    assert trace.op_name(hlo) == \
        "%spectral_contract_pallas.54 custom-call:tpu_custom_call"
    assert trace.op_name("%copy.5 = f32[8]{0} copy(f32[8]{0} %p)") == \
        "%copy.5 copy"
