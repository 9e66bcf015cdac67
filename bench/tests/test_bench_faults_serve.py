"""A serving cell's comparison catches an altered answer and the
low-precision control (see test_bench_faults_train.py for the method)."""
from __future__ import annotations

import numpy as np
import pytest
from faults_common import (SEED, caught_by, run_cell, small,  # noqa: E402
                           workloads)

from harness import check, registry  # noqa: E402

SERVE = workloads("serve")


@pytest.mark.parametrize("workload", SERVE)
def test_answer_altered(workload, monkeypatch):
    """Each answer comes back 10% too large."""
    from repro.serve import OperatorEngine

    base = run_cell(small(workload))
    assert base["correct"], base["checked"]
    tick = OperatorEngine._tick_impl

    def altered(self):
        done = tick(self)
        for r in done:
            r.y = r.y * np.float32(1.10)
        return done

    monkeypatch.setattr(OperatorEngine, "_tick_impl", altered)
    r = run_cell(small(workload))
    assert r["readings"]["field_gap"] > 0.09
    assert not r["correct"]
    assert caught_by(r["readings"], base["readings"],
                     small(workload)["limits"])


@pytest.mark.parametrize("workload", SERVE)
def test_control_fails(workload):
    from harness import serve

    cell = small(workload)
    data = registry.load_module("datasets", cell["config"]["data"])
    x = data.serving_fields(np.random.default_rng(SEED), cell["config"], 8)
    ref = serve.reference_answers(cell, SEED, x)
    low = serve.reference_answers(cell, SEED, x, "fp8_e4m3")
    ok, _ = check.judge(serve.answer_gaps(list(low), list(ref)),
                        cell["limits"])
    assert not ok
