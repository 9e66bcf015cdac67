"""A serving mix that exists only as a traffic file runs with no edit
to the harness: on/off bursts, two grids in one queue, repeated fields
and the engine's memo, at a size a CPU test holds."""
from __future__ import annotations

from faults_common import run_cell, small  # noqa: E402


def test_burst_mixed_grids_with_memo():
    cell = small("fno_darcy.serve_saturated")
    cell["traffic"].update(rate_per_s=60, pool=8, check_sample=6,
                           rate_phases=[[0.25, 3.0], [0.25, 0.0]],
                           grids=[[[16, 16], 0.7], [[32, 32], 0.3]],
                           repeat_share=0.3, repeat_lag=8,
                           engine={"max_batch": 4, "memo_window": 16})
    r = run_cell(cell)
    w = r["window"]
    assert r["correct"], r["checked"]
    assert w["failed"] == 0 and w["answered"] > 0
    assert set(w["engine"]["buckets"]) == {"16x16", "32x32"}
    assert w["engine"]["memo"]["hits"] > 0
    assert r["values"]["serve_fields_per_s"] > 0
    assert r["in_window"] == (0, 0), "every shape was warmed up"
