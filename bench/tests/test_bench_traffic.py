"""The general generator: schedules repeat exactly from a seed, every
seed offers the same amount of work, and an epoch never repeats a row."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import traffic  # noqa: E402

SEEDS = [0, 7, 2 ** 31 + 11, 2 ** 33 + 5]


@pytest.mark.parametrize("seed", SEEDS)
def test_poisson_schedule_repeats_from_seed(seed):
    a = traffic.arrivals(350.0, 20.0, seed)
    b = traffic.arrivals(350.0, 20.0, seed)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 7000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 20.0
    np.testing.assert_array_equal(traffic.request_fields(len(a), 512, seed),
                                  traffic.request_fields(len(a), 512, seed))


def test_seeds_change_order_not_load():
    a = traffic.arrivals(350.0, 20.0, 1)
    b = traffic.arrivals(350.0, 20.0, 2)
    assert len(a) == len(b) and not np.array_equal(a, b)
    # exponential gaps at the offered rate
    gaps = np.diff(a)
    assert abs(gaps.mean() * 350.0 - 1.0) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1


@pytest.mark.parametrize("seed", SEEDS)
def test_epoch_feed_rows_distinct(seed):
    feed = traffic.EpochFeed(1024, 64, seed)
    epoch = np.concatenate([feed.indices(s) for s in range(16)])
    assert sorted(epoch.tolist()) == list(range(1024))
    np.testing.assert_array_equal(
        feed.indices(3), traffic.EpochFeed(1024, 64, seed).indices(3))
    assert not np.array_equal(feed.indices(0), feed.indices(16))


def test_request_fields_cycle_the_pool():
    which = traffic.request_fields(1200, 512, 3)
    assert len(set(which[:512].tolist())) == 512
    np.testing.assert_array_equal(which[:512], which[512:1024])


@pytest.mark.parametrize("seed", SEEDS)
def test_phases_keep_the_mean_rate_and_the_load(seed):
    """On/off bursts: every seed offers the same count in each phase, and
    the cycle's mean rate is the traffic file's."""
    phases = [[0.5, 4.0], [1.5, 0.0]]
    a = traffic.arrivals(200.0, 20.0, seed, phases)
    np.testing.assert_array_equal(a, traffic.arrivals(200.0, 20.0, seed,
                                                      phases))
    assert len(a) == 4000
    assert np.all(np.diff(a) >= 0)
    assert np.all(a % 2.0 < 0.5), "nothing arrives in an off phase"
    assert len(a) == len(traffic.arrivals(200.0, 20.0, seed + 1, phases))


def test_one_phase_is_the_constant_rate():
    np.testing.assert_array_equal(traffic.arrivals(50.0, 4.0, 3),
                                  traffic.arrivals(50.0, 4.0, 3, [[9.0, 2.0]]))
    assert len(traffic.arrivals(50.0, 4.0, 3, [[1.0, 2.0]])) == 200


@pytest.mark.parametrize("seed", SEEDS)
def test_requests_mix_grids_and_repeats(seed):
    n, pool = 3000, 256
    grid, field = traffic.requests(n, pool, seed, [0.5, 0.3, 0.2], 0.3, 64)
    g2, f2 = traffic.requests(n, pool, seed, [0.5, 0.3, 0.2], 0.3, 64)
    np.testing.assert_array_equal(grid, g2)
    np.testing.assert_array_equal(field, f2)
    assert np.bincount(grid).tolist() == [1500, 900, 600]
    assert field.min() >= 0 and field.max() < pool
    # a request repeats when it carries a field one of the last 64 of its
    # grid carried; fresh fields cycle a pool of 256, so only repeats do
    repeats = 0
    for k in range(3):
        seq = field[grid == k]
        repeats += sum(seq[i] in seq[max(0, i - 64):i]
                       for i in range(1, len(seq)))
    assert abs(repeats - 0.3 * n) < 0.02 * n
    plain_grid, plain = traffic.requests(n, pool, seed)
    assert not plain_grid.any()
    np.testing.assert_array_equal(plain, traffic.request_fields(n, pool, seed))
