"""The general generator that every traffic file parameterises.

Training: an epoch-shuffled feed over a fixed pool of samples.  Every
batch of an epoch holds distinct rows; the order, not the set, depends
on the seed.

Serving: open-loop arrivals.  The window is cut into segments of
constant rate (one segment, or the traffic file's ``rate_phases``
cycled).  In each segment of length ``d`` at rate ``r`` the arrivals are
a Poisson process conditioned on its expected count ``round(r * d)``:
that many sorted uniform draws on the segment.  Every seed then offers
the same amount of work, in another order and with other gaps, so seeds
do not change the load.  Each request carries one field of one grid
(``grids``, shares of the requests, fixed counts), from a pool per grid
cycled in a seeded order; a fixed share (``repeat_share``) instead
repeats the field of one of the last ``repeat_lag`` requests of its
grid.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose)."""
    return np.random.default_rng([int(seed), int(stream)])


class EpochFeed:
    """``batch_at(step)`` -> indices of the pool rows of that step."""

    def __init__(self, pool_size: int, batch: int, seed: int):
        if batch > pool_size:
            raise ValueError(f"batch {batch} exceeds the pool of {pool_size}")
        self.pool_size, self.batch, self.seed = pool_size, batch, seed
        self._perms: dict = {}

    def _perm(self, epoch: int) -> np.ndarray:
        if epoch not in self._perms:
            self._perms = {epoch: rng(self.seed, 1000 + epoch).permutation(
                self.pool_size)}
        return self._perms[epoch]

    def indices(self, step: int) -> np.ndarray:
        per_epoch = self.pool_size // self.batch
        epoch, k = divmod(step, per_epoch)
        return self._perm(epoch)[k * self.batch:(k + 1) * self.batch]


def segments(rate_per_s: float, seconds: float,
             phases: Optional[Sequence[Sequence[float]]] = None
             ) -> List[Tuple[float, float, float]]:
    """(start, end, rate) of each constant-rate segment of the window.
    ``phases`` is a list of [seconds, relative rate], cycled from the
    window's start; the relative rates are scaled so that one cycle's
    mean rate is ``rate_per_s``."""
    if not phases:
        return [(0.0, float(seconds), float(rate_per_s))]
    dur = np.array([p[0] for p in phases], np.float64)
    rel = np.array([p[1] for p in phases], np.float64)
    if np.any(dur <= 0) or np.any(rel < 0) or not np.any(rel > 0):
        raise ValueError(f"rate_phases need positive lengths and a "
                         f"positive rate: {phases}")
    rel = rel * dur.sum() / float(np.dot(rel, dur))
    out, t, i = [], 0.0, 0
    while t < seconds:
        end = min(t + dur[i % len(dur)], float(seconds))
        out.append((t, end, float(rate_per_s * rel[i % len(dur)])))
        t, i = end, i + 1
    return out


def arrivals(rate_per_s: float, seconds: float, seed: int,
             phases: Optional[Sequence[Sequence[float]]] = None
             ) -> np.ndarray:
    """Due times (s from the window's start) of the open-loop requests."""
    g = rng(seed, 1)
    parts = [np.sort(g.uniform(a, b, int(round(r * (b - a)))))
             for a, b, r in segments(rate_per_s, seconds, phases)]
    return np.concatenate(parts)


def request_fields(n_requests: int, pool_size: int, seed: int,
                   stream: int = 2) -> np.ndarray:
    """Which pool field each request carries: the pool cycled in a seeded
    order, so consecutive requests carry distinct fields."""
    order = rng(seed, stream).permutation(pool_size)
    return order[np.arange(n_requests) % pool_size]


def requests(n_requests: int, pool_size: int, seed: int,
             shares: Sequence[float] = (1.0,), repeat_share: float = 0.0,
             repeat_lag: int = 64) -> Tuple[np.ndarray, np.ndarray]:
    """(grid index, pool index) of each request.  Each grid gets a fixed
    count, ``shares`` of the requests, in seeded positions; a fixed
    number of requests, ``repeat_share`` of them, repeat the field of a
    seeded one of the last ``repeat_lag`` requests of their grid."""
    shares = np.asarray(shares, np.float64) / float(np.sum(shares))
    counts = np.floor(shares * n_requests).astype(int)
    counts[-1] += n_requests - counts.sum()
    grid = np.repeat(np.arange(len(shares)), counts)
    if len(shares) > 1:
        grid = grid[rng(seed, 5).permutation(n_requests)]
    field = np.zeros(n_requests, np.int64)
    for k in range(len(shares)):
        at = np.flatnonzero(grid == k)
        field[at] = request_fields(len(at), pool_size, seed, 2 + 100 * k)
    n_rep = int(round(repeat_share * n_requests))
    if n_rep:
        g = rng(seed, 6)
        rank = np.zeros(n_requests, np.int64)   # position within the grid
        by_grid = [np.flatnonzero(grid == k) for k in range(len(shares))]
        for at in by_grid:
            rank[at] = np.arange(len(at))
        for i in np.sort(g.choice(np.arange(1, n_requests), n_rep,
                                  replace=False)):
            at, r = by_grid[grid[i]], rank[i]
            if r == 0:
                continue
            src = at[r - 1 - g.integers(min(repeat_lag, r))]
            field[i] = field[src]
    return grid, field
