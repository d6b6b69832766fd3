"""Traffic for the benchmark's cells.

A traffic mix is a data file ``<mix>.json`` here; its ``kind`` names the
generator ``<kind>.py`` beside it, which turns the mix, the cell's own
numbers and the seed into an ``OpenSchedule`` (open loop: every event with
its scheduled creation time) or a ``ClosedSource`` (closed loop: each lane's
next chunks on demand).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class OpenSchedule:
    """Every event of an open-loop run, sorted by scheduled creation time.

    ``t_us`` counts microseconds from the start of the schedule; the
    measured window opens ``preroll_s`` into it and lasts ``seconds``."""

    lane: np.ndarray        # (n,) int32
    xy: np.ndarray          # (n, 2) int32, (col, row)
    t_us: np.ndarray        # (n,) int64, non-decreasing
    preroll_s: float
    seconds: float

    def lane_events(self, lane: int) -> np.ndarray:
        """Indices of one lane's events, in stream order."""
        return np.flatnonzero(self.lane == lane)


@dataclasses.dataclass
class ClosedSource:
    """A closed loop: each lane keeps at most ``inflight_chunks`` chunks fed
    but not yet returned, and every lane is topped up by ``refill_chunks``
    at once; ``chunks(lanes, first, n)`` gives chunks ``first .. first + n``
    of each lane as ``(xy (m, n*chunk, 2), ts (m, n*chunk))`` with the
    content's own timestamps."""

    inflight_chunks: int
    refill_chunks: int
    chunk: int
    content: object         # LaneContent

    def chunks(self, lanes, first, n: int):
        first = np.asarray(first, np.int64)
        return self.content.events(lanes, first * self.chunk, n * self.chunk)


def poisson_arrivals(k: int, span_us: int, rng) -> np.ndarray:
    """``k`` sorted arrival times in ``[0, span_us)``: the ``k`` gaps around
    the circle of length ``span_us`` are the exponential distribution's
    quantiles, in an order drawn from ``rng``, turned by a random phase.
    Poisson-like arrivals with the same gaps for every seed."""
    if k == 0:
        return np.zeros(0, np.int64)
    gaps = -np.log1p(-(np.arange(k) + 0.5) / k)
    gaps = rng.permutation(gaps) * (span_us / gaps.sum())
    t = (np.cumsum(gaps) + rng.uniform(0, span_us)) % span_us
    return np.sort(t).astype(np.int64)
