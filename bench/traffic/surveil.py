"""Open loop of a surveillance fleet: cameras watch mostly still scenes,
a few busy ones carry most of the events, and motion arrives in bursts.

Cameras share the aggregate ``rate_eps`` by Zipf(``zipf_s``) over their
rank: the camera of rank ``r`` (1-based) sends a share ``r^-s / sum_k
k^-s`` of the bursts (largest remainders rounding the count of each).  A
burst is ``burst_chunks`` whole chunks of the camera's content, its events
spaced at the content's native rate (``NATIVE_RATE_PER_US``), and burst
starts are Poisson-like (``poisson_arrivals``); a burst that would start
before the camera's previous one ends waits for it.  The burst times and
the rank-to-camera map are drawn from ``LAYOUT_SEED``, the same for every
seed; the seed draws only what the cameras see (``LaneContent``).
"""
from __future__ import annotations

import numpy as np

from bench.traffic import OpenSchedule, poisson_arrivals
from bench.traffic.content import NATIVE_RATE_PER_US, LaneContent

LAYOUT_SEED = 20170          # fixes burst times and ranks across seeds


def zipf_counts(total: int, n: int, s: float) -> np.ndarray:
    """``total`` split over ranks 1..n by Zipf(s), by largest remainders."""
    share = np.arange(1, n + 1, dtype=np.float64) ** -s
    want = total * share / share.sum()
    out = np.floor(want).astype(np.int64)
    extra = total - int(out.sum())
    out[np.argsort(out - want, kind="stable")[:extra]] += 1
    return out


def burst_offsets(events: int) -> np.ndarray:
    """Offsets (us) of a burst's events from its start, at the native rate."""
    return np.round(np.arange(events) / NATIVE_RATE_PER_US).astype(np.int64)


def layout(cams: int, bursts: int, span_us: int, burst_us: int,
           s: float) -> tuple:
    """``(camera, start_us)`` of every burst, sorted by start: the same for
    every seed."""
    rng = np.random.default_rng(LAYOUT_SEED)
    cam_of_rank = rng.permutation(cams)
    counts = zipf_counts(bursts, cams, s)
    cam, start = [], []
    for rank in np.flatnonzero(counts).tolist():
        t = poisson_arrivals(int(counts[rank]), span_us, rng)
        for k in range(1, t.size):          # a camera's bursts do not overlap
            t[k] = max(t[k], t[k - 1] + burst_us)
        cam.append(np.full(t.size, cam_of_rank[rank], np.int32))
        start.append(t)
    cam, start = np.concatenate(cam), np.concatenate(start)
    order = np.argsort(start, kind="stable")
    return cam[order], start[order]


def build(mix: dict, cell: dict, config: dict, seed: int, seconds: float,
          rate_eps: float | None = None) -> OpenSchedule:
    rate = float(cell["rate_eps"] if rate_eps is None else rate_eps)
    cams = int(config["capacity"])
    events = int(mix["burst_chunks"]) * int(config["detector"]["chunk"])
    preroll = float(mix["preroll_s"])
    span_us = int(round((preroll + seconds) * 1e6))
    offsets = burst_offsets(events)
    bursts = int(round(rate * span_us * 1e-6 / events))
    cam, start = layout(cams, bursts, span_us, int(offsets[-1]) + 1,
                        float(mix["zipf_s"]))

    content = LaneContent(config["height"], config["width"], seed)
    xy = np.empty((cam.size, events, 2), np.int32)
    for c in np.unique(cam).tolist():
        mine = np.flatnonzero(cam == c)
        got, _ = content.events([c], [0], mine.size * events)
        xy[mine] = got.reshape(mine.size, events, 2)
    t = (start[:, None] + offsets[None, :]).reshape(-1)
    lane = np.repeat(cam, events)
    order = np.argsort(t, kind="stable")
    return OpenSchedule(lane[order], xy.reshape(-1, 2)[order], t[order],
                        preroll, seconds)
