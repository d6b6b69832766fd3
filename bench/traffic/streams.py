"""Open loop of continuous streams: every camera streams all the time.

Each camera sends an equal share of the aggregate ``rate_eps`` with
Poisson-like arrivals (``poisson_arrivals``: the same gaps for every seed,
in an order and at a phase drawn from the seed), so the offered rate holds
no shape beyond the cell's fixed rate.  Events carry the content's positions
(``LaneContent``) at their scheduled times.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import OpenSchedule, poisson_arrivals
from bench.traffic.content import LaneContent


def build(mix: dict, cell: dict, config: dict, seed: int, seconds: float,
          rate_eps: float | None = None) -> OpenSchedule:
    rate = float(cell["rate_eps"] if rate_eps is None else rate_eps)
    cams = int(config["capacity"])
    preroll = float(mix["preroll_s"])
    span_us = int(round((preroll + seconds) * 1e6))
    per_cam = int(round(rate * span_us * 1e-6 / cams))
    rng = np.random.default_rng([seed, 2])

    t = np.concatenate([poisson_arrivals(per_cam, span_us, rng)
                        for _ in range(cams)])
    lane = np.repeat(np.arange(cams, dtype=np.int32), per_cam)
    content = LaneContent(config["height"], config["width"], seed)
    xy, _ = content.events(np.arange(cams), np.zeros(cams, np.int64), per_cam)
    order = np.argsort(t, kind="stable")
    return OpenSchedule(lane[order], xy.reshape(-1, 2)[order], t[order],
                        preroll, seconds)
