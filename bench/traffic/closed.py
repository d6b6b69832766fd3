"""Closed loop: uniform cameras replay recorded content as fast as the pool
takes it (offline re-processing of a recorded fleet).  Every lane keeps at
most ``inflight_chunks`` chunks fed but not yet returned; once every lane
has room, each is fed ``refill_chunks`` more at once."""
from __future__ import annotations

from bench.traffic import ClosedSource
from bench.traffic.content import LaneContent


def build(mix: dict, cell: dict, config: dict, seed: int, seconds: float,
          rate_eps: float | None = None) -> ClosedSource:
    content = LaneContent(config["height"], config["width"], seed)
    return ClosedSource(int(mix["inflight_chunks"]), int(mix["refill_chunks"]),
                        int(config["detector"]["chunk"]), content)
