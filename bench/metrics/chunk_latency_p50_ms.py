"""Median chunk latency, ms: from the scheduled creation time of a chunk's
last event until its results came back from ``poll``, over every chunk
returned in the window (open loops)."""
import numpy as np


def read(ctx):
    lat = ctx["latency_ms"]
    return float(np.percentile(lat, 50)) if lat.size else None
