"""99th percentile of the chunk latency (see ``chunk_latency_p50_ms``)."""
import numpy as np


def read(ctx):
    lat = ctx["latency_ms"]
    return float(np.percentile(lat, 99)) if lat.size else None
