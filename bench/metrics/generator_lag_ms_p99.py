"""99th percentile, over events scheduled in the window, of the time the
generator fed an event minus its scheduled creation time, ms: how late the
load generator ran."""
import numpy as np


def read(ctx):
    lag = ctx.get("lag_ms")
    return float(np.percentile(lag, 99)) if lag is not None and lag.size else None
