"""The least time the window's completed work needs on this chip
(``bench.roofline``) over the device busy time in the trace, %."""
from bench import roofline


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["least_s"] or tr["busy_s"] <= 0:
        return None
    return 100.0 * roofline.share(ctx["least_s"], tr["busy_s"])
