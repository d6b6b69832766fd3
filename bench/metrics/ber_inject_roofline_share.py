"""The least time the window's write errors need on this chip
(bench.ber_cost, for the ``ber_chunks`` chunks folded at a BER above 0)
over the device self time of the ber_inject scope, %.  A program without
the counter or the scope reports nothing."""
from bench import ber_cost, harness, roofline


def read(ctx):
    n = ctx["delta"].get("ber_chunks")
    s = ber_cost.scope_self_s(ctx)
    if not n or not s:
        return None
    least = ber_cost.least_time_s(n, ber_cost.frame_pixels(),
                                  roofline.peaks(harness.device_info()["kind"]))
    return 100.0 * roofline.share(least, s)
