"""Share of device busy time spent drawing and applying the TOS macro's
write errors (named scope ber_inject in detector_step): the scope's device
self time (bench.ber_cost.scope_self_s) over the busy time of the traced
window, %.  A program without the scope reports nothing."""
from bench import ber_cost


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    s = ber_cost.scope_self_s(ctx)
    return 100.0 * s / tr["busy_s"] if s else None
