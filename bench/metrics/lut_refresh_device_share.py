"""Share of device busy time spent in the Harris LUT refresh (named scope
lut_refresh in detector_step): the self time of the device operations under
that scope (bench.program_trace.from_run) over the busy time of the traced window, %."""
from bench import program_trace


def read(ctx):
    prog, tr = program_trace.from_run(ctx), ctx["trace"]
    if not prog or tr is None or tr["busy_s"] <= 0:
        return None
    s = prog["scope_self_s"].get("lut_refresh")
    return 100.0 * s / tr["busy_s"] if s else None
