"""Registry ``pump_stage_s`` over ``rounds_executed`` (window deltas), in
microseconds: host gather and H2D upload per pump round."""


def read(ctx):
    d = ctx["delta"]
    return d["pump_stage_s"] / d["rounds_executed"] * 1e6 if d["rounds_executed"] else None
