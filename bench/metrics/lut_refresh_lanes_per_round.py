"""Lanes the executors' LUT refresh runs the Harris on per executed round:
registry ``lut_refresh_lane_runs`` over ``rounds_executed`` (window deltas).
A program without that counter reports nothing."""


def read(ctx):
    d = ctx["delta"]
    if "lut_refresh_lane_runs" not in d or not d["rounds_executed"]:
        return None
    return d["lut_refresh_lane_runs"] / d["rounds_executed"]
