"""Host microseconds per ``pool.poll`` call (the benchmark's own span
around each call), over the window."""


def read(ctx):
    s, calls, _ = ctx["spans"].get("poll", (0.0, 0, 0))
    return s / calls * 1e6 if calls else None
