"""Host microseconds per event inside ``pool.feed`` (the benchmark's own
span around each call), over the window."""


def read(ctx):
    s, _, events = ctx["spans"].get("feed", (0.0, 0, 0))
    return s / events * 1e6 if events else None
