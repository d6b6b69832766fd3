"""Registry ``d2h_bytes`` (window delta) over the events returned in the
window."""


def read(ctx):
    n = ctx["events_in_window"]
    return ctx["delta"]["d2h_bytes"] / n if n else None
