"""Events whose results came back from ``poll`` in the window, over the
window's seconds."""


def read(ctx):
    return ctx["events_in_window"] / ctx["seconds"]
