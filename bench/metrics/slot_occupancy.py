"""Share of uploaded lane slots that carry an event, %: registry
``h2d_valid_events`` over ``h2d_event_slots`` (window deltas)."""


def read(ctx):
    d = ctx["delta"]
    return 100.0 * d["h2d_valid_events"] / d["h2d_event_slots"] if d["h2d_event_slots"] else None
