"""Microseconds per event that ``PoolRuntime.feed`` waited for the pool
lock: registry ``feed_lock_wait_s`` over ``events_fed`` (window deltas)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("events_fed")
    if not n or "feed_lock_wait_s" not in d:
        return None
    return d["feed_lock_wait_s"] / n * 1e6
