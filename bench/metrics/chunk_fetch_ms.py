"""Mean ms per returned chunk from the seal until the ring's device_get returns
(the device's queue ahead of it included): registry chunk_fetch_wait_s over
chunks_returned (window deltas)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("chunks_returned")
    if not n or "chunk_fetch_wait_s" not in d:
        return None
    return d["chunk_fetch_wait_s"] / n * 1e3
