"""Mean ms per returned chunk from the launch until its ring is sealed to the
reader (or drained inline): registry chunk_ring_wait_s over chunks_returned
(window deltas)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("chunks_returned")
    if not n or "chunk_ring_wait_s" not in d:
        return None
    return d["chunk_ring_wait_s"] / n * 1e3
