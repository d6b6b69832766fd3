"""Mean ms per returned chunk from the lane's result queue until poll returns
the chunk: registry chunk_handoff_wait_s over chunks_returned (window
deltas)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("chunks_returned")
    if not n or "chunk_handoff_wait_s" not in d:
        return None
    return d["chunk_handoff_wait_s"] / n * 1e3
