"""Mean ms per returned chunk from the fetch until the chunk is in its lane's
result queue (the reader's lock wait and the densify included): registry
chunk_distribute_wait_s over chunks_returned (window deltas)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("chunks_returned")
    if not n or "chunk_distribute_wait_s" not in d:
        return None
    return d["chunk_distribute_wait_s"] / n * 1e3
