"""Mean ms per returned chunk from the collect until its block's executor
launches: registry chunk_stage_wait_s over chunks_returned (window deltas)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("chunks_returned")
    if not n or "chunk_stage_wait_s" not in d:
        return None
    return d["chunk_stage_wait_s"] / n * 1e3
