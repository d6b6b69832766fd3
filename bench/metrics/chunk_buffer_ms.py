"""Mean ms per returned chunk from feeding a chunk's last event until the pump
collects the chunk: registry chunk_buffer_wait_s over chunks_returned
(window deltas)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("chunks_returned")
    if not n or "chunk_buffer_wait_s" not in d:
        return None
    return d["chunk_buffer_wait_s"] / n * 1e3
