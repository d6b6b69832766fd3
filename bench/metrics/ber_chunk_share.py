"""Chunks handed back that were folded at a Vdd with write errors, over
all chunks handed back: registry ``ber_chunks`` over ``chunks_returned``
(window deltas), %.  A program without that counter reports nothing."""


def read(ctx):
    d = ctx["delta"]
    if "ber_chunks" not in d or not d.get("chunks_returned"):
        return None
    return 100.0 * d["ber_chunks"] / d["chunks_returned"]
