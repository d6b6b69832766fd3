"""Device-side stream compaction of ring result slots.

Each ``(round, lane)`` slot of the result ring holds a dense ``(chunk,)``
score/keep pair, but corners are *sparse* — only a few percent of events
survive the threshold-ordinal test — so the drain's blocking ``device_get``
ships mostly ``-inf``.  This kernel packs each lane's kept events into
``(cap,)`` record buffers (event index + score) plus an i32 count *on
device*, so the reader thread fetches ``O(cap)`` bytes per slot-lane
instead of ``O(chunk)``: the near-memory thesis applied to the readout
path, the same way the macro never ships the dense surface off-chip.

One grid cell per block of 8 lanes (the f32 sublane tile); lanes are padded
to a multiple of 8 and cropped back.  Record ``j`` of every lane in the
block is extracted at once: the first kept event not yet recorded is the
row-wise minimum of a masked event-index iota, its score the row-wise max
of the scores masked to that one-hot column.  ``cap`` such vector passes
replace the oracle's cumsum-scatter (``ref.compact_ref``) and are bit-exact
against it: record ``j`` is the j-th kept event in stream order, and
records past ``cap`` are never extracted (the caller falls back to the
dense slot it still has — overflow is lossless by design, never a drop).

Unused record slots read ``idx=0, val=-inf`` so a host densify can
scatter the first ``min(count, cap)`` records into a ``-inf``/``False``
field and reproduce the dense slot bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["compact_slots_call"]

LANE_BLOCK = 8   # lanes per grid cell: the sublane tile of i32/f32 blocks


def _compact_kernel(scores_ref, keep_ref, idx_out, val_out, cnt_out, *,
                    n_events: int, cap: int):
    kept = keep_ref[...]                                      # (8, E) 0/1
    scores = scores_ref[...]
    ev = jax.lax.broadcasted_iota(jnp.int32, kept.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, idx_out.shape, 1)
    neg_inf = jnp.float32(-jnp.inf)

    def body(j, carry):
        todo, idx, val = carry
        first = jnp.min(jnp.where(todo > 0, ev, n_events), axis=1,
                        keepdims=True)                        # (8, 1)
        hit = ev == first
        v = jnp.max(jnp.where(hit, scores, neg_inf), axis=1, keepdims=True)
        put = slot == j
        idx = jnp.where(put, jnp.where(first < n_events, first, 0), idx)
        val = jnp.where(put, v, val)
        return jnp.where(hit, 0, todo), idx, val

    _, idx, val = jax.lax.fori_loop(
        0, cap, body,
        (kept, jnp.zeros(idx_out.shape, jnp.int32),
         jnp.full(val_out.shape, neg_inf, jnp.float32)),
    )
    idx_out[...] = idx
    val_out[...] = val
    cnt_out[...] = jnp.sum(kept, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def compact_slots_call(
    scores: jax.Array,    # (L, E) f32 dense slot scores
    keep: jax.Array,      # (L, E) i32 (0/1) dense keep flags
    *,
    cap: int,
    interpret: bool,
):
    """Compact ``L`` lane slots at once: one grid cell per 8 lanes.

    Returns ``(idx (L, cap) i32, val (L, cap) f32, count (L,) i32)``;
    ``count`` is the TOTAL kept (it may exceed ``cap`` — that is the
    caller's overflow signal, the records themselves stop at ``cap``).
    """
    l, e = scores.shape
    lp = -(-l // LANE_BLOCK) * LANE_BLOCK
    pad = ((0, lp - l), (0, 0))
    kernel = functools.partial(_compact_kernel, n_events=e, cap=cap)
    idx, val, cnt = pl.pallas_call(
        kernel,
        grid=(lp // LANE_BLOCK,),
        in_specs=[
            pl.BlockSpec((LANE_BLOCK, e), lambda i: (i, 0)),
            pl.BlockSpec((LANE_BLOCK, e), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((LANE_BLOCK, cap), lambda i: (i, 0)),
            pl.BlockSpec((LANE_BLOCK, cap), lambda i: (i, 0)),
            pl.BlockSpec((LANE_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lp, cap), jnp.int32),
            jax.ShapeDtypeStruct((lp, cap), jnp.float32),
            jax.ShapeDtypeStruct((lp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.pad(scores, pad), jnp.pad(keep.astype(jnp.int32), pad))
    return idx[:l], val[:l], cnt[:l, 0]
