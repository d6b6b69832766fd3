"""Fused chunk-step megakernel: the whole per-chunk inner pipeline in one
``pallas_call``.

The unfused serving step lowers as separate XLA ops with an HBM round-trip
of the surface between each stage:

    STCF (read SAE, write SAE + keep) -> TOS update (read/write TOS)
    -> BER injection (read/write TOS again) -> LUT score gather

This kernel executes STCF support check, TOS patch decrement / threshold /
centre-set, BER write-error application, and the per-event Harris-LUT score
lookup in one kernel instance, keeping the TOS, the SAE and the LUT resident
in VMEM for the whole chain — the software twin of the paper's near-memory
macro, which wins its 24.7x latency by never letting the surface leave SRAM
between update, compare and write-back.

Indexing that Mosaic lowers: every per-event access is a row window of a
whole-array VMEM ref whose start is a multiple of 8 (``pl.ds`` with
``pl.multiple_of``), full width, and an iota mask then selects the pixels
the event touches — the 3x3 STCF window, the LUT pixel, the P x P TOS
patch.  No ``dynamic_slice`` on values, no unaligned sublane index, no
scalar VMEM read.  Only the rows an event touches are read and written back.

Bit-exactness contract (property-tested in ``tests/test_fused_step.py``):

  * STCF: events replay *sequentially* — event ``i`` reads its 3x3 window
    from ``max(SAE_pre, earlier in-chunk valid writes)``, which equals
    ``stcf_chunked``'s ``surf_recent | chunk_recent`` disjunction exactly:
    recency is monotone in the timestamp, so the max over the two sources is
    recent iff either is, and rebased device timestamps are non-negative so
    a valid in-chunk write always dominates ``_NEVER``.  The accumulated
    per-pixel max equals the chunked scatter-max.  Window pixels outside the
    sensor are masked out (rows above / columns left of the origin are never
    in the window block) or read the ``_NEVER`` pad (== the oracle's
    in-bounds mask).
  * TOS: the in-loop decrement/threshold/centre-set gated on ``keep`` is the
    sequential TOS spelling, property-equal to ``tos_update_batched``.
  * BER: the Bernoulli bit draws happen *outside* (``ber.write_error_bits``,
    same key-split discipline as ``inject_write_errors_at``); the kernel
    applies the encode5/xor/decode5 chain to its VMEM surface, replicating
    ``ber.apply_write_errors`` exactly.
  * Scores: ``where(keep, LUT[y, x], -inf)``; the LUT pixel is the max of
    its row block masked to ``-inf`` elsewhere, which returns the stored
    float unchanged.  The ``lut_ready`` gate stays outside (scalar select).

Events stream through SMEM; ``keep``/``scores`` are SMEM outputs written
once per event.  Under ``vmap`` (the pool's lane axis) Pallas adds a grid
axis over lanes, one lane per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ber import _BASE
from repro.core.stcf import _NEVER

__all__ = ["fused_chunk_step_call", "RS", "SUBLANES", "LANES",
           "window_rows"]

RS = 1  # STCF neighbourhood radius (3x3, fixed — matches stcf.DEFAULT_RADIUS)
SUBLANES = 8   # row alignment of a dynamic VMEM window (f32/i32 tiling)
LANES = 128    # surfaces are padded to a multiple of this many columns


def window_rows(radius: int) -> int:
    """Rows of the aligned block that holds any ``2*radius + 1``-row window:
    the window may start anywhere inside the block's first 8 rows."""
    return -(-(2 * radius + SUBLANES) // SUBLANES) * SUBLANES


def _row_block(y, radius: int, rows: int, height: int):
    """8-aligned start of the ``rows``-row block holding rows
    ``[y - radius, y + radius]`` clipped to ``[0, height)``."""
    start = (jnp.maximum(y - radius, 0) // SUBLANES) * SUBLANES
    return pl.multiple_of(jnp.minimum(start, height - rows), SUBLANES)


def _fused_kernel(
    ev_ref,            # (E, 4) int32 SMEM: x, y, ts, valid
    sae_ref,           # (hp, wp) int32 VMEM (pad = _NEVER)
    lut_ref,           # (hp, wp) f32 VMEM
    tos_ref,           # (hp, wp) int32 VMEM
    *refs,             # [bits_ref, ber_ref] if inject, then the 4 outputs
    patch: int,
    th: int,
    support: int,
    tw: int,
    stcf_enabled: bool,
    inject: bool,
):
    if inject:
        bits_ref, ber_ref, tos_out, sae_out, keep_out, scores_out = refs
    else:
        tos_out, sae_out, keep_out, scores_out = refs

    hp, wp = tos_ref.shape
    r = (patch - 1) // 2
    rows_s = window_rows(RS)
    rows_t = window_rows(r)

    def iotas(n):
        return (jax.lax.broadcasted_iota(jnp.int32, (n, wp), 0),
                jax.lax.broadcasted_iota(jnp.int32, (n, wp), 1))

    tos_out[...] = tos_ref[...]
    sae_out[...] = sae_ref[...]

    def body(i, carry):
        x = ev_ref[i, 0]
        y = ev_ref[i, 1]
        t = ev_ref[i, 2]
        ok = ev_ref[i, 3] > 0

        if stcf_enabled:
            # 3x3 window of the *running* SAE centred at (y, x); the centre
            # pixel is excluded from the support count.
            a = _row_block(y, RS, rows_s, hp)
            blk = sae_out[pl.ds(a, rows_s), :]
            ri, ci = iotas(rows_s)
            rows = a + ri
            near = (jnp.abs(rows - y) <= RS) & (jnp.abs(ci - x) <= RS)
            centre = (rows == y) & (ci == x)
            recent = (near & jnp.logical_not(centre)
                      & (t - blk <= tw) & (blk > _NEVER // 2))
            cnt = jnp.sum(recent.astype(jnp.int32))
            keep = ok & (cnt >= support)
            # SAE refresh: scatter-max at the centre, valid events only.
            sae_out[pl.ds(a, rows_s), :] = jnp.where(
                centre & ok, jnp.maximum(blk, t), blk
            )
        else:
            keep = ok

        # LUT read at (y, x): the max over a -inf-masked row block returns
        # the stored float bit-for-bit.
        a8 = pl.multiple_of((y // SUBLANES) * SUBLANES, SUBLANES)
        lrow = lut_ref[pl.ds(a8, SUBLANES), :]
        ri, ci = iotas(SUBLANES)
        hit = ((a8 + ri) == y) & (ci == x)
        v = jnp.max(jnp.where(hit, lrow, jnp.float32(-jnp.inf)))
        keep_out[0, i] = keep.astype(jnp.int32)
        scores_out[0, i] = jnp.where(keep, v, jnp.float32(-jnp.inf))

        # TOS patch op gated on keep: decrement the P x P neighbourhood with
        # threshold clamp, then set the centre.
        a = _row_block(y, r, rows_t, hp)
        surf = tos_out[pl.ds(a, rows_t), :]
        ri, ci = iotas(rows_t)
        rows = a + ri
        inside = (jnp.abs(rows - y) <= r) & (jnp.abs(ci - x) <= r) & keep
        dec = surf - 1
        dec = jnp.where(dec >= th, dec, 0)
        surf = jnp.where(inside, dec, surf)
        centre = (rows == y) & (ci == x) & keep
        tos_out[pl.ds(a, rows_t), :] = jnp.where(centre, 255, surf)
        return carry

    jax.lax.fori_loop(0, ev_ref.shape[0], body, 0)

    if inject:
        # ber.apply_write_errors on the VMEM surface: 5-bit storage code,
        # xor with the precomputed Bernoulli bits, decode; value-0 pixels
        # skip write-back, and ber == 0 is an exact identity select.
        surf = tos_out[...]
        code = jnp.where(surf > _BASE, surf - _BASE, 0)
        flipped = jnp.bitwise_xor(code, bits_ref[...])
        res = jnp.where(code > 0, flipped, code)
        dec5 = jnp.where(res > 0, res + _BASE, 0)
        tos_out[...] = jnp.where(ber_ref[0, 0] > 0.0, dec5, surf)


def _vmem_limit(hp: int, wp: int, inject: bool) -> int:
    """Scoped VMEM for the resident surfaces (x2: pipelined buffers when a
    ``vmap`` adds a grid axis) plus headroom for the window temporaries."""
    planes = 6 + (1 if inject else 0)   # tos/sae in+out, lut, bits
    return min(2 * planes * hp * wp * 4 + (8 << 20), 120 << 20)


@functools.partial(
    jax.jit,
    static_argnames=(
        "patch", "th", "support", "tw", "stcf_enabled", "interpret"
    ),
)
def fused_chunk_step_call(
    tos_pad: jax.Array,     # (hp, wp) int32
    sae_pad: jax.Array,     # (hp, wp) int32, _NEVER-padded
    lut_pad: jax.Array,     # (hp, wp) f32
    ev: jax.Array,          # (E, 4) int32: x, y, ts, valid
    bits_pad: jax.Array | None,  # (hp, wp) int32 BER bits, or None
    ber: jax.Array | None,       # f32 traced BER scalar, or None
    *,
    patch: int,
    th: int,
    support: int,
    tw: int,
    stcf_enabled: bool,
    interpret: bool,
):
    """One fused chunk step over padded surfaces.

    ``hp`` must be a multiple of 8 and at least ``window_rows`` of both
    radii; ``wp`` a multiple of 128 (``ops.fused_step_op`` pads and crops).
    Returns ``(tos, sae, keep_i32, scores)`` with the surfaces still padded;
    ``keep``/``scores`` are (1, E) and exact (2-D so that a ``vmap``-added
    lane axis leaves whole trailing SMEM dims).  BER injection is compiled in
    iff ``bits_pad``/``ber`` are given.
    """
    hp, wp = tos_pad.shape
    e = ev.shape[0]
    inject = bits_pad is not None
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    in_specs = [smem, vmem, vmem, vmem]
    args = [ev, sae_pad, lut_pad, tos_pad]
    if inject:
        in_specs += [vmem, smem]
        args += [bits_pad, ber.reshape((1, 1)).astype(jnp.float32)]

    kernel = functools.partial(
        _fused_kernel,
        patch=patch, th=th, support=support, tw=tw,
        stcf_enabled=stcf_enabled, inject=inject,
    )
    return pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=[vmem, vmem, smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((hp, wp), jnp.int32),
            jax.ShapeDtypeStruct((hp, wp), jnp.int32),
            jax.ShapeDtypeStruct((1, e), jnp.int32),
            jax.ShapeDtypeStruct((1, e), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(hp, wp, inject)
        ),
        interpret=interpret,
    )(*args)
