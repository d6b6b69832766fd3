"""Stateful streaming detector core: the explicit ``DetectorState`` pytree
and the pure ``detector_init`` / ``detector_step`` / ``detector_scan``
functions every execution mode shares.

The paper's detector is *online* — events arrive continuously and the TOS is
updated incrementally — so the state that persists between arrivals is made
explicit here instead of living inside one monolithic pipeline function:

  ``DetectorState``   — surface, SAE, Harris LUT, lut_ready flag, PRNG key,
                        chunk cursor, streaming DVFS rate estimator, and
                        on-device kept/energy/latency accumulators.
  ``ChunkInput``      — one fixed-size chunk of events plus its per-chunk
                        hardware riders (BER, energy/latency coefficients)
                        for the host-precomputed DVFS modes.
  ``ChunkOutput``     — per-event scores/keep mask plus the per-chunk kept
                        count and (online mode) chosen operating point.
  ``RingState``       — fixed-capacity on-device result ring the pool's
                        K-round executor pushes per-round outputs into, so
                        the host fetches once per drain instead of once per
                        round (``ring_init`` / ``ring_push``).
  ``CompactRingState``— the ring plus per-slot compacted kept-corner
                        records, so drains fetch ``O(cap)`` bytes per
                        slot-lane instead of the dense slab
                        (``compact_ring_init`` / ``ring_push_compact``).

``detector_step`` folds exactly one chunk:

    STCF denoise -> [online DVFS picks the operating point] -> TOS update
    -> [BER injection at the operating voltage] -> score events against the
    latest Harris LUT -> (every Nth chunk) refresh the LUT.

``detector_scan`` is ``lax.scan`` of that step over pre-stacked chunks — the
batch path.  The serving layer (``repro.serve``) instead calls the step one
chunk at a time (``StreamingDetector``) or vmapped over many per-camera
states (``DetectorPool``); all three spellings run the *same* pure function,
so equivalence is structural rather than hoped-for.

DVFS has two modes:

  * precomputed (``cfg.dvfs_online=False``): per-chunk Vdd/BER/energy ride
    in as ``ChunkInput`` data, computed on the host from the whole stream
    (requires the stream upfront — batch only).
  * online (``cfg.dvfs_online=True``): the step carries a streaming rate
    estimator (``dvfs.RateState``) and picks the operating point *inside*
    the fold from chunk timestamps — no host knowledge of the future, so it
    works for live streams.  Property-tested equal to the precomputed path
    on full streams.

All functions are pure; ``cfg`` is a ``repro.core.pipeline.PipelineConfig``
(duck-typed here to avoid a circular import) and must be hashable/static.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ber as ber_mod
from repro.core import dvfs as dvfs_mod
from repro.core import harris as harris_mod
from repro.core import stcf as stcf_mod
from repro.core import tos as tos_mod

__all__ = [
    "ControlState",
    "DetectorState",
    "ChunkInput",
    "ChunkOutput",
    "RingState",
    "CompactRingState",
    "control_init",
    "detector_init",
    "detector_step",
    "detector_scan",
    "donation_ok",
    "lut_due",
    "refresh_ladder",
    "refresh_luts",
    "rate_estimate_eps",
    "ring_init",
    "ring_push",
    "compact_ring_init",
    "ring_push_compact",
    "ring_slot_order",
    "select_update",
    "chunk_input_riders",
]


def donation_ok(tree) -> bool:
    """True iff every leaf of ``tree`` lives exclusively on non-CPU devices,
    i.e. buffer donation would actually buy an in-place accelerator update.

    Donation decisions must key off the *actual placement* of the state that
    will be donated — NOT ``jax.default_backend()``: a session explicitly
    placed on CPU under a GPU default backend must not donate host buffers
    (the CPU runtime ignores donation, so a stale-keyed cache entry silently
    loses the optimization), and a state placed on an accelerator under a
    CPU default backend should still donate.  Leaves without a ``devices()``
    method (e.g. host numpy arrays about to be uploaded) disqualify the tree
    — donating what is not yet device-resident is meaningless.
    """
    devs: set = set()
    for leaf in jax.tree.leaves(tree):
        get = getattr(leaf, "devices", None)
        if not callable(get):
            return False
        devs |= set(get())
    return bool(devs) and all(d.platform != "cpu" for d in devs)


def rate_estimate_eps(prev1, prev2, dvfs_cfg) -> float:
    """Events/s read-out of the streaming rate estimator's closed pair.

    The single formula both rate sources share (host scalar math):

      * the estimator carried in ``DetectorState.rate`` (``prev1``/
        ``prev2`` fetched off device) — only integrated by the step in
        online-DVFS mode;
      * the serving layer's host twin, which bins *fed* timestamps with
        the same half-window rotation so rate-aware scheduling works for
        every servable config without a device sync.

    Mirrors ``dvfs.online_vdd_from_chunk_ts``'s read exactly: both closed
    counters saturate at ``2^counter_bits - 1``, and the rate divide is
    float32 like the device path (the estimate an operating-point choice
    would see), scaled from events/us to events/s.
    """
    sat = (1 << dvfs_cfg.counter_bits) - 1
    pair = min(int(prev1), sat) + min(int(prev2), sat)
    est_mpus = np.float32(pair) / np.float32(dvfs_cfg.tw_us)
    return float(est_mpus) * 1e6


class ControlState(NamedTuple):
    """Per-stream degradation knobs carried as *runtime data*, not config.

    Everything the serving layer's overload ladder can move lives here, so
    turning a knob is an ``at[lane].set`` on state leaves — the compiled
    executors never respecialize (the knobs are traced values, never
    constants baked into an executable).  Each knob has a pure-config
    oracle it is property-tested bit-exact against:

      ``lut_every`` — Harris LUT refresh interval in chunks; oracle is a
                      config with that ``lut_every_chunks``.
      ``vdd_cap``   — highest selectable DVFS operating-point index;
                      oracle is ``DvfsConfig(vdd_ceiling=...)`` (clamping
                      the chosen index == truncating the table, because
                      the picker takes the lowest index that fits else the
                      highest entry).  Inert in fixed-Vdd mode — there is
                      no in-step controller to re-point, matching the
                      paper's fixed-voltage baseline.
      ``shed``      — suspend LUT refresh entirely (the ladder's deepest
                      in-state rung; refresh resumes the chunk after the
                      flag clears); oracle is a refresh interval longer
                      than the stream.
    """

    lut_every: jax.Array    # int32 scalar — LUT refresh interval (>= 1)
    vdd_cap: jax.Array      # int32 scalar — max operating-point index
    shed: jax.Array         # bool scalar  — suspend LUT refresh


class DetectorState(NamedTuple):
    """Everything the detector carries between chunks — a single pytree.

    Rides in a ``lax.scan`` carry, a ``vmap`` lane (one per camera), or a
    host-held session object; ``jax.device_get`` of it is a checkpoint.
    """

    surface: jax.Array      # uint8  (H, W)  — the TOS
    sae: jax.Array          # int32  (H, W)  — STCF last-timestamp surface
    lut: jax.Array          # float32 (H, W) — latest Harris response
    lut_ready: jax.Array    # bool scalar    — has the LUT ever been built?
    key: jax.Array          # PRNG key       — BER injection draws
    chunk_idx: jax.Array    # int32 scalar   — chunks folded so far (cursor)
    rate: dvfs_mod.RateState  # streaming DVFS rate estimator carry
    kept_total: jax.Array   # int32 scalar   — events surviving STCF so far
    energy_pj: jax.Array    # float32 scalar — on-device energy accumulator
    latency_ns: jax.Array   # float32 scalar — on-device latency accumulator
    ctrl: ControlState      # per-stream degradation knobs (runtime data)


class ChunkInput(NamedTuple):
    """One fixed-size event chunk plus its host-precomputed hardware riders.

    ``ts`` is chunk-relative int32 microseconds: the host rebases the int64
    stream timestamps by a per-stream base aligned to a DVFS half-window
    multiple, so device arithmetic (STCF recency diffs, DVFS window indices)
    never sees an int64 and never wraps for streams up to ~35 minutes past
    the base (the serving layer re-bases long sessions explicitly).

    In online-DVFS mode ``ber``/``energy_coef``/``latency_coef`` are ignored
    (pass zeros); the step derives them from the chosen operating point.
    """

    xy: jax.Array            # (chunk, 2) int32
    ts: jax.Array            # (chunk,)   int32, chunk-relative microseconds
    valid: jax.Array         # (chunk,)   bool
    ber: jax.Array           # f32 scalar — write BER for this chunk
    energy_coef: jax.Array   # f32 scalar — pJ per kept event
    latency_coef: jax.Array  # f32 scalar — ns per kept event


class ChunkOutput(NamedTuple):
    scores: jax.Array        # (chunk,) f32 — Harris LUT read per event
    keep: jax.Array          # (chunk,) bool — survived STCF
    n_kept: jax.Array        # i32 scalar
    vdd_idx: jax.Array       # i32 scalar — operating point (online mode)


class RingState(NamedTuple):
    """Fixed-capacity on-device result ring for multi-round pool execution.

    The pool's K-round executor pushes one slot per *active* round (vmapped
    ``ChunkOutput`` over the lane axis, plus the round's lane mask and
    per-lane valid counts) instead of syncing the host every round; the host
    performs ONE blocking fetch per drain and walks the slots oldest-first.
    All cursors are device scalars so the ring rides inside ``lax.scan``
    without host round-trips.

    Overflow semantics are mechanical here and policy lives in the caller:
    pushing onto a full ring overwrites the oldest slot and increments
    ``dropped`` (the pool's ``"drain"`` policy pre-drains so this never
    fires; its ``"drop_oldest"`` real-time policy lets it count lost
    rounds).  ``dropped`` counts drops since the owner last reset it: the
    pool zeroes it (with ``count``) every drain/recycle so each fetch
    reports a disjoint delta, and accumulates the ground truth on the host
    (``dropped_rounds_confirmed``) — the per-fetch audit point for host
    mirrors.  Don't treat a single ring's ``dropped`` as a monotonic
    lifetime total.
    """

    scores: jax.Array   # (R, lanes, chunk) f32
    keep: jax.Array     # (R, lanes, chunk) bool
    n_kept: jax.Array   # (R, lanes) i32
    vdd_idx: jax.Array  # (R, lanes) i32
    n_valid: jax.Array  # (R, lanes) i32 — valid events per lane that round
    mask: jax.Array     # (R, lanes) bool — lanes that folded that round
    head: jax.Array     # i32 scalar — next slot to write
    count: jax.Array    # i32 scalar — undrained slots (saturates at R)
    dropped: jax.Array  # i32 scalar — rounds overwritten before a drain


def ring_init(rounds: int, lanes: int, chunk: int) -> RingState:
    """Empty ring of ``rounds`` slots for a ``lanes``-wide, ``chunk``-sized
    pool bucket (host call; arrays land on the default device)."""
    if rounds < 1:
        raise ValueError("ring needs at least one slot")
    return RingState(
        scores=jnp.zeros((rounds, lanes, chunk), jnp.float32),
        keep=jnp.zeros((rounds, lanes, chunk), bool),
        n_kept=jnp.zeros((rounds, lanes), jnp.int32),
        vdd_idx=jnp.zeros((rounds, lanes), jnp.int32),
        n_valid=jnp.zeros((rounds, lanes), jnp.int32),
        mask=jnp.zeros((rounds, lanes), bool),
        head=jnp.int32(0),
        count=jnp.int32(0),
        dropped=jnp.int32(0),
    )


def ring_push(
    ring: RingState,
    outs: ChunkOutput,
    mask: jax.Array,
    n_valid: jax.Array,
    active: jax.Array,
) -> RingState:
    """Append one pool round to the ring (pure; used inside ``lax.scan``).

    ``outs`` is the lane-stacked ``ChunkOutput`` of one vmapped round,
    ``mask``/``n_valid`` are ``(lanes,)``, and ``active`` is a bool scalar —
    padded no-op rounds (all lanes inactive) pass ``active=False`` and leave
    the ring untouched, so a fixed-K executor block never consumes slots for
    its padding.  A push onto a full ring overwrites the oldest slot and
    counts it in ``dropped``.
    """
    rounds = ring.scores.shape[0]

    def push(r: RingState) -> RingState:
        slot = r.head

        def wr(buf, val):
            return jax.lax.dynamic_update_index_in_dim(buf, val, slot, 0)

        return RingState(
            scores=wr(r.scores, outs.scores),
            keep=wr(r.keep, outs.keep),
            n_kept=wr(r.n_kept, outs.n_kept),
            vdd_idx=wr(r.vdd_idx, outs.vdd_idx),
            n_valid=wr(r.n_valid, n_valid),
            mask=wr(r.mask, mask),
            head=(slot + 1) % rounds,
            count=jnp.minimum(r.count + 1, rounds),
            dropped=r.dropped
            + jnp.where(r.count == rounds, jnp.int32(1), jnp.int32(0)),
        )

    return jax.lax.cond(active, push, lambda r: r, ring)


class CompactRingState(NamedTuple):
    """``RingState`` plus per-slot compacted kept-corner records.

    The pool's ``readout="compact"`` mode pushes both representations per
    round: the dense ``scores``/``keep`` slabs (HBM writes are cheap and
    they are the *lossless overflow fallback*) and, via the compaction
    kernel, ``(cap,)`` record buffers per ``(round, lane)`` slot —
    ``c_idx[r, l, j]`` / ``c_val[r, l, j]`` hold the event index and score
    of that slot's j-th kept event in stream order, with ``n_kept`` doubling
    as the record count.  The drain then fetches ONLY the compact leaves
    (plus the scalar cursors in the same ``device_get``) and densifies on
    host; a slot with ``n_kept > cap`` is flagged overflowed and its dense
    row is fetched in a targeted second gather — drop nothing, ever.

    Field order keeps the ``RingState`` prefix so shared code
    (``ring_slot_order`` walks, ``_replace`` resets, the runtime's
    tree-mapped shard specs) treats both rings uniformly.
    """

    scores: jax.Array   # (R, lanes, chunk) f32 — dense fallback
    keep: jax.Array     # (R, lanes, chunk) bool — dense fallback
    n_kept: jax.Array   # (R, lanes) i32 — doubles as compact record count
    vdd_idx: jax.Array  # (R, lanes) i32
    n_valid: jax.Array  # (R, lanes) i32
    mask: jax.Array     # (R, lanes) bool
    head: jax.Array     # i32 scalar
    count: jax.Array    # i32 scalar
    dropped: jax.Array  # i32 scalar
    c_idx: jax.Array    # (R, lanes, cap) i32 — kept events' chunk indices
    c_val: jax.Array    # (R, lanes, cap) f32 — kept events' scores


def compact_ring_init(
    rounds: int, lanes: int, chunk: int, cap: int
) -> CompactRingState:
    """Empty compact ring: the dense ring plus ``(cap,)`` record buffers
    per slot-lane (host call; arrays land on the default device)."""
    if not 1 <= cap <= chunk:
        raise ValueError(f"compact cap must be in [1, chunk], got {cap}")
    dense = ring_init(rounds, lanes, chunk)
    return CompactRingState(
        *dense,
        c_idx=jnp.zeros((rounds, lanes, cap), jnp.int32),
        c_val=jnp.full((rounds, lanes, cap), -jnp.inf, jnp.float32),
    )


def ring_push_compact(
    ring: CompactRingState,
    outs: ChunkOutput,
    mask: jax.Array,
    n_valid: jax.Array,
    active: jax.Array,
    *,
    compact_fn: Callable,
) -> CompactRingState:
    """``ring_push`` that also stores the round's compacted records.

    ``compact_fn(scores, keep) -> (idx, val, count)`` is injected by the
    caller (the runtime binds either the vmapped jnp oracle or the Pallas
    compaction op at executor-build time, so this module never imports
    ``repro.kernels``); ``count`` must equal ``sum(keep)`` per lane — it is
    cross-checked against ``outs.n_kept`` downstream, not here.  The dense
    slot is still written every push: it is the lossless fallback the
    drain reaches for when ``n_kept > cap`` overflows the records.
    """
    rounds = ring.scores.shape[0]
    c_idx, c_val, _ = compact_fn(outs.scores, outs.keep)

    def push(r: CompactRingState) -> CompactRingState:
        slot = r.head

        def wr(buf, val):
            return jax.lax.dynamic_update_index_in_dim(buf, val, slot, 0)

        return CompactRingState(
            scores=wr(r.scores, outs.scores),
            keep=wr(r.keep, outs.keep),
            n_kept=wr(r.n_kept, outs.n_kept),
            vdd_idx=wr(r.vdd_idx, outs.vdd_idx),
            n_valid=wr(r.n_valid, n_valid),
            mask=wr(r.mask, mask),
            head=(slot + 1) % rounds,
            count=jnp.minimum(r.count + 1, rounds),
            dropped=r.dropped
            + jnp.where(r.count == rounds, jnp.int32(1), jnp.int32(0)),
            c_idx=wr(r.c_idx, c_idx),
            c_val=wr(r.c_val, c_val),
        )

    return jax.lax.cond(active, push, lambda r: r, ring)


def ring_slot_order(head: int, count: int, rounds: int) -> list[int]:
    """Host helper: slot indices of the ``count`` undrained rounds, oldest
    first (the order drains must distribute results in)."""
    return [(int(head) - int(count) + i) % int(rounds)
            for i in range(int(count))]


def select_update(cfg) -> Callable:
    """TOS chunk-update callable for the configured backend."""
    if cfg.backend == "jnp":
        fn = (
            tos_mod.tos_update_batched_onehot
            if cfg.use_onehot_update
            else tos_mod.tos_update_batched
        )
        return lambda s, xy, v: fn(s, xy, v, patch=cfg.patch, th=cfg.th)
    if cfg.backend in ("pallas_nmc", "pallas_batched"):
        from repro.kernels import ops  # deferred: keep jnp path Pallas-free

        mode = "nmc" if cfg.backend == "pallas_nmc" else "batched"
        return lambda s, xy, v: ops.tos_update_op(
            s, xy, v, patch=cfg.patch, th=cfg.th, mode=mode,
            interpret=cfg.interpret,
        )
    if cfg.backend == "pallas_fused":
        raise ValueError(
            "backend 'pallas_fused' fuses the whole chunk step (STCF -> TOS "
            "-> BER -> LUT score) into one kernel — it has no standalone TOS "
            "update; route through detector_step / run_pipeline / the "
            "serving layer instead"
        )
    raise ValueError(
        f"unknown backend {cfg.backend!r}; expected ('jnp', 'pallas_nmc', "
        f"'pallas_batched', 'pallas_fused')"
    )


def _online(cfg) -> bool:
    return bool(cfg.dvfs and getattr(cfg, "dvfs_online", False))


def control_init(cfg) -> ControlState:
    """Neutral knobs for ``cfg``: the config's own refresh cadence, the full
    operating-point table, no shedding — folding with these is bit-identical
    to the pre-knob detector."""
    if _online(cfg):
        top = len(dvfs_mod.op_point_table(cfg.dvfs_cfg).caps) - 1
    else:
        top = 0                 # inert: fixed-Vdd mode never reads the cap
    return ControlState(
        lut_every=jnp.int32(cfg.lut_every_chunks),
        vdd_cap=jnp.int32(top),
        shed=jnp.asarray(False),
    )


def detector_init(cfg, *, seed: Optional[int] = None) -> DetectorState:
    """Fresh per-stream state (host call; arrays land on the default device)."""
    return DetectorState(
        surface=tos_mod.tos_new(cfg.height, cfg.width),
        sae=stcf_mod.fresh_sae(cfg.height, cfg.width),
        lut=jnp.full((cfg.height, cfg.width), -jnp.inf, dtype=jnp.float32),
        lut_ready=jnp.asarray(False),
        key=jax.random.PRNGKey(cfg.seed if seed is None else seed),
        chunk_idx=jnp.int32(0),
        rate=dvfs_mod.rate_state_init(),
        kept_total=jnp.int32(0),
        energy_pj=jnp.float32(0.0),
        latency_ns=jnp.float32(0.0),
        ctrl=control_init(cfg),
    )


def _operating_point(cfg, state: DetectorState, chunk: ChunkInput):
    """This chunk's (rate, vdd_idx, ber, energy_coef, latency_coef).

    Shared verbatim by the jnp and fused steps: online mode runs the
    streaming estimator and clamps the pick to the ladder's per-stream
    ceiling (bit-identical to a table truncated at the cap — see
    ``ControlState.vdd_cap`` — but traced data, so moving it never
    respecializes); precomputed mode passes the chunk riders through.
    """
    if _online(cfg):
        tab = dvfs_mod.op_point_table(cfg.dvfs_cfg)
        rate, vdd_idx = dvfs_mod.online_vdd_from_chunk_ts(
            state.rate, chunk.ts, chunk.valid,
            cfg=cfg.dvfs_cfg, caps=jnp.asarray(tab.caps),
        )
        vdd_idx = jnp.minimum(vdd_idx, state.ctrl.vdd_cap)
        return (rate, vdd_idx, jnp.asarray(tab.ber)[vdd_idx],
                jnp.asarray(tab.energy_pj)[vdd_idx],
                jnp.asarray(tab.latency_ns)[vdd_idx])
    return (state.rate, jnp.int32(0), chunk.ber,
            chunk.energy_coef, chunk.latency_coef)


def lut_due(state: DetectorState) -> jax.Array:
    """Whether folding the next chunk rebuilds the Harris LUT.

    Refresh cadence is runtime data (ControlState), not the config
    constant — the ladder stretches it without a recompile.  ``shed``
    suspends refresh outright; scoring continues against the stale LUT
    (the luvHarris overload mode: degrade quality, never latency).
    Elementwise, so a lane-stacked state gives one flag per lane.
    """
    return (
        ((state.chunk_idx + 1) % state.ctrl.lut_every) == 0
    ) & jnp.logical_not(state.ctrl.shed)


def _harris(cfg, surface):
    return harris_mod.harris_response(
        surface,
        sobel_size=cfg.sobel_size,
        window_size=cfg.window_size,
        k=cfg.harris_k,
    )


def _refresh_lut(cfg, state: DetectorState, surface, lut):
    """Periodic Harris LUT rebuild; returns (lut, due).

    Unbatched it is a ``lax.cond``.  Under ``vmap`` a cond on a batched
    flag would become a select that runs the Harris for every lane, so
    its batching rule is ``refresh_luts`` instead: the Harris runs for the
    due lanes only, batched to their count.
    """
    @jax.custom_batching.custom_vmap
    def refresh(surface, lut, due):
        return jax.lax.cond(
            due, lambda s: _harris(cfg, s), lambda s: lut, surface
        )

    @refresh.def_vmap
    def refresh_lanes(axis_size, in_batched, surface, lut, due):
        surface, lut, due = (
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip((surface, lut, due), in_batched)
        )
        return refresh_luts(cfg, surface, lut, due), True

    with jax.named_scope("lut_refresh"):
        due = lut_due(state)
        return refresh(surface, lut, due), due


def refresh_ladder(lanes: int) -> tuple[int, ...]:
    """The batch sizes ``refresh_luts`` runs the Harris at over ``lanes``
    stacked lanes, ascending: 0, then ``lanes``, ``lanes // 2``,
    ``lanes // 4``, ... down to ``max(1, lanes // 16)``."""
    least = max(1, lanes // 16)
    sizes = {0}
    while lanes >= least:
        sizes.add(lanes)
        lanes //= 2
    return tuple(sorted(sizes))


def refresh_luts(cfg, surfaces, luts, due):
    """Harris LUT rebuild of the lanes flagged ``due`` in lane-stacked
    ``surfaces`` / ``luts`` (leading lane axis); the other lanes keep
    their LUT.

    The count of due lanes picks, through ``lax.switch``, the smallest
    size of ``refresh_ladder`` that holds them: none; a batch of that many
    lanes gathered by ``jnp.nonzero`` (padded with an out-of-range index,
    whose scatter is dropped); or every lane, with no gather.  Each lane's
    response is the unbatched one's.
    """
    lanes = due.shape[0]
    ladder = refresh_ladder(lanes)
    harris = jax.vmap(functools.partial(_harris, cfg))

    def none(surfaces, luts, due):
        return luts

    def every(surfaces, luts, due):
        return jnp.where(due[:, None, None], harris(surfaces), luts)

    def batch(size):
        def run(surfaces, luts, due):
            idx = jnp.nonzero(due, size=size, fill_value=lanes)[0]
            fresh = harris(jnp.take(surfaces, idx, axis=0, mode="clip"))
            return luts.at[idx].set(fresh, mode="drop")
        return run

    branches = [none] + [batch(s) for s in ladder[1:-1]] + [every]
    with jax.named_scope("lut_refresh"):
        n = jnp.sum(due.astype(jnp.int32))
        which = jnp.sum((jnp.asarray(ladder) < n).astype(jnp.int32))
        return jax.lax.switch(which, branches, surfaces, luts, due)


def detector_step(
    cfg, state: DetectorState, chunk: ChunkInput
) -> tuple[DetectorState, ChunkOutput]:
    """Fold one chunk of events into the detector state (pure, jit-able).

    This is THE detector: ``detector_scan`` folds it over a pre-chunked
    stream, ``StreamingDetector`` calls it per arriving chunk, and
    ``DetectorPool`` vmaps it over camera lanes.  Per-event scores read the
    *latest available* LUT — the EBE/FBF decoupling of luvHarris.

    ``backend="pallas_fused"`` swaps the four-stage STCF/TOS/BER/score
    block for the single VMEM-resident Pallas kernel (property-tested
    bit-exact); the DVFS pick, accumulators, and LUT refresh are shared
    code either way, so every serving path gets the fusion unchanged.
    Vmapped, the LUT refresh runs for the due lanes only (``refresh_luts``).
    """
    if cfg.backend == "pallas_fused":
        return _detector_step_fused(cfg, state, chunk)
    update = select_update(cfg)
    surface, sae, lut = state.surface, state.sae, state.lut
    lut_ready, key = state.lut_ready, state.key

    with jax.named_scope("stcf"):
        sae, keep = stcf_mod.stcf_step(
            sae, chunk.xy, chunk.ts, chunk.valid,
            enabled=cfg.stcf_enabled,
            support=cfg.stcf_support, tw=cfg.stcf_tw_us,
        )

    rate, vdd_idx, ber_c, energy_coef, latency_coef = _operating_point(
        cfg, state, chunk
    )

    with jax.named_scope("tos_update"):
        surface = update(surface, chunk.xy, keep)

        if cfg.inject_ber:
            key, sub = jax.random.split(key)
            surface = ber_mod.inject_write_errors_at(sub, surface, ber_c)

    n_kept = jnp.sum(keep).astype(jnp.int32)

    # Tag this chunk's events against the latest available LUT.
    with jax.named_scope("score_read"):
        scores = jnp.where(
            lut_ready,
            harris_mod.score_events(lut, chunk.xy, keep),
            -jnp.inf,
        ).astype(jnp.float32)

    lut, due = _refresh_lut(cfg, state, surface, lut)
    lut_ready = lut_ready | due

    new_state = DetectorState(
        surface=surface,
        sae=sae,
        lut=lut,
        lut_ready=lut_ready,
        key=key,
        chunk_idx=state.chunk_idx + 1,
        rate=rate,
        kept_total=state.kept_total + n_kept,
        energy_pj=state.energy_pj + n_kept.astype(jnp.float32) * energy_coef,
        latency_ns=state.latency_ns
        + n_kept.astype(jnp.float32) * latency_coef,
        ctrl=state.ctrl,
    )
    return new_state, ChunkOutput(
        scores=scores, keep=keep, n_kept=n_kept, vdd_idx=vdd_idx
    )


def _detector_step_fused(
    cfg, state: DetectorState, chunk: ChunkInput
) -> tuple[DetectorState, ChunkOutput]:
    """``detector_step`` with the STCF/TOS/BER/score block replaced by the
    fused Pallas megakernel (``kernels.fused_step``) — surfaces stay VMEM-
    resident across the whole chain instead of round-tripping HBM between
    stages.  Everything around the block (online DVFS pick, PRNG key
    discipline, accumulators, LUT refresh cond) is the same code as the jnp
    step, so bit-exactness reduces to the kernel contract, which the
    ``tests/test_fused_step.py`` property suite pins across paths.
    """
    from repro.kernels import ops  # deferred: keep jnp path Pallas-free

    surface, sae, lut = state.surface, state.sae, state.lut
    lut_ready, key = state.lut_ready, state.key

    rate, vdd_idx, ber_c, energy_coef, latency_coef = _operating_point(
        cfg, state, chunk
    )

    # Same key-split discipline as the jnp step: one split iff injecting,
    # Bernoulli draws on the host-traced side (ops shares them with the
    # oracle via ber.write_error_bits), xor/decode applied in-kernel.
    bits = None
    if cfg.inject_ber:
        with jax.named_scope("tos_update"):
            key, sub = jax.random.split(key)
            bits = ber_mod.write_error_bits(sub, surface.shape, ber_c)

    # STCF, the TOS update and the score read run in the one kernel: its
    # device time is the fused_step scope's
    with jax.named_scope("fused_step"):
        surface, sae, keep, raw_scores = ops.fused_step_op(
            surface, sae, lut, chunk.xy, chunk.ts, chunk.valid, ber_c, bits,
            patch=cfg.patch, th=cfg.th,
            support=cfg.stcf_support, tw=cfg.stcf_tw_us,
            stcf_enabled=cfg.stcf_enabled, inject_ber=cfg.inject_ber,
            interpret=cfg.interpret,
        )

    n_kept = jnp.sum(keep).astype(jnp.int32)
    with jax.named_scope("score_read"):
        scores = jnp.where(lut_ready, raw_scores,
                           -jnp.inf).astype(jnp.float32)

    lut, due = _refresh_lut(cfg, state, surface, lut)
    lut_ready = lut_ready | due

    new_state = DetectorState(
        surface=surface,
        sae=sae,
        lut=lut,
        lut_ready=lut_ready,
        key=key,
        chunk_idx=state.chunk_idx + 1,
        rate=rate,
        kept_total=state.kept_total + n_kept,
        energy_pj=state.energy_pj + n_kept.astype(jnp.float32) * energy_coef,
        latency_ns=state.latency_ns
        + n_kept.astype(jnp.float32) * latency_coef,
        ctrl=state.ctrl,
    )
    return new_state, ChunkOutput(
        scores=scores, keep=keep, n_kept=n_kept, vdd_idx=vdd_idx
    )


def detector_scan(
    cfg, state: DetectorState, chunks: ChunkInput
) -> tuple[DetectorState, ChunkOutput]:
    """Fold a whole pre-stacked stream: ``lax.scan`` of ``detector_step``.

    ``chunks`` leaves carry a leading ``(n_chunks, ...)`` axis.  Returns the
    final state and the stacked per-chunk outputs; the host blocks only when
    it fetches them.
    """
    return jax.lax.scan(functools.partial(detector_step, cfg), state, chunks)


def chunk_input_riders(
    n_chunks: int, vdd_arr: Optional[np.ndarray], cfg
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side per-chunk (ber, energy_coef, latency_coef) arrays.

    ``vdd_arr=None`` means online mode — the riders are ignored by the step,
    so zeros keep the traced program identical across streams.
    """
    from repro.core import hwmodel

    if vdd_arr is None:
        z = np.zeros((n_chunks,), np.float32)
        return z, z.copy(), z.copy()
    ber = np.asarray([hwmodel.ber_at(float(v)) for v in vdd_arr], np.float32)
    e = np.asarray(
        [hwmodel.patch_energy_pj(float(v)) for v in vdd_arr], np.float32
    )
    lat = np.asarray(
        [hwmodel.patch_latency_ns(float(v)) for v in vdd_arr], np.float32
    )
    return ber, e, lat
