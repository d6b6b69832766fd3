"""Data plane of the multi-camera pool: the device-resident runtime.

``PoolRuntime`` owns every *mechanism* the serving layer needs — compiled
per-bucket executors, the on-device result rings and their reader thread,
lane state/donation bookkeeping, host re-chunk buffers, and the migration
machinery — and exposes them as verbs (``connect`` a lane into a bucket,
``pump_pass`` an ordered list of buckets, ``stage_migration`` /
apply-on-next-pump).  It never decides *which* bucket a lane belongs in or
*when* to migrate: those are policy, owned by ``repro.serve.scheduler``
and wired to this runtime by the ``DetectorPool`` façade.  The split is
the serving-layer analogue of the paper's controller/datapath separation —
the DVFS controller picks the operating point, the macro just runs it —
and is what lets multi-host sharding and new placement policies land
without touching the executor/ring/thread machinery below.

Mechanisms (PR 3 + PR 4, generalized here):

**Ring-buffered multi-round pump.**  Rounds execute in jitted K-round
``lax.scan`` blocks whose per-round outputs (scores, keep masks, kept
counts, chunk metadata) land in a fixed-capacity on-device result ring
(``repro.core.state.RingState``).  The host performs ONE blocking fetch
per drain — K back-to-back rounds cost one sync, not K.  Padded no-op
rounds inside a block are skipped by a round-level ``lax.cond`` (data, not
shape); a block with exactly ONE ready round takes a second, 1-round
executor whose input shapes drop the K axis entirely.  Each bucket
therefore compiles at most two executables (K-block + 1-round), each
exactly once — membership churn and live migration must not grow either
(asserted in CI).  Overflow policy:

  * ``on_overflow="drain"`` (default): the host drains the ring before a
    block that would not fit — lossless backpressure.
  * ``on_overflow="drop_oldest"``: a full ring overwrites its oldest slot
    and counts the loss; the in-state device accumulators stay complete.

**Pipelined pump (stage -> dispatch).**  Each executor block's life splits
in two: *stage* gathers the block's chunks into padded host slabs and
starts their H2D upload (through ``launch.sharding.HostStager``'s pinned
double buffer where the runtime exposes one), *dispatch* makes ring room
and launches the executor.  A pump pass keeps a stage-ahead deque of up to
``pipeline_depth - 1`` staged blocks (default depth 2 — the classic double
buffer), so block *i+1* is gathered and uploaded while block *i* still
runs on device: JAX dispatch is async, so the host-side gather — the
pump's remaining serial cost after PR 7 — hides behind device compute.
Dispatch order is stage order (one FIFO across all buckets of a pass), so
results are bit-exact vs the unpipelined pump; a timebase rebase — a
device write to the stacked states — only applies when the deque is empty
(the pump flushes it first), keeping device-op order identical to the
serial path.  ``pipeline_depth=1`` *is* the serial path.  Knob actions are
coalesced the same way: all of a pass's ctrl writes become ONE batched
leaf replace instead of one ``at[lane].set`` dispatch per action.

**N-deep ring-of-rings** (``ring_depth``, default 2).  In async drain mode
each bucket owns ``ring_depth`` device rings: one live, the rest a spare
pool.  Draining *seals* the live ring — an atomic swap that installs a
spare as the new live ring and hands the sealed one to a dedicated reader
thread, which performs the blocking ``device_get`` off the pump thread.
Depth 2 is PR 4's double buffer (the pump waits only when the reader still
holds the one spare); deeper rings absorb longer fetch stalls — up to
``ring_depth - 1`` seals can be in flight before a pump blocks — at the
cost of one more ring's device memory per extra slot.  All depths are
bit-exact vs each other and vs sync mode (property-tested for depth 2 and
3); ``drain_mode="sync"`` keeps the single-ring PR 3 inline fetch.

**Live bucket migration mechanics.**  ``stage_migration(lane, bucket)``
seals+drains the lane's current bucket (so every pumped round is
distributed in order), then takes a donation-proof host snapshot of the
lane's ``DetectorState`` (owned deep copies — the same discipline as
``StreamingDetector.snapshot``).  The staged move applies at the start of
the next pump pass, under the pump token, before any round is collected:
the snapshot is ``device_put`` back into the stacked lane state (an owned
copy, re-placed on the lane mesh) and the lane's bucket flips — its
re-chunk buffer simply re-chunks at the new size from the next collect.
Nothing recompiles (both buckets' executors already exist; the restore
rides the same jitted per-lane reset ``connect`` uses) and no round is
lost or duplicated (the drain barrier plus the no-pump window between
stage and apply guarantee the snapshot can never go stale).
``disconnect`` of a lane mid-migration discards the staged snapshot — a
reused slot must inherit nothing.

**Rate observation.**  The runtime measures, policy consumes: ``feed``
folds each slab's timestamps into a per-lane host twin of the paper's
3-counter DVFS rate estimator (same half-window binning, same saturating
read, same float32 divide — ``repro.core.state.rate_estimate_eps``), so
``lane_halfwin_rate`` is available for any config without a device sync;
in online-DVFS mode the device estimator carried in ``DetectorState`` is
surfaced through ``stats()`` as ``device_events_per_s_est`` and equals the
host twin (property-tested).  ``h2d_event_slots``/``h2d_valid_events``
count uploaded vs useful chunk slots — the padding-bytes witness the
migration benchmarks gate.

Sharded lanes, donation, thread safety, and the active-mask membership
system are unchanged from PR 3/4 — see the class docstrings below and
``repro.serve.pool`` for the façade-level contracts.
"""
from __future__ import annotations

import bisect
import collections
import queue
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P, SingleDeviceSharding

from repro import compat
from repro import obs as obs_mod
from repro.obs.schema import POOL_BUCKET_STATS, POOL_STATS
from repro.core import dvfs as dvfs_mod
from repro.core import pipeline as pipeline_mod
from repro.core import state as state_mod
from repro.launch import sharding as sharding_mod
from repro.serve import scheduler as scheduler_mod
from repro.serve import streaming as streaming_mod

__all__ = ["PoolRuntime"]

_OVERFLOW_POLICIES = ("drain", "drop_oldest")
_DRAIN_MODES = ("sync", "async")
_READOUTS = ("dense", "compact")
_STOP = object()          # reader-thread shutdown sentinel

# H2D bytes per uploaded chunk slot: xy int32 pair + ts int32 + valid bool.
EVENT_SLOT_BYTES = 13


def _mask_tree(active, new_tree, old_tree):
    """Per-leaf select: lane i takes ``new`` iff ``active[i]``."""
    def sel(new, old):
        m = active.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    return jax.tree.map(sel, new_tree, old_tree)


def _fold_round(tcfg, states, chunk, mask):
    """One pool round: the vmapped step, then the mask select.  Returns
    (states, outs).

    The vmapped step refreshes the LUTs of the lanes due only, batched to
    their count (``state.refresh_luts``).  A lane masked out is shed for
    the step: its refresh is not due, and the select drops its new state,
    the changed knob with it."""
    ctrl = states.ctrl._replace(shed=states.ctrl.shed | ~mask)
    new, outs = jax.vmap(
        lambda s, c: state_mod.detector_step(tcfg, s, c)
    )(states._replace(ctrl=ctrl), chunk)
    with jax.named_scope("mask_select"):
        return _mask_tree(mask, new, states), outs


class _Lane:
    """Host-side bookkeeping for one pool slot."""

    __slots__ = ("bucket", "buf_xy", "buf_ts", "base", "results", "n_events",
                 "n_chunks", "kept_total", "energy_pj", "latency_ns",
                 "vdd_trace", "events_folded", "chunks_folded",
                 "migrations", "migration_log",
                 "r_win", "r_cur", "r_p1", "r_p2",
                 "qos", "tier", "knob_lut_every", "knob_vdd_cap",
                 "knob_shed", "shed_events", "gen", "obs_cache",
                 "fed_cum", "feed_times", "res_n", "res_tsum", "res_ber")

    def __init__(self, bucket: int, *, qos: str = "standard",
                 lut_every: int = 1, vdd_cap: int = 0):
        self.bucket = bucket
        # -- control-plane view: QoS class, actuated-tier mirror, and host
        # mirrors of the lane's in-state degradation knobs (the device
        # truth lives in DetectorState.ctrl; connect resets both together)
        self.qos = qos
        self.tier = 0
        self.knob_lut_every = int(lut_every)
        self.knob_vdd_cap = int(vdd_cap)
        self.knob_shed = False
        self.shed_events = 0            # oldest events dropped while shedding
        self.buf_xy = np.zeros((0, 2), np.int32)
        self.buf_ts = np.zeros((0,), np.int64)
        self.base: Optional[int] = None
        self.results: list[tuple[np.ndarray, np.ndarray]] = []
        self.n_events = 0
        self.n_chunks = 0
        self.kept_total = 0
        self.energy_pj = 0.0
        self.latency_ns = 0.0
        self.vdd_trace: list[float] = []
        self.events_folded = 0          # events consumed by executed rounds
        self.chunks_folded = 0          # their chunks: the device chunk_idx
        self.migrations = 0             # bucket moves applied to this lane
        # (events_folded, from_bucket, to_bucket) per applied migration —
        # the replay oracle: a StreamingDetector fed the same stream and
        # rebucket()ed at each logged boundary reproduces this lane's
        # outputs bit-for-bit.
        self.migration_log: list[tuple[int, int, int]] = []
        # Host twin of the 3-counter DVFS rate estimator (half-window
        # binning of *fed* timestamps; same rotation the device step does).
        self.r_win = 0
        self.r_cur = 0
        self.r_p1 = 0
        self.r_p2 = 0
        # Observation memoization: ``gen`` bumps on every mutation that
        # could change this lane's LaneObservation (feed, round collect,
        # shed, migration apply, tier write); ``obs_cache`` holds
        # ``(gen, LaneObservation)`` so idle lanes cost a dict lookup per
        # pump observation, not a rebuild.
        self.gen = 0
        self.obs_cache: Optional[tuple] = None
        # Chunk-life stamps: ``feed_times`` holds (events ever buffered
        # after the slab, its feed time) per slab still in the buffer, so
        # a collect finds when its chunk's last event was fed; ``res_n``
        # results wait in ``results``, distributed at times summing to
        # ``res_tsum``, ``res_ber`` of them folded at a Vdd with write
        # errors.
        self.fed_cum = 0
        self.feed_times: collections.deque = collections.deque()
        self.res_n = 0
        self.res_tsum = 0.0
        self.res_ber = 0

    def rate_update(self, ts: np.ndarray, half: int) -> None:
        """Fold one time-sorted slab into the rate twin (vectorized; only
        the last three half-windows can ever be read again, exactly like
        ``dvfs.online_vdd_from_chunk_ts``)."""
        w = ts // half
        wl = int(w[-1])
        n0 = int(np.count_nonzero(w == wl))
        n1 = int(np.count_nonzero(w == wl - 1))
        n2 = int(np.count_nonzero(w == wl - 2))
        d = wl - self.r_win
        if d == 0:
            cur, p1, p2 = self.r_cur + n0, self.r_p1 + n1, self.r_p2 + n2
        elif d == 1:
            cur, p1, p2 = n0, self.r_cur + n1, self.r_p1 + n2
        elif d == 2:
            cur, p1, p2 = n0, n1, self.r_cur + n2
        else:
            cur, p1, p2 = n0, n1, n2
        self.r_win, self.r_cur, self.r_p1, self.r_p2 = wl, cur, p1, p2


class _Round:
    """One collected pump round (host arrays, lane-stacked) for a bucket,
    with its collect time, how many chunks and events it holds, and its
    LUT refreshes: the lanes due, and the lanes the executor's refresh
    batch runs the Harris on."""

    __slots__ = ("xy", "ts", "valid", "mask", "n_valid", "t", "n_chunks",
                 "n_events", "lut_due", "lut_runs")

    def __init__(self, xy, ts, valid, mask, n_valid, t, n_chunks, n_events,
                 lut_due, lut_runs):
        self.xy, self.ts, self.valid = xy, ts, valid
        self.mask, self.n_valid = mask, n_valid
        self.t, self.n_chunks, self.n_events = t, n_chunks, n_events
        self.lut_due, self.lut_runs = lut_due, lut_runs


class _StagedBlock:
    """One executor block whose H2D upload has been issued but whose
    executor has not yet launched — the unit of the pump's stage-ahead
    deque.  Holds only device-side chunk inputs (plus the accounting the
    dispatch half needs); it never references the stacked states or the
    rings, so a staged block stays valid across other blocks' dispatches
    and is inert to everything except a timebase rebase (which the pump
    therefore fences behind a pipeline flush)."""

    __slots__ = ("bucket", "n", "single", "chunks", "mask", "n_valid",
                 "round_active", "n_valid_sum", "seq", "n_chunks", "t_sum")

    def __init__(self, bucket, n, single, chunks, mask, n_valid,
                 round_active, n_valid_sum, seq, n_chunks, t_sum):
        self.bucket, self.n, self.single = bucket, n, single
        self.chunks, self.mask, self.n_valid = chunks, mask, n_valid
        self.round_active = round_active
        self.n_valid_sum = n_valid_sum
        # the block's sequence number (its spans' ``block`` id), its
        # chunks, and their collect times summed
        self.seq, self.n_chunks, self.t_sum = seq, n_chunks, t_sum


class PoolRuntime:
    """Mechanics of a fixed-capacity camera pool: per-bucket K-round
    ring-buffered executors (at most one K-block and one 1-round
    executable per chunk-size bucket), an async N-deep ring-of-rings drain
    runtime, and staged lane migration.  Placement decisions come from
    outside (``DetectorPool`` + a scheduler); this class only refuses the
    physically impossible.

    **Thread safety.**  One re-entrant lock guards ALL mutable state (host
    mirrors, lane buffers, result queues, ring bindings, staged
    migrations); every public method acquires it, and the reader thread
    acquires it only to distribute fetched results and recycle sealed
    rings — the blocking ``device_get`` itself runs unlocked, so it
    overlaps with the pump.  Waits use a condition variable on the same
    lock.  A pump token serializes whole pump passes (a seal waiting on a
    spare ring releases the lock mid-block; two pumpers must not
    interleave their round order).

    **Membership** is an active-mask lane system: a ``(capacity,)`` bool
    mask plus per-lane dummy chunks — data, never a shape — so session
    churn and bucket migration NEVER trigger a recompile.  Per lane the
    runtime keeps exactly what a ``StreamingDetector`` keeps (host
    re-chunk buffer, int64 timebase, float64 energy books, result queue),
    so a lane's outputs are bit-identical to a standalone session and to
    ``run_pipeline`` on its full stream (property-tested).

    **Sharded lanes.**  With more than one local device (or
    ``shard=True``) the lane axis of the stacked state, chunk inputs, and
    rings splits across a 1-D ``('lanes',)`` mesh (zero collectives;
    placement is data).  **Donation**: on accelerator-resident pools the
    executors donate the stacked states and the live ring, keyed off the
    actual placement (``repro.core.state.donation_ok``), never the default
    backend; sealed rings in the reader's hands are never the donated
    buffer.
    """

    def __init__(self, cfg, capacity: int, *, seed: int = 0,
                 ring_rounds: int = 8,
                 buckets: Optional[tuple] = None,
                 on_overflow: str = "drain",
                 shard: object = "auto",
                 drain_mode: str = "async",
                 ring_depth: int = 2,
                 pipeline_depth: int = 2,
                 readout: str = "dense",
                 compact_cap: Optional[int] = None,
                 metrics: Optional[obs_mod.MetricsRegistry] = None):
        streaming_mod._check_streamable(cfg)
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if ring_rounds < 1:
            raise ValueError("ring_rounds must be >= 1")
        if pipeline_depth < 1:
            raise ValueError(
                "pipeline_depth must be >= 1 (1 = unpipelined: every block "
                "dispatches as soon as it is staged)"
            )
        if on_overflow not in _OVERFLOW_POLICIES:
            raise ValueError(
                f"on_overflow must be one of {_OVERFLOW_POLICIES}, "
                f"got {on_overflow!r}"
            )
        if drain_mode not in _DRAIN_MODES:
            raise ValueError(
                f"drain_mode must be one of {_DRAIN_MODES}, "
                f"got {drain_mode!r}"
            )
        if ring_depth < 2:
            raise ValueError(
                "ring_depth must be >= 2 (one live ring plus at least one "
                "spare for the reader)"
            )
        if readout not in _READOUTS:
            raise ValueError(
                f"readout must be one of {_READOUTS}, got {readout!r}"
            )
        if compact_cap is not None and int(compact_cap) < 1:
            raise ValueError("compact_cap must be >= 1")
        if buckets is None:
            buckets = (cfg.chunk,)
        buckets = tuple(sorted({int(b) for b in buckets}))
        if any(b < 1 for b in buckets):
            raise ValueError("chunk buckets must be positive")
        self._cfg = cfg
        self._capacity = capacity
        self._seed = seed
        self._ring_rounds = ring_rounds
        self._buckets = buckets
        self._overflow = on_overflow
        self._drain_mode = drain_mode
        self._ring_depth = ring_depth
        self._pipeline_depth = int(pipeline_depth)
        self._readout = readout
        # Per-bucket compact record capacity: by default chunk/8 — corners
        # are sparse (luvHarris keeps a few percent), so an eighth of the
        # chunk absorbs real traffic with headroom while keeping the fetch
        # ~5x smaller; a slot that still overflows falls back to its dense
        # row, losslessly.  An explicit compact_cap clamps to the bucket.
        self._compact_caps = {
            int(b): (max(1, int(b) // 8) if compact_cap is None
                     else max(1, min(int(compact_cap), int(b))))
            for b in buckets
        }
        self._half_us = int(cfg.dvfs_cfg.half_us)
        self._online = bool(cfg.dvfs and cfg.dvfs_online)
        self._tab = dvfs_mod.op_point_table(cfg.dvfs_cfg)
        # Highest DVFS operating-point index a knob may select; the cap is
        # inert in fixed-Vdd mode (no in-step controller reads it).
        self._vdd_top = len(self._tab.caps) - 1 if self._online else 0
        if not self._online:
            r = state_mod.chunk_input_riders(
                1, np.full((1,), cfg.vdd, np.float64), cfg
            )
            self._riders = tuple(np.float32(x[0]) for x in r)
        else:
            z = np.float32(0.0)
            self._riders = (z, z, z)

        # -- one lock for ALL pool mutable state; the condition variable
        # shares it so waiters (spare ring, drain barrier) release it for
        # the reader thread.  Public methods acquire it; the reader takes
        # it only to distribute/recycle — never across a device fetch.
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._closed = False

        # -- lane sharding: a 1-D 'lanes' mesh over the local devices -------
        n_dev = len(jax.local_devices())
        self._mesh = None
        if shard is True or (shard == "auto" and n_dev > 1):
            self._mesh = sharding_mod.local_lane_mesh()
        # Physical lane count: padded so the lane axis splits evenly; the
        # padding lanes are permanently inactive (masked, never connectable).
        self._phys = (
            sharding_mod.lane_padded_capacity(capacity, self._mesh)
            if self._mesh is not None else capacity
        )
        # the executors' LUT refresh batches (``refresh_luts``): one ladder
        # per shard of the lane axis, as ``shard_map`` hands each its lanes
        self._shards = (int(self._mesh.devices.size)
                        if self._mesh is not None else 1)
        self._lut_ladder = state_mod.refresh_ladder(self._phys // self._shards)

        # Unsharded pools commit every executor-carried array to one
        # device sharding, as the sharded path commits to its mesh: staged
        # uploads arrive committed, so carried state must never flip
        # between committed and uncommitted (each flip is a new executable).
        self._device = (
            SingleDeviceSharding(jax.devices()[0], memory_kind="device")
            if self._mesh is None else None
        )
        self._states = self._place(jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[state_mod.detector_init(cfg, seed=seed + i)
              for i in range(self._phys)],
        ))
        self._active = np.zeros((self._phys,), bool)
        self._lanes: list[Optional[_Lane]] = [None] * self._phys

        # Host mirrors of the FULL (phys,) ctrl leaves — the device truth
        # every lane's knobs currently sit at, inactive slots included
        # (they keep whatever their last write left; detector_init seeds
        # the defaults below).  The batched knob write replaces the leaves
        # wholesale from these mirrors, so coalescing N actions into one
        # update is value-identical to N per-lane ``at[lane].set`` writes.
        self._ctrl_lut = np.full(
            (self._phys,), int(cfg.lut_every_chunks), np.int32
        )
        self._ctrl_cap = np.full((self._phys,), self._vdd_top, np.int32)
        self._ctrl_shed = np.zeros((self._phys,), bool)

        # Staged migrations: lane -> (host state snapshot, target bucket).
        # Applied at the start of the next pump pass; discarded by
        # disconnect (a reused slot must inherit nothing).
        self._staged: dict[int, tuple[dict, int]] = {}

        # Donation keyed off the stacked state's actual placement (never
        # jax.default_backend()); a no-op on CPU-resident pools.
        self._donate = state_mod.donation_ok(self._states)

        # Pinned-host staging for the H2D event uploads (both executor
        # paths): on CUDA the copy becomes async-capable, on CPU-only hosts
        # the stager transparently degrades to jnp.asarray.  Sized to the
        # pump's stage-ahead window so an upload still in flight keeps its
        # pinned slab alive while the next block stages.  Single-device
        # pools only — the sharded path scatters through lane_put and
        # keeps its own placement logic.
        self._stager = (
            sharding_mod.HostStager(depth=self._pipeline_depth)
            if self._mesh is None else None
        )

        # -- per-bucket runtime: ring-of-rings + K-round/1-round executors --
        self._rings: dict[int, state_mod.RingState] = {}    # live ring
        self._spares: dict[int, collections.deque] = {}
        self._exec: dict[int, object] = {}      # K-block executor
        self._exec1: dict[int, object] = {}     # 1-round fast path (K > 1)
        self._inflight: dict[int, int] = {}       # sealed rings being fetched
        # the live ring's chunk book: [chunks, their launch times summed,
        # block ids] — closed when the ring is sealed (or drained inline)
        self._ring_book: dict[int, list] = {}
        for b in buckets:
            self._rings[b] = self._make_ring(b)
            self._spares[b] = collections.deque(
                self._make_ring(b) for _ in
                range(ring_depth - 1 if drain_mode == "async" else 0)
            )
            self._exec[b] = self._build_executor(b)
            if ring_rounds > 1:
                self._exec1[b] = self._build_single_executor(b)
            self._inflight[b] = 0
            self._ring_book[b] = [0, 0.0, []]

        # -- witnesses: every counter/gauge below lives in the metrics
        # registry (repro.obs) — the single write path.  ``stats()`` /
        # ``pool_stats()`` / Observation are thin exports of these handles;
        # descriptions come from repro.obs.schema (one source of truth for
        # docs, HELP text, and the golden-key tests).  Handles are bound
        # once here so hot paths pay one locked add, no name resolution.
        self._metrics = (metrics if metrics is not None
                         else obs_mod.MetricsRegistry(namespace="pool"))
        self._declare_metrics(buckets)
        self._pass_dispatches = 0  # blocks dispatched in the current pass
        self._block_seq = 0        # blocks staged so far: the next block id
        # (bucket, single) -> the executor as noted at its first call,
        # from which ``executor_hlo`` lowers it again
        self._exec_notes = obs_mod.ExecutorNotes()
        self._t_fetched = None     # when the last fetch's device_get returned
        # One pump at a time: _seal_ring can wait on the cv (releasing the
        # lock) AFTER chunks were popped into a pending block, so a second
        # concurrent pump could otherwise collect and execute LATER chunks
        # first — folding a lane's stream out of order.  The token
        # serializes whole pump passes; poll/feed/stats still interleave.
        self._pump_busy = False

        # -- async drain: dedicated reader thread + sealed-ring queue -------
        self._reader_exc: Optional[BaseException] = None
        self._sealed_q: Optional[queue.Queue] = None
        self._reader: Optional[threading.Thread] = None
        if drain_mode == "async":
            self._sealed_q = queue.Queue()
            self._reader = threading.Thread(
                target=self._reader_loop, daemon=True,
                name="PoolRuntime-reader",
            )
            self._reader.start()

        def _reset(states, lane, fresh):
            return jax.tree.map(
                lambda arr, f: arr.at[lane].set(f), states, fresh
            )

        self._vreset = jax.jit(_reset)

        def _ctrl(states, lane, lut_every, vdd_cap, shed):
            c = states.ctrl
            return states._replace(ctrl=state_mod.ControlState(
                lut_every=c.lut_every.at[lane].set(lut_every),
                vdd_cap=c.vdd_cap.at[lane].set(vdd_cap),
                shed=c.shed.at[lane].set(shed),
            ))

        # Knob actuation: an ``at[lane].set`` on the ctrl leaves, same
        # jitted-write + re-place discipline as _vreset — moving a knob is
        # a data write, never a recompile of the executors.
        self._vctrl = jax.jit(_ctrl)

        def _ctrl_all(states, lut_every, vdd_cap, shed):
            return states._replace(ctrl=state_mod.ControlState(
                lut_every=lut_every, vdd_cap=vdd_cap, shed=shed,
            ))

        # Coalesced knob actuation: ONE batched ctrl-leaf replace for all
        # of a pass's knob Actions, fed from the full (phys,) host mirrors
        # — value-identical to applying the same actions one at[lane].set
        # at a time, at one dispatch instead of one per action.
        self._vctrl_all = jax.jit(_ctrl_all)

        half = cfg.dvfs_cfg.half_us

        def _rebase(states, lane, delta):
            one = jax.tree.map(lambda a: a[lane], states)
            one = streaming_mod.shift_state_base(one, delta, half)
            return jax.tree.map(
                lambda arr, f: arr.at[lane].set(f), states, one
            )

        self._vrebase = jax.jit(_rebase)

    # -- metrics ------------------------------------------------------------

    def _declare_metrics(self, buckets: tuple) -> None:
        """Declare every runtime witness on the registry and bind its
        handle(s).  Pool-wide scalars are label-less metrics; per-bucket
        tallies are one labeled metric each, bound per configured bucket.
        ``dropped_rounds_predicted`` and ``ring_sealed_rounds`` are gauges
        (drops move predicted -> confirmed on fetch; seals drain back
        down); everything else only grows."""
        reg = self._metrics
        p, bk = POOL_STATS, POOL_BUCKET_STATS

        def ctr(name):
            return reg.counter(name, p[name])

        self._m_host_fetches = ctr("host_fetches")
        self._m_rounds_executed = ctr("rounds_executed")
        self._m_drain_wait = ctr("pump_drain_wait_s")
        self._m_forced_drains = ctr("pump_forced_drains")
        self._m_stages = ctr("pump_stages")
        self._m_stages_overlapped = ctr("pump_stages_overlapped")
        self._m_stage_s = ctr("pump_stage_s")
        self._m_ctrl_writes = ctr("ctrl_batched_writes")
        self._m_ctrl_coalesced = ctr("ctrl_actions_coalesced")
        self._m_obs_rebuilds = ctr("observation_rebuilds")
        self._m_obs_reuses = ctr("observation_reuses")
        self._m_migrations = ctr("migrations_total")
        # D2H accounting (parity with the H2D side): honest fetched bytes
        # on BOTH readouts, the dense-equivalent bytes compaction skipped,
        # and how many slot-lanes overflowed into the dense fallback.
        # Incremented inside the fetch paths — which run UNLOCKED on the
        # reader thread in async mode; registry handles carry their own
        # per-metric locks, so that is safe by design.
        self._m_d2h_bytes = ctr("d2h_bytes")
        self._m_d2h_saved = ctr("d2h_bytes_saved")
        self._m_d2h_overflow = ctr("d2h_compact_overflow_slots")
        # A chunk's life in six wall-clock segments, each summed over
        # chunks (stamps are per round, block or ring, times its chunks).
        self._m_events_fed = ctr("events_fed")
        self._m_feed_lock_wait = ctr("feed_lock_wait_s")
        self._m_chunks_returned = ctr("chunks_returned")
        self._m_chunk_buffer = ctr("chunk_buffer_wait_s")
        self._m_chunk_stage = ctr("chunk_stage_wait_s")
        self._m_chunk_ring = ctr("chunk_ring_wait_s")
        self._m_chunk_fetch = ctr("chunk_fetch_wait_s")
        self._m_chunk_distribute = ctr("chunk_distribute_wait_s")
        self._m_chunk_handoff = ctr("chunk_handoff_wait_s")
        # LUT refreshes of executed rounds, counted from the host mirrors
        self._m_lut_due = ctr("lut_refreshes_due")
        self._m_lut_runs = ctr("lut_refresh_lane_runs")
        # chunks handed back whose operating point writes with errors, from
        # the vdd_idx the readout fetches anyway (online DVFS only)
        self._m_ber_chunks = ctr("ber_chunks")

        def per_bucket(metric):
            return {b: metric.labels(bucket=b) for b in buckets}

        lbl = ("bucket",)
        self._m_h2d_slots = per_bucket(
            reg.counter("h2d_event_slots", bk["h2d_event_slots"], lbl))
        self._m_h2d_valid = per_bucket(
            reg.counter("h2d_valid_events", bk["h2d_valid_events"], lbl))
        self._m_ring_count = per_bucket(
            reg.gauge("ring_rounds_buffered", bk["ring_rounds_buffered"],
                      lbl))
        self._m_sealed = per_bucket(
            reg.gauge("ring_sealed_rounds", bk["ring_sealed_rounds"], lbl))
        self._m_dropped_dev = per_bucket(
            reg.counter("dropped_rounds_confirmed",
                        p["dropped_rounds_confirmed"], lbl))
        self._m_dropped_pred = per_bucket(
            reg.gauge("dropped_rounds_predicted",
                      "overflow drops predicted for undrained rounds", lbl))
        self._m_last_drain_wait = per_bucket(
            reg.gauge("last_drain_wait_s",
                      "wall seconds of this bucket's last forced drain",
                      lbl))

    @property
    def metrics(self) -> obs_mod.MetricsRegistry:
        """The pool-scoped metrics registry (attach sinks here)."""
        return self._metrics

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Stop the reader thread (async mode).  Rounds still sealed or
        buffered on device are abandoned — ``flush`` the lanes first if
        their results matter.  Idempotent; the runtime rejects further use.
        """
        with self._lock:
            if self._closed:
                return
            self._closed = True
        if self._reader is not None:
            self._sealed_q.put(_STOP)
            self._reader.join(timeout=30)

    def __del__(self):  # best-effort: don't leak the reader thread
        try:
            self.close()
        except Exception:
            pass

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("DetectorPool is closed")
        if self._reader_exc is not None:
            raise RuntimeError(
                "DetectorPool reader thread failed; results since the last "
                "successful drain are lost and the pool cannot continue"
            ) from self._reader_exc

    # -- executors ----------------------------------------------------------

    def _ring_specs(self, bucket: int):
        """(states_spec, ring_spec, out_shardings) for the sharded paths."""
        from jax.sharding import NamedSharding

        lane0 = sharding_mod.lane_spec(0)
        lane1 = sharding_mod.lane_spec(1)
        states_spec = jax.tree.map(lambda _: lane0, self._states)
        # Shape-generic over ring flavours (RingState / CompactRingState):
        # every per-slot buffer carries the lane axis second, every cursor
        # is a scalar — so the spec is derivable from the leaf rank.
        ring_spec = jax.tree.map(
            lambda a: lane1 if a.ndim >= 2 else P(), self._rings[bucket]
        )
        # Pin output shardings to the same spelling lane_put uses for the
        # inputs: jit would otherwise canonicalize equivalent specs (e.g.
        # P(None,'lanes') -> P('lanes') on a 1-wide mesh) and the changed
        # cache key would recompile the second block.
        out_shardings = (
            jax.tree.map(
                lambda a: NamedSharding(self._mesh, lane0), self._states
            ),
            jax.tree.map(
                lambda a: NamedSharding(
                    self._mesh, lane1 if a.ndim >= 2 else P()
                ),
                self._rings[bucket],
            ),
        )
        return states_spec, ring_spec, out_shardings

    def _build_executor(self, bucket: int):
        """Jitted K-round block: ``lax.scan`` of (``_fold_round`` + ring
        push) over ``ring_rounds`` rounds.  Padded rounds are skipped
        by a round-level ``lax.cond`` — block occupancy is data, so this
        compiles exactly once per bucket (the compile-count witness).  When
        a mesh is configured, the whole block runs under ``shard_map`` with
        the lane axis split across devices (no collectives: the step has no
        cross-lane term).  On accelerator-resident pools the stacked states
        and the live ring are donated (in-place update; the sealed rings the
        reader holds are different buffers, so async drain stays safe)."""
        tcfg = pipeline_mod._trace_cfg(self._cfg, chunk=bucket)
        donate = ("states", "ring") if self._donate else ()
        push = self._ring_push_fn(bucket)

        def block(states, ring, chunks, mask, n_valid, round_active):
            def body(carry, xs):
                states, ring = carry
                chunk, m, nv, act = xs

                def real(states, ring):
                    states, outs = _fold_round(tcfg, states, chunk, m)
                    with jax.named_scope("ring_push"):
                        ring = push(ring, outs, m, nv, act)
                    return states, ring

                states, ring = jax.lax.cond(
                    act, real, lambda s, r: (s, r), states, ring
                )
                return (states, ring), None

            (states, ring), _ = jax.lax.scan(
                body, (states, ring), (chunks, mask, n_valid, round_active)
            )
            return states, ring

        if self._mesh is not None:
            states_spec, ring_spec, out_shardings = self._ring_specs(bucket)
            lane1 = sharding_mod.lane_spec(1)
            block = compat.shard_map(
                block,
                mesh=self._mesh,
                in_specs=(states_spec, ring_spec,
                          jax.tree.map(lambda _: lane1,
                                       self._chunk_spec_template()),
                          lane1, lane1, P()),
                out_specs=(states_spec, ring_spec),
                check_vma=False,
            )
            return jax.jit(block, out_shardings=out_shardings,
                           donate_argnames=donate)
        return jax.jit(block, out_shardings=self._device,
                       donate_argnames=donate)

    def _build_single_executor(self, bucket: int):
        """Jitted 1-round block: the H2D fast path for sparse arrivals.

        Same math as one active row of the K-block (``_fold_round`` + ring
        push), but the input shapes drop the leading K axis —
        a block with exactly one ready round uploads ``(phys, chunk)``
        bytes instead of ``(K, phys, chunk)``, so a trickle of events no
        longer pays K rounds of padding per dispatch.  The price is a
        second executable per bucket (also compiled exactly once; see
        ``compile_cache_sizes``)."""
        tcfg = pipeline_mod._trace_cfg(self._cfg, chunk=bucket)
        donate = ("states", "ring") if self._donate else ()
        push = self._ring_push_fn(bucket)

        def single(states, ring, chunk, mask, n_valid):
            states, outs = _fold_round(tcfg, states, chunk, mask)
            with jax.named_scope("ring_push"):
                ring = push(ring, outs, mask, n_valid, jnp.bool_(True))
            return states, ring

        if self._mesh is not None:
            states_spec, ring_spec, out_shardings = self._ring_specs(bucket)
            lane0 = sharding_mod.lane_spec(0)
            single = compat.shard_map(
                single,
                mesh=self._mesh,
                in_specs=(states_spec, ring_spec,
                          jax.tree.map(lambda _: lane0,
                                       self._chunk_spec_template()),
                          lane0, lane0),
                out_specs=(states_spec, ring_spec),
                check_vma=False,
            )
            return jax.jit(single, out_shardings=out_shardings,
                           donate_argnames=donate)
        return jax.jit(single, out_shardings=self._device,
                       donate_argnames=donate)

    @staticmethod
    def _chunk_spec_template():
        """A ChunkInput-shaped tree to map PartitionSpecs over."""
        return state_mod.ChunkInput(
            xy=0, ts=0, valid=0, ber=0, energy_coef=0, latency_coef=0
        )

    def _make_ring(self, bucket: int) -> state_mod.RingState:
        if self._readout == "compact":
            ring = state_mod.compact_ring_init(
                self._ring_rounds, self._phys, bucket,
                self._compact_caps[bucket],
            )
        else:
            ring = state_mod.ring_init(self._ring_rounds, self._phys, bucket)
        if self._mesh is not None:
            return sharding_mod.lane_put(self._mesh, ring, 1)
        return jax.device_put(ring, self._device)

    def _ring_push_fn(self, bucket: int):
        """The executor's ring-push callable, chosen once at build time so
        the compiled-once witness holds: dense readout pushes the plain
        ring; compact readout pushes through ``ring_push_compact`` with the
        compaction routine bound — the jnp ``cumsum``-scatter oracle on the
        jnp backend (keeping that path Pallas-free), the Pallas compaction
        kernel on every pallas backend (same dual-path discipline as the
        fused step, parity-tested in ``tests/test_compact_ring.py``)."""
        if self._readout != "compact":
            return state_mod.ring_push
        cap = self._compact_caps[bucket]
        if self._cfg.backend == "jnp":
            from repro.kernels import ref as ref_mod  # pure jnp, Pallas-free

            compact_lanes = jax.vmap(
                lambda s, k: ref_mod.compact_ref(s, k, cap=cap)
            )
        else:
            from repro.kernels import ops

            interpret = self._cfg.interpret

            def compact_lanes(s, k):
                return ops.compact_slots_op(
                    s, k, cap=cap, interpret=interpret
                )

        def compact_fn(s, k):
            with jax.named_scope("compact"):
                return compact_lanes(s, k)

        import functools

        return functools.partial(
            state_mod.ring_push_compact, compact_fn=compact_fn
        )

    def _reset_ring(self, ring: state_mod.RingState) -> state_mod.RingState:
        """Mark a drained ring empty (count/dropped -> 0) without touching
        its data buffers.  The zeroed scalars are committed like the old
        ones (a bare jnp scalar would flip the executor's cache key and
        recompile), and each is its own device buffer: the executor donates
        the ring, and the runtime refuses one buffer in two donated leaves."""
        return ring._replace(
            count=jax.device_put(np.int32(0), ring.count.sharding),
            dropped=jax.device_put(np.int32(0), ring.dropped.sharding),
        )

    # -- membership ---------------------------------------------------------

    def connect(self, bucket: int, seed: Optional[int] = None,
                qos: str = "standard") -> int:
        """Claim a free lane in ``bucket`` (a configured chunk-size bucket)
        for a new camera session; returns the lane id.  Bucket and QoS
        class are the caller's choices (the façade asks its scheduler).
        The lane starts at neutral degradation knobs — ``detector_init``
        seeds ``DetectorState.ctrl`` from the config, and the host mirrors
        here match it."""
        with self._lock:
            self._check_open()
            if bucket not in self._buckets:
                raise ValueError(
                    f"{bucket} is not a configured bucket ({self._buckets})"
                )
            free = np.flatnonzero(~self._active[:self._capacity])
            if not free.size:
                raise RuntimeError(f"pool full ({self._capacity} sessions)")
            lane = int(free[0])
            fresh = state_mod.detector_init(
                self._cfg, seed=self._seed + lane if seed is None else seed
            )
            self._states = self._place(
                self._vreset(self._states, jnp.int32(lane), fresh)
            )
            self._active[lane] = True
            # the fresh state's ctrl leaves are control_init's defaults —
            # keep the full-leaf mirrors in lockstep with the device truth
            self._ctrl_lut[lane] = int(self._cfg.lut_every_chunks)
            self._ctrl_cap[lane] = self._vdd_top
            self._ctrl_shed[lane] = False
            self._lanes[lane] = _Lane(
                bucket, qos=str(qos),
                lut_every=self._cfg.lut_every_chunks,
                vdd_cap=self._vdd_top,
            )
            return lane

    def disconnect(self, lane: int) -> dict:
        """Release a lane; returns its final accounting stats.  Undrained
        ring slots referencing the lane are drained first (waiting for the
        reader in async mode), so the stats are complete and a later
        session reusing the slot inherits nothing — including a staged
        migration snapshot, which is discarded here (the mid-migration
        disconnect fix: a snapshot taken for a retired session must never
        be restored into the slot's next tenant)."""
        with self._lock:
            self._check_open()
            self._check_lane(lane)
            # take the pump token: a pump parked on the spare-ring wait
            # still holds collected-but-unexecuted rounds for this lane —
            # retiring it now would silently drop them
            self._acquire_pump()
            try:
                # re-validate: the token wait released the lock, so a
                # concurrent disconnect may have retired the lane already
                self._check_lane(lane)
                self._staged.pop(lane, None)
                self._drain_bucket(self._lanes[lane].bucket)
                out, dev = self._lane_stats_locked(lane)
                self._active[lane] = False
                self._lanes[lane] = None
            finally:
                self._release_pump()
        # device fetch after release (same discipline as stats())
        return self._finish_stats(out, dev)

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def drain_mode(self) -> str:
        return self._drain_mode

    @property
    def ring_depth(self) -> int:
        return self._ring_depth

    @property
    def active_lanes(self) -> list[int]:
        return [int(i) for i in np.flatnonzero(self._active)]

    @property
    def buckets(self) -> tuple:
        return self._buckets

    @property
    def host_fetches(self) -> int:
        """Blocking result transfers so far (one per ring drain; counted on
        the reader thread in async mode)."""
        return self._m_host_fetches.value()

    @property
    def rounds_executed(self) -> int:
        return self._m_rounds_executed.value()

    def compile_cache_size(self) -> int:
        """Total executor executables across buckets and shapes (grows only
        when a new bucket or block shape is first exercised; membership
        churn and migration must not grow it)."""
        return sum(n for d in self.compile_cache_sizes().values()
                   for n in d.values())

    def compile_cache_sizes(self) -> dict:
        """Per-bucket executable counts, per block shape:
        ``{bucket: {"block": n, "single": n}}``.  Each entry must stay <= 1
        — occupancy, membership, and lane placement are data, so nothing
        recompiles; the ``"single"`` entry (the 1-round H2D fast path,
        built when ``ring_rounds > 1``) is simply absent until first used.
        """
        out: dict = {}
        for b in self._buckets:
            d = {"block": self._exec[b]._cache_size()}
            if b in self._exec1:
                d["single"] = self._exec1[b]._cache_size()
            out[b] = d
        return out

    def executor_hlo(self) -> list:
        """The compiled HLO text of every executor that has run, lowered
        again from the abstract arguments of its first call (the same
        program, so the same instruction names as in a device trace;
        nothing new is compiled where JAX still holds the executable).
        ``repro.obs.latest_hlo_texts`` gives the same after ``close``."""
        return self._exec_notes.hlo_texts()

    def executors_compiled_once(self) -> bool:
        """The churn witness: every executor (per bucket, per block shape)
        has compiled at most one executable."""
        return all(n <= 1 for d in self.compile_cache_sizes().values()
                   for n in d.values())

    # -- feeding ------------------------------------------------------------

    def feed(self, lane: int, xy: np.ndarray, ts_us: np.ndarray) -> None:
        """Buffer a slab for one session (any length, time-sorted) and fold
        its timestamps into the lane's host rate-estimator twin.  A lane
        in shed mode additionally caps its re-chunk buffer at one ring of
        rounds, dropping the *oldest* buffered events (the real-time
        regime: stale events are worthless; the rate twin still counts
        them, so recovery sees the true arrival rate)."""
        with obs_mod.span("feed"):
            t0 = obs_mod.timer()
            self._take_lock()
            try:
                t_fed = obs_mod.timer()
                self._m_feed_lock_wait.inc(t_fed - t0)
                self._check_open()
                self._check_lane(lane)
                ln = self._lanes[lane]
                xy = np.asarray(xy, np.int32).reshape(-1, 2)
                ts = np.asarray(ts_us, np.int64).reshape(-1)
                if not ts.size:
                    return
                if ln.base is None:
                    ln.base = streaming_mod.session_base_us(
                        int(ts[0]), self._cfg
                    )
                ln.buf_xy = np.concatenate([ln.buf_xy, xy], 0)
                ln.buf_ts = np.concatenate([ln.buf_ts, ts], 0)
                ln.n_events += int(ts.size)
                ln.fed_cum += int(ts.size)
                ln.feed_times.append((ln.fed_cum, t_fed))
                self._m_events_fed.inc(int(ts.size))
                ln.rate_update(ts, self._half_us)
                ln.gen += 1           # backlog and rate twin changed
                if ln.knob_shed:
                    self._shed_buffer(ln)
            finally:
                self._lock.release()

    def _shed_buffer(self, ln: _Lane) -> None:
        """Drop-oldest a shedding lane's re-chunk buffer down to one ring
        of rounds (caller holds the lock)."""
        cap = self._ring_rounds * ln.bucket
        excess = int(ln.buf_ts.size) - cap
        if excess > 0:
            ln.buf_xy = ln.buf_xy[excess:]
            ln.buf_ts = ln.buf_ts[excess:]
            ln.shed_events += excess
            ln.gen += 1           # backlog changed
            head = ln.fed_cum - int(ln.buf_ts.size)
            while ln.feed_times and ln.feed_times[0][0] <= head:
                ln.feed_times.popleft()     # slabs shed whole

    def pump_pass(self, order: tuple,
                  max_rounds: Optional[int] = None,
                  decide=None) -> int:
        """One serialized pump pass: apply staged migrations, run the
        control loop (observe -> ``decide`` -> actuate, when a policy's
        ``decide`` is passed), then fold every buffered full chunk through
        the ring executors, visiting buckets in ``order`` (the scheduler's
        choice; each bucket pumps until dry or the round budget runs out).
        Returns rounds executed.

        The control loop runs under the pump token before any round is
        collected: knob actions apply to *this* pass's rounds, migrate
        actions stage and apply at the *next* pass (the same deferral
        window staged migrations already use — the no-pump gap guarantees
        the snapshot cannot go stale).  Results stay in the on-device
        rings until ``poll``/``flush`` (or a backpressure drain/seal under
        the ``"drain"`` policy).  K-round blocks with one fetch per drain
        are bit-exact vs the same rounds pumped one at a time; concurrent
        pumpers serialize on the pump token (round order must match the
        sequential path even while a seal waits on a spare ring).

        The pass pipelines blocks through one stage-ahead deque shared
        across its buckets: a block's H2D upload is issued at *stage*, its
        executor launches at *dispatch*, and up to ``pipeline_depth - 1``
        staged blocks ride ahead of the dispatch point.  Dispatch order is
        stage order, and the deque is always flushed before the pass
        returns (``finally`` — an exception mid-pass cannot strand an
        uploaded block), so every staged round executes exactly once, in
        the serial path's order."""
        with obs_mod.span("pump_pass"), self._lock:
            self._check_open()
            self._acquire_pump()
            try:
                with obs_mod.span("control"):
                    self._apply_staged_locked()
                    if decide is not None:
                        actions = decide(self._observation_locked())
                        if actions:
                            self._apply_actions_locked(actions)
                total = 0
                q: collections.deque = collections.deque()
                self._pass_dispatches = 0
                try:
                    for bucket in order:
                        left = (None if max_rounds is None
                                else max_rounds - total)
                        if left is not None and left <= 0:
                            break
                        total += self._pump_bucket(bucket, q,
                                                   max_rounds=left)
                finally:
                    self._flush_pipeline(q)
                return total
            finally:
                self._release_pump()

    def flush(self, lane: int, order: tuple) -> tuple[np.ndarray, np.ndarray]:
        """Drain the lane's full chunks, then its padded partial tail, and
        return everything not yet polled.  A lane with an empty re-chunk
        buffer just drains its ring (no extra round is scheduled)."""
        with self._lock:
            self._check_open()
            self._check_lane(lane)
            self._acquire_pump()
            try:
                # re-validate after the token wait (see disconnect)
                self._check_lane(lane)
                self._apply_staged_locked()
                q: collections.deque = collections.deque()
                self._pass_dispatches = 0
                try:
                    for bucket in order:
                        self._pump_bucket(bucket, q)   # until dry
                    ln = self._lanes[lane]
                    if ln.buf_ts.size:
                        self._pump_bucket(ln.bucket, q, max_rounds=1,
                                          flush_lane=lane)
                finally:
                    self._flush_pipeline(q)
            finally:
                self._release_pump()
            return self.poll(lane)

    def _take_lock(self) -> None:
        """Acquire the pool lock; a wait for it is a ``pool.lock_wait``
        span (an uncontended take records none)."""
        if not self._lock.acquire(blocking=False):
            with obs_mod.span("lock_wait"):
                self._lock.acquire()

    def _acquire_pump(self) -> None:
        """Take the pump token (caller holds the lock); waits out any pump
        in flight so two pumpers cannot interleave their round order."""
        while self._pump_busy:
            self._check_open()
            self._cv.wait()
        self._pump_busy = True

    def _release_pump(self) -> None:
        self._pump_busy = False
        self._cv.notify_all()

    def poll(self, lane: int, *,
             wait: bool = True) -> tuple[np.ndarray, np.ndarray]:
        """Drain the lane's accumulated (scores, kept), in stream order.

        This is the readout (and backpressure) point.  In ``"sync"`` mode
        it fetches the lane's bucket ring inline — ONE blocking transfer
        for everything buffered since the last drain, however many pump
        rounds that spans.  In ``"async"`` mode it *seals* the live ring
        (atomic swap with a spare; the reader thread performs the fetch)
        and, with ``wait=True`` (default), blocks until the reader has
        drained it — same results as sync, fetched off this thread.
        ``wait=False`` never blocks on a transfer in either mode: async
        seals only when a spare ring is free (never joining an in-flight
        fetch) and returns what the reader has already drained; sync skips
        the inline fetch entirely and returns what earlier drains (e.g.
        backpressure pre-drains) already distributed.  The rest arrives on
        a later poll.  Under ``on_overflow="drop_oldest"``, rounds lost to
        overflow are simply absent here and counted in
        ``stats()['ring_dropped_rounds']``."""
        self._take_lock()
        try:
            if not wait and not self._poll_finds_work(lane):
                return self._poll_locked(lane, wait)    # no span: a no-op
            with obs_mod.span("poll"):
                return self._poll_locked(lane, wait)
        finally:
            self._lock.release()

    def _poll_finds_work(self, lane: int) -> bool:
        """Whether a non-blocking poll of ``lane`` hands back results or
        seals a ring (caller holds the lock): a poller spinning over idle
        lanes records no span."""
        ln = self._lanes[lane] if 0 <= lane < self._capacity else None
        if ln is None:
            return True
        b = ln.bucket
        return bool(ln.results) or (
            self._drain_mode == "async" and bool(self._spares[b])
            and self._m_ring_count[b].value() > 0)

    def _poll_locked(self, lane: int, wait: bool):
        """The body of ``poll`` (caller holds the lock); hands the lane's
        results back and counts their handoff wait."""
        self._check_open()
        self._check_lane(lane)
        bucket = self._lanes[lane].bucket
        self._drain_bucket(bucket, wait=wait, block=wait)
        # re-validate: an async drain waits on the reader with the lock
        # released, so a concurrent disconnect may have retired the lane —
        # surface the documented KeyError, not a crash on the None slot
        self._check_lane(lane)
        ln = self._lanes[lane]
        if not ln.results:
            return (np.zeros((0,), np.float32), np.zeros((0,), bool))
        scores = np.concatenate(
            [r[0] for r in ln.results]
        ).astype(np.float32)
        kept = np.concatenate([r[1] for r in ln.results]).astype(bool)
        ln.results.clear()
        self._m_chunk_handoff.inc(ln.res_n * obs_mod.timer() - ln.res_tsum)
        self._m_chunks_returned.inc(ln.res_n)
        self._m_ber_chunks.inc(ln.res_ber)
        ln.res_n, ln.res_tsum, ln.res_ber = 0, 0.0, 0
        return scores, kept

    # -- migration mechanics -------------------------------------------------

    def stage_migration(self, lane: int, new_bucket: int) -> None:
        """Stage a live-lane bucket move: seal+drain the lane's current
        bucket (every executed round reaches its result queue, in order),
        then snapshot the lane's device state to a donation-proof host
        checkpoint (owned deep copies, like ``StreamingDetector.snapshot``).
        The restore half applies at the start of the next pump pass —
        rounds cannot execute between stage and apply (both pump entry
        points apply first, under the pump token), so the snapshot can
        never go stale.  Re-staging a lane replaces its pending move;
        staging its current bucket cancels it."""
        with self._lock:
            self._check_open()
            self._check_lane(lane)
            if new_bucket not in self._buckets:
                raise ValueError(
                    f"{new_bucket} is not a configured bucket "
                    f"({self._buckets})"
                )
            ln = self._lanes[lane]
            if new_bucket == ln.bucket:
                self._staged.pop(lane, None)
                return
            self._acquire_pump()
            try:
                # Re-validate after the token wait: the lane may have been
                # retired (and its slot even re-connected) by a concurrent
                # disconnect while we waited — the decision belonged to
                # the dead session, so drop it rather than migrate the new
                # tenant on the old tenant's rate history.  A pump pass
                # that ran meanwhile may also have applied an earlier
                # staged move; if the lane already sits in the target
                # bucket, cancel.  (While we HOLD the token no disconnect
                # can complete — it needs the token too — so one re-check
                # here covers the drain's cv waits below.)
                if self._lanes[lane] is not ln or not self._active[lane]:
                    return
                self._stage_locked(lane, new_bucket)
            finally:
                self._release_pump()

    def _stage_locked(self, lane: int, new_bucket: int) -> None:
        """The stage body: seal+drain the lane's bucket and checkpoint its
        state.  Caller holds the lock AND the pump token (either via
        ``stage_migration`` or from inside a pump pass actuating a migrate
        Action — the token is not re-entrant, so the in-pump path must not
        call ``stage_migration`` itself)."""
        ln = self._lanes[lane]
        if new_bucket == ln.bucket:
            self._staged.pop(lane, None)
            return
        self._drain_bucket(ln.bucket)
        snap = jax.tree.map(
            lambda a: np.array(a),
            jax.device_get(
                jax.tree.map(lambda a: a[lane], self._states)
            ),
        )
        self._staged[lane] = (snap, new_bucket)

    def staged_migrations(self) -> dict:
        """Pending (staged, not yet applied) moves: ``{lane: bucket}``."""
        with self._lock:
            return {ln: b for ln, (_, b) in self._staged.items()}

    def _apply_staged_locked(self) -> None:
        """Restore every staged lane into its target bucket (caller holds
        the lock AND the pump token, before any round collection).  The
        snapshot is ``device_put`` back as an owned copy and written into
        the stacked lane state through the same jitted per-lane reset
        ``connect`` uses — nothing recompiles, placement is re-pinned on
        the lane mesh, and the lane's re-chunk buffer simply re-chunks at
        the new size from the next collect."""
        for lane in sorted(self._staged):
            snap, new_bucket = self._staged.pop(lane)
            ln = self._lanes[lane]
            if ln is None or not self._active[lane]:
                continue                      # retired between stage and apply
            old = ln.bucket
            self._drain_bucket(old)           # belt & braces: stream order
            restored = jax.device_put(jax.tree.map(np.array, snap))
            self._states = self._place(
                self._vreset(self._states, jnp.int32(lane), restored)
            )
            # the restore rewrote the lane's ctrl leaves from the snapshot
            # — fold the snapshot values into the full-width knob mirrors
            self._ctrl_lut[lane] = int(snap.ctrl.lut_every)
            self._ctrl_cap[lane] = int(snap.ctrl.vdd_cap)
            self._ctrl_shed[lane] = bool(snap.ctrl.shed)
            ln.bucket = new_bucket
            ln.gen += 1           # bucket (and backlog-rounds basis) changed
            ln.migrations += 1
            ln.migration_log.append((ln.events_folded, old, new_bucket))
            self._m_migrations.inc()

    # -- control loop: observe -> decide -> actuate --------------------------

    def _observation_locked(self) -> scheduler_mod.Observation:
        """Per-pump observation snapshot (caller holds lock + pump token,
        staged migrations already applied).  All host data — observing
        costs no device sync.

        Per-lane fields are memoized on the lane's generation counter
        (bumped by feed, round collection, shed, migration apply, and tier
        writes): an idle pass re-serves cached ``LaneObservation`` tuples
        and costs O(changed lanes), witnessed by
        ``observation_rebuilds``/``observation_reuses``."""
        lanes = []
        backlog = {b: 0 for b in self._buckets}
        for lane in self.active_lanes:
            ln = self._lanes[lane]
            cached = ln.obs_cache
            if cached is not None and cached[0] == ln.gen:
                lob = cached[1]
                self._m_obs_reuses.inc()
            else:
                eps = state_mod.rate_estimate_eps(
                    ln.r_p1, ln.r_p2, self._cfg.dvfs_cfg
                )
                lob = scheduler_mod.LaneObservation(
                    lane=lane,
                    bucket=ln.bucket,
                    qos=ln.qos,
                    tier=ln.tier,
                    events_per_halfwin=eps * self._half_us * 1e-6,
                    backlog_rounds=int(ln.buf_ts.size) // ln.bucket,
                    win=ln.r_win,
                )
                ln.obs_cache = (ln.gen, lob)
                self._m_obs_rebuilds.inc()
            backlog[lob.bucket] += lob.backlog_rounds
            lanes.append(lob)
        h2d_slots = sum(h.value() for h in self._m_h2d_slots.values())
        h2d_valid = sum(h.value() for h in self._m_h2d_valid.values())
        return scheduler_mod.Observation(
            lanes=tuple(lanes),
            backlog_rounds=backlog,
            reader_lag_rounds={b: self._m_sealed[b].value()
                               for b in self._buckets},
            drain_wait_s=float(self._m_drain_wait.value()),
            last_drain_wait_s={b: float(self._m_last_drain_wait[b].value())
                               for b in self._buckets},
            padding_ratio=(
                1.0 - h2d_valid / h2d_slots if h2d_slots else 0.0
            ),
            h2d_event_slots=h2d_slots,
            h2d_valid_events=h2d_valid,
            h2d_padding_bytes=(h2d_slots - h2d_valid) * EVENT_SLOT_BYTES,
            h2d_by_bucket={
                b: {"slots": self._m_h2d_slots[b].value(),
                    "valid": self._m_h2d_valid[b].value()}
                for b in self._buckets
            },
            phys=self._phys,
            ring_rounds=self._ring_rounds,
        )

    def _apply_actions_locked(self, actions) -> None:
        """Actuate a policy's decisions (caller holds lock + pump token).
        Knob writes and drop-policy flips apply now — before this pass's
        rounds; migrations stage and apply at the next pass.  Actions for
        lanes retired since the observation are dropped: the decision
        belonged to the dead session, and a slot's next tenant starts at
        neutral knobs regardless.

        Knob writes are coalesced: the pass collects every action's wanted
        knob triple first, then actuates them all in ONE batched ctrl-leaf
        replace (fed from the full-width host mirrors) instead of one
        jitted ``at[lane].set`` dispatch per action — value-identical,
        since unmentioned lanes re-write their mirror (= device) values.
        Migrations stage *after* the knob batch, so an action carrying
        both sees its own knob write in the snapshot, exactly like the
        serial one-action-at-a-time path did.  A pass with a single knob
        write keeps the per-lane ``at[lane].set`` spelling (no cheaper to
        batch)."""
        writes = []                # (lane, ln, want triple) in action order
        for act in actions:
            if act.drop_policy is not None:
                if act.drop_policy not in _OVERFLOW_POLICIES:
                    raise ValueError(
                        f"drop_policy must be one of {_OVERFLOW_POLICIES}, "
                        f"got {act.drop_policy!r}"
                    )
                self._overflow = act.drop_policy
            lane = act.lane
            if lane is None:
                continue
            if not (0 <= lane < self._capacity) or not self._active[lane]:
                continue                       # raced a disconnect
            ln = self._lanes[lane]
            want = self._knob_want(ln, act.lut_every, act.vdd_cap, act.shed)
            if want is not None:
                writes.append((lane, ln, want))
        if len(writes) == 1:
            self._apply_knobs_locked(*writes[0])
        elif writes:
            self._apply_knob_batch_locked(writes)

        for act in actions:
            lane = act.lane
            if lane is None or not (0 <= lane < self._capacity) \
                    or not self._active[lane]:
                continue
            ln = self._lanes[lane]
            if act.tier is not None and int(act.tier) != ln.tier:
                ln.tier = int(act.tier)
                ln.gen += 1       # the tier mirror is observable
            if act.migrate is not None:
                if act.migrate not in self._buckets:
                    raise ValueError(
                        f"{act.migrate} is not a configured bucket "
                        f"({self._buckets})"
                    )
                self._stage_locked(lane, act.migrate)

    def _knob_want(self, ln: _Lane, lut_every: Optional[int],
                   vdd_cap: Optional[int],
                   shed: Optional[bool]) -> Optional[tuple]:
        """Clamp a knob request against the lane's current mirrors; None
        when the write would be a no-op."""
        want = (
            ln.knob_lut_every if lut_every is None else max(1,
                                                            int(lut_every)),
            ln.knob_vdd_cap if vdd_cap is None
            else max(0, min(int(vdd_cap), self._vdd_top)),
            ln.knob_shed if shed is None else bool(shed),
        )
        if want == (ln.knob_lut_every, ln.knob_vdd_cap, ln.knob_shed):
            return None
        return want

    def _set_knobs_locked(self, lane: int, ln: _Lane,
                          lut_every: Optional[int],
                          vdd_cap: Optional[int],
                          shed: Optional[bool]) -> None:
        """Write a lane's degradation knobs (caller holds lock + pump
        token).  One jitted ``at[lane].set`` writes all three ctrl leaves
        — unspecified knobs re-write their current mirror value, so the
        write's trace never depends on which knobs the caller moved."""
        want = self._knob_want(ln, lut_every, vdd_cap, shed)
        if want is not None:
            self._apply_knobs_locked(lane, ln, want)

    def _apply_knobs_locked(self, lane: int, ln: _Lane, want: tuple) -> None:
        """The single-lane actuation: one jitted ``at[lane].set``."""
        self._states = self._place(self._vctrl(
            self._states, jnp.int32(lane),
            jnp.int32(want[0]), jnp.int32(want[1]), jnp.asarray(want[2]),
        ))
        self._commit_knobs(lane, ln, want)

    def _apply_knob_batch_locked(self, writes: list) -> None:
        """The coalesced actuation: fold every wanted triple into the
        full-width host mirrors, then replace the three ctrl leaves in one
        jitted update.  Later writes to the same lane win, matching the
        serial order."""
        lut = self._ctrl_lut.copy()
        cap = self._ctrl_cap.copy()
        shd = self._ctrl_shed.copy()
        for lane, _ln, want in writes:
            lut[lane], cap[lane], shd[lane] = want
        self._states = self._place(self._vctrl_all(
            self._states, jnp.asarray(lut), jnp.asarray(cap),
            jnp.asarray(shd),
        ))
        self._ctrl_lut, self._ctrl_cap, self._ctrl_shed = lut, cap, shd
        self._m_ctrl_writes.inc()
        self._m_ctrl_coalesced.inc(len(writes))
        for lane, ln, want in writes:
            self._commit_knobs(lane, ln, want, device_written=True)

    def _commit_knobs(self, lane: int, ln: _Lane, want: tuple,
                      *, device_written: bool = False) -> None:
        """Post-write bookkeeping shared by both actuation spellings:
        update the lane + full-width mirrors and shed immediately on a
        shed entry.  ``device_written`` marks mirrors already folded into
        a batched leaf replace."""
        entered_shed = want[2] and not ln.knob_shed
        ln.knob_lut_every, ln.knob_vdd_cap, ln.knob_shed = want
        if not device_written:
            self._ctrl_lut[lane], self._ctrl_cap[lane], \
                self._ctrl_shed[lane] = want
        if entered_shed:
            self._shed_buffer(ln)     # immediate relief, not just next feed

    def set_lane_control(self, lane: int, *,
                         lut_every: Optional[int] = None,
                         vdd_cap: Optional[int] = None,
                         shed: Optional[bool] = None) -> None:
        """Manually set a lane's degradation knobs (the out-of-band spelling
        of a knob ``Action``; serialized on the pump token so it cannot
        interleave with a pass's rounds)."""
        with self._lock:
            self._check_open()
            self._check_lane(lane)
            self._acquire_pump()
            try:
                self._check_lane(lane)    # re-validate after the token wait
                self._set_knobs_locked(lane, self._lanes[lane],
                                       lut_every, vdd_cap, shed)
            finally:
                self._release_pump()

    @property
    def vdd_top(self) -> int:
        """Highest DVFS operating-point index a knob may select (0 in
        fixed-Vdd mode, where the cap is inert)."""
        return self._vdd_top

    # -- observability -------------------------------------------------------

    def lane_halfwin_rate(self, lane: int) -> float:
        """Observed events per DVFS half-window for one lane, read off the
        host rate twin (no device sync).  The scheduler's migration metric:
        a lane is well-bucketed when this sits at or below its bucket's
        chunk size."""
        with self._lock:
            self._check_lane(lane)
            ln = self._lanes[lane]
            eps = state_mod.rate_estimate_eps(
                ln.r_p1, ln.r_p2, self._cfg.dvfs_cfg
            )
            return eps * self._half_us * 1e-6

    def bucket_backlog_rounds(self) -> dict:
        """Ready-but-unpumped rounds per bucket (full chunks waiting in
        lane re-chunk buffers) — the starvation signal the adaptive pump
        order consumes."""
        with self._lock:
            out = {b: 0 for b in self._buckets}
            for lane in self.active_lanes:
                ln = self._lanes[lane]
                out[ln.bucket] += int(ln.buf_ts.size) // ln.bucket
            return out

    def stats(self, lane: int) -> dict:
        """Lane accounting: host float64 books plus the lane's on-device
        accumulators (f32/i32 — aggregatable without per-chunk host sync),
        plus ring/bucket occupancy so callers can observe backpressure,
        plus the lane's rate/migration view (``events_per_s_est`` is the
        host rate twin — live for every config; ``device_events_per_s_est``
        reads the in-state estimator, which only integrates in online-DVFS
        mode and reports 0 otherwise).

        Host books (``kept_total``/``energy_pj``/...) cover *drained*
        rounds only.  ``ring_rounds_buffered`` says how many rounds sit in
        the live on-device ring; ``ring_sealed_rounds`` how many are sealed
        and in the reader's hands but not yet drained (async mode — the
        reader lag for this bucket; always 0 in sync mode).
        ``ring_dropped_rounds`` is drops confirmed by fetches plus drops
        predicted for rounds still on device (the host mirror is audited
        against the device counter at every fetch).  The ``device_*``
        accumulators are always complete — including rounds dropped under
        ``drop_oldest``."""
        with self._lock:
            self._check_open()
            self._check_lane(lane)
            out, dev = self._lane_stats_locked(lane)
        return self._finish_stats(out, dev)

    def _lane_stats_locked(self, lane: int):
        """Host-side stats dict + *pre-indexed* device scalars (caller
        holds the lock).  Indexing only dispatches; the blocking
        ``device_get`` belongs in ``_finish_stats``, AFTER the lock is
        released — the lock discipline keeps blocking transfers off the
        pool lock, so a monitoring thread syncing on a deep pump queue
        cannot stall the pump, the reader, or other callers (``stats`` and
        ``disconnect`` both follow this split)."""
        ln = self._lanes[lane]
        n_scored = max(ln.kept_total, 1)
        dev = (
            self._states.kept_total[lane],
            self._states.energy_pj[lane],
            self._states.latency_ns[lane],
            self._states.rate.prev1[lane],
            self._states.rate.prev2[lane],
        )
        b = ln.bucket
        out = {
            "lane": lane,
            "bucket": b,
            "n_events": ln.n_events,
            "n_chunks": ln.n_chunks,
            "kept_total": ln.kept_total,
            "energy_pj": ln.energy_pj,
            "latency_ns_per_event": ln.latency_ns / n_scored,
            "buffered": int(ln.buf_ts.size),
            "events_per_s_est": state_mod.rate_estimate_eps(
                ln.r_p1, ln.r_p2, self._cfg.dvfs_cfg
            ),
            "migrations": ln.migrations,
            "migration_log": list(ln.migration_log),
            "migration_staged": lane in self._staged,
            "ring_capacity": self._ring_rounds,
            "ring_rounds_buffered": self._m_ring_count[b].value(),
            "ring_sealed_rounds": self._m_sealed[b].value(),
            "ring_dropped_rounds": (
                self._m_dropped_dev[b].value()
                + self._m_dropped_pred[b].value()
            ),
            # -- the ladder's per-lane inputs and outputs (ISSUE 6):
            # how far behind this lane runs (re-chunk backlog depth +
            # reader lag on its bucket + the bucket's last forced-drain
            # wait) and where its degradation knobs currently sit.
            "backlog_rounds": int(ln.buf_ts.size) // b,
            "reader_lag_rounds": self._m_sealed[b].value(),
            # wall-time witnesses export as float even before the first
            # drain (fresh gauges hold int 0) — the legacy dicts did
            "last_drain_wait_s": float(self._m_last_drain_wait[b].value()),
            "qos": ln.qos,
            "ladder_tier": ln.tier,
            "ctrl_lut_every": ln.knob_lut_every,
            "ctrl_vdd_cap": ln.knob_vdd_cap,
            "ctrl_shed": ln.knob_shed,
            "shed_events": ln.shed_events,
        }
        return out, dev

    def _finish_stats(self, out: dict, dev) -> dict:
        dev_kept, dev_energy, dev_latency, dev_p1, dev_p2 = \
            jax.device_get(dev)
        out["device_kept_total"] = int(dev_kept)
        out["device_energy_pj"] = float(dev_energy)
        out["device_latency_ns"] = float(dev_latency)
        out["device_events_per_s_est"] = state_mod.rate_estimate_eps(
            dev_p1, dev_p2, self._cfg.dvfs_cfg
        )
        return out

    def pool_stats(self) -> dict:
        """Pool-level runtime counters (no device sync): fetch/round ratio,
        per-bucket ring occupancy and drop counts, reader lag, pump drain
        wait, sharding layout, migration and H2D-padding tallies.

        ``pump_drain_wait_s`` is the wall time the *pump* path spent making
        ring room before a block (sync: the inline fetch+distribute; async:
        the seal — usually just an enqueue, plus any wait for a spare
        ring).  ``reader_lag_rounds`` counts rounds sealed to the reader
        thread but not yet drained; ``dropped_rounds_confirmed`` is the
        device-counter ground truth accumulated over fetches (equals
        ``dropped_rounds_total`` once everything has been drained — the
        host-mirror audit).  ``pump_forced_drains`` counts mid-pump
        makes-room events (ring occupancy forced a drain/seal before a
        block) — the reliable backpressure signal; in async mode
        ``host_fetches`` deltas are NOT, since fetches are counted when the
        reader completes them, not when the pump seals.
        ``h2d_event_slots`` vs ``h2d_valid_events`` is the upload-padding
        audit (``h2d_padding_bytes`` = the gap times the 13-byte event
        slot): the quantity adaptive bucket migration exists to shrink."""
        with self._lock:
            self._check_open()
            exe = self.compile_cache_sizes()
            h2d_slots = sum(h.value() for h in self._m_h2d_slots.values())
            h2d_valid = sum(h.value() for h in self._m_h2d_valid.values())
            stages = self._m_stages.value()
            overlapped = self._m_stages_overlapped.value()
            dropped_pred = sum(h.value()
                               for h in self._m_dropped_pred.values())
            dropped_dev = sum(h.value()
                              for h in self._m_dropped_dev.values())
            return {
                "capacity": self._capacity,
                "active": len(self.active_lanes),
                "sharded": self._mesh is not None,
                "devices": (int(self._mesh.devices.size)
                            if self._mesh is not None else 1),
                "ring_rounds": self._ring_rounds,
                "ring_depth": self._ring_depth,
                "pipeline_depth": self._pipeline_depth,
                "on_overflow": self._overflow,
                "drain_mode": self._drain_mode,
                "readout": self._readout,
                "host_fetches": self._m_host_fetches.value(),
                "rounds_executed": self._m_rounds_executed.value(),
                "pump_drain_wait_s": float(self._m_drain_wait.value()),
                "pump_forced_drains": self._m_forced_drains.value(),
                # pipelined-pump witnesses: how many block stages began
                # while an earlier block of the same pass was already
                # dispatched (structural, deterministic at fixed sizes),
                # plus the wall time staging took
                "pump_stages": stages,
                "pump_stages_overlapped": overlapped,
                "pump_stage_overlap_ratio": (
                    overlapped / stages if stages else 0.0
                ),
                "pump_stage_s": float(self._m_stage_s.value()),
                "ctrl_batched_writes": self._m_ctrl_writes.value(),
                "ctrl_actions_coalesced": self._m_ctrl_coalesced.value(),
                "observation_rebuilds": self._m_obs_rebuilds.value(),
                "observation_reuses": self._m_obs_reuses.value(),
                "reader_lag_rounds": sum(
                    h.value() for h in self._m_sealed.values()
                ),
                "migrations_total": self._m_migrations.value(),
                "migrations_staged": len(self._staged),
                "h2d_event_slots": h2d_slots,
                "h2d_valid_events": h2d_valid,
                "h2d_pinned_staging": bool(
                    self._stager is not None and self._stager.pinned
                ),
                "h2d_staged_uploads": (
                    self._stager.uploads if self._stager is not None else 0
                ),
                "h2d_padding_bytes": (
                    (h2d_slots - h2d_valid) * EVENT_SLOT_BYTES
                ),
                "d2h_bytes": self._m_d2h_bytes.value(),
                "d2h_bytes_saved": self._m_d2h_saved.value(),
                "d2h_compact_overflow_slots": self._m_d2h_overflow.value(),
                "dropped_rounds_total": dropped_dev + dropped_pred,
                "dropped_rounds_confirmed": dropped_dev,
                "shed_events_total": sum(
                    ln.shed_events for ln in self._lanes if ln is not None
                ),
                # a chunk's life: ingest, the feed's lock wait, and the
                # six segments from feed to poll, each summed over chunks
                "events_fed": self._m_events_fed.value(),
                "feed_lock_wait_s": float(self._m_feed_lock_wait.value()),
                "chunks_returned": self._m_chunks_returned.value(),
                "chunk_buffer_wait_s": float(self._m_chunk_buffer.value()),
                "chunk_stage_wait_s": float(self._m_chunk_stage.value()),
                "chunk_ring_wait_s": float(self._m_chunk_ring.value()),
                "chunk_fetch_wait_s": float(self._m_chunk_fetch.value()),
                "chunk_distribute_wait_s": float(
                    self._m_chunk_distribute.value()),
                "chunk_handoff_wait_s": float(self._m_chunk_handoff.value()),
                # the executors' LUT refreshes: lanes due, and the lanes
                # the refresh batches ran the Harris on
                "lut_refreshes_due": self._m_lut_due.value(),
                "lut_refresh_lane_runs": self._m_lut_runs.value(),
                "ber_chunks": self._m_ber_chunks.value(),
                "buckets": {
                    b: {
                        "lanes": sum(
                            1 for ln in self._lanes
                            if ln is not None and ln.bucket == b
                        ),
                        "events_per_s_est": sum(
                            state_mod.rate_estimate_eps(
                                ln.r_p1, ln.r_p2, self._cfg.dvfs_cfg
                            )
                            for ln in self._lanes
                            if ln is not None and ln.bucket == b
                        ),
                        "ring_rounds_buffered":
                            self._m_ring_count[b].value(),
                        "ring_sealed_rounds": self._m_sealed[b].value(),
                        "ring_dropped_rounds": (
                            self._m_dropped_dev[b].value()
                            + self._m_dropped_pred[b].value()
                        ),
                        "h2d_event_slots": self._m_h2d_slots[b].value(),
                        "h2d_valid_events": self._m_h2d_valid[b].value(),
                        "executables": exe[b],
                    }
                    for b in self._buckets
                },
            }

    # -- internals ----------------------------------------------------------

    def _check_lane(self, lane: int) -> None:
        if not (0 <= lane < self._capacity) or not self._active[lane]:
            raise KeyError(f"lane {lane} is not an active session")

    def _place(self, states):
        """Pin the pool's sharding after a per-lane host update (`_vreset` /
        `_vrebase` infer their own output sharding, which can canonicalize
        away the NamedSharding on a 1-wide mesh, or the memory kind on one
        device, and flip the executor's cache key).  No copy when already
        placed."""
        if self._mesh is None:
            return jax.device_put(states, self._device)
        return sharding_mod.lane_put(self._mesh, states, 0)

    def _pump_bucket(self, bucket: int, q: collections.deque,
                     max_rounds: Optional[int] = None,
                     flush_lane: Optional[int] = None) -> int:
        """Run this bucket's ready rounds through its K-round executor,
        cutting a block early when a lane needs a timebase rebase (the hop
        applies between blocks; rebases are ~hourly per session).

        ``q`` is the pass's stage-ahead deque: a completed block is
        *staged* (host gather + H2D upload issued) immediately, but its
        executor *dispatches* only once the deque holds ``pipeline_depth``
        blocks — so with the default depth 2, block *i+1* stages while
        block *i* still runs on device.  A rebase is a device write to the
        stacked states, and a staged block's timestamps are relative to
        its collect-time base — so a rebase may only apply when nothing is
        staged ahead: the pump flushes the deque first and retries the
        collect (``allow_rebase`` also requires an empty deque)."""
        executed = 0
        while True:
            pending: list[_Round] = []
            stop = False
            with obs_mod.span("collect"):
                while len(pending) < self._ring_rounds:
                    if max_rounds is not None and \
                            executed + len(pending) >= max_rounds:
                        stop = True
                        break
                    rnd = self._collect_round(
                        bucket, flush_lane,
                        allow_rebase=not pending and not q,
                    )
                    if rnd == "rebase":
                        if not pending and q:
                            # blocked only by staged-ahead blocks: drain
                            # the pipeline, then retry with the rebase
                            # allowed
                            self._flush_pipeline(q)
                            continue
                        break      # cut the block; rebase opens the next
                    if rnd is None:
                        stop = True
                        break
                    pending.append(rnd)
            if pending:
                q.append(self._stage_block(bucket, pending,
                                           stage_ahead=bool(q)))
                while len(q) >= self._pipeline_depth:
                    self._dispatch_block(q.popleft())
                executed += len(pending)
            if stop or not pending:
                break
        return executed

    def _flush_pipeline(self, q: collections.deque) -> None:
        """Dispatch every staged-ahead block, in stage order.  Runs before
        a pass returns (and before any rebase), so a staged upload can
        never be dropped, reordered, or executed against a shifted
        timebase."""
        while q:
            self._dispatch_block(q.popleft())

    def _collect_round(self, bucket: int, flush_lane: Optional[int],
                       allow_rebase: bool):
        """Pop one round's worth of chunks from this bucket's lane buffers.

        Returns a ``_Round``, ``None`` (nothing ready), or ``"rebase"``
        (a lane needs a timebase hop first but the current block already
        holds rounds — the caller must execute them before the hop so the
        round order matches the sequential path bit-for-bit)."""
        ready: list[tuple[int, int]] = []
        for lane in self.active_lanes:
            ln = self._lanes[lane]
            if ln.bucket != bucket:
                continue
            if ln.buf_ts.size >= bucket:
                ready.append((lane, bucket))
            elif lane == flush_lane and ln.buf_ts.size:
                ready.append((lane, int(ln.buf_ts.size)))
        if not ready:
            return None

        hops_needed = []
        for lane, n in ready:
            ln = self._lanes[lane]
            new_base, hops = streaming_mod.plan_rebase(
                ln.base, ln.buf_ts[:n], self._cfg
            )
            if hops:
                hops_needed.append((lane, new_base, hops))
        if hops_needed and not allow_rebase:
            return "rebase"
        for lane, new_base, hops in hops_needed:
            self._lanes[lane].base = new_base
            for hop in hops:
                self._states = self._place(self._vrebase(
                    self._states, jnp.int32(lane), np.int32(hop)
                ))

        xy = np.zeros((self._phys, bucket, 2), np.int32)
        ts = np.zeros((self._phys, bucket), np.int32)
        valid = np.zeros((self._phys, bucket), bool)
        mask = np.zeros((self._phys,), bool)
        n_valid = np.zeros((self._phys,), np.int32)
        due = np.zeros((self._phys,), bool)
        t = obs_mod.timer()
        fed_sum = 0.0          # feed times of the chunks' last events
        n_events = 0
        for lane, n in ready:
            ln = self._lanes[lane]
            # state.lut_due from the host mirrors of chunk_idx and ctrl
            ln.chunks_folded += 1
            due[lane] = (ln.chunks_folded % int(self._ctrl_lut[lane]) == 0
                         and not self._ctrl_shed[lane])
            last = ln.fed_cum - int(ln.buf_ts.size) + n
            while ln.feed_times[0][0] < last:
                ln.feed_times.popleft()
            fed_sum += ln.feed_times[0][1]
            if ln.feed_times[0][0] == last:
                ln.feed_times.popleft()
            n_events += n
            xy[lane, :n] = ln.buf_xy[:n]
            ts64 = np.full((bucket,), ln.buf_ts[min(n, ln.buf_ts.size) - 1],
                           np.int64)
            ts64[:n] = ln.buf_ts[:n]
            ts[lane] = (ts64 - ln.base).astype(np.int32)
            valid[lane, :n] = True
            mask[lane] = True
            n_valid[lane] = n
            ln.buf_xy = ln.buf_xy[n:]
            ln.buf_ts = ln.buf_ts[n:]
            ln.events_folded += n
            ln.gen += 1           # backlog changed
        self._m_chunk_buffer.inc(len(ready) * t - fed_sum)
        per_shard = due.reshape(self._shards, -1).sum(axis=1)
        runs = sum(self._lut_ladder[bisect.bisect_left(self._lut_ladder, n)]
                   for n in per_shard.tolist())
        return _Round(xy, ts, valid, mask, n_valid, t, len(ready), n_events,
                      int(per_shard.sum()), runs)

    def _stage_block(self, bucket: int, rounds: list, *,
                     stage_ahead: bool = False) -> _StagedBlock:
        """The stage half: gather a block's rounds into padded host slabs
        and issue their H2D upload (through the pinned-host stager where
        available — both executor paths).  Shapes never depend on
        occupancy: a block with 2..K ready rounds targets the fixed
        (K, ...) executor (padding skipped by the round-level cond); a
        block with exactly ONE round targets the 1-round executor, whose
        inputs drop the K axis — so sparse arrivals upload (phys, chunk)
        H2D bytes, not (K, phys, chunk).  Uploads are accounted here (per
        bucket — this is when the bytes move); rings and states are not
        touched, so staged blocks ride ahead of the dispatch point safely.
        """
        k = self._ring_rounds
        n = len(rounds)
        seq = self._block_seq
        self._block_seq += 1
        book = (sum(r.n_events for r in rounds), seq,
                sum(r.n_chunks for r in rounds),
                sum(r.n_chunks * r.t for r in rounds))
        with obs_mod.span("stage", block=seq, bucket=bucket, rounds=n,
                          valid_events=book[0]):
            t0 = obs_mod.timer()
            up = (self._stager.put if self._stager is not None
                  else jnp.asarray)
            if n == 1 and bucket in self._exec1:
                rnd = rounds[0]
                chunks = state_mod.ChunkInput(
                    xy=up(rnd.xy),
                    ts=up(rnd.ts),
                    valid=up(rnd.valid),
                    ber=jnp.full(
                        (self._phys,), self._riders[0], jnp.float32
                    ),
                    energy_coef=jnp.full(
                        (self._phys,), self._riders[1], jnp.float32
                    ),
                    latency_coef=jnp.full(
                        (self._phys,), self._riders[2], jnp.float32
                    ),
                )
                blk = _StagedBlock(
                    bucket, n, True, chunks, up(rnd.mask), up(rnd.n_valid),
                    None, *book,
                )
                self._m_h2d_slots[bucket].inc(self._phys * bucket)
            else:
                xy = np.zeros((k, self._phys, bucket, 2), np.int32)
                ts = np.zeros((k, self._phys, bucket), np.int32)
                valid = np.zeros((k, self._phys, bucket), bool)
                mask = np.zeros((k, self._phys), bool)
                n_valid = np.zeros((k, self._phys), np.int32)
                for i, rnd in enumerate(rounds):
                    xy[i], ts[i], valid[i] = rnd.xy, rnd.ts, rnd.valid
                    mask[i], n_valid[i] = rnd.mask, rnd.n_valid
                round_active = np.arange(k) < n

                chunks = state_mod.ChunkInput(
                    xy=up(xy),
                    ts=up(ts),
                    valid=up(valid),
                    ber=jnp.full(
                        (k, self._phys), self._riders[0], jnp.float32
                    ),
                    energy_coef=jnp.full(
                        (k, self._phys), self._riders[1], jnp.float32
                    ),
                    latency_coef=jnp.full(
                        (k, self._phys), self._riders[2], jnp.float32
                    ),
                )
                blk = _StagedBlock(
                    bucket, n, False, chunks, jnp.asarray(mask),
                    jnp.asarray(n_valid), jnp.asarray(round_active), *book,
                )
                self._m_h2d_slots[bucket].inc(k * self._phys * bucket)
            self._m_h2d_valid[bucket].inc(blk.n_valid_sum)
            self._m_lut_due.inc(sum(r.lut_due for r in rounds))
            self._m_lut_runs.inc(sum(r.lut_runs for r in rounds))
            self._m_stages.inc()
            self._m_stage_s.inc(obs_mod.timer() - t0)
        if stage_ahead and self._pass_dispatches > 0:
            # structural overlap witness: this stage began with an earlier
            # block staged-but-undispatched in the deque AND a block of
            # this pass already dispatched — the gather/upload ran ahead
            # of the dispatch point, concurrent with device compute.  At
            # depth 1 the deque is always empty here, so the serial pump
            # reports 0 by construction.
            self._m_stages_overlapped.inc()
        return blk

    def _dispatch_block(self, blk: _StagedBlock) -> None:
        """The dispatch half: make ring room (under the ``"drain"`` policy
        a block that would overflow the live ring first drains it — sync:
        inline fetch; async: seal to the reader and keep pumping, the
        wait, if any, is for a spare ring, not for PCIe) and launch the
        staged block's executor."""
        with obs_mod.span("dispatch", block=blk.seq):
            bucket, k, n = blk.bucket, self._ring_rounds, blk.n
            if self._overflow == "drain" and \
                    self._m_ring_count[bucket].value() + n > k:
                t0 = obs_mod.timer()
                self._drain_bucket(bucket, wait=False)
                w = obs_mod.timer() - t0
                self._m_drain_wait.inc(w)
                self._m_last_drain_wait[bucket].set(w)
                self._m_forced_drains.inc()

            t = obs_mod.timer()
            self._m_chunk_stage.inc(blk.n_chunks * t - blk.t_sum)
            book = self._ring_book[bucket]
            book[0] += blk.n_chunks
            book[1] += blk.n_chunks * t
            book[2].append(blk.seq)
            args = (self._states, self._rings[bucket], blk.chunks, blk.mask,
                    blk.n_valid)
            if not blk.single:
                args += (blk.round_active,)
            run = self._exec1[bucket] if blk.single else self._exec[bucket]
            self._exec_notes.note((bucket, blk.single), run, args)
            self._states, self._rings[bucket] = run(*args)
            c = self._m_ring_count[bucket].value()
            self._m_ring_count[bucket].set(min(c + n, k))
            self._m_dropped_pred[bucket].add(max(0, c + n - k))
            self._m_rounds_executed.inc(n)
            self._pass_dispatches += 1

    # -- draining: sync (inline fetch) and async (seal to the reader) -------

    def _drain_bucket(self, bucket: int, *, wait: bool = True,
                      block: bool = True) -> None:
        """Get this bucket's buffered rounds on their way to the host.  In
        sync mode that is the inline blocking fetch; in async mode it seals
        the live ring to the reader and, with ``wait=True``, blocks until
        the reader has drained everything sealed for this bucket.
        ``block=False`` is the non-blocking poll path: sync skips the
        inline fetch entirely, async skips the seal when no spare ring is
        available."""
        if self._drain_mode == "sync":
            if block:
                self._drain_ring(bucket)
        else:
            self._seal_ring(bucket, block=block)
            if wait:
                self._wait_bucket_drained(bucket)

    def _drain_ring(self, bucket: int) -> None:
        """Sync mode: ONE blocking fetch of the live ring on the calling
        thread, then distribute and mark the ring empty."""
        if self._m_ring_count[bucket].value() == 0:
            return
        t_seal, blocks = self._close_ring_book(bucket)
        ring, t_fetched = self._timed_fetch(self._rings[bucket], blocks)
        self._m_host_fetches.inc()
        with obs_mod.span("distribute"):
            self._distribute(bucket, ring, t_seal, t_fetched)
        self._m_ring_count[bucket].set(0)
        self._rings[bucket] = self._reset_ring(self._rings[bucket])

    def _close_ring_book(self, bucket: int) -> tuple:
        """The live ring leaves the pump (sealed, or drained inline): count
        its chunks' ring wait and start a fresh book.  Returns the seal
        time and the ring's block ids."""
        n, t_sum, blocks = self._ring_book[bucket]
        t = obs_mod.timer()
        self._m_chunk_ring.inc(n * t - t_sum)
        self._ring_book[bucket] = [0, 0.0, []]
        return t, blocks

    def _timed_fetch(self, ring, blocks: list) -> tuple:
        """``_fetch_ring`` in its ``pool.fetch`` span; returns the host
        ring and when its ``device_get`` returned."""
        self._t_fetched = None
        with obs_mod.span("fetch", blocks=",".join(map(str, blocks))):
            host = self._fetch_ring(ring)
        t = self._t_fetched
        return host, obs_mod.timer() if t is None else t

    def _seal_ring(self, bucket: int, *, block: bool = True) -> None:
        """Async mode's atomic swap point (caller holds the lock): install
        a spare as the live ring and hand the sealed one to the reader
        thread.  If every spare is still in the reader's hands (the ring of
        rings is ``ring_depth`` deep, not infinite) this waits on the
        condition variable — releasing the lock so the reader can
        distribute and recycle — or, with ``block=False``, simply returns
        (the live ring keeps accumulating; a later poll seals it)."""
        if self._m_ring_count[bucket].value() == 0:
            return
        if not self._spares[bucket]:
            if not block:
                return
            with obs_mod.span("seal_wait"):
                while not self._spares[bucket]:
                    self._check_open()
                    self._cv.wait()
                    # re-validate after the wakeup: another thread (a
                    # concurrent poll, or the pump making room) may have
                    # sealed meanwhile — sealing an empty ring would cost a
                    # pointless blocking fetch and inflate the
                    # rounds-per-fetch witness
                    if self._m_ring_count[bucket].value() == 0:
                        return
        sealed = self._rings[bucket]
        self._rings[bucket] = self._spares[bucket].popleft()
        self._m_sealed[bucket].add(self._m_ring_count[bucket].value())
        self._inflight[bucket] += 1
        self._m_ring_count[bucket].set(0)
        t_seal, blocks = self._close_ring_book(bucket)
        self._sealed_q.put((bucket, sealed, t_seal, blocks))

    def _wait_bucket_drained(self, bucket: int) -> None:
        """Block (releasing the lock) until the reader has fetched and
        distributed every ring sealed for this bucket."""
        while self._inflight[bucket] > 0:
            self._check_open()
            self._cv.wait()

    def _fetch_ring(self, ring: state_mod.RingState):
        """The blocking device transfer (both drain modes funnel through
        here; on the async path it runs on the reader thread with no lock
        held — the D2H registry handles are internally locked, so the
        accounting below is thread-safe).  Split out so tests can inject
        fetch failures.  Always returns a *dense* host ``RingState`` —
        compact rings are densified here, so ``_distribute`` and the
        public result contract never see the representation change."""
        if self._readout == "compact":
            return self._fetch_compact(ring)
        host = jax.device_get(ring)
        self._t_fetched = obs_mod.timer()
        self._m_d2h_bytes.inc(obs_mod.leaves_nbytes(*host))
        return host

    def _fetch_compact(self, ring: state_mod.CompactRingState):
        """Compact readout: fetch the packed ``(cap,)`` kept-corner records
        plus the scalar cursors in ONE ``device_get`` (no per-scalar
        syncs), gather dense rows only for slot-lanes whose kept count
        overflowed the cap (lossless fallback — drop nothing, ever), and
        scatter back to a dense host ``RingState``.

        The densify is bit-exact: ``detector_step`` scores every non-kept
        event exactly ``-inf`` with ``keep=False``, which is precisely the
        fill value, so scattering the ``n_kept`` records reproduces the
        dense row byte-for-byte.  ``vdd_idx`` is only consumed by
        ``account_chunk`` when DVFS is online; fixed-Vdd pools skip that
        leaf entirely and substitute zeros the accounting never reads."""
        rounds, lanes, chunk = ring.scores.shape
        cap = ring.c_idx.shape[2]
        leaves = [ring.c_idx, ring.c_val, ring.n_kept, ring.n_valid,
                  ring.mask, ring.head, ring.count, ring.dropped]
        if self._online:
            leaves.append(ring.vdd_idx)
        (c_idx, c_val, n_kept, n_valid, mask,
         head, count, dropped, *rest) = jax.device_get(leaves)
        self._t_fetched = obs_mod.timer()
        with obs_mod.span("densify"):
            vdd_idx = (rest[0] if rest
                       else np.zeros((rounds, lanes), np.int32))
            fetched = obs_mod.leaves_nbytes(*leaves)

            # Overflowed slot-lanes fall back to their dense rows.
            # Restrict the scan to undrained slots: recycled rings only
            # reset their cursors, so stale (already-drained) slots can
            # still look masked.
            live = state_mod.ring_slot_order(int(head), int(count), rounds)
            rows = [
                (slot, int(lane))
                for slot in live
                for lane in np.flatnonzero(
                    mask[slot] & (n_kept[slot] > cap))
            ]
            over = []
            if rows:
                over = jax.device_get(
                    [(ring.scores[s, l], ring.keep[s, l]) for s, l in rows]
                )
                fetched += obs_mod.leaves_nbytes(*over)
                self._m_d2h_overflow.inc(len(rows))

            scores = np.full((rounds, lanes, chunk), -np.inf, np.float32)
            keep = np.zeros((rounds, lanes, chunk), bool)
            for slot in live:
                for lane in np.flatnonzero(mask[slot]):
                    nk = int(n_kept[slot, lane])
                    if nk > cap:
                        continue  # filled from the overflow gather below
                    idx = c_idx[slot, lane, :nk]
                    scores[slot, lane, idx] = c_val[slot, lane, :nk]
                    keep[slot, lane, idx] = True
            for (slot, lane), (s_row, k_row) in zip(rows, over):
                scores[slot, lane] = np.asarray(s_row, np.float32)
                keep[slot, lane] = np.asarray(k_row, bool)

            self._m_d2h_bytes.inc(fetched)
            # nbytes is metadata on device arrays — the dense-equivalent
            # baseline costs no transfer and no sync.
            dense_eq = obs_mod.leaves_nbytes(
                ring.scores, ring.keep, ring.n_kept, ring.vdd_idx,
                ring.n_valid, ring.mask, ring.head, ring.count, ring.dropped,
            )
            self._m_d2h_saved.inc(max(0, dense_eq - fetched))
            return state_mod.RingState(
                scores=scores, keep=keep, n_kept=n_kept, vdd_idx=vdd_idx,
                n_valid=n_valid, mask=mask, head=head, count=count,
                dropped=dropped,
            )

    def _reader_loop(self) -> None:
        """Async drain: fetch sealed rings FIFO (order preserves the
        sequential result order bit-for-bit), distribute under the lock,
        recycle the buffer into the bucket's spare pool.  Any exception is
        stored and re-raised to the next public API caller."""
        while True:
            item = self._sealed_q.get()
            if item is _STOP:
                return
            bucket, sealed, t_seal, blocks = item
            try:
                host, t_fetched = self._timed_fetch(sealed, blocks)
            except BaseException as e:
                with self._cv:
                    self._reader_exc = e
                    self._cv.notify_all()
                return
            with obs_mod.span("distribute"):
                self._take_lock()
                try:
                    self._m_host_fetches.inc()
                    self._distribute(bucket, host, t_seal, t_fetched)
                    self._spares[bucket].append(self._reset_ring(sealed))
                    self._m_sealed[bucket].set(max(
                        0, self._m_sealed[bucket].value() - int(host.count)
                    ))
                    self._inflight[bucket] -= 1
                except BaseException as e:
                    self._reader_exc = e
                    self._cv.notify_all()
                    return
                else:
                    self._cv.notify_all()
                finally:
                    self._lock.release()

    def _distribute(self, bucket: int, ring, t_seal: float,
                    t_fetched: float) -> None:
        """Walk a fetched ring's undrained slots (oldest first), hand each
        lane its results, fold the float64 accounting, and audit the drop
        mirror against the device counter (caller holds the lock; ``ring``
        is host data).  The ring was sealed at ``t_seal`` and its
        ``device_get`` returned at ``t_fetched``."""
        n_slots = ring.scores.shape[0]
        got: dict = {}            # lane -> chunks handed to it
        ber_hot = self._tab.ber > 0 if self._online else None
        for slot in state_mod.ring_slot_order(ring.head, ring.count, n_slots):
            for lane in np.flatnonzero(ring.mask[slot]):
                ln = self._lanes[int(lane)]
                if ln is None:
                    continue
                got[ln] = got.get(ln, 0) + 1
                n = int(ring.n_valid[slot, lane])
                streaming_mod.account_chunk(
                    ln, ring.n_kept[slot, lane], ring.vdd_idx[slot, lane],
                    online=self._online, tab=self._tab,
                    fixed_vdd=self._cfg.vdd,
                )
                if ber_hot is not None:
                    ln.res_ber += int(ber_hot[ring.vdd_idx[slot, lane]])
                # copy: a view would pin the whole fetched (R, lanes,
                # chunk) buffer in the lane queue until the lane polls
                ln.results.append((
                    ring.scores[slot, lane, :n].astype(np.float32,
                                                       copy=True),
                    ring.keep[slot, lane, :n].astype(bool, copy=True),
                ))
        t = obs_mod.timer()
        n = sum(got.values())
        self._m_chunk_fetch.inc(n * (t_fetched - t_seal))
        self._m_chunk_distribute.inc(n * (t - t_fetched))
        for ln, c in got.items():
            ln.res_n += c
            ln.res_tsum += c * t
        # The device counter is ground truth: drops confirmed by this fetch
        # move from the predicted mirror to the confirmed tally.  (Each ring
        # resets its dropped counter when recycled, so per-fetch counts are
        # disjoint and the two host tallies always sum to the truth.)
        d = int(ring.dropped)
        self._m_dropped_dev[bucket].inc(d)
        self._m_dropped_pred[bucket].add(-d)
