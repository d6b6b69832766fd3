"""Multi-camera serving: data-plane runtime wired to a control-plane
scheduler.

``DetectorPool`` is now a thin façade over two layers (the PR 5 split):

  * ``repro.serve.runtime.PoolRuntime`` — the data plane.  Compiled
    per-bucket K-round executors, the on-device result rings (an N-deep
    ring-of-rings drained by a dedicated reader thread in async mode),
    donation and sharding bookkeeping, host re-chunk buffers, and the
    seal/drain/snapshot/restore mechanics of live lane migration.  Pure
    mechanism: it can run any lane in any bucket, but never decides which.
  * ``repro.serve.scheduler`` — the control plane.  Lane->bucket placement
    as *policy*: ``policy="static"`` (default) freezes the PR 4 behavior —
    a lane stays in the bucket chosen at ``connect()`` for life, buckets
    pump in ascending order; ``policy="adaptive"`` re-budgets lanes from
    their *measured* event rate, the serving-layer twin of the paper's
    DVFS controller (which re-picks the operating point from the same
    3-counter estimate): lanes whose events-per-half-window drift past
    hysteresis thresholds for ``migrate_patience`` consecutive drains are
    live-migrated to the better-fitting bucket, and buckets with the
    deepest re-chunk backlog pump first when a round budget is in force;
    ``policy="ladder"`` runs the overload ladder — per-pump observations
    of backlog pressure drive hysteretic tiered degradation (stretch LUT
    refresh -> lower the DVFS ceiling -> shed -> pack lanes into fewer
    buckets) with QoS classes so premium lanes degrade last
    (``connect(qos=...)``); ``policy="pack"`` runs the packing move
    standalone — every pump observation re-packs lanes across buckets to
    minimize the fleet-wide padded H2D upload bytes per round.

The façade wires them together as an observe -> decide -> actuate loop:
``connect`` asks the scheduler where a lane lands, ``pump``/``flush``
pass the scheduler's bucket order to the runtime (which first applies any
staged migrations, under the pump token) along with the scheduler's
``decide`` callback when the policy consumes per-pump observations —
returned knob Actions actuate before the pass's rounds, migrate Actions
stage for the next pass.  Every drain observation (``poll``/``flush``)
additionally feeds the scheduler one rate sample per lane — a returned
migration target is staged with the runtime (seal + drain +
donation-proof snapshot) and restored into the new bucket at the start of
the next pump pass.

Migration is invisible to results: a lane served with ``policy=
"adaptive"`` is bit-exact (scores, kept, final TOS/SAE/LUT, float64
energy books) vs the same stream served fixed in each bucket and
rebucketed at the same boundaries — no round is lost, duplicated, or
reordered, and nothing recompiles (``executors_compiled_once()`` holds
through migrations: at most one K-block and one 1-round executable per
bucket, ever).  ``stats(lane)['migration_log']`` is not needed for that
replay — the per-lane ``migrations`` count and the runtime's
``lane.migration_log`` give the exact event boundaries (property-tested
against ``StreamingDetector.rebucket`` replays).

Everything below the policy line — ring-buffered multi-round pump, async
N-deep drain, overflow policies, sharded lanes, chunk-size buckets,
donation, the active-mask membership system, thread safety — is the
PR 3/4 machinery, documented in ``repro.serve.runtime``.  A lane's
outputs remain bit-identical to a standalone ``StreamingDetector`` and to
``run_pipeline`` on that lane's full stream regardless of interleaving,
K-blocking, sharding, drain mode, or migrations (property-tested).

Like ``StreamingDetector``, only fixed-Vdd and online-DVFS configs are
servable (host-precomputed DVFS needs future knowledge).
"""
from __future__ import annotations

from typing import Optional

from repro import obs as obs_mod
from repro.serve import scheduler as scheduler_mod
from repro.serve.runtime import PoolRuntime

__all__ = ["DetectorPool"]


class DetectorPool:
    """Fixed-capacity pool of detector sessions: a ``PoolRuntime`` data
    plane driven by a placement scheduler (``policy="static"`` freezes
    PR 4 behavior; ``policy="adaptive"`` adds rate-aware live bucket
    migration and starved-first pump order; ``policy="ladder"`` the
    overload ladder; ``policy="pack"`` fleet-wide padding-minimizing lane
    packing).  ``pipeline_depth`` sizes the pump's stage-ahead window
    (blocks staged while earlier blocks run on device; 1 = the serial
    pre-PR 8 pump, bit-exact either way).  ``readout="compact"`` stores
    each ring slot's kept corners as packed ``(cap,)`` records on device
    so drains fetch ~``chunk/cap``-fold fewer D2H bytes (``compact_cap``
    overrides the ``chunk // 8`` default per-slot record capacity;
    slot-lanes whose kept count overflows the cap fall back to their
    dense rows losslessly) — results stay bit-identical to ``"dense"``."""

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
                 policy: str = "static",
                 migrate_patience: int = 3,
                 migrate_margin: float = 0.9,
                 ladder: Optional[scheduler_mod.LadderConfig] = None,
                 scheduler: Optional[scheduler_mod.StaticScheduler] = None,
                 metrics: Optional[obs_mod.MetricsRegistry] = None):
        self._rt = PoolRuntime(
            cfg, capacity, seed=seed, ring_rounds=ring_rounds,
            buckets=buckets, on_overflow=on_overflow, shard=shard,
            drain_mode=drain_mode, ring_depth=ring_depth,
            pipeline_depth=pipeline_depth, readout=readout,
            compact_cap=compact_cap, metrics=metrics,
        )
        if scheduler is not None:
            if tuple(scheduler.buckets) != self._rt.buckets:
                raise ValueError(
                    f"scheduler buckets {scheduler.buckets} do not match "
                    f"pool buckets {self._rt.buckets}"
                )
            self._sched = scheduler
        else:
            self._sched = scheduler_mod.make_scheduler(
                policy, self._rt.buckets, patience=migrate_patience,
                down_margin=migrate_margin, ladder=ladder,
                base_lut_every=cfg.lut_every_chunks,
                vdd_top=self._rt.vdd_top,
            )
        # one registry per pool: policy counters re-home onto the
        # runtime's so a single emission carries both halves of the loop
        self._sched.bind_metrics(self._rt.metrics)
        self._cfg = cfg
        # Migration targets decided during non-blocking polls: staging
        # seals+drains (it may wait on the reader), which poll(wait=False)
        # must never do — so the decision parks here and is staged at the
        # next blocking fold point (pump/flush).  Guarded by the runtime
        # lock.
        self._deferred: dict[int, int] = {}

    # Data-plane attributes (including the ``_``-prefixed internals the
    # test suites witness: ``_states``, ``_rings``, ``_donate``, ``_phys``,
    # ``_reader``, ...) resolve on the runtime.
    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_rt"), name)

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Stop the runtime (reader thread included).  Rounds still sealed
        or buffered on device are abandoned — ``flush`` the lanes first if
        their results matter.  Idempotent; the pool rejects further use."""
        self._rt.close()

    def __enter__(self) -> "DetectorPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- membership ---------------------------------------------------------

    def connect(self, *, seed: Optional[int] = None,
                chunk: Optional[int] = None,
                qos: str = "standard") -> int:
        """Claim a free lane for a new camera session; returns the lane id.

        ``chunk`` requests a per-session chunk size: the scheduler places
        the lane in the smallest configured bucket that fits (>= the
        request) and the lane behaves bit-identically to ``run_pipeline``
        at that bucket's chunk size.  Default: the pool config's
        ``cfg.chunk``.  Under ``policy="adaptive"`` the placement is only
        the starting point — the lane follows its measured rate.

        ``qos`` names the session's QoS class for the overload ladder
        (``policy="ladder"``: lower classes degrade first; validated
        against the ladder's configured classes).  Other policies carry it
        as an inert label."""
        want = self._cfg.chunk if chunk is None else int(chunk)
        bucket = self._sched.place(want)
        if bucket is None:
            raise ValueError(
                f"no chunk bucket fits {want} (buckets: {self._rt.buckets})"
            )
        lad = getattr(self._sched, "ladder", None)
        if lad is not None and qos not in lad.qos_names():
            raise ValueError(
                f"unknown QoS class {qos!r} (ladder classes: "
                f"{lad.qos_names()})"
            )
        lane = self._rt.connect(bucket, seed, qos=qos)
        self._sched.forget(lane)          # recycled slot: fresh streaks
        with self._rt._lock:              # _deferred is lock-guarded
            self._deferred.pop(lane, None)
        return lane

    def disconnect(self, lane: int) -> dict:
        """Release a lane; returns its final accounting stats.  Undrained
        ring slots are drained first and any staged (snapshot-taken,
        restore-pending) migration for the lane is discarded — the slot's
        next tenant inherits nothing."""
        out = self._rt.disconnect(lane)
        self._sched.forget(lane)
        with self._rt._lock:              # _deferred is lock-guarded
            self._deferred.pop(lane, None)
        return out

    def warmup(self, xy, ts_us) -> None:
        """Compile every executor shape for the default bucket outside any
        timed region: a scratch lane pumps a multi-round block (the K-block
        executor) and then a lone round (the 1-round fast path), then
        disconnects.  Drivers and benches share this recipe so 'warm every
        shape before timing' has one owner; with ``ring_rounds=1`` both
        pumps take the one block executor.  Membership churn never
        recompiles, so one warmup covers the pool's lifetime (per bucket:
        re-call with ``connect(chunk=...)``-sized data if you time other
        buckets)."""
        import numpy as np

        lane = self.connect()
        b = self._rt._lanes[lane].bucket
        xy = np.asarray(xy)
        ts = np.asarray(ts_us)
        self.feed(lane, xy[:3 * b], ts[:3 * b])
        self.pump()
        self.feed(lane, xy[:b], ts[:b])
        self.pump()
        self.disconnect(lane)

    # -- serving ------------------------------------------------------------

    def feed(self, lane: int, xy, ts_us) -> None:
        """Buffer a slab for one session (any length, time-sorted)."""
        self._rt.feed(lane, xy, ts_us)

    def pump(self) -> int:
        """Fold every buffered full chunk through the ring executors, K
        rounds per device dispatch, until no active lane has a full chunk
        left.  Staged migrations apply first; buckets pump in the
        scheduler's order.  Returns the number of rounds executed."""
        return self.pump_rounds(None)

    def pump_rounds(self, max_rounds: Optional[int] = None) -> int:
        """Like ``pump`` but stops after at most ``max_rounds`` rounds
        (``None`` = run until dry).  Under a budget the scheduler's pump
        order matters: the adaptive policy folds the most backlogged
        (starved) bucket first, the static policy keeps ascending bucket
        order — with no budget every bucket pumps until dry either way, so
        the order never changes results."""
        self._stage_deferred()
        return self._rt.pump_pass(self._order(), max_rounds,
                                  decide=self._decide())

    def flush(self, lane: int):
        """Drain the lane's full chunks, then its padded partial tail, and
        return everything not yet polled.  Counts as a drain observation
        for the adaptive scheduler (like ``poll``)."""
        self._stage_deferred()
        out = self._rt.flush(lane, self._order())
        self._observe(lane)
        return out

    def poll(self, lane: int, *, wait: bool = True):
        """Drain the lane's accumulated (scores, kept), in stream order —
        the readout/backpressure point (see ``PoolRuntime.poll`` for the
        sync/async and wait semantics).  Each poll is one drain
        observation for the scheduler: under ``policy="adaptive"`` a lane
        whose measured rate has outgrown (or undershot) its bucket for
        ``migrate_patience`` consecutive rate windows gets its migration
        staged here (or, for ``wait=False`` — which must never block —
        parked and staged at the next pump/flush), to apply at the next
        pump pass."""
        out = self._rt.poll(lane, wait=wait)
        self._observe(lane, allow_stage=wait)
        return out

    def _order(self) -> tuple:
        """The scheduler's bucket pump order.  The backlog walk holds the
        runtime lock over every active lane, so it only runs for policies
        that declare they use it (static ignores its argument)."""
        backlog = (self._rt.bucket_backlog_rounds()
                   if self._sched.needs_backlog else {})
        return self._sched.order(backlog)

    def _decide(self):
        """The scheduler's ``decide`` callback for the runtime's per-pump
        control loop — or ``None`` for policies that never act there, so
        the default static/adaptive paths skip building the Observation
        entirely (zero per-pump overhead, byte-for-byte PR 5 behavior)."""
        if not getattr(self._sched, "needs_pump_observation", False):
            return None
        return self._sched.decide

    def _observe(self, lane: int, *, allow_stage: bool = True) -> None:
        """Feed the scheduler one rate sample for ``lane`` and act on any
        migration it decides: stage it (blocking contexts), or park it in
        ``_deferred`` when the caller must not block (staging seals and
        drains the lane's bucket, which can wait on the reader thread).
        Serialized under the runtime lock so concurrent pollers cannot
        interleave scheduler state.  Skipped wholesale for policies that
        never migrate (the default static path pays zero per-poll cost)."""
        if not self._sched.needs_observation:
            return
        with self._rt._lock:
            if not self._rt._active[lane]:
                return                      # retired by a concurrent caller
            ln = self._rt._lanes[lane]
            target = self._sched.observe(
                lane, ln.bucket, self._rt.lane_halfwin_rate(lane),
                win=ln.r_win,
            )
            if target is None or target == ln.bucket:
                return
            if allow_stage:
                self._deferred.pop(lane, None)
                self._rt.stage_migration(lane, target)
            else:
                self._deferred[lane] = target

    def _stage_deferred(self) -> None:
        """Stage migration decisions parked by non-blocking polls (we are
        now at a fold point that may block anyway)."""
        if not self._deferred:
            return
        with self._rt._lock:
            for lane, target in list(self._deferred.items()):
                # pop, not del: a concurrent disconnect can clear the
                # entry while a prior iteration's staging waits on the
                # pump token (cv waits release the lock)
                self._deferred.pop(lane, None)
                if (self._rt._active[lane]
                        and self._rt._lanes[lane].bucket != target):
                    self._rt.stage_migration(lane, target)

    # -- introspection ------------------------------------------------------

    @property
    def policy(self) -> str:
        return self._sched.policy

    @property
    def scheduler(self) -> scheduler_mod.StaticScheduler:
        return self._sched

    def stats(self, lane: int) -> dict:
        """Lane accounting + rate/migration view; see ``PoolRuntime.stats``."""
        return self._rt.stats(lane)

    def executor_hlo(self) -> list:
        """Compiled HLO text of each executor that has run; see
        ``PoolRuntime.executor_hlo``."""
        return self._rt.executor_hlo()

    def pool_stats(self) -> dict:
        """Pool-level runtime counters plus the active policy and any
        policy-side counters (``ladder_level`` / ``ladder_transitions``
        under ``policy="ladder"``); see ``PoolRuntime.pool_stats`` for the
        runtime field glossary."""
        out = self._rt.pool_stats()
        out["policy"] = self._sched.policy
        stats_fn = getattr(self._sched, "scheduler_stats", None)
        if callable(stats_fn):
            out.update(stats_fn())
        return out

    def emit_metrics(self, kind: str = "pool") -> dict:
        """Snapshot the pool's registry into one record, fold the
        scheduler's policy counters in as extras, and fan it out to every
        attached sink (``pool.metrics.attach(...)``).  Returns the record."""
        extra = {"policy": self._sched.policy}
        stats_fn = getattr(self._sched, "scheduler_stats", None)
        if callable(stats_fn):
            extra.update(stats_fn())
        return self._rt.metrics.emit(kind, extra={"scheduler": extra})
