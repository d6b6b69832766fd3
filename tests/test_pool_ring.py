"""Device-resident pool runtime: ring-buffered K-round execution, async
N-deep ring-of-rings drain, chunk-size buckets, sharded lanes, and live
bucket migration.

Acceptance contracts (ISSUE 3 + ISSUE 4 + ISSUE 5):

  * K-round ring-buffered ``pump_rounds(K)`` is bit-exact (scores, kept,
    final TOS, float64 energy books) vs K sequential single-round pumps,
    for K in {1, 3, 8}, on the jnp and Pallas backends, with lanes joining
    and leaving mid-run — in BOTH drain modes (``sync``: the PR 3 inline
    fetch; ``async``: double-buffered rings drained by a reader thread).
  * Compile-count assertions hold per bucket: at most one K-block and one
    1-round executable per chunk-size bucket tier, each compiled at most
    once through membership churn, flushes, drains, and lane migration.
  * The ring cuts host fetches: K back-to-back rounds cost one blocking
    fetch, not K (``host_fetches`` is the witness, counted on the reader
    thread in async mode).
  * Edge cases: ``flush()`` with an empty re-chunk buffer, ``disconnect()``
    with undrained ring slots, ragged slabs crossing bucket boundaries,
    ``poll()`` under ring overflow (both policies x both drain modes, with
    the drop host-mirror audited against the device counter).
  * The async ring *pair* generalizes to an N-deep ring-of-rings
    (``ring_depth``), bit-exact for depth in {2, 3} through the staggered
    churn harness.
  * ``policy="adaptive"`` live bucket migration: a rate-ramp stream is
    bit-exact (scores/kept/TOS/LUT/float64 energy books) vs a
    ``StreamingDetector.rebucket`` replay at the logged boundaries — no
    round lost, duplicated, or reordered; nothing recompiles through
    migrations — across both drain modes x both overflow policies with
    join/leave churn.  ``policy="static"`` (the default) never migrates.
"""
import dataclasses
import pathlib
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import pipeline
from repro.events import synthetic
from repro.serve import DetectorPool

REPO = pathlib.Path(__file__).resolve().parents[1]

_RING_CFG = pipeline.PipelineConfig(
    chunk=256, lut_every_chunks=2, vdd=0.6, inject_ber=True
)


@pytest.fixture(scope="module")
def streams():
    a = synthetic.shapes_stream(duration_us=40_000, seed=0)
    b = synthetic.dynamic_stream(duration_us=40_000, seed=1)
    return [
        (a.xy[:2000], a.ts[:2000]),
        (b.xy[:1500], b.ts[:1500]),
        (a.xy[2000:3700], a.ts[2000:3700]),
    ]


def _lane_state(pool, lane):
    return jax.device_get(jax.tree.map(lambda x: x[lane], pool._states))


def _assert_states_equal(sa, sb):
    for x, y in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_compiled_once(pool):
    """The churn witness: every executor (per bucket, per block shape —
    K-block and the 1-round H2D fast path) compiled at most once."""
    assert pool.executors_compiled_once(), pool.compile_cache_sizes()


def _serve_staggered_k(pool, streams, cfg, k, *, slab_rng_seed=0):
    """Staggered joins/leaves; pumps via ``pump_rounds(k)`` until dry each
    step.  Returns per-stream (scores, kept, final_stats)."""
    rng = np.random.default_rng(slab_rng_seed)
    n = len(streams)
    lanes, cursors = {}, {i: 0 for i in range(n)}
    out = {i: ([], [], None) for i in range(n)}
    step = 0
    lanes[0] = pool.connect(seed=cfg.seed)
    while lanes or any(cursors[i] < len(streams[i][1]) for i in range(n)):
        step += 1
        joined = len([i for i in range(n) if i in lanes or cursors[i] > 0])
        if step % 2 == 1 and joined < n:
            nxt = next(i for i in range(n)
                       if i not in lanes and cursors[i] == 0)
            lanes[nxt] = pool.connect(seed=cfg.seed)
        for i, lane in list(lanes.items()):
            xy, ts = streams[i]
            c = cursors[i]
            if c >= len(ts):
                s, kk = pool.flush(lane)
                out[i][0].append(s)
                out[i][1].append(kk)
                stats = pool.disconnect(lane)
                out[i] = (out[i][0], out[i][1], stats)
                del lanes[i]
                continue
            slab = int(rng.integers(40, 600))
            pool.feed(lane, xy[c:c + slab], ts[c:c + slab])
            cursors[i] = c + slab
        while pool.pump_rounds(k):
            pass
        for i, lane in lanes.items():
            s, kk = pool.poll(lane)
            out[i][0].append(s)
            out[i][1].append(kk)
    return {
        i: (np.concatenate(out[i][0]), np.concatenate(out[i][1]), out[i][2])
        for i in range(n)
    }


@pytest.fixture(scope="module")
def ring_refs(streams):
    """run_pipeline oracle per stream for _RING_CFG (computed once)."""
    return [pipeline.run_pipeline(xy, ts, _RING_CFG) for xy, ts in streams]


@pytest.fixture(scope="module")
def seq_served(streams):
    """The sequential baseline: single-round pumps, synchronous drain —
    the PR 3 reference execution plan every (K, drain_mode) must match."""
    seq = DetectorPool(_RING_CFG, capacity=3, ring_rounds=1,
                       drain_mode="sync")
    out = _serve_staggered_k(seq, streams, _RING_CFG, 1)
    _assert_compiled_once(seq)
    seq.close()
    return out


@pytest.mark.parametrize("drain_mode", ["sync", "async"])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_ring_k_rounds_bitexact_vs_sequential(streams, ring_refs,
                                              seq_served, k, drain_mode):
    """pump_rounds(K) through a ring_rounds=K executor == K single-round
    pumps, bit for bit, under membership churn (and both == run_pipeline) —
    whether the ring drains inline (sync) or on the reader thread (async,
    double-buffered)."""
    ring = DetectorPool(_RING_CFG, capacity=3, ring_rounds=k,
                        drain_mode=drain_mode)
    a = _serve_staggered_k(ring, streams, _RING_CFG, k)
    b = seq_served
    for i in range(len(streams)):
        ref = ring_refs[i]
        np.testing.assert_array_equal(
            a[i][0], ref.scores,
            err_msg=f"lane {i} scores (ring, {drain_mode})"
        )
        np.testing.assert_array_equal(a[i][0], b[i][0])
        np.testing.assert_array_equal(a[i][1], b[i][1])
        np.testing.assert_array_equal(a[i][1], ref.kept)
        # float64 energy books identical between the execution plans
        assert a[i][2]["energy_pj"] == b[i][2]["energy_pj"] == ref.energy_pj
        assert a[i][2]["kept_total"] == int(ref.kept.sum())
    # churn (3 joins, 3 leaves, ragged arrivals) => nothing recompiled
    _assert_compiled_once(ring)
    ring.close()


@pytest.mark.parametrize("backend", ["pallas_nmc", "pallas_batched"])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_ring_k_rounds_pallas_backends(backend, k):
    """The K-round executor is backend-agnostic: Pallas kernels inside the
    vmapped scan match the scan pipeline bit-for-bit, with a mid-run join
    (async drain — the default — exercises the reader thread here too)."""
    rng = np.random.default_rng(0)
    e, h, w = 768, 64, 64
    mk = lambda s: (
        np.stack([rng.integers(0, w, e), rng.integers(0, h, e)], 1)
        .astype(np.int32),
        np.sort(rng.integers(0, 20_000, e)).astype(np.int64),
    )
    s0, s1 = mk(0), mk(1)
    cfg = pipeline.PipelineConfig(
        height=h, width=w, chunk=128, lut_every_chunks=2, backend=backend
    )
    pool = DetectorPool(cfg, capacity=2, ring_rounds=k)
    a = pool.connect(seed=cfg.seed)
    pool.feed(a, s0[0][:400], s0[1][:400])
    pool.pump()
    b = pool.connect(seed=cfg.seed)          # joins mid-run
    pool.feed(a, s0[0][400:], s0[1][400:])
    pool.feed(b, *s1)
    pool.pump()
    res_a = pool.flush(a)
    pool.disconnect(a)                       # leaves while b still live
    res_b = pool.flush(b)
    for res, st in ((res_a, s0), (res_b, s1)):
        ref = pipeline.run_pipeline(st[0], st[1], cfg)
        np.testing.assert_array_equal(res[0], ref.scores)
        np.testing.assert_array_equal(res[1], ref.kept)
    _assert_compiled_once(pool)
    pool.close()


def test_ring_residency_final_state_matches(streams):
    """Ring vs sequential execution also agree on the carried device state
    (TOS/SAE/LUT/key/accumulators), not just the fetched outputs."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2,
                                  vdd=0.6, inject_ber=True)
    ring = DetectorPool(cfg, capacity=1, ring_rounds=4)
    seq = DetectorPool(cfg, capacity=1, ring_rounds=1, drain_mode="sync")
    xy, ts = streams[0]
    for pool in (ring, seq):
        lane = pool.connect(seed=cfg.seed)
        pool.feed(lane, xy, ts)
        pool.pump()
        pool.flush(lane)
    _assert_states_equal(_lane_state(ring, 0), _lane_state(seq, 0))
    ref = pipeline.run_pipeline(xy, ts, cfg)
    np.testing.assert_array_equal(
        np.asarray(_lane_state(ring, 0).surface), ref.tos
    )
    np.testing.assert_array_equal(
        np.asarray(_lane_state(ring, 0).lut), ref.lut
    )


@pytest.mark.parametrize("drain_mode", ["sync", "async"])
def test_ring_fewer_host_fetches(streams, drain_mode):
    """K rounds back-to-back cost ~K/ring_rounds fetches, not K (the
    serving-layer analogue of PR 1's O(n_chunks) -> 1 transfer cut).  The
    count is mode-independent: async moves the fetch to the reader thread,
    it does not add transfers."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    xy, ts = streams[0]                       # 2000 events -> 7 full rounds
    ring = DetectorPool(cfg, capacity=1, ring_rounds=8,
                        drain_mode=drain_mode)
    seq = DetectorPool(cfg, capacity=1, ring_rounds=1,
                       drain_mode=drain_mode)
    for pool in (ring, seq):
        lane = pool.connect(seed=cfg.seed)
        pool.feed(lane, xy, ts)
        rounds = pool.pump()
        pool.poll(lane)
        assert rounds == 7
    assert ring.host_fetches == 1             # 7 rounds, one drain
    assert seq.host_fetches == 7              # the per-round world
    assert ring.rounds_executed == seq.rounds_executed == 7
    ring.close()
    seq.close()


def test_pump_rounds_budget(streams):
    """pump_rounds(k) executes at most k rounds and reports what it did."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    pool = DetectorPool(cfg, capacity=1, ring_rounds=4)
    lane = pool.connect(seed=cfg.seed)
    xy, ts = streams[0]
    pool.feed(lane, xy, ts)                   # 7 full rounds buffered
    assert pool.pump_rounds(3) == 3
    assert pool.pump_rounds(2) == 2
    assert pool.pump() == 2                   # the rest
    assert pool.pump_rounds(5) == 0           # dry
    s, _ = pool.flush(lane)
    ref = pipeline.run_pipeline(xy, ts, cfg)
    np.testing.assert_array_equal(s, ref.scores)


# ---------------------------------------------------------------------------
# Chunk-size buckets
# ---------------------------------------------------------------------------


def test_bucketed_lanes_ragged_slabs_cross_bucket_boundaries(streams):
    """Lanes in different chunk-size buckets, fed ragged slabs that straddle
    every bucket size, each match run_pipeline at their own bucket's chunk;
    at most one K-block + one 1-round executable per exercised bucket."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    pool = DetectorPool(cfg, capacity=3, ring_rounds=3,
                        buckets=(128, 256, 512))
    a = pool.connect(seed=cfg.seed, chunk=128)
    b = pool.connect(seed=cfg.seed)               # default -> 256
    c = pool.connect(seed=cfg.seed, chunk=300)    # rounds up -> 512
    assert pool.stats(a)["bucket"] == 128
    assert pool.stats(b)["bucket"] == 256
    assert pool.stats(c)["bucket"] == 512
    rng = np.random.default_rng(7)
    cur = {a: 0, b: 0, c: 0}
    src = {a: streams[0], b: streams[1], c: streams[2]}
    while any(cur[ln] < len(src[ln][1]) for ln in cur):
        for ln in (a, b, c):
            xy, ts = src[ln]
            n = int(rng.integers(100, 600))       # crosses 128/256/512
            pool.feed(ln, xy[cur[ln]:cur[ln] + n], ts[cur[ln]:cur[ln] + n])
            cur[ln] += n
        pool.pump()
    for ln, bucket in ((a, 128), (b, 256), (c, 512)):
        s, kk = pool.flush(ln)
        ref = pipeline.run_pipeline(
            *src[ln], dataclasses.replace(cfg, chunk=bucket)
        )
        np.testing.assert_array_equal(s, ref.scores, err_msg=f"bucket {bucket}")
        np.testing.assert_array_equal(kk, ref.kept)
        assert pool.disconnect(ln)["energy_pj"] == ref.energy_pj
    sizes = pool.compile_cache_sizes()
    assert set(sizes) == {128, 256, 512}
    # every exercised bucket compiled something; nothing compiled twice
    # (a bucket whose rounds always arrived one at a time legitimately
    # never traces its K-block — only the 1-round fast path)
    assert all(sum(d.values()) >= 1 for d in sizes.values()), sizes
    _assert_compiled_once(pool)
    pool.close()


def test_bucket_selection_and_errors(streams):
    cfg = pipeline.PipelineConfig(chunk=256)
    pool = DetectorPool(cfg, capacity=2, buckets=(128, 256))
    with pytest.raises(ValueError, match="no chunk bucket fits"):
        pool.connect(chunk=512)
    lane = pool.connect(chunk=64)                 # rounds up to 128
    assert pool.stats(lane)["bucket"] == 128
    # a freed lane can land in a different bucket (lane migration)
    pool.disconnect(lane)
    lane2 = pool.connect(chunk=256)
    assert lane2 == lane
    assert pool.stats(lane2)["bucket"] == 256
    with pytest.raises(ValueError, match="buckets must be positive"):
        DetectorPool(cfg, capacity=1, buckets=(0, 128))


# ---------------------------------------------------------------------------
# N-deep ring-of-rings (ISSUE 5 satellite: generalize the PR 4 pair)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [2, 3])
def test_ring_of_rings_depth_bitexact(streams, ring_refs, seq_served, depth):
    """The async drain's ring count is a knob, not a behavior: depth 2 (the
    PR 4 double buffer) and depth 3 both reproduce the sequential
    single-round sync baseline bit for bit through the staggered
    join/leave churn harness."""
    pool = DetectorPool(_RING_CFG, capacity=3, ring_rounds=3,
                        drain_mode="async", ring_depth=depth)
    assert pool.pool_stats()["ring_depth"] == depth
    a = _serve_staggered_k(pool, streams, _RING_CFG, 3)
    for i in range(len(streams)):
        np.testing.assert_array_equal(a[i][0], ring_refs[i].scores,
                                      err_msg=f"depth {depth} lane {i}")
        np.testing.assert_array_equal(a[i][0], seq_served[i][0])
        np.testing.assert_array_equal(a[i][1], seq_served[i][1])
        assert a[i][2]["energy_pj"] == seq_served[i][2]["energy_pj"]
    _assert_compiled_once(pool)
    pool.close()


# ---------------------------------------------------------------------------
# Live bucket migration (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------


def _ramp_stream(rates, half_us, seed):
    """(xy, ts) of a deterministic rate-ramp: window j carries exactly
    ``rates[j]`` events (shared generator — the bench witnesses use it)."""
    st = synthetic.ramp_stream(rates, half_us, seed=seed)
    return st.xy, st.ts


def _replay_with_rebucket(cfg, xy, ts, start_bucket, migration_log):
    """The migration oracle: a standalone session fed the same stream,
    rebucketed at each logged (events_folded, from, to) boundary."""
    from repro.serve import StreamingDetector

    det = StreamingDetector(cfg, chunk=start_bucket, seed=cfg.seed)
    ss, kk = [], []
    cur = 0
    for m, _frm, to in migration_log:
        s, k = det.feed(xy[cur:m], ts[cur:m])
        ss.append(s)
        kk.append(k)
        det.rebucket(to)
        cur = m
    s, k = det.feed(xy[cur:], ts[cur:])
    ss.append(s)
    kk.append(k)
    s, k = det.flush()
    ss.append(s)
    kk.append(k)
    return np.concatenate(ss), np.concatenate(kk), det


@pytest.mark.parametrize("drain_mode", ["sync", "async"])
@pytest.mark.parametrize("overflow", ["drain", "drop_oldest"])
def test_adaptive_migration_bitexact_vs_rebucket_replay(drain_mode,
                                                        overflow):
    """Rate-ramp lanes under ``policy="adaptive"``: each lane migrates up
    when its measured rate outgrows its bucket, and its full readout
    (scores, kept, final TOS/LUT state, float64 energy books) equals a
    ``StreamingDetector.rebucket`` replay at the logged boundaries — under
    both drain modes and both overflow policies (ring sized so nothing
    drops: the policies must not perturb a lossless run), with a third
    lane joining and leaving mid-ramp (churn must not recompile or
    perturb the migrating lanes)."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    half = cfg.dvfs_cfg.half_us
    rates = [100] * 5 + [512] * 8                 # ~100 -> 512 ev/half-win
    ramps = [_ramp_stream(rates, half, seed=11 + i) for i in range(2)]
    churn_xy, churn_ts = _ramp_stream([300] * 4, half, seed=40)

    pool = DetectorPool(cfg, capacity=3, ring_rounds=4,
                        buckets=(128, 512), policy="adaptive",
                        migrate_patience=2, drain_mode=drain_mode,
                        on_overflow=overflow)
    lanes = [pool.connect(seed=cfg.seed, chunk=128) for _ in range(2)]
    out = {i: ([], []) for i in range(2)}
    churn_lane = None
    n_win = len(rates)
    for j in range(n_win):
        if j == 3:                                # churn: join mid-ramp
            churn_lane = pool.connect(seed=cfg.seed, chunk=512)
            pool.feed(churn_lane, churn_xy, churn_ts)
        for i, lane in enumerate(lanes):
            xy, ts = ramps[i]
            m = (ts // half) == j
            pool.feed(lane, xy[m], ts[m])
        pool.pump()
        for i, lane in enumerate(lanes):
            s, k = pool.poll(lane)
            out[i][0].append(s)
            out[i][1].append(k)
        if j == 7:                                # churn: leave mid-ramp
            s, k = pool.flush(churn_lane)
            ref = pipeline.run_pipeline(
                churn_xy, churn_ts,
                dataclasses.replace(cfg, chunk=512),
            )
            np.testing.assert_array_equal(s, ref.scores)
            assert pool.disconnect(churn_lane)["migrations"] == 0
            churn_lane = None
    final_states = {}
    logs = {}
    for i, lane in enumerate(lanes):
        s, k = pool.flush(lane)
        out[i][0].append(s)
        out[i][1].append(k)
        final_states[i] = _lane_state(pool, lane)
        st = pool.disconnect(lane)
        logs[i] = (st["migration_log"], st)
        assert st["migrations"] >= 1, f"lane {i} never migrated"
        assert st["bucket"] == 512                # ended in the big bucket
    assert pool.pool_stats()["migrations_total"] >= 2
    _assert_compiled_once(pool)                   # migrations: 0 recompiles
    pool.close()

    for i in range(2):
        xy, ts = ramps[i]
        # poll-drained segments concatenate in stream order
        got_s = np.concatenate([np.zeros((0,), np.float32)] + out[i][0])
        got_k = np.concatenate([np.zeros((0,), bool)] + out[i][1])
        log, st = logs[i]
        rep_s, rep_k, det = _replay_with_rebucket(cfg, xy, ts, 128, log)
        np.testing.assert_array_equal(got_s, rep_s, err_msg=f"lane {i}")
        np.testing.assert_array_equal(got_k, rep_k)
        assert st["energy_pj"] == det.energy_pj   # float64 books identical
        assert st["kept_total"] == det.kept_total
        # carried device state identical too (TOS/SAE/LUT/key/accums)
        _assert_states_equal(final_states[i], jax.device_get(det.state))


def test_adaptive_migration_poll_cadence_collects_everything():
    """The migration path loses nothing even when polls are sparse: one
    lane polled only at the end still reads its full stream (migration
    seal+drain delivered the pre-move rounds to the queue in order)."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    half = cfg.dvfs_cfg.half_us
    xy, ts = _ramp_stream([100] * 4 + [512] * 6, half, seed=5)
    pool = DetectorPool(cfg, capacity=1, ring_rounds=8, buckets=(128, 512),
                        policy="adaptive", migrate_patience=2)
    lane = pool.connect(chunk=128, seed=cfg.seed)
    wins = ts // half
    scored = 0
    for j in range(int(wins[-1]) + 1):
        m = wins == j
        pool.feed(lane, xy[m], ts[m])
        pool.pump()
        # a drain observation without a full readout: non-blocking poll
        s, _ = pool.poll(lane, wait=False)
        scored += s.size
    s, _ = pool.flush(lane)
    scored += s.size
    st = pool.stats(lane)
    assert st["migrations"] >= 1
    # every event scored exactly once across all readouts
    assert scored == len(ts)
    pool.close()


def test_static_policy_never_migrates_on_ramp():
    """The default policy is frozen placement: the same ramp that moves an
    adaptive lane leaves a static lane in its connect-time bucket, and its
    readout equals run_pipeline at that bucket (PR 4 behavior exactly)."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    half = cfg.dvfs_cfg.half_us
    xy, ts = _ramp_stream([100] * 4 + [512] * 6, half, seed=5)
    pool = DetectorPool(cfg, capacity=1, ring_rounds=4, buckets=(128, 512))
    assert pool.policy == "static"
    lane = pool.connect(chunk=128, seed=cfg.seed)
    wins = ts // half
    for j in range(int(wins[-1]) + 1):
        m = wins == j
        pool.feed(lane, xy[m], ts[m])
        pool.pump()
        pool.poll(lane)
    s, k = pool.flush(lane)
    st = pool.stats(lane)
    assert st["migrations"] == 0 and st["bucket"] == 128
    assert pool.pool_stats()["migrations_total"] == 0
    pool.close()


# ---------------------------------------------------------------------------
# Serving edge cases
# ---------------------------------------------------------------------------


def test_flush_with_empty_rechunk_buffer(streams):
    """flush() on a lane whose re-chunk buffer is empty schedules no extra
    round: it just drains the ring and returns what's pending (possibly
    nothing)."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    pool = DetectorPool(cfg, capacity=1, ring_rounds=4)
    lane = pool.connect(seed=cfg.seed)
    s, k = pool.flush(lane)                       # never fed
    assert s.size == 0 and k.size == 0
    assert pool.rounds_executed == 0
    xy, ts = streams[0]
    pool.feed(lane, xy[:512], ts[:512])           # exact multiple of chunk
    pool.pump()
    pool.poll(lane)
    before = pool.rounds_executed
    s, k = pool.flush(lane)                       # buffer empty again
    assert s.size == 0 and k.size == 0
    assert pool.rounds_executed == before
    assert pool.stats(lane)["buffered"] == 0


def test_disconnect_with_undrained_ring_slots(streams):
    """disconnect() drains the lane's ring first (waiting on the reader in
    async mode): its final stats cover all pumped rounds, and a session
    reusing the slot inherits nothing."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    xy, ts = streams[0]
    ref = pipeline.run_pipeline(xy[:1792], ts[:1792], cfg)   # 7 full chunks
    pool = DetectorPool(cfg, capacity=1, ring_rounds=8)
    lane = pool.connect(seed=cfg.seed)
    pool.feed(lane, xy[:1792], ts[:1792])
    pool.pump()
    assert pool.stats(lane)["ring_rounds_buffered"] == 7     # undrained
    stats = pool.disconnect(lane)                            # no poll first
    assert stats["kept_total"] == int(ref.kept.sum())
    assert stats["energy_pj"] == ref.energy_pj
    assert stats["ring_rounds_buffered"] == 0
    assert stats["ring_sealed_rounds"] == 0                  # reader caught up
    # slot reuse starts clean
    lane2 = pool.connect(seed=cfg.seed)
    s, k = pool.flush(lane2)
    assert s.size == 0
    assert pool.stats(lane2)["kept_total"] == 0


@pytest.mark.parametrize("drain_mode", ["sync", "async"])
def test_poll_under_ring_overflow_drop_oldest(streams, drain_mode):
    """drop_oldest: a full ring overwrites its oldest rounds; poll() returns
    only the survivors, the drop counters (host mirror and device ground
    truth) agree, and the in-state device accumulators stay complete — in
    both drain modes (the host-mirror audit runs under the reader thread
    in async mode)."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    xy, ts = streams[0]
    pool = DetectorPool(cfg, capacity=1, ring_rounds=2,
                        on_overflow="drop_oldest", drain_mode=drain_mode)
    lane = pool.connect(seed=cfg.seed)
    pool.feed(lane, xy[:1792], ts[:1792])         # 7 rounds into 2 slots
    assert pool.pump() == 7
    s, k = pool.poll(lane)
    assert s.size == 2 * 256                      # rounds 5 and 6 survive
    ref = pipeline.run_pipeline(xy[:1792], ts[:1792], cfg)
    np.testing.assert_array_equal(s, ref.scores[5 * 256:])
    st = pool.stats(lane)
    assert st["ring_dropped_rounds"] == 5
    # host books only cover what was polled; the device accumulators in the
    # carried state never lost a round
    assert st["kept_total"] == int(ref.kept[5 * 256:].sum())
    assert st["device_kept_total"] == int(ref.kept.sum())
    ps = pool.pool_stats()
    assert ps["dropped_rounds_total"] == 5
    # everything has been fetched, so the predicted mirror has fully
    # resolved against the device counter (the audit)
    assert ps["dropped_rounds_confirmed"] == 5
    pool.close()


@pytest.mark.parametrize("drain_mode", ["sync", "async"])
def test_ring_overflow_drain_policy_is_lossless(streams, drain_mode):
    """drain: the pump makes room in a full ring (sync: inline fetch;
    async: seal to the reader) instead of dropping — more fetches under
    overload, never data loss."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    xy, ts = streams[0]
    pool = DetectorPool(cfg, capacity=1, ring_rounds=2,
                        drain_mode=drain_mode)
    lane = pool.connect(seed=cfg.seed)
    pool.feed(lane, xy, ts)
    pool.pump()                                   # 7 rounds, R=2 -> drains
    s, k = pool.flush(lane)
    assert pool.host_fetches >= 3
    ref = pipeline.run_pipeline(xy, ts, cfg)
    np.testing.assert_array_equal(s, ref.scores)
    assert pool.stats(lane)["ring_dropped_rounds"] == 0
    assert pool.pool_stats()["dropped_rounds_confirmed"] == 0
    pool.close()


def test_pool_rejects_bad_config():
    cfg = pipeline.PipelineConfig(chunk=128)
    with pytest.raises(ValueError, match="ring_rounds"):
        DetectorPool(cfg, capacity=1, ring_rounds=0)
    with pytest.raises(ValueError, match="on_overflow"):
        DetectorPool(cfg, capacity=1, on_overflow="block")
    with pytest.raises(ValueError, match="drain_mode"):
        DetectorPool(cfg, capacity=1, drain_mode="threaded")


# ---------------------------------------------------------------------------
# Sharded lanes
# ---------------------------------------------------------------------------


def test_sharded_executor_single_device_fallback(streams):
    """shard=True on a 1-device host runs the shard_map path on a 1-wide
    lane mesh — same bits, same executables (the transparency contract that
    lets one code path serve laptops and pods)."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2,
                                  dvfs=True, dvfs_online=True)
    pool = DetectorPool(cfg, capacity=2, ring_rounds=3, shard=True)
    assert pool.pool_stats()["sharded"]
    xy, ts = streams[0]
    lane = pool.connect(seed=cfg.seed)
    pool.feed(lane, xy, ts)
    pool.pump()
    s, k = pool.flush(lane)
    ref = pipeline.run_pipeline(xy, ts, cfg)
    np.testing.assert_array_equal(s, ref.scores)
    np.testing.assert_array_equal(k, ref.kept)
    _assert_compiled_once(pool)
    pool.close()


_SHARDED_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro.core import pipeline
    from repro.events import synthetic
    from repro.serve import DetectorPool

    assert len(jax.local_devices()) == 4
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2,
                                  dvfs=True, dvfs_online=True)
    streams = [synthetic.shapes_stream(duration_us=25_000, seed=s)
               for s in range(3)]
    pool = DetectorPool(cfg, capacity=3, ring_rounds=4)   # auto-shards
    ps = pool.pool_stats()
    assert ps["sharded"] and ps["devices"] == 4, ps
    assert ps["drain_mode"] == "async"                    # reader + shards
    assert pool._phys == 4                                # padded to mesh
    lanes = [pool.connect(seed=cfg.seed) for _ in range(3)]
    for i, ln in enumerate(lanes):
        pool.feed(ln, streams[i].xy[:1500], streams[i].ts[:1500])
    pool.pump()
    # churn mid-run: retire lane 2, reuse its slot for a fresh session
    s2, _ = pool.flush(lanes[2])
    ref2 = pipeline.run_pipeline(streams[2].xy[:1500], streams[2].ts[:1500],
                                 cfg)
    assert np.array_equal(s2, ref2.scores)
    pool.disconnect(lanes[2])
    lanes[2] = pool.connect(seed=cfg.seed)
    pool.feed(lanes[2], streams[2].xy[:1500], streams[2].ts[:1500])
    for i in (0, 1):
        pool.feed(lanes[i], streams[i].xy[1500:2500],
                  streams[i].ts[1500:2500])
    pool.pump()
    for i, e in ((0, 2500), (1, 2500), (2, 1500)):
        s, k = pool.flush(lanes[i])
        ref = pipeline.run_pipeline(streams[i].xy[:e], streams[i].ts[:e],
                                    cfg)
        assert np.array_equal(s, ref.scores), i
        assert np.array_equal(k, ref.kept), i
    assert pool.executors_compiled_once(), pool.compile_cache_sizes()
    sizes = pool.compile_cache_sizes()
    assert sizes[256]["block"] == 1, sizes
    pool.close()
    print("OK")
""")


@pytest.mark.slow
def test_sharded_pool_4_devices_subprocess():
    """Lane-sharded pool on 4 forced host devices, async drain: bit-exact
    vs run_pipeline per lane, nothing recompiled through churn (out-of-
    process so the main pytest run stays on 1 device)."""
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_SUBPROCESS],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
