"""Pipelined pump + fleet packing (ISSUE 8).

Contracts:

  * **Depth is invisible in the data.**  The staged pump (stage the next
    block's host gather + H2D upload while earlier blocks run on device)
    is bit-exact vs the serial ``pipeline_depth=1`` pump — scores, kept
    masks, and final device state — across both drain modes, both
    overflow policies, and staggered join/leave churn.  Rebase fencing is
    part of the contract: a timebase hop must flush staged-ahead blocks
    first, or uploads collected against the old base would fold against
    the new one.
  * **Packing is invisible in the data.**  ``policy="pack"`` migrations
    (consolidating sparse buckets to cut padded upload bytes) reuse the
    seal/drain/snapshot/restore machinery, so each packed lane equals a
    ``StreamingDetector.rebucket`` replay at its logged boundaries —
    books included — and ``executors_compiled_once()`` holds.
  * **Stage-ahead is safe under concurrency.**  Mutators that could
    invalidate a staged block (disconnect, knob writes, migration
    staging) park on the pump token until the pass — stage queue
    included — has fully dispatched; they cannot interleave between a
    block's stage and its dispatch.
  * **The witnesses witness.**  Structural overlap counters read >0 only
    when blocks actually staged ahead of the dispatch point (0 at
    depth 1); a pass's knob actions coalesce into one batched ctrl
    write that lands the same values as the per-lane path; per-lane
    ``Observation`` fields rebuild only when the lane's generation
    moved; H2D upload accounting is per bucket and covers the 1-round
    fast path.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import pipeline
from repro.events import synthetic
from repro.serve import DetectorPool, StreamingDetector
from repro.serve.runtime import EVENT_SLOT_BYTES
from repro.serve.scheduler import LadderConfig

_CFG = pipeline.PipelineConfig(
    chunk=256, lut_every_chunks=2, vdd=0.6, inject_ber=True
)


@pytest.fixture(scope="module")
def streams():
    a = synthetic.shapes_stream(duration_us=30_000, seed=0)
    b = synthetic.dynamic_stream(duration_us=30_000, seed=1)
    return [
        (a.xy[:1500], a.ts[:1500]),
        (b.xy[:1200], b.ts[:1200]),
        (a.xy[1500:2800], a.ts[1500:2800]),
    ]


def _serve_churn(pool, streams, cfg, k, *, slab_rng_seed=0):
    """Staggered joins/leaves, random slab sizes, pump-until-dry each
    step; returns per-stream (scores, kept) plus the final pool."""
    rng = np.random.default_rng(slab_rng_seed)
    n = len(streams)
    lanes, cursors = {}, {i: 0 for i in range(n)}
    out = {i: ([], []) for i in range(n)}
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
                pool.disconnect(lane)
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
        i: (np.concatenate(out[i][0]), np.concatenate(out[i][1]))
        for i in range(n)
    }


@pytest.fixture(scope="module")
def serial_ref(streams):
    """The unpipelined oracle: depth 1 is the exact pre-pipeline pump."""
    pool = DetectorPool(_CFG, capacity=3, ring_rounds=3, pipeline_depth=1)
    out = _serve_churn(pool, streams, _CFG, 3)
    assert pool.pool_stats()["pump_stages_overlapped"] == 0
    pool.close()
    return out


@pytest.mark.parametrize("drain_mode", ["sync", "async"])
@pytest.mark.parametrize("overflow", ["drain", "drop_oldest"])
def test_pipelined_pump_bitexact_vs_serial(streams, serial_ref,
                                           drain_mode, overflow):
    pool = DetectorPool(_CFG, capacity=3, ring_rounds=3, pipeline_depth=2,
                        drain_mode=drain_mode, on_overflow=overflow)
    got = _serve_churn(pool, streams, _CFG, 3)
    assert pool.executors_compiled_once(), pool.compile_cache_sizes()
    pool.close()
    for i in serial_ref:
        np.testing.assert_array_equal(serial_ref[i][0], got[i][0],
                                      err_msg=f"stream {i} scores")
        np.testing.assert_array_equal(serial_ref[i][1], got[i][1],
                                      err_msg=f"stream {i} kept")


def test_deeper_pipeline_bitexact(streams, serial_ref):
    pool = DetectorPool(_CFG, capacity=3, ring_rounds=3, pipeline_depth=3)
    got = _serve_churn(pool, streams, _CFG, 3)
    pool.close()
    for i in serial_ref:
        np.testing.assert_array_equal(serial_ref[i][0], got[i][0])
        np.testing.assert_array_equal(serial_ref[i][1], got[i][1])


def test_pipeline_depth_validation():
    with pytest.raises(ValueError, match="pipeline_depth"):
        DetectorPool(_CFG, capacity=1, pipeline_depth=0)


def test_overlap_counters_structural():
    """Multi-block backlog pass at depth 2 overlaps (B-2)/B stages; the
    serial pump reports exactly zero by construction."""
    cfg = pipeline.PipelineConfig(chunk=128, lut_every_chunks=2)
    st = synthetic.ramp_stream([4 * 8 * 128], 20_000, seed=3)

    def burst(depth):
        pool = DetectorPool(cfg, capacity=2, ring_rounds=4, buckets=(128,),
                            pipeline_depth=depth)
        lane = pool.connect()
        pool.feed(lane, st.xy, st.ts)
        while pool.pump_rounds(32):
            pass
        pool.poll(lane)
        s, k = pool.flush(lane)
        ps = pool.pool_stats()
        assert pool.executors_compiled_once(), pool.compile_cache_sizes()
        pool.close()
        return s, k, ps

    s2, k2, ps2 = burst(2)
    assert ps2["pipeline_depth"] == 2
    assert ps2["pump_stages_overlapped"] > 0
    assert ps2["pump_stage_overlap_ratio"] >= 0.5, ps2
    assert ps2["pump_stage_s"] > 0.0

    s1, k1, ps1 = burst(1)
    assert ps1["pump_stages_overlapped"] == 0
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(k1, k2)


# ---------------------------------------------------------------------------
# Fleet packing
# ---------------------------------------------------------------------------


def _replay_with_rebucket(cfg, xy, ts, start_bucket, migration_log):
    """The migration oracle: a standalone (unpipelined, never-packed)
    session fed the same stream, rebucketed at each logged
    (events_folded, from, to) boundary."""
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
def test_pack_policy_bitexact_vs_rebucket_replay(drain_mode, overflow):
    """Heterogeneous fleet: one low-rate 128-chunk lane plus two sparse
    512-chunk lanes — both buckets pay (phys - ready) padding on every
    upload.  ``policy="pack"`` consolidates the fleet into ONE bucket
    (whichever direction the cost model scores cheaper); every packed
    lane's readout and books equal the never-packed single-session
    replay at the logged boundaries, under churn, with zero recompiles."""
    cfg = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)
    half = cfg.dvfs_cfg.half_us
    n_win = 12
    busy = synthetic.ramp_stream([96] * n_win, half, seed=21)
    sparse = [synthetic.ramp_stream([100] * n_win, half, seed=31 + i)
              for i in range(2)]
    churn = synthetic.ramp_stream([300] * 4, half, seed=41)

    pool = DetectorPool(cfg, capacity=4, ring_rounds=4, buckets=(128, 512),
                        policy="pack", migrate_patience=2,
                        drain_mode=drain_mode, on_overflow=overflow)
    b_lane = pool.connect(seed=cfg.seed, chunk=128)
    s_lanes = [pool.connect(seed=cfg.seed, chunk=512) for _ in range(2)]
    out = {ln: ([], []) for ln in [b_lane] + s_lanes}
    churn_lane = None
    churn_out = ([], [])
    logs = {}
    for j in range(n_win):
        if j == 3:                     # churn: a fourth camera joins
            churn_lane = pool.connect(seed=cfg.seed, chunk=512)
            churn_out = ([], [])
        m = (busy.ts // half) == j
        pool.feed(b_lane, busy.xy[m], busy.ts[m])
        for i, ln in enumerate(s_lanes):
            m = (sparse[i].ts // half) == j
            pool.feed(ln, sparse[i].xy[m], sparse[i].ts[m])
        if churn_lane is not None:
            m = (churn.ts // half) == (j - 3)
            pool.feed(churn_lane, churn.xy[m], churn.ts[m])
        pool.pump()
        for ln in out:
            s, k = pool.poll(ln)
            out[ln][0].append(s)
            out[ln][1].append(k)
        if churn_lane is not None:
            s, k = pool.poll(churn_lane)
            churn_out[0].append(s)
            churn_out[1].append(k)
        if j == 7:                     # churn: ...and leaves mid-run
            s, k = pool.flush(churn_lane)
            churn_out[0].append(s)
            churn_out[1].append(k)
            logs["churn"] = pool.disconnect(churn_lane)
            churn_lane = None
    for ln in [b_lane] + s_lanes:
        s, k = pool.flush(ln)
        out[ln][0].append(s)
        out[ln][1].append(k)
        logs[ln] = pool.disconnect(ln)
    ps = pool.pool_stats()
    assert ps["pack_moves"] >= 1, ps
    assert ps["pack_saved_slots"] > 0, ps
    assert pool.executors_compiled_once(), pool.compile_cache_sizes()
    pool.close()

    # the fleet consolidated: all persistent lanes ended in ONE bucket
    finals = {logs[ln]["bucket"] for ln in [b_lane] + s_lanes}
    assert len(finals) == 1, {ln: logs[ln]["bucket"]
                              for ln in [b_lane] + s_lanes}
    assert any(logs[ln]["migrations"] >= 1 for ln in [b_lane] + s_lanes)

    refs = {b_lane: (busy, 128, out[b_lane])}
    refs.update({ln: (sparse[i], 512, out[ln])
                 for i, ln in enumerate(s_lanes)})
    refs["churn"] = (churn, 512, churn_out)
    for key, (st, bucket0, acc) in refs.items():
        got_s = np.concatenate([np.zeros((0,), np.float32)] + acc[0])
        got_k = np.concatenate([np.zeros((0,), bool)] + acc[1])
        rep_s, rep_k, det = _replay_with_rebucket(
            cfg, st.xy, st.ts, bucket0, logs[key]["migration_log"])
        np.testing.assert_array_equal(got_s, rep_s, err_msg=f"lane {key}")
        np.testing.assert_array_equal(got_k, rep_k)
        assert logs[key]["energy_pj"] == det.energy_pj
        assert logs[key]["kept_total"] == det.kept_total


# ---------------------------------------------------------------------------
# Stage/dispatch concurrency
# ---------------------------------------------------------------------------


def test_midpass_mutations_park_on_pump_token():
    """A lane disconnect, knob write, or migration staging issued while a
    pass still holds staged-ahead blocks parks until the whole pass —
    stage queue included — has dispatched, so a staged upload can never
    be invalidated between its stage and its dispatch."""
    cfg = pipeline.PipelineConfig(chunk=128, lut_every_chunks=2)
    st = synthetic.ramp_stream([4 * 6 * 128], 20_000, seed=5)
    pool = DetectorPool(cfg, capacity=3, ring_rounds=4,
                        buckets=(128, 512), pipeline_depth=2)
    lane = pool.connect(chunk=128)
    victim = pool.connect(chunk=128)
    pool.feed(lane, st.xy, st.ts)

    rt = pool._rt
    orig = rt._stage_block
    fired = threading.Event()
    entered = threading.Event()
    done = threading.Event()
    errors = []

    def mutate():
        entered.set()
        try:
            pool.set_lane_control(victim, lut_every=8)
            rt.stage_migration(victim, 512)
            pool.disconnect(victim)
        except Exception as e:          # pragma: no cover - surfaced below
            errors.append(e)
        done.set()

    def spy(bucket, rounds, **kw):
        blk = orig(bucket, rounds, **kw)
        if not fired.is_set():
            fired.set()
            threading.Thread(target=mutate, daemon=True).start()
            assert entered.wait(5.0)
            time.sleep(0.05)
            # the pump token is held: every mutator above must be parked
            assert not done.is_set(), \
                "mutator ran while staged blocks were in flight"
        return blk

    rt._stage_block = spy
    try:
        while pool.pump_rounds(24):
            pass
    finally:
        rt._stage_block = orig
    assert done.wait(5.0)
    assert not errors, errors
    assert fired.is_set()
    s, k = pool.flush(lane)
    pool.disconnect(lane)
    assert pool.executors_compiled_once(), pool.compile_cache_sizes()
    pool.close()

    # same stream through a serial pool, no concurrent mutators: the
    # parked mutators touched only the victim lane, so the fed lane's
    # full readout is bit-exact
    ref = DetectorPool(cfg, capacity=3, ring_rounds=4, buckets=(128, 512),
                       pipeline_depth=1)
    rl = ref.connect(chunk=128)
    ref.feed(rl, st.xy, st.ts)
    while ref.pump_rounds(24):
        pass
    rs, rk = ref.flush(rl)
    ref.close()
    np.testing.assert_array_equal(s, rs)
    np.testing.assert_array_equal(k, rk)


# ---------------------------------------------------------------------------
# Witness counters
# ---------------------------------------------------------------------------


def test_observation_memoized_on_lane_generation():
    """Idle pump passes reuse every lane's cached LaneObservation; any
    feed/collect/shed/migration/tier write invalidates exactly that
    lane."""
    cfg = pipeline.PipelineConfig(chunk=128, lut_every_chunks=2)
    pool = DetectorPool(cfg, capacity=2, ring_rounds=2, buckets=(128,),
                        policy="ladder", ladder=LadderConfig())
    lane = pool.connect()
    st = synthetic.ramp_stream([256] * 2, 5_000, seed=6)
    pool.feed(lane, st.xy, st.ts)
    while pool.pump_rounds(2):
        pass
    base = pool.pool_stats()
    for _ in range(4):
        pool.pump_rounds(2)            # idle: nothing buffered, gen static
    idle = pool.pool_stats()
    assert idle["observation_reuses"] >= base["observation_reuses"] + 4
    assert idle["observation_rebuilds"] == base["observation_rebuilds"]
    pool.feed(lane, st.xy[:128], st.ts[:128])   # gen bump -> rebuild once
    pool.pump_rounds(2)
    fed = pool.pool_stats()
    assert fed["observation_rebuilds"] > idle["observation_rebuilds"]
    pool.flush(lane)
    pool.disconnect(lane)
    pool.close()


def test_knob_actions_coalesce_into_one_batched_write():
    """A ladder transition touching several lanes in one pass lands as a
    single batched ctrl write, and the written knobs equal what the
    per-lane ``set_lane_control`` path writes for the same values."""
    cfg = pipeline.PipelineConfig(chunk=128, lut_every_chunks=2)
    lad = LadderConfig(hi_rounds=0.5, lo_rounds=0.1, patience=1,
                       recover_patience=1, classes=(("standard", 3),))
    pool = DetectorPool(cfg, capacity=3, ring_rounds=2, buckets=(128,),
                        policy="ladder", ladder=lad)
    lanes = [pool.connect() for _ in range(3)]
    st = synthetic.ramp_stream([400] * 10, cfg.dvfs_cfg.half_us, seed=7)
    half = cfg.dvfs_cfg.half_us
    for j in range(8):
        m = (st.ts // half) == j
        for ln in lanes:
            pool.feed(ln, st.xy[m], st.ts[m])
        pool.pump_rounds(2)            # backlog stays high: ladder descends
    ps = pool.pool_stats()
    assert ps["ctrl_batched_writes"] >= 1, ps
    assert ps["ctrl_actions_coalesced"] >= 2, ps
    knobs = {ln: (pool.stats(ln)["ctrl_lut_every"],
                  pool.stats(ln)["ctrl_vdd_cap"],
                  pool.stats(ln)["ctrl_shed"]) for ln in lanes}
    batch_ctrl = jax.device_get(pool._rt._states.ctrl)

    # replay the same knob values through the single-write path
    ref = DetectorPool(cfg, capacity=3, ring_rounds=2, buckets=(128,))
    rlanes = [ref.connect() for _ in range(3)]
    for ln, rl in zip(lanes, rlanes):
        lut, cap, shed = knobs[ln]
        ref.set_lane_control(rl, lut_every=lut, vdd_cap=cap,
                             shed=bool(shed))
        assert ref.pool_stats()["ctrl_batched_writes"] == 0
        rs = ref.stats(rl)
        assert (rs["ctrl_lut_every"], rs["ctrl_vdd_cap"],
                rs["ctrl_shed"]) == knobs[ln]
    ref_ctrl = jax.device_get(ref._rt._states.ctrl)
    for a, b in zip(jax.tree.leaves(batch_ctrl), jax.tree.leaves(ref_ctrl)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for p in (pool, ref):
        for ln in (lanes if p is pool else rlanes):
            p.flush(ln)
            p.disconnect(ln)
        assert p.executors_compiled_once(), p.compile_cache_sizes()
        p.close()


def test_h2d_accounting_per_bucket_and_single_round_path():
    """Upload accounting is per bucket and includes the 1-round fast
    path: a sparse arrival (exactly one ready round) goes through
    ``_exec1`` and still lands in ``h2d_event_slots`` and its bucket's
    entry — the pack planner's measured signal."""
    cfg = pipeline.PipelineConfig(chunk=128, lut_every_chunks=2)
    pool = DetectorPool(cfg, capacity=2, ring_rounds=4, buckets=(128, 512))
    a = pool.connect(chunk=128)
    b = pool.connect(chunk=512)
    st = synthetic.ramp_stream([128], 5_000, seed=8)
    big = synthetic.ramp_stream([512], 5_000, seed=9)

    ps0 = pool.pool_stats()
    assert ps0["h2d_event_slots"] == 0
    pool.feed(a, st.xy, st.ts)         # exactly ONE 128-round: _exec1 path
    pool.pump()
    ps1 = pool.pool_stats()
    phys = pool._rt._phys
    assert ps1["h2d_event_slots"] - ps0["h2d_event_slots"] == phys * 128
    assert ps1["h2d_valid_events"] - ps0["h2d_valid_events"] == 128
    assert ps1["buckets"][128]["h2d_event_slots"] == phys * 128
    assert ps1["buckets"][128]["h2d_valid_events"] == 128
    assert ps1["buckets"][512]["h2d_event_slots"] == 0

    pool.feed(b, big.xy, big.ts)       # one 512-round in the other bucket
    pool.pump()
    ps2 = pool.pool_stats()
    assert ps2["buckets"][512]["h2d_event_slots"] == phys * 512
    assert ps2["buckets"][128]["h2d_event_slots"] == phys * 128  # untouched
    # totals are the per-bucket sums, padding priced at the AER slot width
    slots = sum(v["h2d_event_slots"] for v in ps2["buckets"].values())
    valid = sum(v["h2d_valid_events"] for v in ps2["buckets"].values())
    assert ps2["h2d_event_slots"] == slots
    assert ps2["h2d_valid_events"] == valid
    assert ps2["h2d_padding_bytes"] == (slots - valid) * EVENT_SLOT_BYTES
    for ln in (a, b):
        pool.poll(ln)
        pool.flush(ln)
        pool.disconnect(ln)
    pool.close()
