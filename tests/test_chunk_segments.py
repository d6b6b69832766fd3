"""The per-chunk wait counters of ``PoolRuntime``: each of the six
segments of a chunk's life (feed -> collect -> launch -> seal -> fetched
-> distributed -> polled), summed over chunks, against the sum its
definition gives on a clock that only the test moves."""
import numpy as np
import pytest

from repro import obs as obs_mod
from repro.core import pipeline
from repro.events import synthetic
from repro.serve import DetectorPool

CHUNK = 64
STAGE_S = 5.0         # the stage hook's cost: collect -> launch
FETCH_S = 100.0       # seal -> device_get returned
DIST_S = 1000.0       # fetched -> distributed


class _Clock:
    """``obs.timer`` stand-in: time moves only when the test moves it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


@pytest.mark.parametrize("readout", ["dense", "compact"])
@pytest.mark.parametrize("drain_mode", ["sync", "async"])
def test_chunk_segments_sum_their_definitions(monkeypatch, drain_mode,
                                              readout):
    clock = _Clock()
    monkeypatch.setattr(obs_mod, "timer", clock)
    cfg = pipeline.PipelineConfig(chunk=CHUNK, lut_every_chunks=2)
    st = synthetic.ramp_stream([400], 20_000, seed=5)
    xy, ts = st.xy, st.ts
    pool = DetectorPool(cfg, capacity=2, ring_rounds=4, pipeline_depth=1,
                        drain_mode=drain_mode, readout=readout)
    rt = pool._rt
    a, b = pool.connect(), pool.connect()

    real_stage, real_fetch = rt._stage_block, rt._fetch_ring

    def stage(*args, **kw):
        blk = real_stage(*args, **kw)
        clock.t += STAGE_S
        return blk

    def fetch(ring):
        clock.t += FETCH_S              # inside the device_get
        host = real_fetch(ring)
        clock.t += DIST_S               # densify, lock wait
        return host

    rt._stage_block, rt._fetch_ring = stage, fetch
    # lane a: 100 events at t=0 and 28 at t=1 -> two chunks whose last
    # events came in at t=0 and t=1; lane b: 64 at t=2 (one chunk), then
    # 10 at t=3 that stay buffered
    for t, lane, lo, hi in ((0, a, 0, 100), (1, a, 100, 128),
                            (2, b, 0, 64), (3, b, 64, 74)):
        clock.t = t
        pool.feed(lane, xy[lo:hi], ts[lo:hi])
    clock.t = 10.0
    assert pool.pump() == 2             # one block of two rounds, 3 chunks
    t_launch = 10.0 + STAGE_S
    clock.t = t_seal = 20.0
    sa, _ = pool.poll(a)                # seals (or drains) the ring
    t_dist = t_seal + FETCH_S + DIST_S
    assert clock.t == t_dist
    clock.t = t_dist + 7.0
    sb, _ = pool.poll(b)                # b's chunk waited 7 s in its queue
    ps = pool.pool_stats()
    pool.close()

    assert (sa.size, sb.size) == (2 * CHUNK, CHUNK)
    assert ps["chunks_returned"] == 3 == (sa.size + sb.size) // CHUNK
    assert ps["events_fed"] == 100 + 28 + 64 + 10
    assert ps["feed_lock_wait_s"] == 0.0
    assert ps["chunk_buffer_wait_s"] == (10 - 0) + (10 - 1) + (10 - 2)
    assert ps["chunk_stage_wait_s"] == 3 * STAGE_S
    assert ps["chunk_ring_wait_s"] == 3 * (t_seal - t_launch)
    assert ps["chunk_fetch_wait_s"] == 3 * FETCH_S
    assert ps["chunk_distribute_wait_s"] == 3 * DIST_S
    assert ps["chunk_handoff_wait_s"] == 2 * 0.0 + 1 * 7.0


@pytest.mark.parametrize("readout", ["dense", "compact"])
@pytest.mark.parametrize("drain_mode", ["sync", "async"])
def test_chunks_returned_counts_polled_chunks(drain_mode, readout):
    """On the real clock, under pipelining, forced drains and a flushed
    partial tail: ``chunks_returned`` is every chunk poll handed back, and
    no segment is negative."""
    cfg = pipeline.PipelineConfig(chunk=CHUNK, lut_every_chunks=2)
    streams = [synthetic.ramp_stream([300, 900], 20_000, seed=s)
               for s in (1, 2, 3)]
    pool = DetectorPool(cfg, capacity=3, ring_rounds=2, pipeline_depth=2,
                        drain_mode=drain_mode, readout=readout)
    lanes = [pool.connect() for _ in streams]
    got = np.zeros(len(lanes), np.int64)
    for lo in range(0, max(s.ts.size for s in streams), 97):
        for i, (lane, st) in enumerate(zip(lanes, streams)):
            pool.feed(lane, st.xy[lo:lo + 97], st.ts[lo:lo + 97])
        pool.pump()
        for i, lane in enumerate(lanes):
            got[i] += pool.poll(lane, wait=bool(lo % 2))[0].size
    for i, lane in enumerate(lanes):
        got[i] += pool.flush(lane)[0].size
    ps = pool.pool_stats()
    pool.close()
    fed = np.array([s.ts.size for s in streams])
    assert (got == fed).all()
    assert ps["events_fed"] == fed.sum()
    # one chunk per lane per round, the flushed tails included
    assert ps["chunks_returned"] == sum(-(-n // CHUNK) for n in fed)
    for k in ("chunk_buffer_wait_s", "chunk_stage_wait_s",
              "chunk_ring_wait_s", "chunk_fetch_wait_s",
              "chunk_distribute_wait_s", "chunk_handoff_wait_s",
              "feed_lock_wait_s"):
        assert ps[k] > -1e-6, k       # sums of stamp differences
