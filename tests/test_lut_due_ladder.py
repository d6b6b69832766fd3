"""The pool executors' LUT refresh: only the lanes due and masked in run the
Harris, in a batch sized to their count (``state.refresh_luts``).

Contracts: (1) ``refresh_luts`` gives every due lane the unbatched
``harris_response`` bit for bit and leaves the others alone, on every
branch of its ladder; (2) every pool lane stays bit-equal to
``run_pipeline_reference`` (scores, kept, TOS, LUT, ``lut_ready``) through
lockstep, staggered and masked rounds, per-lane knobs, both executors and
both step backends; (3) ``lut_refreshes_due`` counts the refreshes the
reference performs and ``lut_refresh_lane_runs`` the ladder sizes the
rounds took.
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import harris as harris_mod
from repro.core import pipeline
from repro.core import state as state_mod
from repro.serve import DetectorPool

H, W, CHUNK, LUT_EVERY = 24, 40, 64, 4
# 4 cameras on 6 lanes: the ladder (0, 1, 3, 6) takes 2 due lanes in a
# batch of 3 with one slot dropped, and 4 in the every-lane branch
LANES, CAPACITY = 4, 6


def _cfg(backend="jnp"):
    return pipeline.PipelineConfig(height=H, width=W, chunk=CHUNK,
                                   lut_every_chunks=LUT_EVERY,
                                   backend=backend)


# -- refresh_luts on its own ----------------------------------------------------


@pytest.mark.parametrize("lanes, ladder", [
    (1, (0, 1)),
    (3, (0, 1, 3)),
    (4, (0, 1, 2, 4)),
    (16, (0, 1, 2, 4, 8, 16)),
    (1024, (0, 64, 128, 256, 512, 1024)),
])
def test_refresh_ladder(lanes, ladder):
    assert state_mod.refresh_ladder(lanes) == ladder


@pytest.mark.parametrize("due", [
    [0, 0, 0, 0, 0, 0, 0, 0],      # nothing runs
    [0, 0, 1, 0, 0, 0, 0, 0],      # a batch of 1
    [1, 0, 0, 0, 0, 0, 0, 1],      # a batch of 2
    [0, 1, 1, 0, 1, 0, 0, 0],      # a batch of 4, one slot dropped
    [1, 1, 1, 1, 0, 1, 0, 0],      # every lane, due ones selected
    [1, 1, 1, 1, 1, 1, 1, 1],
], ids=["none", "one", "two", "three", "five", "all"])
def test_refresh_luts_matches_the_unbatched_harris(due):
    cfg = _cfg()
    rng = np.random.default_rng(3)
    lanes = len(due)
    surfaces = jnp.asarray(rng.integers(0, 256, (lanes, H, W)), jnp.uint8)
    luts = jnp.asarray(rng.normal(size=(lanes, H, W)), jnp.float32)
    due = jnp.asarray(due, bool)
    got = np.asarray(jax.jit(
        lambda s, l, d: state_mod.refresh_luts(cfg, s, l, d)
    )(surfaces, luts, due))
    for i in range(lanes):
        want = (harris_mod.harris_response(
                    surfaces[i], sobel_size=cfg.sobel_size,
                    window_size=cfg.window_size, k=cfg.harris_k)
                if due[i] else luts[i])
        np.testing.assert_array_equal(got[i], np.asarray(want),
                                      err_msg=f"lane {i}")


# -- the pool against the reference ---------------------------------------------


def _streams(n_chunks):
    """Dense per-lane streams of whole chunks (most events pass STCF)."""
    out = []
    for lane in range(LANES):
        rng = np.random.default_rng(100 + lane)
        n = n_chunks * CHUNK
        xy = np.stack([rng.integers(0, W, n), rng.integers(0, H, n)], 1)
        ts = 1_000 + np.cumsum(rng.integers(1, 12, n))
        out.append((xy.astype(np.int32), ts.astype(np.int64)))
    return out


# chunks fed to each lane before each pump: a pump folds rounds 0..max-1,
# lane i in round r iff it was fed more than r chunks
SCHEDULES = {
    # lanes in phase: rounds with no lane due, then rounds with all due
    "lockstep": [[4, 4, 4, 4]] * 3,
    # out of phase, several rounds per pump (the K-block executor)
    "staggered": [[1, 2, 3, 4], [3, 2, 1, 0], [4, 4, 2, 1], [0, 3, 4, 4],
                  [2, 1, 0, 3]],
    # one round per pump (the 1-round executor), lanes masked in turn
    "one_round": [[int((j + i) % 3 != 0) for i in range(LANES)]
                  for j in range(14)],
    # lane 0 waits at its due phase, masked out, for six pumps of both
    # executors, then resumes
    "masked_at_due": [[3, 1, 2, 3]] + [[0, 1, 1, 1], [0, 3, 2, 4]] * 3
                     + [[5, 1, 0, 2]],
    # three lanes due at once, the fourth one round behind
    "three_due": [[4, 4, 4, 3], [4, 4, 4, 5]],
}

# per-lane knobs set right after connect, and the config each is the
# oracle of (ControlState): shed is an interval longer than the stream
KNOBS = {
    "none": {},
    "lut_every_and_shed": {1: {"lut_every": 3}, 2: {"shed": True},
                           3: {"lut_every": 2}},
}


def _lane_cfg(cfg, knob):
    if knob.get("shed"):
        return dataclasses.replace(cfg, lut_every_chunks=1 << 20)
    return dataclasses.replace(
        cfg, lut_every_chunks=knob.get("lut_every", cfg.lut_every_chunks))


def _rounds(schedule):
    """Per executed round, the lanes in it and each one's chunk count
    after it (independent of the runtime's own bookkeeping)."""
    folded = [0] * LANES
    out = []
    for fed in schedule:
        for r in range(max(fed)):
            lanes = [i for i in range(LANES) if fed[i] > r]
            for i in lanes:
                folded[i] += 1
            out.append([(i, folded[i]) for i in lanes])
    return out


@pytest.fixture(scope="module", params=["jnp", "pallas_fused"])
def pool(request):
    p = DetectorPool(_cfg(request.param), capacity=CAPACITY, ring_rounds=4,
                     drain_mode="sync")
    yield p
    p.close()


def _fed(schedule):
    """Each lane's events over the whole schedule."""
    n = [sum(f[i] for f in schedule) * CHUNK for i in range(LANES)]
    streams = _streams(max(n) // CHUNK)
    return [(xy[:m], ts[:m]) for (xy, ts), m in zip(streams, n)]


def _serve(pool, schedule, knobs):
    cfg = pool._rt._cfg
    streams = _fed(schedule)
    lanes = [pool.connect(seed=cfg.seed) for _ in range(LANES)]
    for i, kw in knobs.items():
        pool.set_lane_control(lanes[i], **kw)
    before = pool.pool_stats()
    cursor = [0] * LANES
    for fed in schedule:
        for i, k in enumerate(fed):
            xy, ts = streams[i]
            c, n = cursor[i], k * CHUNK
            if n:
                pool.feed(lanes[i], xy[c:c + n], ts[c:c + n])
            cursor[i] = c + n
        pool.pump()
    after = pool.pool_stats()
    served = [pool.flush(lane) for lane in lanes]
    st = pool._rt._states
    finals = [jax.device_get((st.surface[lane], st.lut[lane],
                              st.lut_ready[lane])) for lane in lanes]
    for lane in lanes:
        pool.disconnect(lane)
    return streams, served, finals, before, after


@pytest.mark.parametrize("knob_case", sorted(KNOBS))
@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_pool_lanes_match_the_reference(pool, schedule, knob_case):
    cfg, knobs = pool._rt._cfg, KNOBS[knob_case]
    fed, served, finals, before, after = _serve(
        pool, SCHEDULES[schedule], knobs)
    ref_cfg = dataclasses.replace(cfg, backend="jnp")
    for i, (xy, ts) in enumerate(fed):
        lane_cfg = _lane_cfg(ref_cfg, knobs.get(i, {}))
        ref = pipeline.run_pipeline_reference(xy, ts, lane_cfg)
        scores, kept = served[i]
        surface, lut, lut_ready = finals[i]
        msg = f"{schedule}/{knob_case} lane {i}"
        np.testing.assert_array_equal(scores, ref.scores, err_msg=msg)
        np.testing.assert_array_equal(kept, ref.kept, err_msg=msg)
        np.testing.assert_array_equal(surface, ref.tos, err_msg=msg)
        np.testing.assert_array_equal(lut, ref.lut, err_msg=msg)
        n_chunks = len(ts) // CHUNK
        assert bool(lut_ready) == (n_chunks >= lane_cfg.lut_every_chunks), msg


@pytest.mark.parametrize("knob_case", sorted(KNOBS))
@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_refresh_counters(pool, schedule, knob_case):
    """``lut_refreshes_due`` is the refreshes the reference performs (one
    per ``lut_every`` chunks, none while shed); ``lut_refresh_lane_runs``
    sums, over rounds, the least ladder size that holds the round's due
    count."""
    cfg, knobs = pool._rt._cfg, KNOBS[knob_case]
    fed, _, _, before, after = _serve(pool, SCHEDULES[schedule], knobs)
    every = [_lane_cfg(cfg, knobs.get(i, {})).lut_every_chunks
             for i in range(LANES)]
    refreshes = sum(len(ts) // CHUNK // every[i]
                    for i, (_, ts) in enumerate(fed))
    ladder = state_mod.refresh_ladder(CAPACITY)
    runs = 0
    for rnd in _rounds(SCHEDULES[schedule]):
        n = sum(1 for i, k in rnd if k % every[i] == 0)
        runs += min(s for s in ladder if s >= n)
    due = after["lut_refreshes_due"] - before["lut_refreshes_due"]
    lane_runs = (after["lut_refresh_lane_runs"]
                 - before["lut_refresh_lane_runs"])
    rounds = after["rounds_executed"] - before["rounds_executed"]
    assert rounds == len(_rounds(SCHEDULES[schedule]))
    assert due == refreshes
    assert lane_runs == runs
    assert due <= lane_runs <= CAPACITY * rounds


_SHARDED = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    sys.path.insert(0, sys.argv[1])
    import test_lut_due_ladder as t
    from repro.serve import DetectorPool

    assert len(jax.local_devices()) == 4
    pool = DetectorPool(t._cfg(), capacity=8, ring_rounds=4)
    assert pool.pool_stats()["sharded"]
    out = {}
    for name in ("staggered", "three_due"):
        fed, served, finals, before, after = t._serve(
            pool, t.SCHEDULES[name], {})
        out[name] = {
            "runs": after["lut_refresh_lane_runs"]
                    - before["lut_refresh_lane_runs"],
            "lanes": [[s.tolist(), k.tolist(), f[1].tolist()]
                      for (s, k), f in zip(served, finals)],
        }
    pool.close()
    print(json.dumps(out))
""")


def test_sharded_pool_refreshes_per_shard():
    """On four devices each shard of the lane axis takes its own ladder
    (2 lanes a shard: 0, 1, 2), so the lane runs count per shard (three
    due lanes on two shards run 3, where one 8-lane ladder would run 4);
    lanes stay bit-equal to the reference."""
    p = subprocess.run(
        [sys.executable, "-c", _SHARDED, str(pathlib.Path(__file__).parent)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    cfg = _cfg()
    for name, res in got.items():
        runs = 0
        for rnd in _rounds(SCHEDULES[name]):
            due = [i for i, k in rnd if k % LUT_EVERY == 0]
            runs += sum(min(s for s in (0, 1, 2)
                            if s >= sum(1 for i in due if i // 2 == shard))
                        for shard in range(4))
        assert res["runs"] == runs, name
        fed = _fed(SCHEDULES[name])
        for i, (scores, kept, lut) in enumerate(res["lanes"]):
            ref = pipeline.run_pipeline_reference(*fed[i], cfg)
            np.testing.assert_array_equal(np.float32(scores), ref.scores)
            np.testing.assert_array_equal(kept, ref.kept)
            np.testing.assert_array_equal(np.float32(lut), ref.lut)
