"""The benchmark's traffic generators and its closed-loop driver (CPU)."""
import pathlib
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import harness
from bench.traffic import closed, poisson_arrivals, streams

SEED = 2**31 + 977        # seeds may exceed 32 signed bits
GEN4 = {"height": 720, "width": 1280, "capacity": 16,
        "detector": {"chunk": 256}}
STREAMS = {"kind": "streams", "preroll_s": 1.0}


def _open(kind, mix, config, seed, rate=200_000.0, seconds=4.0):
    return kind.build(mix, {"rate_eps": rate}, config, seed, seconds)


@pytest.mark.parametrize("kind,mix,config", [(streams, STREAMS, GEN4)])
def test_same_seed_same_events(kind, mix, config):
    a = _open(kind, mix, config, SEED)
    b = _open(kind, mix, config, SEED)
    c = _open(kind, mix, config, SEED + 1)
    for f in ("lane", "xy", "t_us"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert a.t_us.size != c.t_us.size or not np.array_equal(a.t_us, c.t_us)


@pytest.mark.parametrize("kind,mix,config", [(streams, STREAMS, GEN4)])
def test_schedule_is_sorted_in_bounds_and_at_rate(kind, mix, config):
    rate, seconds = 200_000.0, 4.0
    s = _open(kind, mix, config, SEED, rate, seconds)
    assert np.all(np.diff(s.t_us) >= 0)
    span = (mix["preroll_s"] + seconds) * 1e6
    assert s.t_us[0] >= 0 and s.t_us[-1] < span
    assert 0 <= s.xy[:, 0].min() and s.xy[:, 0].max() < config["width"]
    assert 0 <= s.xy[:, 1].min() and s.xy[:, 1].max() < config["height"]
    # the aggregate rate holds
    want = rate * span * 1e-6
    assert abs(s.t_us.size - want) <= 0.02 * want


def test_poisson_arrivals_same_gaps_for_every_seed():
    span = 1_000_000
    a = poisson_arrivals(5_000, span, np.random.default_rng([SEED, 1]))
    b = poisson_arrivals(5_000, span, np.random.default_rng([SEED + 1, 1]))
    assert a.size == b.size == 5_000
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < span
    assert not np.array_equal(a, b)

    def gaps(t):
        return np.sort(np.diff(np.concatenate([t, [t[0] + span]])))
    np.testing.assert_allclose(gaps(a), gaps(b), atol=2)
    assert poisson_arrivals(0, span, np.random.default_rng(0)).size == 0


def test_streams_give_every_camera_its_share():
    s = _open(streams, STREAMS, GEN4, SEED, 160_000.0, 4.0)
    per_cam = np.bincount(s.lane, minlength=16)
    assert np.all(per_cam == per_cam[0])
    # another seed: the same gaps per camera, in another order
    o = _open(streams, STREAMS, GEN4, SEED + 1, 160_000.0, 4.0)
    for cam in (0, 7):
        a, b = s.t_us[s.lane == cam], o.t_us[o.lane == cam]
        assert not np.array_equal(a, b)
        span = (STREAMS["preroll_s"] + 4.0) * 1e6
        ga = np.sort(np.diff(np.concatenate([a, [a[0] + span]])))
        gb = np.sort(np.diff(np.concatenate([b, [b[0] + span]])))
        np.testing.assert_allclose(ga, gb, atol=2)
    # Poisson-like: the gaps' spread is that of an exponential
    gaps = np.diff(s.t_us[s.lane == 0]).astype(float)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)


class _FakePool:
    """Records what the closed loop feeds; returns nothing itself."""

    def __init__(self, fleet, inflight):
        self.fleet, self.inflight, self.worst = fleet, inflight, 0
        self.lock = threading.Lock()

    def feed(self, lane, xy, ts):
        c = self.fleet.chunk
        with self.lock:
            after = (self.fleet.fed[lane] + len(ts)) // c - self.fleet.ret[lane] // c
            self.worst = max(self.worst, int(after))


@pytest.mark.parametrize("inflight,refill", [(16, 8), (2, 1)])
def test_sat_loop_never_exceeds_inflight_chunks(inflight, refill):
    config = {"height": 24, "width": 32, "capacity": 8,
              "detector": {"chunk": 256}}
    src = closed.build({"kind": "closed", "inflight_chunks": inflight,
                        "refill_chunks": refill}, {}, config, SEED, 1.0)
    fleet = harness.Fleet(8, 256, ())
    pool = _FakePool(fleet, inflight)
    stop = threading.Event()
    gen = threading.Thread(target=harness.closed_generator,
                           args=(pool, src, fleet, stop, harness.Spans(),
                                 False, threading.Lock()))
    gen.start()
    rng = np.random.default_rng(0)
    try:
        for _ in range(300):
            time.sleep(0.001)
            lane = int(rng.integers(0, 8))
            done = fleet.fed[lane] // 256 - fleet.ret[lane] // 256
            if done:
                k = int(rng.integers(1, done + 1))
                fleet.record(lane, np.zeros(256 * k, np.float32),
                             np.zeros(256 * k, bool), 0.0)
    finally:
        stop.set()
        gen.join(timeout=10)
    assert not gen.is_alive()
    assert pool.worst == inflight
    assert np.all(fleet.fed // 256 - fleet.ret // 256 <= inflight)
    # every lane was fed whole refills
    assert np.all(fleet.fed % (256 * refill) == 0)
    # a lane's chunks continue its content in order
    xy, ts = src.chunks([3], [0], 2)
    xy2, ts2 = src.chunks([3], [1], 1)
    np.testing.assert_array_equal(xy[0, 256:], xy2[0])
    np.testing.assert_array_equal(ts[0, 256:], ts2[0])
