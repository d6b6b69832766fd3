"""What decides ``correct``: the plain reference agrees with the program,
its control (the reference one precision below float32) fails the limits,
and a run whose timed path is broken underneath comes out not correct."""
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, reference
from bench.traffic.content import LaneContent

TINY = {"height": 24, "width": 32, "capacity": 8, "sample_lanes": 4}
CELL = "gen4-720p-16.streams-open"
SEED = 2**31 + 4242


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


@pytest.fixture(scope="module")
def stream():
    xy, ts = LaneContent(180, 240, SEED, bases=1, length=8192).events(
        [0], [0], 256 * 22)
    return xy[0], ts[0] + harness.TS_ORIGIN_US


@pytest.mark.parametrize("config", ["davis240-1024", "gen4-720p-16"])
def test_reference_agrees_with_the_program(bench, stream, config):
    from repro.core import pipeline

    cfg = bench.config(config)
    cfg = {**cfg, "height": 180, "width": 240}
    det = reference.Detector.from_config(cfg)
    ref = reference.run_lane(*stream, det)
    out = pipeline.run_pipeline(*stream, harness.pipeline_config(cfg))
    state = {"surface": out.tos, "sae": out.sae, "lut": out.lut,
             "lut_ready": True}
    got = reference.compare_lane(out.scores, out.kept, state, ref, det,
                                 cfg["limits"]["score_gap"])
    assert got["bad_chunks"] == 0
    for k in ("lost_events", "extra_events", "kept_mismatch",
              "inf_mismatch", "tos_mismatch", "sae_mismatch"):
        assert got[k] == 0, k
    assert got["score_gap"] <= cfg["limits"]["score_gap"]
    assert got["lut_gap"] <= cfg["limits"]["lut_gap"]
    assert ref.keep.any() and np.isfinite(ref.scores).any()


@pytest.mark.parametrize("config", ["davis240-1024", "gen4-720p-16"])
def test_control_fails_the_limits(bench, stream, config):
    cfg = {**bench.config(config), "height": 180, "width": 240}
    det = reference.Detector.from_config(cfg)
    ref = reference.run_lane(*stream, det)
    low = reference.run_lane(*stream, det, "bfloat16")
    got = reference.compare_lane(low.scores, low.keep,
                                 reference.lane_state(low, det, "bfloat16"),
                                 ref, det, cfg["limits"]["score_gap"])
    assert got["score_gap"] > cfg["limits"]["score_gap"]
    assert got["lut_gap"] > cfg["limits"]["lut_gap"]
    assert got["bad_chunks"] > 0


def _step_unchanged(monkeypatch):
    from repro.core import state as state_mod

    real = state_mod.detector_step

    def step(cfg, state, chunk):
        _, out = real(cfg, state, chunk)
        return state, out

    monkeypatch.setattr(state_mod, "detector_step", step)


def _score_altered(monkeypatch):
    from repro.core import state as state_mod

    real = state_mod.detector_step

    def step(cfg, state, chunk):
        new, out = real(cfg, state, chunk)
        return new, out._replace(scores=out.scores * 1.001)

    monkeypatch.setattr(state_mod, "detector_step", step)


class _Wrap:
    def __init__(self, pool, poll):
        self._pool, self.poll = pool, poll

    def __getattr__(self, name):
        return getattr(self._pool, name)


def _half_left_out(pool):
    def poll(lane, wait=True):
        out = pool.poll(lane, wait=wait)
        if lane % 2:
            return np.zeros(0, np.float32), np.zeros(0, bool)
        return out
    return _Wrap(pool, poll)


def _duplicated(pool):
    seen = set()

    def poll(lane, wait=True):
        s, k = pool.poll(lane, wait=wait)
        if s.size and lane not in seen:
            seen.add(lane)
            return np.concatenate([s, s[:256]]), np.concatenate([k, k[:256]])
        return s, k
    return _Wrap(pool, poll)


def _run(bench, hook=None):
    opts = harness.Options(CELL, SEED, 1.0, drain_s=5.0)
    return harness.run_cell(bench, opts, time.perf_counter(),
                            config_override=TINY,
                            cell_override={"rate_eps": 16384},
                            pool_hook=hook)


def test_sound_run_is_correct(bench):
    out = _run(bench)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", ["step_unchanged", "score_altered",
                                   "half_left_out", "duplicated"])
def test_broken_timed_path_is_not_correct(bench, monkeypatch, fault):
    hook = None
    if fault == "step_unchanged":
        _step_unchanged(monkeypatch)
    elif fault == "score_altered":
        _score_altered(monkeypatch)
    elif fault == "half_left_out":
        hook = _half_left_out
    else:
        hook = _duplicated
    out = _run(bench, hook)
    assert not out["correct"], out["checks"]
