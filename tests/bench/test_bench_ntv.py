"""The near-threshold fleet's reference (``bench/reference_ntv.py``): it
agrees with a seeded ``DetectorPool`` running the 0.6 V detector through
``harness.check``, planted faults of the write errors and the bfloat16
control fail it, and its operating-point table and rate estimator are the
program's."""
import dataclasses
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness, reference, reference_ntv
from bench.traffic import closed

CONFIG = "davis240-ntv-1024"
CELL = "davis240-ntv-1024.replay-sat"
SMALL = {"capacity": 3, "sample_lanes": 3}      # a few lanes at 180x240
CHUNKS = 20
SEED = 2**31 + 6061


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


def _fold(bench):
    """Feed every lane ``CHUNKS`` chunks of the closed loop's content,
    fold them through the pool the harness builds, and check every lane
    with the configuration's reference: ``(checks, bad_chunks, stats)``."""
    config = {**bench.config(CONFIG), **SMALL}
    lanes = int(config["capacity"])
    src = closed.build(bench.mix("replay-sat"), {}, config, SEED, 1.0)
    pool = harness.build_pool(config, 1)
    fleet = harness.Fleet(lanes, src.chunk, tuple(range(lanes)))
    try:
        xy, ts = src.chunks(np.arange(lanes), np.zeros(lanes, np.int64),
                            CHUNKS)
        for lane in range(lanes):
            pool.feed(lane, xy[lane], ts[lane] + harness.TS_ORIGIN_US)
            fleet.fed[lane] += ts[lane].size
        deadline = time.perf_counter() + 120
        while fleet.outstanding().size and time.perf_counter() < deadline:
            pool.pump()
            for lane in fleet.outstanding().tolist():
                s, k = pool.poll(lane, wait=False)
                if s.size:
                    fleet.record(lane, s, k, 0.0)
        states = harness.fetch_states(pool, fleet.sampled)
        stats = pool.pool_stats()
    finally:
        pool.close()
    checks, bad = harness.check(bench.reference_of(config), config, fleet,
                                states, src, fleet.sampled)
    return checks, bad, stats


def _correct(checks) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def test_the_configuration_names_the_ntv_reference(bench):
    assert bench.reference_of(bench.config(CONFIG)) is not reference
    det = reference_ntv.Detector.from_config(bench.config(CONFIG))
    assert (det.tw_us, det.counter_bits, det.headroom, det.vdd_floor) == \
        (10_000, 20, 1.25, 0.6)
    plain = bench.config("davis240-1024")
    with pytest.raises(ValueError, match="online DVFS"):
        reference_ntv.Detector.from_config(plain)


def test_ntv_reference_agrees_with_the_pool(bench):
    checks, bad, stats = _fold(bench)
    assert _correct(checks), checks
    assert bad == 0
    for k in ("lost_chunks", "extra_chunks", "kept_mismatch", "inf_mismatch",
              "tos_mismatch", "sae_mismatch"):
        assert checks[k]["value"] == 0, k
    assert checks["score_gap"]["value"] <= 1e-6
    assert checks["lut_gap"]["value"] <= 1e-6
    # every chunk ran at 0.6 V, with its write errors
    assert stats["chunks_returned"] == SMALL["capacity"] * CHUNKS
    assert stats["ber_chunks"] == stats["chunks_returned"]


def _errors_off(monkeypatch):
    """The pool runs with the program's write errors off."""
    real = harness.pipeline_config

    def pipeline_config(config):
        return dataclasses.replace(real(config), inject_ber=False)

    monkeypatch.setattr(harness, "pipeline_config", pipeline_config)


def _split_late(monkeypatch):
    """The program splits its key one chunk late: chunk ``c >= 1`` draws
    the reference's bits of chunk ``c - 1``."""
    from repro.core import state as state_mod

    real = state_mod.detector_step

    def step(cfg, state, chunk):
        new, out = real(cfg, state, chunk)
        key = jnp.where(state.chunk_idx == 0, state.key, new.key)
        return new._replace(key=key), out

    monkeypatch.setattr(state_mod, "detector_step", step)


def _ber_0_2_percent(monkeypatch):
    """The program writes at 0.2% where the paper gives 2.5% at 0.6 V."""
    from repro.core import dvfs

    real = dvfs.op_point_table

    def op_point_table(cfg=dvfs.DvfsConfig()):
        tab = real(cfg)
        ber = tab.ber.copy()
        ber[ber == np.float32(0.025)] = np.float32(0.002)
        return tab._replace(ber=ber)

    monkeypatch.setattr(dvfs, "op_point_table", op_point_table)


@pytest.mark.parametrize("fault", [_errors_off, _split_late,
                                   _ber_0_2_percent],
                         ids=["errors_off", "split_late", "ber_0_2_percent"])
def test_planted_write_error_faults_fail(bench, monkeypatch, fault):
    fault(monkeypatch)
    checks, bad, _ = _fold(bench)
    assert not _correct(checks)
    assert checks["tos_mismatch"]["value"] > 0
    assert bad > 0


def test_the_bfloat16_control_fails_both_gaps(bench):
    got = control.readings(bench, CELL, SEED, 1.0, config_override=SMALL,
                           lane_events=CHUNKS * 256)
    limits = bench.config(CONFIG)["limits"]
    assert got["score_gap"] > limits["score_gap"]
    assert got["lut_gap"] > limits["lut_gap"]
    for k in ("kept_mismatch", "tos_mismatch", "sae_mismatch"):
        assert got[k] == 0, k          # the same write errors, drawn alike


def test_operating_points_are_the_programs():
    from repro.core import dvfs, hwmodel

    lut = hwmodel.dvfs_lut()
    assert [p[0] for p in reference_ntv.OP_POINTS] == [p["vdd"] for p in lut]
    assert [p[1] for p in reference_ntv.OP_POINTS] == \
        [p["max_meps"] for p in lut]
    assert [p[2] for p in reference_ntv.OP_POINTS] == [p["ber"] for p in lut]
    det = reference_ntv.Detector.from_config(
        harness.Bench(ROOT).config(CONFIG))
    vdd, caps, ber = reference_ntv.op_points(det)
    tab = dvfs.op_point_table(dvfs.DvfsConfig(vdd_floor=det.vdd_floor))
    np.testing.assert_array_equal(vdd, tab.vdd64)
    np.testing.assert_array_equal(caps, tab.caps)
    np.testing.assert_array_equal(ber, tab.ber)


def _ramp(lo: float, hi: float, span_us: int, t0: int) -> np.ndarray:
    """Timestamps whose rate rises linearly from ``lo`` to ``hi``
    events/us over ``span_us``."""
    t, out = 0.0, []
    while t < span_us:
        out.append(t)
        t += 1.0 / (lo + (hi - lo) * t / span_us)
    return np.floor(np.asarray(out)).astype(np.int64) + t0


def test_estimator_matches_the_program_across_operating_points():
    """On a rate ramp from 1 to 12 events/us the reference's estimator
    picks, chunk by chunk, the points the program's streaming controller
    picks, and the ramp crosses 0.6, 0.7 and 0.8 V."""
    from repro.core import dvfs

    det = reference_ntv.Detector.from_config(
        harness.Bench(ROOT).config(CONFIG))
    cfg = dvfs.DvfsConfig(tw_us=det.tw_us, counter_bits=det.counter_bits,
                          headroom=det.headroom, vdd_floor=det.vdd_floor)
    ts = _ramp(1.0, 12.0, 30_000, harness.TS_ORIGIN_US + 1234)
    n = (ts.size // det.chunk) * det.chunk
    base = (int(ts[0]) // cfg.half_us) * cfg.half_us
    chunks = (ts[:n] - base).astype(np.int32).reshape(-1, det.chunk)
    caps = jnp.asarray(dvfs.op_point_table(cfg).caps)

    def step(rate, c):
        return dvfs.online_vdd_from_chunk_ts(
            rate, c, jnp.ones(c.shape, bool), cfg=cfg, caps=caps)

    _, want = jax.lax.scan(step, dvfs.rate_state_init(), jnp.asarray(chunks))
    got = reference_ntv.vdd_indices(ts, det)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert {0, 1, 2} <= set(got.tolist())
    np.testing.assert_array_equal(
        reference_ntv.chunk_bers(ts, det) > 0, got == 0)
