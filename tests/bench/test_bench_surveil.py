"""The surveillance open loop (``bench/traffic/surveil.py``), the
near-threshold cell's readers (``bench/ber_cost.py``, the ``ber_*``
metrics), and small runs of both new cells through ``harness.run_cell``."""
import json
import pathlib
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import ber_cost, harness, program_trace, roofline
from bench.traffic import surveil
from bench.traffic.content import NATIVE_RATE_PER_US

SEED = 2**31 + 8081
SURVEIL = "davis240-1024.surveil-open"
NTV = "davis240-ntv-1024.replay-sat"


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


def _schedule(bench, seed, rate=8000.0, seconds=6.0, capacity=None):
    config = bench.config("davis240-1024")
    if capacity:
        config = {**config, "capacity": capacity}
    return surveil.build(bench.mix("surveil-open"), {"rate_eps": rate},
                         config, seed, seconds)


def _bursts(s, events):
    """``(camera, start)`` of each burst of a schedule."""
    out = []
    for cam in np.unique(s.lane).tolist():
        t = s.t_us[s.lane == cam]
        assert t.size % events == 0
        out += [(cam, int(b[0])) for b in t.reshape(-1, events)]
    return sorted(out)


def test_burst_times_and_ranks_hold_for_every_seed(bench):
    a, b = _schedule(bench, SEED), _schedule(bench, SEED + 1)
    np.testing.assert_array_equal(a.t_us, b.t_us)
    np.testing.assert_array_equal(a.lane, b.lane)
    assert not np.array_equal(a.xy, b.xy)          # the seed draws content
    c = _schedule(bench, SEED, rate=16000.0)
    assert c.t_us.size > a.t_us.size


def test_schedule_is_sorted_at_rate_in_bursts(bench):
    rate, seconds = 8000.0, 6.0
    s = _schedule(bench, SEED, rate, seconds)
    mix = bench.mix("surveil-open")
    assert np.all(np.diff(s.t_us) >= 0) and s.t_us[0] >= 0
    span = (mix["preroll_s"] + seconds) * 1e6
    want = rate * span * 1e-6
    assert abs(s.t_us.size - want) <= 1024              # one burst
    events = mix["burst_chunks"] * 256
    assert events == 1024
    for cam, start in _bursts(s, events)[:50]:
        t = s.t_us[s.lane == cam]
        i = int(np.searchsorted(t, start))
        np.testing.assert_array_equal(t[i:i + events] - start,
                                      surveil.burst_offsets(events))
    # 4 chunks at the content's native rate: about 3.8 ms a burst
    dur = surveil.burst_offsets(events)[-1]
    assert dur == round((events - 1) / NATIVE_RATE_PER_US)
    assert 3700 < dur < 3900
    # a camera's bursts do not overlap
    for cam in np.unique(s.lane).tolist()[:50]:
        starts = [t for c, t in _bursts(s, events) if c == cam]
        assert np.all(np.diff(starts) > dur)


def test_cameras_share_the_rate_by_zipf():
    counts = surveil.zipf_counts(10_000, 1024, 0.99)
    assert counts.sum() == 10_000
    assert np.all(np.diff(counts) <= 0)
    share = np.arange(1, 1025) ** -0.99
    share /= share.sum()
    np.testing.assert_allclose(counts, 10_000 * share, atol=1.0)


def test_each_lane_continues_its_content(bench):
    s = _schedule(bench, SEED)
    config = bench.config("davis240-1024")
    from bench.traffic.content import LaneContent

    content = LaneContent(config["height"], config["width"], SEED)
    lane = int(np.bincount(s.lane).argmax())
    idx = s.lane_events(lane)
    want, _ = content.events([lane], [0], idx.size)
    np.testing.assert_array_equal(s.xy[idx], want[0])


# -- the near-threshold cell's readers ---------------------------------------


def test_ber_cost_counts():
    ops, nbytes = ber_cost.inject_cost(180 * 240)
    assert nbytes == 2 * 43_200
    assert ops == 43_200 * (5 * (35 + 6) + 9)
    peak = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    assert ber_cost.least_time_s(1024, 43_200, peak) == pytest.approx(
        1024 * 86_400 / 819e9)
    assert ber_cost.frame_pixels(ROOT) == 180 * 240


def test_ber_chunk_share_reads_the_counter(bench):
    read = bench.reader("ber_chunk_share.sat").read
    assert read({"delta": {"ber_chunks": 30, "chunks_returned": 40}}) == 75.0
    assert read({"delta": {"chunks_returned": 40}}) is None     # parent
    assert read({"delta": {"ber_chunks": 0, "chunks_returned": 0}}) is None


def _planes():
    """A hand-made trace: write errors drawn in 30 of 100 ns busy."""
    ops = [["fusion.1", 0, 30, "jit(block)/while/body/vmap(ber_inject)/"
            "jit(inject_write_errors_at)/jit(_bernoulli)/xor", "jit_block"],
           ["fusion.2", 30, 80, "jit(block)/while/body/vmap(tos_update)/"
            "scatter-add", "jit_block"],
           ["fusion.3", 80, 100, "", "jit_block"]]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops", "events": ops}]},
            {"name": "/host:CPU", "lines": [
                {"name": "bench", "events": [["bench.window", 0, 200]]}]}]


def test_ber_inject_readers_on_a_trace(bench, monkeypatch):
    monkeypatch.setattr(program_trace, "load", lambda path: _planes())
    monkeypatch.setattr(program_trace, "executor_hlo", lambda pool=None: [])
    monkeypatch.setattr(harness, "device_info",
                        lambda: {"kind": "TPU v5 lite"})
    scopes = program_trace.SCOPES
    ctx = {"trace": {"busy_s": 100e-9}, "delta": {"ber_chunks": 2}}
    share = bench.reader("ber_inject_device_share.sat").read(ctx)
    assert share == pytest.approx(30.0)
    assert program_trace.SCOPES == scopes and "ber_inject" not in scopes
    # the default reduction gives the scope's work to no scope of its own
    assert "ber_inject" not in program_trace.reduce(_planes())["scope_self_s"]
    monkeypatch.setattr(ber_cost, "frame_pixels", lambda root=None: 64)
    got = bench.reader("ber_inject_roofline_share.sat").read(ctx)
    least = ber_cost.least_time_s(2, 64, roofline.peaks("TPU v5 lite"))
    assert got == pytest.approx(100.0 * least / 30e-9)
    # untraced, or a program without the scope or the counter: nothing
    assert bench.reader("ber_inject_device_share.sat").read(
        {"trace": None, "delta": {}}) is None
    assert bench.reader("ber_inject_roofline_share.sat").read(
        {"trace": ctx["trace"], "delta": {}, "ber_inject_s": 30e-9}) is None
    assert bench.reader("ber_inject_roofline_share.sat").read(
        {"trace": ctx["trace"], "delta": {"ber_chunks": 2},
         "ber_inject_s": None}) is None


# -- the new cells, small, through the harness --------------------------------


def test_new_cells_are_in_the_benchmark(bench):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells[NTV]["config"] == "davis240-ntv-1024"
    assert [m["name"] for m in bench.metrics(NTV, False)] == [
        "events_per_s", "setup_s"]
    ntv = {m["name"] for m in bench.metrics(NTV, True)}
    assert {"ber_inject_device_share.sat", "ber_inject_roofline_share.sat",
            "ber_chunk_share.sat", "tos_update_device_share.sat"} <= ntv


def _bench_with_surveil(root: pathlib.Path) -> harness.Bench:
    """A copy of the benchmark under ``root`` with the surveillance cell
    appended, as a later PR adds it once its knee is swept on the chip."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": SURVEIL, "config": "davis240-1024",
                              "traffic": "surveil-open", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"][:2]:
        m["workloads"].append(SURVEIL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/cells" / f"{SURVEIL}.json").write_text(
        json.dumps({"rate_eps": 40_000}))
    return harness.Bench(root)


def test_small_surveil_run_is_correct(tmp_path):
    bench = _bench_with_surveil(tmp_path)
    opts = harness.Options(SURVEIL, SEED, 1.0, drain_s=10.0)
    out = harness.run_cell(bench, opts, time.perf_counter(),
                           config_override={"height": 24, "width": 32,
                                            "capacity": 8, "sample_lanes": 3})
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"chunk_latency_p50_ms",
                                   "chunk_latency_p99_ms", "setup_s"}


def test_small_ntv_run_is_correct(bench):
    seen = []
    opts = harness.Options(NTV, SEED, 0.5, drain_s=20.0)
    out = harness.run_cell(bench, opts, time.perf_counter(),
                           config_override={"height": 48, "width": 64,
                                            "capacity": 4, "sample_lanes": 4},
                           ctx_hook=seen.append)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"events_per_s", "setup_s"}
    d = seen[0]["delta"]
    assert d["chunks_returned"] > 0 and d["ber_chunks"] == d["chunks_returned"]
    assert bench.reader("ber_chunk_share.sat").read(seen[0]) == 100.0
