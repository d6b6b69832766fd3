"""``lut_refresh_lanes_per_round``: the reader of the executors' refresh
counters, and the scope the refresh carries in the executors' programs,
which ``lut_refresh_device_share`` reads."""
import pathlib
import re
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness
from bench import program_trace as pt
from repro.core import pipeline
from repro.serve import DetectorPool

OP_NAME = re.compile(r'op_name="([^"]*)"')


def _reader(name="lut_refresh_lanes_per_round.sat"):
    return harness.Bench(ROOT).reader(name)


@pytest.mark.parametrize("delta, want", [
    ({"rounds_executed": 40, "lut_refresh_lane_runs": 160}, 4.0),
    ({"rounds_executed": 8, "lut_refresh_lane_runs": 0}, 0.0),
    ({"rounds_executed": 0, "lut_refresh_lane_runs": 0}, None),
    ({"rounds_executed": 40}, None),        # a program with no such counter
], ids=["runs", "none_due", "no_rounds", "no_counter"])
def test_reader_divides_the_window_deltas(delta, want):
    assert _reader().read({"delta": delta}) == want


def test_reader_on_a_closed_loop_run():
    """A small closed-loop run on the host: lanes refilled a K-block at a
    time stay in phase, so a quarter of the rounds refresh every lane."""
    bench = harness.Bench(ROOT)
    opts = harness.Options("davis240-1024.replay-sat", 2**31 + 1501, 1.0)
    seen = []
    out = harness.run_cell(
        bench, opts, time.perf_counter(),
        config_override={"height": 24, "width": 32, "capacity": 8,
                         "sample_lanes": 2},
        ctx_hook=seen.append)
    assert out["correct"], out["checks"]
    d = seen[0]["delta"]
    assert d["rounds_executed"] > 0
    got = bench.reader("lut_refresh_lanes_per_round.sat").read(seen[0])
    assert got == d["lut_refresh_lane_runs"] / d["rounds_executed"]
    assert d["lut_refreshes_due"] <= d["lut_refresh_lane_runs"]
    assert 0 < got < 8


@pytest.mark.parametrize("op_name", [
    "jit(block)/while/body/closed_call/cond/branch_1_fun/vmap(lut_refresh)/"
    "lut_refresh/cond/branch_2_fun/vmap(jit(harris_response))/mul",
    "jit(block)/while/body/closed_call/cond/branch_1_fun/vmap(lut_refresh)/"
    "lut_refresh/cond/branch_1_fun/scatter",
    "jit(single)/vmap(lut_refresh)/lut_refresh/cond/branch_3_fun/"
    "vmap(jit(harris_response))/add:",
    "jit(single)/vmap(lut_refresh)/lut_refresh/reduce_sum",
], ids=["k_block_harris", "k_block_scatter", "one_round_harris",
        "one_round_count"])
def test_executor_level_refresh_maps_to_lut_refresh(op_name):
    assert pt.scope_of(op_name) == "lut_refresh"


@pytest.mark.parametrize("rounds", [1, 3], ids=["one_round", "k_block"])
def test_executor_harris_sits_in_the_batched_refresh(rounds):
    """In both executors every Harris operation is under ``lut_refresh``,
    in a branch of the batched refresh's switch (a vmapped ``lax.cond``
    would name it ``vmap(lut_refresh)/cond/...``)."""
    cfg = pipeline.PipelineConfig(height=24, width=32, chunk=64)
    pool = DetectorPool(cfg, capacity=4, ring_rounds=3, drain_mode="sync")
    lane = pool.connect()
    rng = np.random.default_rng(0)
    n = rounds * 64
    xy = np.stack([rng.integers(0, 32, n), rng.integers(0, 24, n)], 1)
    pool.feed(lane, xy.astype(np.int32), np.arange(n, dtype=np.int64) * 7)
    pool.pump()
    (text,) = pool.executor_hlo()
    pool.close()
    harris = [n for n in OP_NAME.findall(text) if "harris_response" in n]
    assert harris
    for name in harris:
        assert pt.scope_of(name) == "lut_refresh", name
        assert "/lut_refresh/cond/branch_" in name, name
