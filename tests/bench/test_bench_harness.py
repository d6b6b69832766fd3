"""The harness is driven by data and refuses to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, reference

CMD = [sys.executable, "bench/run.py", "--workload",
       "gen4-720p-16.replay-sat", "--seed", str(2**31 + 5), "--seconds",
       "1", "--trace", "0"]

NEW_KIND = '''
import numpy as np
from bench.traffic import OpenSchedule


def build(mix, cell, config, seed, seconds, rate_eps=None):
    """Every lane sends ``per_lane`` events at a steady pace."""
    lanes, n = config["capacity"], mix["per_lane"]
    rng = np.random.default_rng([seed, 9])
    span = (mix["preroll_s"] + seconds) * 1e6
    t = np.sort(rng.integers(0, int(span), lanes * n))
    lane = np.tile(np.arange(lanes, dtype=np.int32), n)
    xy = np.stack([rng.integers(0, config["width"], t.size),
                   rng.integers(0, config["height"], t.size)], 1)
    return OpenSchedule(lane, xy.astype(np.int32), t.astype(np.int64),
                        mix["preroll_s"], seconds)
'''

NEW_READER = '''
def read(ctx):
    d = ctx["delta"]
    return d["rounds_executed"] / d["pump_stages"] if d["pump_stages"] else None
'''


PLANTED_REF = '''
from bench import reference
from bench.reference import Detector, compare_lane, lane_state

FOLDS = []      # (lane_seed, precision) of every fold asked for


def run_lane(xy, ts, det, precision="float64", *, lane_seed):
    FOLDS.append((lane_seed, precision))
    return reference.run_lane(xy, ts, det, precision, lane_seed=lane_seed)
'''

TINY = {"height": 24, "width": 32, "capacity": 8, "sample_lanes": 4}
SEED = 2**31 + 4343
# the cells of the first benchmark, whose reference is the default
FIRST_CELLS = {"gen4-720p-16.streams-open": ("gen4-720p-16", 1),
               "davis240-1024.replay-sat": ("davis240-1024", 1),
               "gen4-720p-16.replay-sat": ("gen4-720p-16", 1)}


def _json(p):
    return json.loads(pathlib.Path(p).read_text())


def _copy_bench(root: pathlib.Path) -> dict:
    """A copy of the benchmark's files under ``root``; returns its spec."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return _json(ROOT / "BENCHMARK.json")


def test_new_files_are_found_without_an_edit(tmp_path):
    """A configuration, a cell, a traffic kind and a per-layer metric added
    as new files and entries run through the harness untouched."""
    spec = _copy_bench(tmp_path)
    base = _json(ROOT / "bench/configs/davis240-1024.json")
    base.update(name="tiny-8", height=24, width=32, capacity=8,
                sample_lanes=2)
    (tmp_path / "bench/configs/tiny-8.json").write_text(json.dumps(base))
    (tmp_path / "bench/traffic/steady.py").write_text(NEW_KIND)
    (tmp_path / "bench/traffic/steady-open.json").write_text(
        json.dumps({"kind": "steady", "per_lane": 1024, "preroll_s": 0.5}))
    (tmp_path / "bench/cells/tiny-8.steady-open.json").write_text("{}")
    (tmp_path / "bench/metrics/rounds_per_dispatch.py").write_text(NEW_READER)
    spec["configs"].append({"name": "tiny-8", "source": "test",
                            "file": "bench/configs/tiny-8.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-8.steady-open",
                              "config": "tiny-8", "traffic": "steady-open",
                              "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("tiny-8.steady-open")
    spec["per_layer"].append({
        "name": "rounds_per_dispatch.open", "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "chunk_latency_p50_ms", "workloads": ["tiny-8.steady-open"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = harness.Bench(tmp_path)
    names = [m["name"] for m in bench.metrics("tiny-8.steady-open", True)]
    assert names == ["rounds_per_dispatch.open"]
    opts = harness.Options("tiny-8.steady-open", 2**31 + 3, 1.0)
    seen = []
    out = harness.run_cell(bench, opts, time.perf_counter(),
                           ctx_hook=seen.append)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"chunk_latency_p50_ms", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    # the new reader reads a registry counter that no reader before it
    # read, from the same run's window deltas
    d = seen[0]["delta"]
    assert d["pump_stages"] > 0
    got = bench.reader("rounds_per_dispatch.open").read(seen[0])
    assert got == d["rounds_executed"] / d["pump_stages"] >= 1


def _planted_bench(root: pathlib.Path) -> harness.Bench:
    """A benchmark under ``root`` with a cell ``tiny-ref.streams-open``
    whose configuration names the planted reference ``planted_ref``, added
    as new files and appended ``BENCHMARK.json`` entries only."""
    spec = _copy_bench(root)
    base = _json(ROOT / "bench/configs/gen4-720p-16.json")
    base.update(TINY, name="tiny-ref", reference="planted_ref")
    (root / "bench/configs/tiny-ref.json").write_text(json.dumps(base))
    (root / "bench/planted_ref.py").write_text(PLANTED_REF)
    (root / "bench/cells/tiny-ref.streams-open.json").write_text(
        json.dumps({"rate_eps": 16384}))
    spec["configs"].append({"name": "tiny-ref", "source": "test",
                            "file": "bench/configs/tiny-ref.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-ref.streams-open",
                              "config": "tiny-ref", "traffic": "streams-open",
                              "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("tiny-ref.streams-open")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Bench(root)


def _record_reference_and_lanes(monkeypatch) -> tuple:
    """Record every module ``Bench.reference_of`` returns and every lane
    sample ``harness.sample_lanes`` draws."""
    refs, sampled = [], []
    real_ref, real_sample = harness.Bench.reference_of, harness.sample_lanes

    def reference_of(self, config):
        refs.append(real_ref(self, config))
        return refs[-1]

    def sample_lanes(*args):
        sampled.append(real_sample(*args))
        return sampled[-1]

    monkeypatch.setattr(harness.Bench, "reference_of", reference_of)
    monkeypatch.setattr(harness, "sample_lanes", sample_lanes)
    return refs, sampled


def test_a_configuration_names_its_reference(tmp_path, monkeypatch):
    """A configuration that names its own reference module runs as new
    files and appended entries: ``check`` folds with that module, given
    each sampled lane's seed."""
    bench = _planted_bench(tmp_path)
    refs, sampled = _record_reference_and_lanes(monkeypatch)
    opts = harness.Options("tiny-ref.streams-open", SEED, 1.0, drain_s=5.0)
    out = harness.run_cell(bench, opts, time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    [planted] = refs
    assert planted is not reference
    assert planted.FOLDS == [(harness.POOL_SEED + lane, "float64")
                             for lane in sampled[0]]


def test_the_control_folds_with_the_named_reference(tmp_path, monkeypatch):
    """``bench/control.py`` puts the configuration's own reference in the
    program's place: it folds each sampled lane with the planted module,
    given the lane's seed, in float64 and in bfloat16, and the bfloat16
    fold fails the score limit."""
    from bench import control

    bench = _planted_bench(tmp_path)
    refs, sampled = _record_reference_and_lanes(monkeypatch)
    got = control.readings(bench, "tiny-ref.streams-open", SEED, 1.0)
    [planted] = refs
    assert planted is not reference
    assert planted.FOLDS == [(harness.POOL_SEED + lane, p)
                             for lane in sampled[0]
                             for p in ("float64", "bfloat16")]
    limits = bench.config("tiny-ref")["limits"]
    assert got["score_gap"] > limits["score_gap"]


def test_write_errors_fail_the_plain_reference():
    """The guard behind the reference seam: a fleet whose TOS macro runs at
    0.6 V with its write errors injected, checked by the plain reference
    (which models no errors), is not correct."""
    bench = harness.Bench(ROOT)
    det = {**bench.config("gen4-720p-16")["detector"], "vdd": 0.6,
           "inject_ber": True}
    opts = harness.Options("gen4-720p-16.streams-open", SEED, 1.0,
                           drain_s=5.0)
    out = harness.run_cell(bench, opts, time.perf_counter(),
                           config_override={**TINY, "detector": det},
                           cell_override={"rate_eps": 16384})
    assert not out["correct"]
    assert out["checks"]["tos_mismatch"]["value"] > 0


def _stub_pool(monkeypatch) -> list:
    """Replace ``DetectorPool`` by a stub; returns the list its calls
    (positional and keyword arguments) are recorded in."""
    import repro.serve

    calls = []

    class Stub:
        def __init__(self, *args, **kwargs):
            calls.append((args, kwargs))

        def warmup(self, xy, ts):
            pass

        def connect(self):
            return 0

    monkeypatch.setattr(repro.serve, "DetectorPool", Stub)
    return calls


@pytest.mark.parametrize("cell", sorted(FIRST_CELLS))
def test_first_cells_keep_the_plain_reference_and_pool(monkeypatch, cell):
    """The first cells' configurations name no reference:
    ``bench.reference`` checks them, and the pool is built as before
    (``seed=0`` is ``DetectorPool``'s default)."""
    bench = harness.Bench(ROOT)
    name, chips = FIRST_CELLS[cell]
    wl = bench.workload(cell)
    assert (wl["config"], wl["chips"]) == (name, chips)
    config = bench.config(name)
    assert bench.reference_of(config) is reference
    calls = _stub_pool(monkeypatch)
    harness.build_pool(config, chips)
    assert calls == [((harness.pipeline_config(config), config["capacity"]),
                      {"seed": 0, "shard": chips > 1})]


def test_a_named_reference_must_exist_and_fold(tmp_path):
    (tmp_path / "bench").mkdir()
    (tmp_path / "BENCHMARK.json").write_text("{}")
    (tmp_path / "bench/half_ref.py").write_text("def run_lane(): pass\n")
    bench = harness.Bench(tmp_path)
    with pytest.raises(FileNotFoundError):
        bench.reference_of({"reference": "no_such_ref"})
    with pytest.raises(ValueError, match="Detector, lane_state, compare_lane"):
        bench.reference_of({"reference": "half_ref"})
    with pytest.raises(ValueError, match="not a module name"):
        bench.reference_of({"reference": "../reference"})


def _davis(**detector) -> dict:
    config = _json(ROOT / "bench/configs/davis240-1024.json")
    config["detector"] = {**config["detector"], **detector}
    return config


def test_dvfs_cfg_is_built():
    from repro.core.dvfs import DvfsConfig

    cfg = harness.pipeline_config(_davis(
        dvfs=True, dvfs_online=True,
        dvfs_cfg={"tw_us": 8000, "vdd_floor": 0.6}))
    assert cfg.dvfs_cfg == DvfsConfig(tw_us=8000, vdd_floor=0.6)
    assert cfg.dvfs_cfg.half_us == 4000


@pytest.mark.parametrize("detector", [{"no_such_key": 1},
                                      {"dvfs_cfg": {"no_such_key": 1}}])
def test_unknown_detector_keys_raise(detector):
    with pytest.raises(TypeError, match="no_such_key"):
        harness.pipeline_config(_davis(**detector))


def test_lane_seed_is_the_lane_id():
    """What ``lane_seed`` rests on: a pool built by the harness gives lane
    ``l`` the random key of ``POOL_SEED + l`` once it is connected."""
    import jax

    config = {**_json(ROOT / "bench/configs/davis240-1024.json"), **TINY}
    pool = harness.build_pool(config, 1)
    try:
        keys = np.asarray(pool._states.key)
    finally:
        pool.close()
    want = [np.asarray(jax.random.PRNGKey(harness.POOL_SEED + lane))
            for lane in range(config["capacity"])]
    assert harness.POOL_SEED == 0
    np.testing.assert_array_equal(keys, np.stack(want))


def test_window_deltas_cover_every_number():
    before = {"a": 1, "b": 0.5, "flag": False, "mode": "async", "n": {"x": 1}}
    after = {"a": 4, "b": 2.0, "flag": True, "mode": "async", "n": {"x": 2}}
    assert harness.window_deltas(before, after) == {"a": 3, "b": 1.5}


def _row(rate, slope, chunk=256):
    return {"rate_eps": rate, "backlog_slope_chunks_per_s": slope,
            "offered_chunks_per_s": rate / chunk}


def test_knee_is_the_last_flat_backlog():
    # 200,000 events/s offer 781 chunks/s: 0.5% of them over 15 s is 58.6
    rows = [_row(175_000, -5.2), _row(200_000, 3.9), _row(225_000, 4.3),
            _row(250_000, 7.3), _row(275_000, 1.0)]
    assert harness.knee(rows, 15.0) == 225_000
    assert harness.knee(rows[:1] + [_row(200_000, 4.0)], 15.0) == 175_000
    # at a low rate one chunk is the least growth the backlog can show
    low = [_row(800, 0.04), _row(1000, 0.06)]
    assert harness.knee(low, 20.0) == 800
    assert harness.knee(low[1:], 20.0) is None


def _run(cwd, env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_run_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert _no_result(p.stdout)


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths
    (no program) exits non-zero with no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in _json(ROOT / "BENCHMARK.json")["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_benchmark_json_names_existing_files():
    spec = _json(ROOT / "BENCHMARK.json")
    bench = harness.Bench(ROOT)
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        bench.cell(w["name"])
        mix = bench.mix(w["traffic"])
        assert (ROOT / "bench/traffic" / f"{mix['kind']}.py").is_file()
        ends = [m["name"] for m in bench.metrics(w["name"], False)]
        assert "setup_s" in ends and len(ends) >= 2
        assert bench.metrics(w["name"], True)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(bench.reader(m["name"]), "read"), m["name"]


def test_sample_lanes_takes_the_heaviest():
    w = np.array([0, 5, 1, 9, 0, 2])
    got = harness.sample_lanes(w, 3, 2**31 + 1)
    assert 3 in got and len(got) == 3 and 0 not in got and 4 not in got
    assert got == harness.sample_lanes(w, 3, 2**31 + 1)
