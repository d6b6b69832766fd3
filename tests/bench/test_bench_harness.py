"""The harness is driven by data and refuses to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness

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


def _json(p):
    return json.loads(pathlib.Path(p).read_text())


def test_new_files_are_found_without_an_edit(tmp_path):
    """A configuration, a cell, a traffic kind and a per-layer metric added
    as new files and entries run through the harness untouched."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = _json(ROOT / "BENCHMARK.json")
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
