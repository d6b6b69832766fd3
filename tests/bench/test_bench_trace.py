"""The trace-to-metrics reduction, on a small trace recorded on a TPU v5e
and on hand-made cases."""
import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import harness
from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "tpu_v5e_720p_open_10ms.json").read_text())


def _timeline(planes, t1):
    busy = np.zeros(int(t1), bool)
    for p in planes:
        if p["name"].startswith("/device:"):
            for ln in p["lines"]:
                for _, s, e in ln["events"]:
                    busy[max(0, int(s)):max(0, min(int(t1), int(e)))] = True
    return busy


def test_recorded_trace_busy_and_idle(recorded):
    planes = recorded["planes"]
    red = trace.reduce(planes)
    busy = _timeline(planes, 10_000_000)
    assert red["window_s"] == pytest.approx(0.01)
    assert red["busy_s"] == pytest.approx(busy.sum() * 1e-9, abs=1e-12)
    # the gaps: runs of idle nanoseconds, longest first
    edges = np.flatnonzero(np.diff(np.concatenate([[1], busy, [1]]).astype(int)))
    runs = sorted((edges[1::2] - edges[0::2]) * 1e-9, reverse=True)[:10]
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(runs, abs=1e-12)
    assert 0 < red["busy_s"] < red["window_s"]


def test_recorded_trace_ops_and_gap_names(recorded):
    planes = recorded["planes"]
    red = trace.reduce(planes)
    ops = [e for p in planes if p["name"].startswith("/device:")
           for ln in p["lines"] for e in ln["events"]]
    by = {}
    for name, s, e in ops:        # no op of this piece holds another
        d = min(e, 1e7) - max(s, 0)
        by[trace.short_name(name)] = by.get(trace.short_name(name), 0) + d
    top = sorted(by.items(), key=lambda kv: -kv[1])[:10]
    assert [k for k, _ in red["device_ops"]] == [k for k, _ in top]
    assert [v for _, v in red["device_ops"]] == pytest.approx(
        [v * 1e-9 for _, v in top])
    # every op's self time is there for a reader, not only the top ten
    assert len(by) > 10
    assert red["op_self_s"] == pytest.approx({k: v * 1e-9 for k, v in by.items()})
    spans = trace.host_spans(planes)
    for name, _ in red["idle_gaps"]:
        assert name == "no_span" or name in {s[0] for s in spans}


def test_nested_ops_count_self_time():
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%while = (...)", 0, 100], ["%fusion.a = f32[]", 10, 40],
            ["%fusion.b = f32[]", 50, 90], ["%copy = u8[]", 120, 130]]}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench.window", 0, 200], ["bench.pump", 95, 125],
            ["bench.poll", 130, 200], ["other", 0, 200]]}]},
    ]
    red = trace.reduce(planes)
    assert red["busy_s"] == pytest.approx(110e-9)
    assert dict(red["device_ops"]) == pytest.approx(
        {"while": 30e-9, "fusion.a": 30e-9, "fusion.b": 40e-9, "copy": 10e-9})
    assert red["idle_gaps"] == [["bench.poll", pytest.approx(70e-9)],
                                ["bench.pump", pytest.approx(20e-9)]]


def test_no_window_or_no_device_is_refused():
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                               "events": [["%x", 0, 1]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "t", "events": [
        ["bench.window", 0, 10]]}]}
    with pytest.raises(ValueError):
        trace.reduce([dev])
    with pytest.raises(ValueError):
        trace.reduce([host])


def test_load_reads_a_profiler_trace(tmp_path):
    """A CPU trace taken the way a traced run takes it: the window span is
    found; with no device plane the reduction refuses."""
    import jax
    import jax.numpy as jnp

    harness.start_trace(tmp_path)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    planes = trace.load(str(tmp_path))
    assert [s[0] for s in trace.host_spans(planes)] == ["bench.window"]
    with pytest.raises(ValueError, match="device"):
        trace.reduce(planes)
