"""Unit tests for the metrics registry and sink layer (``repro.obs``)."""
import json
import threading

import pytest

from repro import obs
from repro.obs import read_jsonl
from repro.obs import schema as obs_schema


# -- registry ---------------------------------------------------------------

def test_counter_inc_and_value():
    reg = obs.MetricsRegistry(namespace="t")
    c = reg.counter("hits", "hits seen")
    assert c.value() == 0
    c.inc()
    c.inc(2)
    c.inc(0.5)  # time accumulators increment by float
    assert c.value() == 3.5


def test_counter_rejects_negative_increment():
    reg = obs.MetricsRegistry(namespace="t")
    c = reg.counter("hits", "hits seen")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_and_add_both_directions():
    reg = obs.MetricsRegistry(namespace="t")
    g = reg.gauge("depth", "ring depth")
    g.set(4)
    g.add(2)
    g.add(-5)
    assert g.value() == 1


def test_declare_once_returns_same_metric():
    reg = obs.MetricsRegistry(namespace="t")
    a = reg.counter("hits", "hits seen")
    b = reg.counter("hits", "hits seen")
    a.inc(3)
    assert b.value() == 3


def test_kind_mismatch_raises():
    reg = obs.MetricsRegistry(namespace="t")
    reg.counter("hits", "hits seen")
    with pytest.raises(ValueError):
        reg.gauge("hits", "hits seen")


def test_labelled_handles_are_independent():
    reg = obs.MetricsRegistry(namespace="t")
    m = reg.counter("slots", "uploaded slots", labels=("bucket",))
    a = m.labels(bucket=64)
    b = m.labels(bucket=256)
    a.inc(10)
    b.inc(1)
    assert a.value() == 10 and b.value() == 1
    assert m.labels(bucket=64) is a


def test_snapshot_keys():
    reg = obs.MetricsRegistry(namespace="t")
    reg.counter("hits", "hits seen").inc(2)
    m = reg.counter("slots", "slots", labels=("bucket",))
    m.labels(bucket=64).inc(5)
    snap = reg.snapshot()
    assert snap["hits"] == 2
    assert snap["slots{bucket=64}"] == 5


def test_histogram_percentile_and_prom_buckets():
    reg = obs.MetricsRegistry(namespace="t")
    h = reg.histogram("lat", "latency s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 2.0):
        h.observe(v)
    assert h.value() == 4  # count
    assert h.percentile(50) == pytest.approx(0.5)
    assert h.percentile(100) == pytest.approx(2.0)


def test_timer_is_monotonic_nondecreasing():
    a = obs.timer()
    b = obs.timer()
    assert b >= a


# -- sinks ------------------------------------------------------------------

def test_jsonl_sink_round_trip(tmp_path):
    path = tmp_path / "m.jsonl"
    reg = obs.MetricsRegistry(namespace="t")
    reg.counter("hits", "hits seen").inc(7)
    sink = obs.JsonlSink(str(path))
    reg.attach(sink)
    reg.emit("periodic")
    reg.emit("final", extra={"note": "done"})
    sink.close()
    recs = read_jsonl(str(path))
    assert [r["kind"] for r in recs] == ["periodic", "final"]
    assert recs[0]["metrics"]["hits"] == 7
    assert recs[1]["note"] == "done"
    assert all(r["namespace"] == "t" for r in recs)


def test_jsonl_sink_concurrent_writers(tmp_path):
    """Records from racing threads must land whole — one JSON object per
    line, none torn or interleaved."""
    path = tmp_path / "m.jsonl"
    sink = obs.JsonlSink(str(path))
    n_threads, n_each = 8, 50

    def worker(tid):
        for i in range(n_each):
            sink.emit({"kind": "w", "tid": tid, "i": i,
                       "pad": "x" * 256})

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    recs = read_jsonl(str(path))
    assert len(recs) == n_threads * n_each
    seen = {(r["tid"], r["i"]) for r in recs}
    assert len(seen) == n_threads * n_each


def test_prom_sink_exposition_golden(tmp_path):
    path = tmp_path / "metrics.prom"
    reg = obs.MetricsRegistry(namespace="pool")
    reg.counter("hits", "hits seen").inc(3)
    m = reg.counter("slots", "uploaded slots", labels=("bucket",))
    m.labels(bucket=64).inc(5)
    g = reg.gauge("depth", "ring depth")
    g.set(2)
    sink = obs.PromSink(str(path), reg)
    reg.attach(sink)
    reg.emit("final")
    text = open(path).read()
    assert "# HELP pool_hits hits seen" in text
    assert "# TYPE pool_hits counter" in text
    assert "pool_hits 3" in text
    assert 'pool_slots{bucket="64"} 5' in text
    assert "# TYPE pool_depth gauge" in text
    assert "pool_depth 2" in text


def test_prom_sink_histogram_exposition(tmp_path):
    path = tmp_path / "metrics.prom"
    reg = obs.MetricsRegistry(namespace="pool")
    h = reg.histogram("lat", "latency s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    obs.PromSink(str(path), reg).emit({"kind": "final"})
    text = open(path).read()
    assert '# TYPE pool_lat histogram' in text
    assert 'pool_lat_bucket{le="0.1"} 1' in text
    assert 'pool_lat_bucket{le="1.0"} 2' in text
    assert 'pool_lat_bucket{le="+Inf"} 3' in text
    assert 'pool_lat_count 3' in text


def test_composite_sink_isolates_faults(tmp_path):
    """One failing sink must not starve the others, and the failure is
    recorded rather than raised into the hot path."""
    path = tmp_path / "m.jsonl"

    class Boom:
        def emit(self, record):
            raise RuntimeError("boom")

        def close(self):
            raise RuntimeError("boom on close")

    good = obs.JsonlSink(str(path))
    errors = []
    comp = obs.CompositeSink(
        [Boom(), good],
        on_error=lambda sink, e: errors.append(type(e).__name__))
    comp.emit({"kind": "x", "v": 1})
    comp.emit({"kind": "x", "v": 2})
    comp.close()
    recs = read_jsonl(str(path))
    assert [r["v"] for r in recs] == [1, 2]
    assert errors == ["RuntimeError"]  # reported once, not per emit
    assert 0 in comp.errors and "boom" in comp.errors[0]


def test_log_sink_field_filter():
    lines = []
    reg = obs.MetricsRegistry(namespace="t")
    reg.counter("pump_stages", "stages").inc(4)
    reg.counter("unrelated", "noise").inc(9)
    reg.attach(obs.LogSink(write=lines.append, fields=("pump_stages",)))
    reg.emit("periodic")
    assert len(lines) == 1
    assert "pump_stages=4" in lines[0]
    assert "unrelated" not in lines[0]


# -- schema -----------------------------------------------------------------

def test_schema_tables_cover_wall_time_keys():
    for k in obs_schema.WALL_TIME_KEYS:
        assert k in obs_schema.LANE_STATS or k in obs_schema.POOL_STATS, k


def test_stats_reference_table_renders_every_export():
    table = obs_schema.stats_reference_table()
    for t in (obs_schema.LANE_STATS, obs_schema.POOL_STATS,
              obs_schema.POOL_BUCKET_STATS, obs_schema.SESSION_STATS):
        for k in t:
            assert k in table, k


def test_emit_record_is_json_serializable():
    reg = obs.MetricsRegistry(namespace="t")
    reg.counter("hits", "hits seen").inc(1)
    rec = reg.emit("final", extra={"scheduler": {"policy": "static"}})
    json.dumps(rec)
    assert rec["metrics"]["hits"] == 1
    assert rec["scheduler"]["policy"] == "static"


# -- executor notes -----------------------------------------------------------

def test_executor_notes_outlive_their_owner():
    """A runtime notes each executor once, at its first call; the newest
    notes give the compiled HLO after the owner is gone."""
    import gc

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    g = jax.jit(lambda x, y: x + y)
    x = jnp.ones(4)
    notes = obs.ExecutorNotes()
    notes.note(("b", True), g, (x, x))
    notes.note(("a", False), f, (x,))
    notes.note(("a", False), f, (jnp.ones(8),))      # noted already
    texts = notes.hlo_texts()
    assert [t.split(",")[0] for t in texts] == ["HloModule jit__lambda"] * 2
    assert "f32[4]" in texts[0] and "multiply" in texts[0]
    assert "add" in texts[1]
    assert obs.latest_hlo_texts() == texts
    del notes
    gc.collect()
    assert obs.latest_hlo_texts() == texts
    other = obs.ExecutorNotes()
    assert obs.latest_hlo_texts() == texts          # nothing noted yet
    other.note("c", f, (jnp.ones(2),))
    assert len(obs.latest_hlo_texts()) == 1
