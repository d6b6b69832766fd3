"""The program-side trace reduction (``bench/program_trace.py``): the
named scopes of the pool's executors, ``pool.*`` span self times and the
naming of idle gaps, on hand-made traces and on a small trace recorded on
a TPU v5e."""
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import program_trace as pt
from bench import trace
from repro.core import pipeline
from repro.core import state as state_mod
from repro.serve import DetectorPool

DATA = pathlib.Path(__file__).resolve().parent / "data"
HEAVY = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = .*?\s"
                   r"(scatter|sort|reduce-window|convolution)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


# -- named scopes in the executors ---------------------------------------------


def _executor_hlo(pool, single: bool) -> str:
    """The compiled HLO text of the pool's K-block or 1-round executor."""
    rt = pool._rt
    b = rt.buckets[0]
    k, lanes = rt._ring_rounds, rt._phys
    sds = jax.ShapeDtypeStruct
    lead = () if single else (k,)
    chunks = state_mod.ChunkInput(
        xy=sds(lead + (lanes, b, 2), jnp.int32),
        ts=sds(lead + (lanes, b), jnp.int32),
        valid=sds(lead + (lanes, b), bool),
        ber=sds(lead + (lanes,), jnp.float32),
        energy_coef=sds(lead + (lanes,), jnp.float32),
        latency_coef=sds(lead + (lanes,), jnp.float32))
    args = [rt._states, rt._rings[b], chunks, sds(lead + (lanes,), bool),
            sds(lead + (lanes,), jnp.int32)]
    if single:
        return rt._exec1[b].lower(*args).compile().as_text()
    return rt._exec[b].lower(*args, sds((k,), bool)).compile().as_text()


@pytest.mark.parametrize("single", [False, True], ids=["k_block", "one_round"])
@pytest.mark.parametrize("variant", ["dense", "compact", "dvfs_online"])
def test_heavy_ops_carry_a_named_scope(variant, single):
    """Every scatter, sort, reduce-window and convolution the program
    traced (those with an ``op_name``; the CPU compiler's own splits of a
    reduction carry none) sits under one of the reduction's scopes."""
    extra = {"dvfs": True, "dvfs_online": True} if variant == "dvfs_online" \
        else {}
    cfg = pipeline.PipelineConfig(height=24, width=32, chunk=64, **extra)
    pool = DetectorPool(cfg, capacity=2, ring_rounds=3,
                        readout="compact" if variant == "compact" else "dense")
    text = _executor_hlo(pool, single)
    pool.close()
    seen = set()
    for line in text.splitlines():
        if not HEAVY.match(line):
            continue
        m = OP_NAME.search(line)
        if m is None or not m.group(1):
            continue
        scope = pt.scope_of(m.group(1))
        assert scope in pt.SCOPES, line.strip()[:300]
        seen.add(scope)
    assert {"stcf", "tos_update"} <= seen
    if variant == "compact":
        assert "compact" in seen


def test_scope_of_takes_the_innermost_scope():
    assert pt.scope_of("jit(block)/while/body/closed_call/cond/branch_1_fun/"
                       "vmap(tos_update)/jit(tos_update_batched)/scatter-add"
                       ) == "tos_update"
    assert pt.scope_of("jit(single)/ring_push/compact/vmap()/scatter") \
        == "compact"
    assert pt.scope_of("jit(block)/while/body/vmap(lut_refresh)/cond/"
                       "branch_1_fun/mul") == "lut_refresh"
    assert pt.scope_of("jit(block)/while/body/add") == pt.OTHER
    assert pt.scope_of("") == pt.OTHER
    assert pt.scope_of("jit(tos_update_batched)/add") == pt.OTHER


# -- hand-made traces ------------------------------------------------------------


def _planes(dev_ops, host_lines, window=(0, 200)):
    host = [{"name": n, "events": ev} for n, ev in host_lines.items()]
    host.append({"name": "bench", "events": [["bench.window", *window]]})
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops", "events": dev_ops}]},
            {"name": "/host:CPU", "lines": host}]


def _hlo_named(planes):
    """The same planes as ``bench.trace.load`` gives them (no op_name)."""
    return [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
        for ln in p["lines"]]} for p in planes]


def test_span_self_time_and_gap_names():
    dev = [["while", 0, 100, "jit(block)/while", "jit_block"],
           ["fusion.1", 10, 40, "jit(block)/while/body/vmap(tos_update)/x",
            "jit_block"],
           ["fusion.2", 50, 90, "jit(block)/while/body/vmap(lut_refresh)/y",
            "jit_block"],
           ["copy", 120, 130, "", ""]]
    host = {
        # the pump: a pass whose stage holds a nested lock wait, then a
        # dispatch; the reader: a fetch during the first idle gap
        "pump": [["pool.pump_pass", 95, 200], ["pool.stage", 100, 119],
                 ["pool.lock_wait", 105, 110], ["pool.dispatch", 119, 125]],
        "reader": [["pool.fetch", 100, 102], ["pool.distribute", 130, 150]],
    }
    red = pt.reduce(_planes(dev, host))
    assert red["scope_self_s"] == pytest.approx(
        {"other": 40e-9, "tos_update": 30e-9, "lut_refresh": 40e-9})
    assert dict(red["device_scopes"]) == red["scope_self_s"]
    assert [v for _, v in red["device_scopes"]] == sorted(
        red["scope_self_s"].values(), reverse=True)
    assert red["span_self_s"] == pytest.approx({
        "pool.pump_pass": (105 - 19 - 6) * 1e-9, "pool.stage": 14e-9,
        "pool.lock_wait": 5e-9, "pool.dispatch": 6e-9,
        "pool.fetch": 2e-9, "pool.distribute": 20e-9})
    # gaps [130, 200) and [100, 120): the first is the pass's own time
    # throughout (the reader's distribute covers 20 of its 70), the
    # second mostly the stage's (14, against 5 of lock wait, 2 of fetch)
    assert red["idle_gaps"] == [["pool.pump_pass", pytest.approx(70e-9)],
                                ["pool.stage", pytest.approx(20e-9)]]
    # the same device busy time as the HLO-named reduction
    assert sum(red["scope_self_s"].values()) == pytest.approx(
        trace.reduce(_hlo_named(_planes(dev, host)))["busy_s"])


def test_a_program_without_names_reduces_to_nothing_named():
    """The parent program: no pool.* span, no scope in any op_name."""
    dev = [["fusion.1", 10, 40, "jit(block)/while/body/scatter-add",
            "jit_block"],
           ["fusion.2", 60, 90, "", "jit_block"]]
    red = pt.reduce(_planes(dev, {"t": [["bench.pump", 0, 200]]}))
    assert red["span_self_s"] == {}
    assert red["scope_self_s"] == pytest.approx({"other": 60e-9})
    assert [g[0] for g in red["idle_gaps"]] == ["no_span"] * 3
    with pytest.raises(ValueError):
        pt.reduce([_planes(dev, {})[0]])


# A compiled module in the shape the TPU compiler leaves it: the fusion
# that carries a scatter and the sort feeding it have no op_name of their
# own; a copy feeds the sort; the loop bookkeeping belongs to no scope.
HLO = "\n".join([
    "HloModule jit_block, is_scheduled=true",
    "",
    "%fused_computation.10 (param_0: s32[4], param_1: s32[4]) -> s32[4] {",
    "  %param_0 = s32[4]{0} parameter(0)",
    "  %param_1 = s32[4]{0} parameter(1)",
    "  ROOT %scatter.1 = s32[4]{0} scatter(%param_0, %param_1, %param_1), "
    "to_apply=%add, "
    'metadata={op_name="jit(block)/vmap(tos_update)/scatter-add"}',
    "}",
    "",
    "%fused_computation.9 (param_0.1: f32[4]) -> f32[4] {",
    "  %param_0.1 = f32[4]{0} parameter(0)",
    "  ROOT %mul.3 = f32[4]{0} multiply(%param_0.1, %param_0.1), "
    'metadata={op_name="jit(block)/vmap(lut_refresh)/jit(harris)/mul:"}',
    "}",
    "",
    "ENTRY %main.5 (p0: s32[4], p1: s32[4], p2: f32[4]) -> "
    "(s32[4], f32[4]) {",
    "  %p0 = s32[4]{0} parameter(0)",
    "  %p1 = s32[4]{0} parameter(1)",
    "  %p2 = f32[4]{0} parameter(2)",
    "  %copy.2 = s32[4]{0} copy(%p1)",
    "  %sort = s32[4]{0} sort(%copy.2), dimensions={0}, to_apply=%lt",
    "  %fusion.10 = s32[4]{0} fusion(%p0, %sort), kind=kLoop, "
    "calls=%fused_computation.10",
    "  %fusion.9 = f32[4]{0} fusion(%p2), kind=kLoop, "
    "calls=%fused_computation.9",
    '  %add.7 = s32[] add(%p0, %p0), metadata={op_name="jit(block)/while/add"}',
    "  ROOT %tuple = (s32[4]{0}, f32[4]{0}) tuple(%fusion.10, %fusion.9)",
    "}",
])


def test_hlo_scopes_name_what_the_compiler_made():
    got = pt.hlo_scopes([HLO])
    assert set(got) == {"jit_block"}
    names = got["jit_block"]
    assert names["fusion.10"] == "tos_update"       # by what is fused in
    assert names["sort"] == "tos_update"            # by its user
    assert names["copy.2"] == "tos_update"          # through the chain
    assert names["fusion.9"] == "lut_refresh"
    for bookkeeping in ("add.7", "tuple", "p0"):
        assert bookkeeping not in names
    # in a trace, an op with no op_name takes the module's name for it
    dev = [["fusion.10", 0, 30, "", "jit_block"],
           ["sort", 30, 40, "", "jit_block"],
           ["fusion.10", 50, 60, "", "jit_other"],
           ["fusion.9", 60, 100, "", "jit_block"]]
    red = pt.reduce(_planes(dev, {}), hlo=got)
    assert red["scope_self_s"] == pytest.approx(
        {"tos_update": 40e-9, "other": 10e-9, "lut_refresh": 40e-9})


def test_executor_hlo_names_every_executor_fusion():
    """On the pool's own executors, compiled here: every fusion, scatter
    and sort of the executor's loop, branches and entry gets a scope."""
    from repro.events import synthetic

    cfg = pipeline.PipelineConfig(height=24, width=32, chunk=64)
    pool = DetectorPool(cfg, capacity=2, ring_rounds=3, readout="compact")
    st = synthetic.ramp_stream([600], 20_000, height=24, width=32, seed=1)
    lanes = [pool.connect() for _ in range(2)]
    for lane in lanes:
        pool.feed(lane, st.xy, st.ts)
    pool.pump()                                     # K-block executor
    for lane in lanes:
        pool.feed(lane, st.xy[:64], st.ts[:64] + 10**6)
    pool.pump()                                     # 1-round executor
    texts = pt.executor_hlo(pool)
    assert pool.executors_compiled_once()
    pool.close()
    assert pt.executor_hlo() == texts               # past the pool's close
    assert [t.split(",")[0] for t in texts] == ["HloModule jit_block",
                                                "HloModule jit_single"]
    got = pt.hlo_scopes(texts)
    for text in texts:
        module = text.split(None, 2)[1].rstrip(",")
        comp = None
        for line in text.splitlines():
            m = pt._HLO_INST.match(line)
            if m is None:
                c = pt._HLO_COMP.match(line)
                comp = c.group(1) if c else comp
                continue
            name, rest = m.groups()
            if re.match(r"(fused|wrapped|region)", comp):
                continue                # inside a fusion or a reducer
            op = pt._HLO_OPCODE.search(rest).group(1)
            if op in ("fusion", "scatter", "sort"):
                assert got[module].get(name) in pt.SCOPES, line[:200]
    assert np.isin(["tos_update", "stcf", "lut_refresh", "compact"],
                   list(got["jit_block"].values())).all()
    assert pt.executor_hlo(object()) == []          # a program without it


def test_device_share_readers_read_the_run_trace_once(monkeypatch, tmp_path):
    """The ``*_device_share`` readers reduce the run's trace (found in the
    trace directory, named through the executors' HLO) once per run, and
    read nothing in an untraced run or where no trace was written."""
    from bench import harness

    dev = [["fusion.10", 0, 30, "", "jit_block"],
           ["sort", 30, 40, "", "jit_block"],
           ["fusion.9", 60, 100, "jit(block)/vmap(lut_refresh)/mul",
            "jit_block"],
           ["copy", 120, 130, "", "jit_block"]]
    loads = []

    def load(path):
        loads.append(path)
        return _planes(dev, {})

    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(pt, "load", load)
    monkeypatch.setattr(pt, "executor_hlo", lambda pool=None: [HLO])
    bench = harness.Bench()
    tos = bench.reader("tos_update_device_share.open")
    lut = bench.reader("lut_refresh_device_share.sat")
    ctx = {"trace": trace.reduce(_hlo_named(_planes(dev, {})))}
    busy = ctx["trace"]["busy_s"]
    assert busy == pytest.approx(90e-9)
    assert tos.read(ctx) == pytest.approx(100 * 40e-9 / busy)
    assert lut.read(ctx) == pytest.approx(100 * 40e-9 / busy)
    assert loads == [str(tmp_path)]
    assert ctx["program"]["scope_self_s"]["other"] == pytest.approx(10e-9)

    assert tos.read({"trace": None}) is None
    assert loads == [str(tmp_path)]
    monkeypatch.undo()
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)       # holds no trace
    assert lut.read({"trace": ctx["trace"]}) is None


def test_load_reads_spans_and_their_threads(tmp_path):
    """A CPU trace taken the way a traced run takes it: ``pool.*`` spans
    (with their args), each on its thread's line, and the window span;
    other host events are left out."""
    from bench import harness
    from repro import obs

    harness.start_trace(tmp_path)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with obs.span("stage", block=3, bucket=256):
                with obs.span("lock_wait"):
                    jnp.ones(8).sum().block_until_ready()
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    planes = pt.load(str(tmp_path))
    names = [e[0] for p in planes for ln in p["lines"] for e in ln["events"]]
    assert sorted(names) == ["bench.window", "pool.lock_wait", "pool.stage"]
    got = {e[0]: e for p in planes for ln in p["lines"] for e in ln["events"]}
    assert got["pool.stage"][1] <= got["pool.lock_wait"][1] \
        <= got["pool.lock_wait"][2] <= got["pool.stage"][2]


# -- a trace recorded on the chip ---------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    return json.loads(
        (DATA / "tpu_v5e_720p_open_spans_10ms.json").read_text())


def test_recorded_trace_scopes(recorded):
    """Device time by scope: the self times of the ops carrying each scope
    (by their op_name, else by the executors' compiled HLO), summing to the
    device's busy time.  On the TPU the ops that carry the TOS scatter have
    no op_name: without the HLO they fall to ``other``."""
    planes, hlo = recorded["planes"], recorded["hlo"]
    red = pt.reduce(planes, hlo=hlo)
    ops = [e for p in planes if p["name"].startswith("/device:")
           for ln in p["lines"] for e in ln["events"]]
    # every nanosecond belongs to the innermost op running then (a loop
    # holds its body): paint the ops from the longest to the shortest
    owner = np.full(10_000_000, -1, np.int64)
    ops.sort(key=lambda ev: ev[1] - ev[2])
    for i, (_, s, e, _, _) in enumerate(ops):
        owner[max(0, int(s)):max(0, min(10_000_000, int(e)))] = i
    by = {}
    for i, n in enumerate(np.bincount(owner[owner >= 0], minlength=len(ops))):
        name, _, _, op, module = ops[i]
        sc = pt.scope_of(op)
        if sc == pt.OTHER:
            sc = hlo.get(module, {}).get(name, pt.OTHER)
        if n:
            by[sc] = by.get(sc, 0) + n * 1e-9
    assert red["scope_self_s"] == pytest.approx(by)
    assert {"tos_update", "lut_refresh", "stcf"} <= set(by)
    assert sum(by.values()) == pytest.approx(
        trace.reduce(_hlo_named(planes))["busy_s"])
    bare = pt.reduce(planes)["scope_self_s"]
    assert bare["other"] > 10 * red["scope_self_s"]["other"]
    assert bare["tos_update"] < red["scope_self_s"]["tos_update"]


def test_recorded_trace_gap_names(recorded):
    """Each idle gap is named by a pool.* span: with the spans on, the
    reader sat in its device_get through every gap of this piece."""
    planes = recorded["planes"]
    red = pt.reduce(planes, hlo=recorded["hlo"])
    spans = {e[0] for p in planes if not p["name"].startswith("/device:")
             for ln in p["lines"] for e in ln["events"]}
    assert red["idle_gaps"]
    for name, sec in red["idle_gaps"]:
        assert name in spans and name.startswith("pool."), name
        assert sec > 0
    assert set(red["span_self_s"]) <= spans
    assert {"pool.pump_pass", "pool.stage", "pool.dispatch",
            "pool.fetch"} <= spans
