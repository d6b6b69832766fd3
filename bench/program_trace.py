"""Reduction of a profiler trace to the program's own names.

``bench/trace.py`` names device operations by their HLO names and idle
gaps by the benchmark's ``bench.*`` spans.  This module reads the same
``.xplane.pb`` for the names the program itself puts there:

- the ``pool.*`` host spans of ``repro.serve.runtime`` (``repro.obs.span``):
  each span's self time (its time less that of the spans nested in it on
  its thread) inside the window;
- the named scopes on the device (``jax.named_scope`` in
  ``repro.core.state`` and in the pool's executors, and the Pallas
  kernels' names): every device operation's self time, grouped by the
  innermost scope of ``SCOPES`` in its HLO ``op_name`` (the ``tf_op`` stat
  of the operation in the trace), the rest as ``other``.  The compiler
  makes some operations with no ``op_name`` (on the TPU, the fusion and
  the sort that carry the TOS scatter): ``hlo_scopes`` names those from
  the executors' compiled HLO (``executor_hlo``), by the scopes of the
  instructions fused into them, else of their users;
- the longest idle gaps between device operations, each named by the
  ``pool.*`` span whose self time covers most of it, or ``no_span``.

A trace of a program that has none of these names reduces to no span
times, all device time under ``other`` and gaps named ``no_span``.

``from_run`` gives the metric readers this reduction of a traced run of
``bench/run.py``: it reads the trace where ``run.py`` has the profiler
write it (``TRACE_DIR``) and the executors' HLO the program keeps past the
pool's close (``repro.obs.latest_hlo_texts``), once per run.

``load`` parses the file with a minimal copy of the XSpace protobuf
schema (``jax.profiler.ProfileData`` does not expose the operations'
metadata stats, where ``tf_op`` lives).
"""
from __future__ import annotations

import glob
import os
import pathlib
import re

import numpy as np

from bench import trace as trace_mod

SPAN_PREFIX = "pool."
SCOPES = ("stcf", "tos_update", "score_read", "lut_refresh", "mask_select",
          "ring_push", "compact", "fused_step")
OTHER = "other"
OP_NAME_STATS = ("tf_op", "op_name")
TRACE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".bench_out" / "trace"
MODULES_LINE = "XLA Modules"
_OP_NAME_IN_TEXT = re.compile(r'op_name="([^"]*)"')


# -- the file -------------------------------------------------------------------


def _xspace_class():
    """The ``XSpace`` message class, from the fields this module reads of
    ``tsl/profiler/protobuf/xplane.proto`` (field numbers as there; the
    maps are read as their repeated entries)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_program_trace_xplane.proto",
        package="bench_program_trace", syntax="proto3")

    def message(name, fields, parent=None):
        m = (parent.nested_type if parent else f.message_type).add(name=name)
        for fname, number, kind, type_name in fields:
            fd = m.field.add(name=fname, number=number, type=kind,
                             label=T.LABEL_OPTIONAL)
            if type_name:
                fd.label = T.LABEL_REPEATED
                fd.type_name = type_name
        return m

    pkg = ".bench_program_trace."
    message("XStat", [("metadata_id", 1, T.TYPE_INT64, None),
                      ("str_value", 5, T.TYPE_STRING, None),
                      ("ref_value", 7, T.TYPE_UINT64, None)])
    message("XEvent", [("metadata_id", 1, T.TYPE_INT64, None),
                       ("offset_ps", 2, T.TYPE_INT64, None),
                       ("duration_ps", 3, T.TYPE_INT64, None),
                       ("stats", 4, T.TYPE_MESSAGE, pkg + "XStat")])
    message("XLine", [("name", 2, T.TYPE_STRING, None),
                      ("timestamp_ns", 3, T.TYPE_INT64, None),
                      ("events", 4, T.TYPE_MESSAGE, pkg + "XEvent")])
    message("XEventMetadata", [("id", 1, T.TYPE_INT64, None),
                               ("name", 2, T.TYPE_STRING, None),
                               ("stats", 5, T.TYPE_MESSAGE, pkg + "XStat")])
    message("XStatMetadata", [("id", 1, T.TYPE_INT64, None),
                              ("name", 2, T.TYPE_STRING, None)])
    plane = message("XPlane", [
        ("name", 2, T.TYPE_STRING, None),
        ("lines", 3, T.TYPE_MESSAGE, pkg + "XLine"),
        ("event_metadata", 4, T.TYPE_MESSAGE, pkg + "XPlane.EventMeta"),
        ("stat_metadata", 5, T.TYPE_MESSAGE, pkg + "XPlane.StatMeta")])
    message("EventMeta", [("key", 1, T.TYPE_INT64, None)], plane).field.add(
        name="value", number=2, type=T.TYPE_MESSAGE,
        label=T.LABEL_OPTIONAL, type_name=pkg + "XEventMetadata")
    message("StatMeta", [("key", 1, T.TYPE_INT64, None)], plane).field.add(
        name="value", number=2, type=T.TYPE_MESSAGE,
        label=T.LABEL_OPTIONAL, type_name=pkg + "XStatMetadata")
    message("XSpace", [("planes", 1, T.TYPE_MESSAGE, pkg + "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_program_trace.XSpace"))


def _op_name(meta_name: str, stats, stat_names: dict) -> str:
    """The HLO ``op_name`` of a device operation: its ``tf_op`` stat (a
    string or a reference to an interned one), else ``op_name="..."`` in
    its HLO text, else ``""``."""
    for st in stats:
        if stat_names.get(st.metadata_id) in OP_NAME_STATS:
            return st.str_value or stat_names.get(st.ref_value, "")
    m = _OP_NAME_IN_TEXT.search(meta_name)
    return m.group(1) if m else ""


def load(path: str) -> list:
    """``[{"name", "lines": [{"name", "events": [...]}]}]`` from an
    ``.xplane.pb`` file, or the newest one under a directory: on device
    planes the ``XLA Ops`` line, each operation as ``[name, start_ns,
    end_ns, op_name, module]`` (the HLO module whose run holds it, from
    the ``XLA Modules`` line); on host planes the ``pool.*`` spans and the
    ``bench.window`` span, as ``[name, start_ns, end_ns]``, one line per
    thread."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    with open(path, "rb") as fh:
        space = _xspace_class().FromString(fh.read())
    planes = []
    for plane in space.planes:
        dev = plane.name.startswith("/device:")
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        metas = {e.key: e.value for e in plane.event_metadata}
        keep = {}
        for mid, md in metas.items():
            if dev:
                keep[mid] = (trace_mod.short_name(md.name),
                             _op_name(md.name, md.stats, stat_names))
            elif (md.name.startswith(SPAN_PREFIX)
                  or md.name == trace_mod.WINDOW_SPAN):
                keep[mid] = (md.name, None)
        lines, modules = [], []
        for line in plane.lines:
            if dev and line.name == MODULES_LINE:
                t0 = float(line.timestamp_ns)
                modules = sorted(
                    (t0 + ev.offset_ps * 1e-3,
                     t0 + (ev.offset_ps + ev.duration_ps) * 1e-3,
                     metas[ev.metadata_id].name.split("(")[0])
                    for ev in line.events)
                continue
            if dev and line.name != trace_mod.OPS_LINE:
                continue
            t0 = float(line.timestamp_ns)
            events = []
            for ev in line.events:
                k = keep.get(ev.metadata_id)
                if k is None:
                    continue
                s = t0 + ev.offset_ps * 1e-3
                e = s + ev.duration_ps * 1e-3
                if dev:
                    op = k[1] or _op_name("", ev.stats, stat_names)
                    events.append([k[0], s, e, op, ""])
                else:
                    events.append([k[0], s, e])
            if events:
                lines.append({"name": line.name, "events": events})
        if dev:
            _tag_modules(lines, modules)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _tag_modules(lines: list, modules: list) -> None:
    """Give each device operation the module whose run holds its start."""
    for ln in lines:
        ops = sorted(ln["events"], key=lambda ev: ev[1])
        i = 0
        for ev in ops:
            while i < len(modules) and modules[i][1] <= ev[1]:
                i += 1
            if i < len(modules) and modules[i][0] <= ev[1]:
                ev[4] = modules[i][2]


# -- names from the compiled program ----------------------------------------------

_HLO_COMP = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)")
_HLO_INST = re.compile(r"^\s+(?:ROOT\s+)?%(\S+) = (.*)$")
_HLO_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_HLO_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%([^\s,)}]+)|branch_computations=\{([^}]*)\}")
_HLO_REF = re.compile(r"%([^\s,()}]+)")
# ops that only hold or route others' values: they name no scope's work
_CONTAINERS = {"while", "conditional", "call", "tuple", "parameter",
               "get-tuple-element", "constant"}


def executor_hlo(pool=None) -> list:
    """The compiled HLO text of the executors, where the program exposes
    it: ``pool``'s (``DetectorPool.executor_hlo``), or without a pool those
    of the runtime that ran an executor last, closed or not
    (``repro.obs.latest_hlo_texts``); else none."""
    if pool is not None:
        fn = getattr(pool, "executor_hlo", None)
    else:
        import repro.obs

        fn = getattr(repro.obs, "latest_hlo_texts", None)
    return fn() if callable(fn) else []


def hlo_scopes(texts: list) -> dict:
    """``{module: {instruction: scope}}`` from compiled HLO texts: an
    instruction's own ``op_name`` scope, else the scope most of the
    instructions fused into it carry, else (repeatedly) the scope most of
    its users carry, else that of its operands.  Instructions left with
    no scope are not listed."""
    out = {}
    for text in texts:
        module = text.split(None, 2)[1].rstrip(",")
        comps, cur = {}, None
        for line in text.splitlines():
            m = _HLO_INST.match(line)
            if m is None:
                c = _HLO_COMP.match(line)
                if c and line.rstrip().endswith("{"):
                    cur = comps.setdefault(c.group(1), {})
                continue
            name, rest = m.groups()
            op = _HLO_OPCODE.search(rest)
            own = _OP_NAME_IN_TEXT.search(rest)
            callees = [x.strip().lstrip("%") for a, b in _HLO_CALLS.findall(
                rest) for x in ([a] if a else b.split(","))]
            head = rest.split(", metadata=")[0]
            cur[name] = {"opcode": op.group(1) if op else "",
                         "scope": scope_of(own.group(1)) if own else OTHER,
                         "callees": callees,
                         "operands": _HLO_REF.findall(head)}
        scopes = {}
        for comp in comps.values():
            _name_fusions(comp, comps)
            _propagate(comp)
            scopes.update({n: i["scope"] for n, i in comp.items()
                           if i["scope"] != OTHER})
        out[module] = scopes
    return out


def _most(scopes) -> str:
    """The scope most of ``scopes`` name (ties: the first in order)."""
    named = [s for s in scopes if s != OTHER]
    return max(sorted(set(named)), key=named.count) if named else OTHER


def _fused_scopes(comp_name: str, comps: dict) -> list:
    """The scopes of every instruction fused into ``comp_name``."""
    out = []
    for inst in comps.get(comp_name, {}).values():
        out.append(inst["scope"])
        if inst["opcode"] == "fusion":
            for c in inst["callees"]:
                out += _fused_scopes(c, comps)
    return out


def _name_fusions(comp: dict, comps: dict) -> None:
    for inst in comp.values():
        if inst["scope"] == OTHER and inst["opcode"] == "fusion":
            inst["scope"] = _most(
                s for c in inst["callees"] for s in _fused_scopes(c, comps))


def _propagate(comp: dict) -> None:
    """Unscoped instructions of one computation take their users' scope
    (repeatedly, through chains), then their operands'."""
    users = {n: [] for n in comp}
    for n, inst in comp.items():
        for o in inst["operands"]:
            if o in users and o != n:
                users[o].append(n)
    free = [n for n, i in comp.items()
            if i["scope"] == OTHER and i["opcode"] not in _CONTAINERS]
    for side in ("users", "operands"):
        changed = True
        while changed:
            changed = False
            for n in free:
                inst = comp[n]
                if inst["scope"] != OTHER:
                    continue
                near = users[n] if side == "users" else inst["operands"]
                got = _most(comp[x]["scope"] for x in near if x in comp
                            and comp[x]["opcode"] not in _CONTAINERS)
                if got != OTHER:
                    inst["scope"] = got
                    changed = True


# -- the reduction ----------------------------------------------------------------


def from_run(ctx: dict, trace_dir=None):
    """``reduce`` of the trace of the run whose metric readers see ``ctx``
    (found under ``trace_dir``, by default ``TRACE_DIR``), with the scopes
    ``hlo_scopes`` gives the program's executors; kept in
    ``ctx["program"]`` for the run's other readers.  None for an untraced
    run, or where no trace is found."""
    if "program" not in ctx:
        ctx["program"] = None
        if ctx.get("trace") is not None:
            try:
                planes = load(str(trace_dir or TRACE_DIR))
            except FileNotFoundError:
                return None
            ctx["program"] = reduce(planes, hlo=hlo_scopes(executor_hlo()))
    return ctx["program"]


def scope_of(op_name: str) -> str:
    """The innermost scope of ``SCOPES`` in an ``op_name`` path such as
    ``jit(block)/while/body/vmap(tos_update)/scatter-add`` (a transform
    wraps a scope as ``vmap(tos_update)``), else ``other``."""
    for part in reversed(op_name.split("/")):
        part = part.split(":")[0]       # a trace's tf_op ends in ":<type>"
        name = part[part.find("(") + 1:].rstrip(")") if "(" in part else part
        if name in SCOPES:
            return name
    return OTHER


def _scope(name: str, op_name: str, module: str, hlo: dict) -> str:
    sc = scope_of(op_name)
    return sc if sc != OTHER else hlo.get(module, {}).get(name, OTHER)


def _self_pieces(events) -> list:
    """The pieces of each span not covered by the spans nested in it (one
    thread's spans nest): ``[(name, start, end)]``."""
    pieces, stack = [], []          # stack: [name, end, uncovered from]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            nm, end, cur = stack.pop()
            if end > cur:
                pieces.append((nm, cur, end))
        if stack:
            top = stack[-1]
            if s > top[2]:
                pieces.append((top[0], top[2], s))
            top[2] = max(top[2], min(e, top[1]))
        stack.append([name, e, s])
    while stack:
        nm, end, cur = stack.pop()
        if end > cur:
            pieces.append((nm, cur, end))
    return pieces


def reduce(planes: list, top: int = 10, hlo: dict = None) -> dict:
    """``span_self_s`` (each ``pool.*`` span's self seconds in the window),
    ``scope_self_s`` (device self seconds per scope, averaged over the
    devices), ``device_scopes`` (the same, largest first) and
    ``idle_gaps`` (the ``top`` longest, as ``[span name, seconds]``).  An
    operation whose ``op_name`` names no scope takes its scope from
    ``hlo`` (``hlo_scopes``), by its module and name.  Raises where
    ``bench.trace.reduce`` does: no window span, no device operation."""
    hlo = hlo or {}
    win = [s for s in trace_mod.host_spans(planes)
           if s[0] == trace_mod.WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {trace_mod.WINDOW_SPAN} span")
    t0, t1 = win[0][1], win[0][2]
    devs = [[tuple(e) for ln in p["lines"] if ln["name"] == trace_mod.OPS_LINE
             for e in ln["events"]]
            for p in planes if p["name"].startswith("/device:")]
    devs = [ops for ops in devs if ops]
    if not devs:
        raise ValueError("trace has no device operations")

    pieces = []
    for p in planes:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            spans = [e for e in ln["events"] if e[0].startswith(SPAN_PREFIX)]
            pieces += trace_mod._clip3(_self_pieces(spans), t0, t1)
    span_self = {}
    for name, s, e in pieces:
        span_self[name] = span_self.get(name, 0.0) + (e - s) * 1e-9

    by_scope, gaps = {}, []
    for ops in devs:
        scoped = [(_scope(name, op, module, hlo), s, e)
                  for name, s, e, op, module in ops]
        for name, d in trace_mod._self_times(trace_mod._clip3(scoped, t0, t1)):
            by_scope[name] = by_scope.get(name, 0.0) + d
        merged = trace_mod._union(
            trace_mod._clip([(op[1], op[2]) for op in ops], t0, t1))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                 if ge > gs]
    n = len(devs)
    scope_self = {k: v / n * 1e-9 for k, v in by_scope.items()}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    names = sorted({p[0] for p in pieces})
    cover = _Cover(names, pieces)
    return {
        "span_self_s": span_self,
        "scope_self_s": scope_self,
        "device_scopes": sorted(([k, v] for k, v in scope_self.items()),
                                key=lambda kv: -kv[1]),
        "idle_gaps": [[cover.name(gs, ge), (ge - gs) * 1e-9]
                      for gs, ge in longest],
    }


class _Cover:
    """Names an interval by the span whose self pieces cover most of it."""

    def __init__(self, names: list, pieces: list):
        self.names = names
        idx = {nm: i for i, nm in enumerate(names)}
        self.arr = np.array([(idx[nm], s, e) for nm, s, e in pieces],
                            np.float64).reshape(-1, 3)

    def name(self, gs: float, ge: float) -> str:
        """The covering span's name, or ``no_span``."""
        a = self.arr
        cover = np.clip(np.minimum(a[:, 2], ge) - np.maximum(a[:, 1], gs),
                        0.0, None)
        per = np.bincount(a[:, 0].astype(np.int64), cover, len(self.names))
        return (self.names[int(np.argmax(per))] if per.size and per.max() > 0
                else "no_span")
