"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
data: planes, their lines, and each event as ``(name, start_ns, end_ns)``.
``reduce`` takes from that:

- the window: the benchmark's ``bench.window`` host span;
- busy time: the union of the intervals of the device's operations (the
  ``XLA Ops`` line of every ``/device:`` plane) inside the window, averaged
  over the devices;
- every device operation's self time (an operation's span less the
  operations nested in it, as a loop holds its body) summed by its short
  name, and the operations that took most time;
- the longest idle gaps between device operations, each named by the
  benchmark host span (``bench.*``) that covers most of it.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(path: str) -> list:
    """``[{"name", "lines": [{"name", "events": [[name, start, end]]}]}]``
    from an ``.xplane.pb`` file, or the newest one under a directory: the
    devices' ``XLA Ops`` lines and the host's ``bench.*`` spans, which is
    all that ``reduce`` reads."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    planes = []
    for plane in ProfileData.from_file(path).planes:
        dev = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            events = [[e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns)]
                      for e in line.events
                      if dev or e.name.startswith(HOST_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def device_ops(planes: list) -> dict:
    """``{device plane name: [(op name, start, end)]}``."""
    out = {}
    for p in planes:
        if not p["name"].startswith("/device:"):
            continue
        ops = [tuple(e) for ln in p["lines"] if ln["name"] == OPS_LINE
               for e in ln["events"]]
        if ops:
            out[p["name"]] = ops
    return out


def host_spans(planes: list) -> list:
    """Every ``bench.*`` host span as ``(name, start, end)``."""
    return [tuple(e) for p in planes if not p["name"].startswith("/device:")
            for ln in p["lines"] for e in ln["events"]
            if e[0].startswith(HOST_PREFIX)]


def reduce(planes: list, top: int = 10) -> dict:
    """Busy and window seconds, every device operation's self time, the top
    ones and the longest idle gaps (see the module docstring).  Raises when the trace holds no
    window span or no device operation."""
    spans = host_spans(planes)
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    t0, t1 = win[0][1], win[0][2]
    devs = device_ops(planes)
    if not devs:
        raise ValueError("trace has no device operations")
    busy, by_name, gaps = [], {}, []
    work = [s for s in spans if s[0] != WINDOW_SPAN]
    for ops in devs.values():
        merged = _union(_clip([(s, e) for _, s, e in ops], t0, t1))
        busy.append(sum(e - s for s, e in merged))
        for name, d in _self_times(_clip3(ops, t0, t1)):
            by_name[name] = by_name.get(name, 0.0) + d
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                 if ge > gs]
    n = len(devs)
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": (t1 - t0) * 1e-9,
        "op_self_s": {k: v / n * 1e-9 for k, v in by_name.items()},
        "device_ops": [[k, v / n * 1e-9] for k, v in ops_top],
        "idle_gaps": [[_gap_name(work, gs, ge), (ge - gs) * 1e-9]
                      for gs, ge in longest],
    }


def short_name(name: str) -> str:
    """``%fusion.10 = s32[...] fusion(...)`` -> ``fusion.10``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _clip3(ops, t0, t1):
    return [(n, max(s, t0), min(e, t1)) for n, s, e in ops
            if e > t0 and s < t1]


def _self_times(ops) -> list:
    """``(short name, self time)`` of each operation: its span less the
    spans of the operations directly nested in it."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out, stack = [], []          # stack: [name, start, end, child time]
    for name, s, e in ops:
        while stack and s >= stack[-1][2]:
            top = stack.pop()
            out.append((short_name(top[0]), top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    out += [(short_name(t[0]), t[2] - t[1] - t[3]) for t in stack]
    return out


def _gap_name(spans, gs, ge) -> str:
    best, name = 0.0, "no_span"
    cover = {}
    for n, s, e in spans:
        d = min(e, ge) - max(s, gs)
        if d > 0:
            cover[n] = cover.get(n, 0.0) + d
    for n, d in cover.items():
        if d > best:
            best, name = d, n
    return name
