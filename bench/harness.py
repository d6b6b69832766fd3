"""The benchmark harness: one cell, one run, one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

- its configuration ``bench/configs/<config>.json`` (the deployment),
  which may name its ``reference``, the module ``bench/<reference>.py``
  that decides ``correct`` (``bench/reference.py`` where it names none);
- its own numbers ``bench/cells/<cell>.json`` (a fixed rate, for an open
  loop);
- its traffic mix ``bench/traffic/<traffic>.json``, whose ``kind`` names
  the generator ``bench/traffic/<kind>.py``;
- a reader ``bench/metrics/<metric>.py`` for every metric it reports (a
  metric ``x.open`` or ``x.sat`` is read by ``x.py``).

A run builds the pool (``DetectorPool`` at the program's defaults, lane
``l`` seeded ``POOL_SEED + l``, every lane connected), warms the cell's
shapes, then three threads drive it for the window: a generator (open
loop: events fed at their scheduled creation times, whatever the pool
does; closed loop: each lane topped up to its chunks in flight), the
application's ``pump()`` loop, and a poller that calls ``poll(lane,
wait=False)`` for lanes with a full chunk outstanding.
After the window the poller keeps draining until every full chunk fed has
come back (at most ``DRAIN_S`` past the close); sampled lanes are then
compared with the configuration's reference.

A reference module has ``Detector.from_config(config)``;
``run_lane(xy, ts, det, precision="float64", *, lane_seed)``, the fold of
one lane's whole chunks given the seed the pool gave that lane;
``lane_state(result, det, precision)``, the lane's final state as the
program keeps it; and ``compare_lane(scores, keep, state, result, det,
score_limit)``, the numbers ``correct`` compares.  ``bench/control.py``
runs it at ``precision="bfloat16"`` in the program's place.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import sys
import threading
import time
from typing import Optional

import numpy as np

from bench import reference, roofline
from bench import trace as trace_mod
from bench.traffic import ClosedSource, OpenSchedule

ROOT = pathlib.Path(__file__).resolve().parents[1]
TS_ORIGIN_US = 1_000_000       # first scheduled event's timestamp
DRAIN_S = 60.0                 # wait for late results past the close
FLAT_SHARE = 0.005             # a flat backlog grows by less than this share
                               # of the chunks offered in the sweep's window
POOL_SEED = 0                  # lane l's state is seeded POOL_SEED + l
REFERENCE_API = ("Detector", "run_lane", "lane_state", "compare_lane")


# -- finding a cell's files ---------------------------------------------------


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, *parts) -> dict:
        return json.loads(self.root.joinpath(*parts).read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._json("bench", "cells", f"{name}.json")

    def mix(self, traffic: str) -> dict:
        return self._json("bench", "traffic", f"{traffic}.json")

    def _module(self, *parts):
        path = self.root.joinpath(*parts)
        spec = importlib.util.spec_from_file_location(
            "bench_" + "_".join(p.replace(".", "_") for p in parts), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reference_of(self, config: dict):
        """The module that decides ``correct`` for ``config``:
        ``bench/<reference>.py`` where the configuration names one, else
        ``bench.reference``."""
        name = config.get("reference")
        if name is None:
            return reference
        if not isinstance(name, str) or not name.isidentifier():
            raise ValueError(f"reference {name!r} is not a module name")
        mod = self._module("bench", f"{name}.py")
        missing = [a for a in REFERENCE_API if not hasattr(mod, a)]
        if missing:
            raise ValueError(f"bench/{name}.py lacks {', '.join(missing)}")
        return mod

    def kind(self, kind: str):
        return self._module("bench", "traffic", f"{kind}.py")

    def reader(self, metric: str):
        return self._module("bench", "metrics", f"{metric.split('.')[0]}.py")

    def metrics(self, workload: str, per_layer: bool) -> list:
        """The metrics a cell reports: end-to-end ones in a plain run,
        per-layer ones in a traced run."""
        group = self.spec["per_layer" if per_layer else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or workload in m["workloads"]]


# -- book-keeping shared by the threads ---------------------------------------


class Spans:
    """Host seconds, calls and events of the benchmark's calls into the
    pool, per name (``feed``, ``pump``, ``poll``); and each call slower than
    ``SLOW_S`` as ``(name, end time, seconds)``, to place a stall."""

    SLOW_S = 0.25

    def __init__(self):
        self._lock = threading.Lock()
        self.tot = {}
        self.slow = []

    def add(self, name: str, seconds: float, calls: int = 1,
            items: int = 0) -> None:
        with self._lock:
            s = self.tot.setdefault(name, [0.0, 0, 0])
            s[0] += seconds
            s[1] += calls
            s[2] += items
            if seconds >= self.SLOW_S:
                self.slow.append((name, time.perf_counter(), seconds))

    def snapshot(self) -> dict:
        with self._lock:
            return {k: list(v) for k, v in self.tot.items()}


@dataclasses.dataclass
class Fleet:
    """What each lane was fed and what came back."""

    lanes: int
    chunk: int
    sampled: tuple
    fed: np.ndarray = None          # events fed, per lane
    ret: np.ndarray = None          # events returned, per lane
    returns: list = None            # (lane, first event, n, host time)
    outputs: dict = None            # sampled lane -> [(scores, kept)]

    def __post_init__(self):
        self.fed = np.zeros(self.lanes, np.int64)
        self.ret = np.zeros(self.lanes, np.int64)
        self.returns = []
        self.outputs = {lane: [] for lane in self.sampled}

    def outstanding(self) -> np.ndarray:
        return np.flatnonzero(self.fed // self.chunk > self.ret // self.chunk)

    def record(self, lane: int, scores, kept, t: float) -> None:
        self.returns.append((lane, int(self.ret[lane]), scores.size, t))
        self.ret[lane] += scores.size
        if lane in self.outputs:
            self.outputs[lane].append((scores, kept))


class Threads:
    """The run's threads; an exception in one is raised again by ``join``."""

    def __init__(self):
        self.threads, self.errors = [], []

    def start(self, target, *args) -> threading.Thread:
        def run():
            try:
                target(*args)
            except BaseException as e:      # re-raised on the main thread
                self.errors.append(e)
                raise

        t = threading.Thread(target=run, name=target.__name__)
        t.start()
        self.threads.append(t)
        return t

    def join(self) -> None:
        for t in self.threads:
            t.join()
        if self.errors:
            raise self.errors[0]


class Clock:
    def __init__(self):
        self.t0 = time.perf_counter()

    def us(self) -> float:
        return (time.perf_counter() - self.t0) * 1e6


def _span(enabled: bool, name: str):
    if not enabled:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- the three threads --------------------------------------------------------


def open_generator(pool, sched: OpenSchedule, fleet: Fleet, clock: Clock,
                   stop: threading.Event, spans: Spans, lags: list,
                   annotate: bool, ts_origin: int = TS_ORIGIN_US) -> None:
    """Feed every event at its scheduled creation time (its timestamp is
    ``ts_origin`` plus that time); lags collect ``(scheduled times, feed
    time)`` per fed group, in microseconds."""
    t_us, lane_of, xy = sched.t_us, sched.lane, sched.xy
    i, n = 0, t_us.size
    while i < n and not stop.is_set():
        now = clock.us()
        j = int(np.searchsorted(t_us, now, side="right"))
        if j <= i:
            time.sleep(min((t_us[i] - now) * 1e-6, 0.002))
            continue
        seg = np.arange(i, j)
        order = seg[np.argsort(lane_of[i:j], kind="stable")]
        cuts = np.flatnonzero(np.diff(lane_of[order])) + 1
        with _span(annotate, "bench.feed"):
            for grp in np.split(order, cuts):
                lane = int(lane_of[grp[0]])
                t0 = time.perf_counter()
                pool.feed(lane, xy[grp], t_us[grp] + ts_origin)
                t1 = time.perf_counter()
                spans.add("feed", t1 - t0, 1, grp.size)
                lags.append((t_us[grp], (t1 - clock.t0) * 1e6))
                fleet.fed[lane] += grp.size
        i = j


def closed_generator(pool, src: ClosedSource, fleet: Fleet,
                     stop: threading.Event, spans: Spans, annotate: bool,
                     app_lock) -> None:
    """Keep every lane at most ``src.inflight_chunks`` chunks in flight:
    once every lane has room for ``src.refill_chunks`` more, feed each of
    them that many, under ``app_lock`` so that a pump sees all of the
    refill or none of it (whole rounds, as a batch client feeds)."""
    c, n = fleet.chunk, src.refill_chunks
    lanes = np.arange(fleet.lanes)
    while not stop.is_set():
        room = src.inflight_chunks - (fleet.fed // c - fleet.ret // c)
        if room.min() < n:
            time.sleep(0.0005)
            continue
        xy, ts = src.chunks(lanes, fleet.fed // c, n)
        with app_lock, _span(annotate, "bench.feed"):
            for lane in lanes.tolist():
                t0 = time.perf_counter()
                pool.feed(lane, xy[lane], ts[lane] + TS_ORIGIN_US)
                spans.add("feed", time.perf_counter() - t0, 1, ts[lane].size)
                fleet.fed[lane] += ts[lane].size


def pump_loop(pool, stop: threading.Event, spans: Spans,
              annotate: bool, app_lock=None) -> None:
    app_lock = app_lock or contextlib.nullcontext()
    while not stop.is_set():
        t0 = time.perf_counter()
        with app_lock, _span(annotate, "bench.pump"):
            rounds = pool.pump()
        spans.add("pump", time.perf_counter() - t0, 1, rounds)
        if not rounds:
            time.sleep(0.0005)


def poller(pool, fleet: Fleet, stop: threading.Event, spans: Spans,
           annotate: bool) -> None:
    while not stop.is_set():
        lanes = fleet.outstanding()
        got = 0
        if lanes.size:
            with _span(annotate, "bench.poll"):
                for lane in lanes.tolist():
                    t0 = time.perf_counter()
                    scores, kept = pool.poll(lane, wait=False)
                    t1 = time.perf_counter()
                    spans.add("poll", t1 - t0, 1, scores.size)
                    if scores.size:
                        fleet.record(lane, scores, kept, t1)
                        got += 1
        if not got:
            time.sleep(0.0005)


# -- one run ------------------------------------------------------------------


@dataclasses.dataclass
class Options:
    workload: str
    seed: int
    seconds: float
    trace: bool = False
    out_dir: Optional[pathlib.Path] = None      # where a trace is written
    drain_s: float = DRAIN_S


def pipeline_config(config: dict):
    from repro.core.dvfs import DvfsConfig
    from repro.core.pipeline import PipelineConfig

    detector = dict(config["detector"])
    if "dvfs_cfg" in detector:
        detector["dvfs_cfg"] = DvfsConfig(**detector["dvfs_cfg"])
    return PipelineConfig(height=config["height"], width=config["width"],
                          **detector)


def sample_lanes(weight: np.ndarray, k: int, seed: int) -> tuple:
    """``k`` lanes drawn from the seed, always with the heaviest in it."""
    rng = np.random.default_rng([seed, 3])
    live = np.flatnonzero(weight > 0)
    top = int(np.argmax(weight))
    rest = rng.permutation(live[live != top])[:max(0, k - 1)]
    return tuple(sorted({top, *rest.tolist()}))


class CompileCount:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


def start_trace(out_dir) -> None:
    """Device trace plus host annotations; the Python call tracer stays off
    (it records every Python call and slows the host many times over)."""
    import jax

    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 2
    po.enable_hlo_proto = False
    jax.profiler.start_trace(str(out_dir), profiler_options=po)


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def build_pool(config: dict, chips: int):
    from repro.serve import DetectorPool

    cfg = pipeline_config(config)
    pool = DetectorPool(cfg, int(config["capacity"]), seed=POOL_SEED,
                        shard=chips > 1)
    n = 3 * cfg.chunk
    k = np.arange(n)
    pool.warmup(np.stack([k % cfg.width, (k // 7) % cfg.height], 1),
                TS_ORIGIN_US + 4 * k)
    for _ in range(int(config["capacity"])):
        pool.connect()
    return pool


def run_cell(bench: Bench, opts: Options, t_start: float, *,
             config_override: Optional[dict] = None,
             cell_override: Optional[dict] = None,
             pool_hook=None, ctx_hook=None) -> dict:
    """Set up, run the window, drain, check and reduce; returns the result
    line.  ``config_override``/``cell_override`` update the files' values
    (for small runs in tests); ``pool_hook(pool)`` may wrap the pool, and
    ``ctx_hook(ctx)`` sees what the metric readers read."""
    wl = bench.workload(opts.workload)
    config = {**bench.config(wl["config"]), **(config_override or {})}
    ref = bench.reference_of(config)
    cell = {**bench.cell(opts.workload), **(cell_override or {})}
    mix = bench.mix(wl["traffic"])
    chunk = int(config["detector"]["chunk"])
    lanes = int(config["capacity"])
    src = bench.kind(mix["kind"]).build(mix, cell, config, opts.seed,
                                        opts.seconds)
    is_open = isinstance(src, OpenSchedule)
    if is_open:
        weight = np.bincount(src.lane, minlength=lanes)
    else:
        weight = np.ones(lanes)
    sampled = sample_lanes(weight, int(config["sample_lanes"]), opts.seed)

    pool = build_pool(config, int(wl["chips"]))
    if pool_hook is not None:
        pool = pool_hook(pool)
    compiles = CompileCount()
    fleet = Fleet(lanes, chunk, sampled)
    spans, lags = Spans(), []
    stop_gen, stop_all = threading.Event(), threading.Event()
    annotate = bool(opts.trace)
    if opts.trace:
        start_trace(opts.out_dir)
    clock = Clock()
    threads = Threads()
    app_lock = None
    if is_open:
        gen = threads.start(open_generator, pool, src, fleet, clock,
                            stop_gen, spans, lags, annotate)
    else:
        app_lock = threading.Lock()
        gen = threads.start(closed_generator, pool, src, fleet, stop_gen,
                            spans, annotate, app_lock)
    threads.start(pump_loop, pool, stop_all, spans, annotate, app_lock)
    threads.start(poller, pool, fleet, stop_all, spans, annotate)
    try:
        if is_open:
            t_open = clock.t0 + src.preroll_s
            time.sleep(max(0.0, t_open - time.perf_counter()))
        else:
            want = lanes * int(mix["preroll_chunks_per_lane"]) * chunk
            deadline = time.perf_counter() + 300
            while fleet.ret.sum() < want:
                if time.perf_counter() > deadline or threads.errors:
                    raise RuntimeError("closed loop never filled")
                time.sleep(0.0005)
            t_open = fleet.returns[-1][3]
        setup_s = t_open - t_start
        stats0, spans0, fed0 = pool.pool_stats(), spans.snapshot(), fleet.fed.copy()
        compiles0 = compiles.n
        t_close = t_open + opts.seconds
        with _span(annotate, "bench.window"):
            time.sleep(max(0.0, t_close - time.perf_counter()))
        stats1, spans1, fed1 = pool.pool_stats(), spans.snapshot(), fleet.fed.copy()
        compiles_in_window = compiles.n - compiles0
        stop_gen.set()
        gen.join()
        if opts.trace:
            import jax

            jax.profiler.stop_trace()
        deadline = t_close + opts.drain_s
        while (fleet.outstanding().size and not threads.errors
               and time.perf_counter() < deadline):
            time.sleep(0.005)
    finally:
        stop_gen.set()
        stop_all.set()
        threads.join()

    mem = memory_peak_bytes()
    recompiled = not pool.executors_compiled_once()
    states = fetch_states(pool, sampled)
    pool.close()
    del pool

    ret = np.array(fleet.returns, dtype=np.float64).reshape(-1, 4)
    in_win = (ret[:, 3] > t_open) & (ret[:, 3] <= t_close)
    ctx = {
        "seconds": opts.seconds,
        "setup_s": setup_s,
        "events_in_window": int(ret[in_win, 2].sum()),
        "delta": window_deltas(stats0, stats1),
        "spans": {k: [v[i] - spans0.get(k, [0, 0, 0])[i] for i in range(3)]
                  for k, v in spans1.items()},
        "trace": None,
    }
    lat, due = [], None
    if is_open:
        lat, due = chunk_latencies(src, fleet, ret, in_win, clock)
        ctx["lag_ms"] = window_lags(lags, src)
    ctx["latency_ms"] = np.asarray(lat)
    first_chunk = ret[in_win, 1] // chunk
    n_chunks = ret[in_win, 2] // chunk
    ctx["least_s"] = None
    dev = device_info()
    dev["memory_peak_bytes"] = mem
    if opts.trace:
        red = trace_mod.reduce(trace_mod.load(str(opts.out_dir)))
        ctx["trace"] = red
        ctx["least_s"] = roofline.least_time_s(
            ctx["events_in_window"],
            roofline.due_refreshes(first_chunk, n_chunks,
                                   config["detector"]["lut_every_chunks"]),
            config["detector"], config["height"], config["width"],
            roofline.peaks(dev["kind"]))
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]

    if ctx_hook is not None:
        ctx_hook(ctx)
    checks, bad_chunks = check(ref, config, fleet, states, src, sampled)
    lost = checks["lost_chunks"]["value"]
    if is_open:
        attempted = int(due)
    else:
        attempted = int(np.sum(fed1 // chunk - fed0 // chunk))
    failed = min(attempted, int(lost + bad_chunks))
    checks["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    checks["recompiled"] = {"value": int(recompiled), "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for m in bench.metrics(opts.workload, per_layer=opts.trace):
        v = bench.reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if ctx["trace"] is not None:
        out["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                            "idle_gaps": ctx["trace"]["idle_gaps"]}
    out["checks"] = checks
    lat, d = ctx["latency_ms"], ctx["delta"]
    t_ret = np.sort(ret[in_win, 3])
    slow = [(n, round(sec * 1e3, 1)) for n, t, sec in spans.slow
            if t_open < t <= t_close]
    out["info"] = {"chunks_in_window": int(lat.size),
                   "rounds": d["rounds_executed"], "dispatches": d["pump_stages"],
                   "longest_return_gap_ms": float(np.diff(
                       np.concatenate([[t_open], t_ret, [t_close]])).max() * 1e3),
                   "slow_calls_ms": slow[:20],
                   **{f"latency_p{q}_ms": float(np.percentile(lat, q))
                      for q in (50, 90, 95, 99) if lat.size}}
    return out


def window_deltas(before: dict, after: dict) -> dict:
    """Every number of ``pool_stats()`` as its change over the window (a
    gauge's change too: a reader that wants a level takes it elsewhere)."""
    return {k: v - before[k] for k, v in after.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
            and isinstance(before.get(k), (int, float))}


def fetch_states(pool, lanes) -> dict:
    import jax

    st = pool._states
    out = {}
    for lane in lanes:
        one = jax.device_get({"surface": st.surface[lane], "sae": st.sae[lane],
                              "lut": st.lut[lane],
                              "lut_ready": st.lut_ready[lane]})
        out[lane] = {k: np.asarray(v) for k, v in one.items()}
    return out


def chunk_latencies(sched: OpenSchedule, fleet: Fleet, ret, in_win, clock,
                    base=None):
    """Latency of each chunk returned in the window, from its last event's
    scheduled creation time, in ms; and the number of chunks whose last
    event was due in the window.  ``base`` counts each lane's events fed
    before this schedule (a sweep's earlier rates)."""
    c = fleet.chunk
    base = np.zeros(fleet.lanes, np.int64) if base is None else base
    order = np.argsort(sched.lane, kind="stable")
    counts = np.bincount(sched.lane, minlength=fleet.lanes)
    start = np.cumsum(counts) - counts
    t_lane = sched.t_us[order]
    lat = []
    for lane, first, n, t in ret[in_win]:
        lane, k0 = int(lane), int(first) // c
        last = np.arange(k0, k0 + int(n) // c) * c + c - 1 - base[lane]
        last = last[(last >= 0) & (last < counts[lane])]
        lat.append((t - clock.t0) * 1e3 - t_lane[start[lane] + last] * 1e-3)
    w0, w1 = sched.preroll_s * 1e6, (sched.preroll_s + sched.seconds) * 1e6
    ends = []
    for ln in range(fleet.lanes):
        k = np.arange((base[ln] + counts[ln]) // c) * c + c - 1 - base[ln]
        ends.append(t_lane[start[ln] + k[k >= 0]])
    last = np.concatenate(ends)
    due_in = int(np.count_nonzero((last >= w0) & (last < w1)))
    return (np.concatenate(lat) if lat else np.zeros(0)), due_in


def window_lags(lags: list, sched: OpenSchedule) -> np.ndarray:
    """Generator lag, in ms, of every event scheduled in the window."""
    w0, w1 = sched.preroll_s * 1e6, (sched.preroll_s + sched.seconds) * 1e6
    out = []
    for t_sched, t_fed in lags:
        m = (t_sched >= w0) & (t_sched < w1)
        if m.any():
            out.append((t_fed - t_sched[m]) * 1e-3)
    return np.concatenate(out) if out else np.zeros(0)


def lane_stream(src, lane: int, n_events: int):
    """The first ``n_events`` events fed to ``lane``: (xy, ts)."""
    if isinstance(src, OpenSchedule):
        idx = src.lane_events(lane)[:n_events]
        return src.xy[idx], src.t_us[idx] + TS_ORIGIN_US
    xy, ts = src.content.events([lane], [0], n_events)
    return xy[0], ts[0] + TS_ORIGIN_US


def check(ref, config: dict, fleet: Fleet, states: dict, src, sampled):
    """Every lane: every full chunk fed came back once.  Sampled lanes:
    what ``poll`` returned and the final state equal the reference module
    ``ref``'s fold of the lane, given the seed the pool gave that lane."""
    c = fleet.chunk
    full = (fleet.fed // c) * c
    lost = int(np.sum(np.maximum(0, full - fleet.ret)) // c)
    extra = int(np.sum(np.maximum(0, fleet.ret - full)) // c)
    det = ref.Detector.from_config(config)
    worst = {"kept_mismatch": 0, "inf_mismatch": 0, "tos_mismatch": 0,
             "sae_mismatch": 0, "score_gap": 0.0, "lut_gap": 0.0}
    bad_chunks = 0
    lim = config["limits"]
    for lane in sampled:
        outs = fleet.outputs[lane]
        scores = np.concatenate([o[0] for o in outs] or [np.zeros(0)])
        kept = np.concatenate([o[1] for o in outs] or [np.zeros(0, bool)])
        xy, ts = lane_stream(src, lane, int(full[lane]))
        want = ref.run_lane(xy, ts, det, lane_seed=POOL_SEED + lane)
        got = ref.compare_lane(scores, kept, states[lane], want, det,
                               lim["score_gap"])
        bad_chunks += got.pop("bad_chunks")
        del got["lost_events"], got["extra_events"]   # counted above
        for k, v in got.items():
            worst[k] = max(worst[k], v) if "gap" in k else worst[k] + v
    checks = {"lost_chunks": {"value": lost, "limit": 0},
              "extra_chunks": {"value": extra, "limit": 0}}
    for k, v in worst.items():
        checks[k] = {"value": v, "limit": lim[k] if k in lim else 0}
    return checks, bad_chunks


def emit(result: dict) -> None:
    """Print the result: its ``info`` (latency quantiles) and then each
    check as the last lines of standard error, and the result line, with
    ``checks`` last, as the last line of standard output."""
    info = result.pop("info", None)
    if info:
        print("info " + json.dumps(info), file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


# -- the knee sweep -------------------------------------------------------------


def flat(row: dict, seconds: float) -> bool:
    """The backlog stays flat: its least-squares growth over the window is
    under ``FLAT_SHARE`` of the chunks offered in it, or under one chunk."""
    growth = row["backlog_slope_chunks_per_s"] * seconds
    offered = row["offered_chunks_per_s"] * seconds
    return growth <= max(FLAT_SHARE * offered, 1.0)


def knee(rows: list, seconds: float):
    """The highest rate of an ascending sweep whose backlog, and every lower
    rate's, stays flat; None if the lowest does not."""
    best = None
    for row in rows:
        if not flat(row, seconds):
            break
        best = row["rate_eps"]
    return best


def sweep(bench: Bench, opts: Options, rates: list) -> list:
    """Offer each aggregate rate of an open-loop cell in turn to one pool
    (one set-up), for ``opts.seconds`` each after the mix's pre-roll, and
    report the backlog slope (full chunks fed but not returned, least
    squares over the window, chunks/s) and the latency quantiles; last, the
    knee and 0.8 times it, the cell's rate."""
    wl = bench.workload(opts.workload)
    config = bench.config(wl["config"])
    cell = bench.cell(opts.workload)
    mix = bench.mix(wl["traffic"])
    chunk = int(config["detector"]["chunk"])
    lanes = int(config["capacity"])
    kind = bench.kind(mix["kind"])
    pool = build_pool(config, int(wl["chips"]))
    fleet = Fleet(lanes, chunk, ())
    spans = Spans()
    stop_all = threading.Event()
    threads = Threads()
    threads.start(pump_loop, pool, stop_all, spans, False)
    threads.start(poller, pool, fleet, stop_all, spans, False)
    out, origin = [], TS_ORIGIN_US
    try:
        for i, rate in enumerate(sorted(rates)):
            sched = kind.build(mix, cell, config, opts.seed + i, opts.seconds,
                               rate_eps=rate)
            base = fleet.fed.copy()
            n_ret = len(fleet.returns)
            stop_gen, lags = threading.Event(), []
            clock = Clock()
            gen = threads.start(open_generator, pool, sched, fleet, clock,
                                stop_gen, spans, lags, False, origin)
            t_open = clock.t0 + sched.preroll_s
            t_close = t_open + opts.seconds
            time.sleep(max(0.0, t_open - time.perf_counter()))
            samples = []
            while time.perf_counter() < t_close:
                samples.append((time.perf_counter() - t_open, int(np.sum(
                    fleet.fed // chunk - fleet.ret // chunk))))
                time.sleep(0.25)
            stop_gen.set()
            gen.join()
            ret = np.array(fleet.returns[n_ret:], np.float64).reshape(-1, 4)
            in_win = (ret[:, 3] > t_open) & (ret[:, 3] <= t_close)
            lat, _ = chunk_latencies(sched, fleet, ret, in_win, clock, base)
            smp = np.array(samples, np.float64)
            slope = float(np.polyfit(smp[:, 0], smp[:, 1], 1)[0])
            row = {"rate_eps": rate,
                   "events_per_s": float(ret[in_win, 2].sum() / opts.seconds),
                   "backlog_slope_chunks_per_s": slope,
                   "backlog_end_chunks": int(smp[-1, 1]),
                   "offered_chunks_per_s": rate / chunk,
                   "chunks": int(lat.size),
                   "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
                   "p99_ms": float(np.percentile(lat, 99)) if lat.size else None,
                   "lag_ms_p99": float(np.percentile(
                       window_lags(lags, sched), 99)) if lags else None}
            print(json.dumps(row), flush=True)
            out.append(row)
            if (row["events_per_s"] < 0.8 * rate
                    or smp[-1, 1] > 0.25 * row["offered_chunks_per_s"]
                    * opts.seconds):
                break               # past the knee: higher rates only grow
            deadline = time.perf_counter() + DRAIN_S
            while fleet.outstanding().size and time.perf_counter() < deadline:
                time.sleep(0.01)
            origin += int((sched.preroll_s + opts.seconds) * 1e6) + 1_000_000
    finally:
        stop_all.set()
        threads.join()
        pool.close()
    k = knee(out, opts.seconds)
    print(json.dumps({"knee_eps": k, "cell_rate_eps":
                      None if k is None else round(0.8 * k)}), flush=True)
    return out
