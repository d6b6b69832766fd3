#!/usr/bin/env python3
"""Drive the DetectorPool serving path once on a TPU and check its results.

    python3 chip_smoke.py             # one chip: every phase below
    python3 chip_smoke.py --chips 4   # only the DAVIS240 fleet, lane-sharded
                                      # over 4 chips, vs the same fleet on one

Phases on one chip (``shard=False`` throughout):

  device     platform, device kind and count; Pallas kernels must compile
             for the chip (no interpret mode, no CPU fallback).
  davis240   a DetectorPool of 1,024 DAVIS240 lanes (180x240, the paper's
             sensor; Event Camera Dataset, Mueggler et al. 2017), jnp
             backend, async drain, compact readout, seeded shapes_stream
             traffic fed in slabs through feed/pump/poll, >= 2 LUT
             refreshes per lane, mid-run disconnect/reconnect churn, and one
             StreamingDetector snapshot()/restore() round trip.
  720p       16 lanes at 1280x720 (Prophesee Gen4 automotive geometry),
             dense readout.
  backends   run_pipeline with every backend == the jnp backend on the chip
             (scores, kept, TOS, SAE, LUT), and a pallas_fused pool with
             compact readout (the Pallas compaction kernel) == the jnp fleet.
  reference  sample lanes of both pools == run_pipeline_reference on the CPU
             backend of this process: kept, TOS and SAE bit-equal; scores and
             LUT within ``FLOAT_ATOL`` (the measured maximum is printed).

Earlier output lines are information (compile seconds, phase wall times).
The last line is ``{"ok": true, "device": {...}}``; any failure raises, so
the exit status is non-zero and no ``ok`` line is printed.  One process,
no children: the process that touches JAX holds the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
from typing import NamedTuple, Optional, Sequence

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Largest |chip - CPU reference| allowed on LUT values and kept-event
# scores.  Harris responses of these streams reach ~3.4e-3 (one ULP there
# is ~2.3e-10); f32 division and FMA contraction may round differently on
# the chip than on XLA:CPU, which moves a response by a few ULP of its
# terms — far below this — while a wrong pixel, a missed refresh or a
# stale LUT moves it by the response itself.
FLOAT_ATOL = 1e-7

DAVIS240 = dict(height=180, width=240)
GEN4_720P = dict(height=720, width=1280)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds XLA spends compiling, summed from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration


class Phase:
    """Times one phase; prints its wall and compile seconds on exit."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            log(f"phase {self.name}: wall {time.perf_counter() - self.t0:.3f} s,"
                f" compile {self.clock.seconds - self.c0:.3f} s")


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_compiled_kernels(cfgs) -> None:
    """Refuse to run if any Pallas kernel would run in interpret mode."""
    from repro.kernels import ops

    if ops.resolve_interpret(None):
        raise RuntimeError("Pallas kernels would run in interpret mode "
                           "(REPRO_PALLAS_INTERPRET or a non-TPU backend)")
    for cfg in cfgs:
        if ops.resolve_interpret(cfg.interpret):
            raise RuntimeError(f"cfg.interpret={cfg.interpret!r} would put "
                               f"the {cfg.backend} kernels in interpret mode")


# -- traffic -----------------------------------------------------------------


def fleet_streams(geometry: dict, lanes: int, events: int, *, seed: int,
                  bases: int = 16) -> list:
    """``lanes`` time-sorted streams of exactly ``events`` events each.

    ``bases`` seeded ``synthetic.shapes_stream`` recordings are generated;
    lane ``i`` replays recording ``i % bases`` with its columns rotated by
    ``7 * (i // bases)`` pixels, so no two lanes fold the same surface.
    """
    from repro.events import synthetic

    h, w = geometry["height"], geometry["width"]
    rate = 0.27                       # shapes_stream events per us
    dur = int(events / rate * 1.5) + 1000
    recs = []
    for b in range(min(bases, lanes)):
        s = synthetic.shapes_stream(height=h, width=w, duration_us=dur,
                                    seed=seed + b)
        if len(s) < events:
            raise RuntimeError(f"shapes_stream gave {len(s)} < {events}")
        recs.append((s.xy[:events], s.ts[:events]))
    out = []
    for i in range(lanes):
        xy, ts = recs[i % len(recs)]
        xy = xy.copy()
        xy[:, 0] = (xy[:, 0] + 7 * (i // len(recs))) % w
        out.append((xy, ts))
    return out


# -- pool --------------------------------------------------------------------


class FleetRun(NamedTuple):
    scores: list            # per stream: (n,) f32
    kept: list              # per stream: (n,) bool
    states: dict            # stream index -> host DetectorState
    stats: dict             # pool_stats() at the end
    lane_bytes: int         # device bytes of one lane's DetectorState


def serve_fleet(cfg, streams: Sequence, *, slab: int, readout: str,
                shard: bool, churn: Sequence[int] = (),
                keep_states: Sequence[int] = ()) -> FleetRun:
    """Serve one stream per lane through ``DetectorPool`` and return every
    stream's (scores, kept) in stream order.

    Slabs of ``slab`` events are fed to every lane, then the pool pumps and
    half the lanes poll without waiting.  Halfway through, the lanes of the
    ``churn`` streams disconnect and a fresh session reconnects for each,
    which is fed that stream again from its start.  At the end the lanes of
    the ``keep_states`` streams flush (their padded partial tail runs) and
    their final states are fetched; every other lane polls, so its results
    stop at its last full chunk.  A flush runs one round of the whole pool
    for one lane, so flushing every lane of a large fleet would cost as
    many rounds as lanes.
    """
    import jax

    from repro.obs import leaves_nbytes
    from repro.serve import DetectorPool

    n = len(streams[0][0])
    t = time.perf_counter()
    marks = []

    def mark(what):
        nonlocal t
        now = time.perf_counter()
        marks.append(f"{what} {now - t:.3f} s")
        t = now

    pool = DetectorPool(cfg, len(streams), shard=shard, readout=readout,
                        drain_mode="async")
    try:
        mark("init")
        pool.warmup(*streams[0])
        mark("warmup")
        lane_of = [pool.connect(seed=cfg.seed) for _ in streams]
        mark("connect")
        got = [[] for _ in streams]
        starts = list(range(0, n, slab))
        for step, start in enumerate(starts):
            if churn and step == len(starts) // 2:
                for i in churn:
                    pool.disconnect(lane_of[i])
                    lane_of[i] = pool.connect(seed=cfg.seed)
                    got[i] = []
                    xy, ts = streams[i]
                    pool.feed(lane_of[i], xy[:start], ts[:start])
            for i, (xy, ts) in enumerate(streams):
                pool.feed(lane_of[i], xy[start:start + slab],
                          ts[start:start + slab])
            pool.pump()
            for i in range(0, len(streams), 2):
                got[i].append(pool.poll(lane_of[i], wait=False))
        mark("serve")
        keep = set(keep_states)
        for i, lane in enumerate(lane_of):
            got[i].append(pool.flush(lane) if i in keep else pool.poll(lane))
        mark("flush")
        if not pool.executors_compiled_once():
            raise AssertionError(f"executors recompiled: "
                                 f"{pool.compile_cache_sizes()}")
        stacked = pool._states        # the runtime's lane-stacked state
        states = {
            i: jax.device_get(jax.tree.map(lambda a, j=lane_of[i]: a[j],
                                           stacked))
            for i in keep
        }
        stats = pool.pool_stats()
        lane_bytes = leaves_nbytes(stacked) // pool.capacity
        mark("fetch states")
    finally:
        pool.close()
    log(f"pool {cfg.height}x{cfg.width} {cfg.backend} {readout} "
        f"{len(streams)} lanes: " + ", ".join(marks))
    scores = [np.concatenate([s for s, _ in g]) for g in got]
    kept = [np.concatenate([k for _, k in g]) for g in got]
    for i, s in enumerate(scores):
        want = n if i in keep else n - n % cfg.chunk
        if s.shape != (want,):
            raise AssertionError(f"stream {i}: {s.shape[0]} results, "
                                 f"expected {want}")
    return FleetRun(scores, kept, states, stats, lane_bytes)


def assert_same_lanes(a: FleetRun, b: FleetRun, what: str) -> None:
    """Bit-equality of two runs over the same streams: results of every
    stream, final states of the streams both fetched."""
    for i, (sa, sb) in enumerate(zip(a.scores, b.scores)):
        np.testing.assert_array_equal(sa, sb, err_msg=f"{what} scores {i}")
        np.testing.assert_array_equal(a.kept[i], b.kept[i],
                                      err_msg=f"{what} kept {i}")
    for i in a.states.keys() & b.states.keys():
        for f in ("surface", "sae", "lut", "chunk_idx"):
            np.testing.assert_array_equal(
                getattr(a.states[i], f), getattr(b.states[i], f),
                err_msg=f"{what} {f} {i}")


# -- session and batch paths -------------------------------------------------


def snapshot_roundtrip(cfg, xy, ts) -> None:
    """One StreamingDetector snapshot()/restore(): the session that goes on
    after the snapshot and the one restored from it agree bit-for-bit, and
    the whole stream equals run_pipeline.  On an accelerator every fold
    donates the previous state, so this exercises the donation paths."""
    import jax

    from repro.core import pipeline
    from repro.serve import StreamingDetector

    half = len(xy) // 2
    det = StreamingDetector(cfg)
    head = det.feed(xy[:half], ts[:half])
    snap = det.snapshot()
    tail = [det.feed(xy[half:], ts[half:]), det.flush()]
    again = StreamingDetector.restore(snap)
    tail2 = [again.feed(xy[half:], ts[half:]), again.flush()]
    for (s1, k1), (s2, k2) in zip(tail, tail2):
        np.testing.assert_array_equal(s1, s2, err_msg="restored scores")
        np.testing.assert_array_equal(k1, k2, err_msg="restored kept")
    for f in ("surface", "sae", "lut"):
        np.testing.assert_array_equal(
            jax.device_get(getattr(det.state, f)),
            jax.device_get(getattr(again.state, f)), err_msg=f"restored {f}")
    ref = pipeline.run_pipeline(xy, ts, cfg)
    np.testing.assert_array_equal(
        np.concatenate([head[0]] + [s for s, _ in tail]), ref.scores)
    np.testing.assert_array_equal(
        np.concatenate([head[1]] + [k for _, k in tail]), ref.kept)


def backend_parity(cfg, xy, ts) -> None:
    """run_pipeline with every backend equals the jnp backend on this
    device: scores, kept, TOS, SAE and LUT bit-for-bit."""
    from repro.core import pipeline

    base = pipeline.run_pipeline(xy, ts, dataclasses.replace(cfg,
                                                             backend="jnp"))
    for backend in pipeline.BACKENDS:
        if backend == "jnp":
            continue
        got = pipeline.run_pipeline(
            xy, ts, dataclasses.replace(cfg, backend=backend))
        for f in ("scores", "kept", "tos", "sae", "lut"):
            np.testing.assert_array_equal(getattr(got, f), getattr(base, f),
                                          err_msg=f"{backend} {f}")
        log(f"backend {backend}: equal to jnp "
            f"({int(base.kept.sum())} kept of {len(xy)})")


def reference_diff(cfg, xy, ts, scores, kept, state) -> float:
    """Compare one served lane with ``run_pipeline_reference`` on the CPU
    backend: kept, TOS and SAE bit-equal, the -inf pattern of the scores
    equal, scores and LUT within ``FLOAT_ATOL``.  Returns the largest
    float difference."""
    import jax

    from repro.core import pipeline

    with jax.default_device(jax.devices("cpu")[0]):
        ref = pipeline.run_pipeline_reference(
            xy, ts, dataclasses.replace(cfg, backend="jnp"))
    np.testing.assert_array_equal(kept, ref.kept, err_msg="kept")
    np.testing.assert_array_equal(np.asarray(state.surface), ref.tos,
                                  err_msg="tos")
    np.testing.assert_array_equal(np.asarray(state.sae), ref.sae,
                                  err_msg="sae")
    np.testing.assert_array_equal(np.isfinite(scores),
                                  np.isfinite(ref.scores), err_msg="-inf")
    fin = np.isfinite(ref.scores)
    d_scores = float(np.max(np.abs(scores[fin] - ref.scores[fin]),
                            initial=0.0))
    lut = np.asarray(state.lut)
    lfin = np.isfinite(ref.lut)
    np.testing.assert_array_equal(np.isfinite(lut), lfin, err_msg="lut -inf")
    d_lut = float(np.max(np.abs(lut[lfin] - ref.lut[lfin]), initial=0.0))
    d = max(d_scores, d_lut)
    if d > FLOAT_ATOL:
        raise AssertionError(f"float difference {d} > {FLOAT_ATOL}")
    return d


def reference_sample(cfg, streams, run: FleetRun) -> float:
    """``reference_diff`` over the streams whose states ``run`` fetched."""
    worst = 0.0
    n_bitequal = 0
    for i in sorted(run.states):
        xy, ts = streams[i]
        d = reference_diff(cfg, xy, ts, run.scores[i], run.kept[i],
                           run.states[i])
        n_bitequal += d == 0.0
        worst = max(worst, d)
    log(f"reference {cfg.height}x{cfg.width}: {len(run.states)} lanes, "
        f"{n_bitequal} bit-equal on scores+LUT, max |diff| {worst!r}")
    return worst


# -- the phases --------------------------------------------------------------


def chunks_for_refreshes(cfg, refreshes: int = 2) -> int:
    """Events per lane for ``refreshes`` LUT refreshes plus a partial tail."""
    return (refreshes * cfg.lut_every_chunks + 1) * cfg.chunk + cfg.chunk // 3


def sample_lanes(lanes: int, sample: int) -> list:
    """The first, middle and last lane plus ``sample`` evenly spaced."""
    return sorted({0, lanes // 2, lanes - 1,
                   *range(1, lanes, max(1, lanes // sample))})


def run_one_chip(*, davis_lanes: int = 1024, gen4_lanes: int = 16,
                 pallas_lanes: int = 64, sample: int = 4, slab: int = 1000,
                 geometry=(DAVIS240, GEN4_720P), chunk: int = 256,
                 clock: Optional[CompileClock] = None) -> None:
    """The one-chip phases; sizes are arguments so tests can shrink them."""
    from repro.core.pipeline import PipelineConfig

    clock = clock or CompileClock()
    davis = PipelineConfig(**geometry[0], chunk=chunk)
    gen4 = PipelineConfig(**geometry[1], chunk=chunk)
    n = chunks_for_refreshes(davis)
    streams = fleet_streams(geometry[0], davis_lanes, n, seed=0)
    picks = sample_lanes(davis_lanes, sample)
    churn = [davis_lanes - 1, davis_lanes // 2]

    with Phase("davis240", clock):
        fleet = serve_fleet(davis, streams, slab=slab, readout="compact",
                            shard=False, churn=churn,
                            keep_states=set(picks) | set(range(pallas_lanes)))
        log(f"davis240: {davis_lanes} lanes x {n} events, "
            f"{fleet.lane_bytes} B/lane, "
            f"{fleet.lane_bytes * davis_lanes} B lane state, "
            f"h2d_pinned_staging={fleet.stats['h2d_pinned_staging']}, "
            f"host_fetches={fleet.stats['host_fetches']}, "
            f"d2h_bytes={fleet.stats['d2h_bytes']}")
        snapshot_roundtrip(davis, *streams[0])
        log("davis240: StreamingDetector snapshot/restore bit-equal")

    with Phase("720p", clock):
        n4 = chunks_for_refreshes(gen4)
        streams4 = fleet_streams(geometry[1], gen4_lanes, n4, seed=100)
        hd = serve_fleet(gen4, streams4, slab=slab, readout="dense",
                         shard=False, keep_states=[0, gen4_lanes - 1])
        log(f"720p: {gen4_lanes} lanes x {n4} events, "
            f"{hd.lane_bytes} B/lane, "
            f"h2d_pinned_staging={hd.stats['h2d_pinned_staging']}")

    with Phase("backends", clock):
        backend_parity(davis, *streams[1])
        fused = dataclasses.replace(davis, backend="pallas_fused")
        sub = streams[:pallas_lanes]
        pk = serve_fleet(fused, sub, slab=slab, readout="compact",
                         shard=False, keep_states=range(pallas_lanes))
        head = FleetRun(fleet.scores[:pallas_lanes], fleet.kept[:pallas_lanes],
                        {i: fleet.states[i] for i in range(pallas_lanes)},
                        {}, 0)
        assert_same_lanes(pk, head, "pallas_fused compact pool vs jnp fleet")
        log(f"backends: pallas_fused pool, compact readout, {pallas_lanes} "
            f"lanes == jnp fleet (d2h_bytes_saved="
            f"{pk.stats['d2h_bytes_saved']})")

    with Phase("reference", clock):
        only = FleetRun(fleet.scores, fleet.kept,
                        {i: fleet.states[i] for i in picks}, {}, 0)
        d = max(reference_sample(davis, streams, only),
                reference_sample(gen4, streams4, hd))
        log(f"reference: max |score or LUT diff| vs CPU {d!r} "
            f"(tolerance {FLOAT_ATOL})")


def run_four_chips(*, lanes: int = 1024, sample: int = 16, slab: int = 1000,
                   chunk: int = 256, geometry: dict = DAVIS240,
                   clock: Optional[CompileClock] = None) -> None:
    """The DAVIS240 fleet lane-sharded over every local device, compared
    lane-for-lane with the same fleet on one device: every lane's results,
    and the final states of a sample of lanes."""
    from repro.core.pipeline import PipelineConfig

    clock = clock or CompileClock()
    cfg = PipelineConfig(**geometry, chunk=chunk)
    streams = fleet_streams(geometry, lanes, chunks_for_refreshes(cfg), seed=0)
    picks = sample_lanes(lanes, sample)
    churn = [lanes - 1, lanes // 2]
    with Phase("davis240 one device", clock):
        one = serve_fleet(cfg, streams, slab=slab, readout="compact",
                          shard=False, churn=churn, keep_states=picks)
    with Phase("davis240 sharded", clock):
        many = serve_fleet(cfg, streams, slab=slab, readout="compact",
                           shard=True, churn=churn, keep_states=picks)
    assert_same_lanes(many, one, "sharded vs one device")
    log(f"sharded: {lanes} lanes over {device_info()['count']} devices == "
        f"one device, every lane ({len(picks)} final states)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the lane-sharded fleet over four chips")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = device_info()
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev['platform']}")
    if dev["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {dev['count']}")
    from repro.core import pipeline

    require_compiled_kernels([pipeline.PipelineConfig(backend=b)
                              for b in pipeline.BACKENDS])
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(clock=clock)
    else:
        run_one_chip(clock=clock)
    log(f"total: wall {time.perf_counter() - t0:.3f} s, "
        f"compile {clock.seconds:.3f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
