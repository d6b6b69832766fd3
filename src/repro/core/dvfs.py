"""Dynamic Voltage & Frequency Scaling controller — paper §III-B, Fig. 2(b).

Event cameras emit at a scene-dependent rate, so the macro's clock/Vdd can
track demand.  The paper's estimator is a 3-counter round-robin moving
average: each counter integrates events for TW/2; while one counts, the other
two (together spanning the last TW) provide the rate estimate.  The estimate
indexes a LUT of (Vdd, f_clk) operating points.

This module simulates the controller bit-faithfully (20-bit saturating
counters, 50% stride) and exposes an energy accounting pass used by the
Table-I / Fig.-8 benchmarks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hwmodel

__all__ = [
    "DvfsConfig",
    "simulate_dvfs",
    "DvfsTrace",
    "per_chunk_vdd",
    "OpPointTable",
    "op_point_table",
    "RateState",
    "rate_state_init",
    "online_vdd_from_chunk_ts",
]


@dataclasses.dataclass(frozen=True)
class DvfsConfig:
    tw_us: int = 10_000          # TW_DVFS = 10 ms for the driving datasets
    counter_bits: int = 20
    headroom: float = 1.25       # pick a Vdd whose capacity >= rate * headroom
    vdd_floor: float = 0.6       # most aggressive operating point allowed
    # Highest operating point selectable (None = full LUT).  Truncating the
    # table at a ceiling is bit-identical to clamping the chosen index at
    # that entry (the picker takes the lowest index whose capacity fits,
    # else the highest entry) — this field is the config-respecialized
    # oracle the serving layer's in-state ``vdd_cap`` knob is tested
    # against.
    vdd_ceiling: float | None = None

    @property
    def half_us(self) -> int:
        return self.tw_us // 2   # each counter spans TW/2; stride = 50%


def _lut_points(cfg: DvfsConfig) -> list:
    """Floor/ceiling-filtered operating points, ascending Vdd."""
    lut = [p for p in hwmodel.dvfs_lut() if p["vdd"] >= cfg.vdd_floor - 1e-9]
    if cfg.vdd_ceiling is not None:
        lut = [p for p in lut if p["vdd"] <= cfg.vdd_ceiling + 1e-9]
        if not lut:
            raise ValueError(
                f"vdd_ceiling={cfg.vdd_ceiling} excludes every operating "
                f"point above vdd_floor={cfg.vdd_floor}"
            )
    return lut


@dataclasses.dataclass
class DvfsTrace:
    """Per-window trace of the controller (numpy, for plotting/benchmarks)."""

    window_t_us: np.ndarray      # window end times
    est_meps: np.ndarray         # estimated event rate
    vdd: np.ndarray              # chosen operating voltage
    cap_meps: np.ndarray         # capacity of the chosen point
    energy_pj: np.ndarray        # dynamic energy spent in the window
    dropped: np.ndarray          # events dropped (rate > capacity)

    def avg_power_mw(self) -> float:
        dt_us = np.diff(self.window_t_us, prepend=0.0)
        total_t_us = max(float(self.window_t_us[-1]), 1e-9)
        leak_mw = np.sum(
            hwmodel.PARAMS.leak_mw_at_12 * (self.vdd / 1.2) * dt_us
        ) / total_t_us
        return float(np.sum(self.energy_pj) * 1e-6 / total_t_us + leak_mw)

    def drop_rate(self, total_events: int) -> float:
        return float(np.sum(self.dropped)) / max(total_events, 1)


def _pick_operating_point(
    est_meps: jax.Array, lut_caps: jax.Array, headroom: float
) -> jax.Array:
    """Index of the lowest-Vdd LUT entry with capacity >= est * headroom.

    Falls back to the highest entry when demand exceeds every capacity.
    """
    need = est_meps * headroom
    ok = lut_caps >= need
    first_ok = jnp.argmax(ok)                       # lowest index that fits
    any_ok = jnp.any(ok)
    return jnp.where(any_ok, first_ok, lut_caps.shape[0] - 1)


@functools.partial(
    jax.jit, static_argnames=("n_windows", "cfg_tw_us", "cfg_bits")
)
def _count_windows(ts_us: jax.Array, n_windows: int, cfg_tw_us: int, cfg_bits: int):
    """Round-robin counters: events per TW/2 window, saturating at 2^bits-1.

    Three physical counters cycle ptr <- (ptr+1) mod 3; two closed counters
    (= the last two half-windows) form the estimate.  Functionally the closed
    pair is just a sliding sum over half-window bins, which is what we compute
    — the round-robin mechanics only decide *which* hardware counter holds
    each bin, so binning is bit-exact w.r.t. the paper's scheme.
    """
    half = cfg_tw_us // 2
    bins = jnp.clip(ts_us // half, 0, n_windows - 1)
    counts = jnp.zeros((n_windows,), jnp.int32).at[bins].add(1)
    sat = (1 << cfg_bits) - 1
    return jnp.minimum(counts, sat)


def simulate_dvfs(
    ts_us: np.ndarray,
    cfg: DvfsConfig = DvfsConfig(),
    *,
    use_dvfs: bool = True,
) -> DvfsTrace:
    """Run the DVFS controller over a time-sorted event stream.

    Returns a per-half-window trace.  With ``use_dvfs=False`` the macro is
    pinned at 1.2 V (the paper's "w/o DVFS" columns of Table I).
    """
    ts = np.asarray(ts_us, dtype=np.int64)
    assert ts.ndim == 1
    t_end = int(ts[-1]) + 1 if len(ts) else 1
    half = cfg.half_us
    n_win = max(2, int(np.ceil(t_end / half)) + 1)

    counts = np.asarray(
        _count_windows(jnp.asarray(ts), n_win, cfg.tw_us, cfg.counter_bits)
    )

    lut = _lut_points(cfg)
    caps = jnp.asarray([p["max_meps"] for p in lut])
    vdds = np.asarray([p["vdd"] for p in lut])
    es = np.asarray([p["energy_pj"] for p in lut])

    # Estimate for window w uses the two *closed* counters: bins w-2, w-1.
    # The divide is done in float32 — the same arithmetic the *online*
    # streaming estimator uses on device — so the precomputed and online
    # paths pick identical operating points (property-tested).
    closed = counts.astype(np.int64)
    pair = np.concatenate([[0, 0], closed[:-2] + closed[1:-1]])
    est_meps = pair.astype(np.float32) / np.float32(cfg.tw_us)  # ev/us == Meps

    if use_dvfs:
        idxs = np.asarray(
            jax.vmap(lambda e: _pick_operating_point(e, caps, cfg.headroom))(
                jnp.asarray(est_meps)
            )
        )
    else:
        idxs = np.full(est_meps.shape, len(lut) - 1, dtype=np.int64)

    vdd = vdds[idxs]
    cap = np.asarray(caps)[idxs]
    # Window w's events are served at window w's operating point.
    served = np.minimum(counts.astype(np.float64), cap * half)
    dropped = counts - served
    energy = served * es[idxs]

    return DvfsTrace(
        window_t_us=(np.arange(n_win, dtype=np.float64) + 1) * half,
        est_meps=est_meps,
        vdd=vdd,
        cap_meps=cap,
        energy_pj=energy,
        dropped=dropped.astype(np.int64),
    )


# ---------------------------------------------------------------------------
# Online (streaming) controller — the device-resident twin of per_chunk_vdd
# ---------------------------------------------------------------------------


class OpPointTable(NamedTuple):
    """DVFS operating points as arrays, floor-filtered like ``simulate_dvfs``.

    ``vdd64`` keeps the exact float64 LUT voltages for host-side accounting;
    every other column is float32 because that is what the device consumes
    (and what ``simulate_dvfs`` already compares in).
    """

    vdd64: np.ndarray        # (P,) float64 — host accounting / vdd traces
    caps: np.ndarray         # (P,) float32 — capacity in Meps
    ber: np.ndarray          # (P,) float32 — bit error rate at that Vdd
    energy_pj: np.ndarray    # (P,) float32 — energy per kept event
    latency_ns: np.ndarray   # (P,) float32 — latency per kept event


@functools.lru_cache(maxsize=None)
def op_point_table(cfg: DvfsConfig = DvfsConfig()) -> OpPointTable:
    """Host-side table of the controller's selectable operating points."""
    lut = _lut_points(cfg)
    return OpPointTable(
        vdd64=np.asarray([p["vdd"] for p in lut], np.float64),
        caps=np.asarray([p["max_meps"] for p in lut], np.float32),
        ber=np.asarray([p["ber"] for p in lut], np.float32),
        energy_pj=np.asarray([p["energy_pj"] for p in lut], np.float32),
        latency_ns=np.asarray(
            [hwmodel.patch_latency_ns(p["vdd"]) for p in lut], np.float32
        ),
    )


class RateState(NamedTuple):
    """Streaming twin of the paper's 3-counter round-robin rate estimator.

    ``win`` is the half-window index of the latest event integrated so far;
    ``cur`` counts events in that (still-open) window, ``prev1``/``prev2``
    the two most recently *closed* half-windows — exactly the pair the
    round-robin scheme reads.  All int32 scalars, so a ``RateState`` rides
    in a ``lax.scan`` carry / ``vmap`` lane without host involvement.
    """

    win: jax.Array
    cur: jax.Array
    prev1: jax.Array
    prev2: jax.Array


def rate_state_init() -> RateState:
    # one array per leaf: a step that donates the state refuses a buffer
    # that two of its leaves share
    return RateState(*(jnp.int32(0) for _ in RateState._fields))


def online_vdd_from_chunk_ts(
    rate: RateState,
    ts: jax.Array,
    valid: jax.Array,
    *,
    cfg: DvfsConfig,
    caps: jax.Array,
) -> tuple[RateState, jax.Array]:
    """One streaming controller step: pick this chunk's operating point.

    ``ts`` are the chunk's (chunk-relative, int32) microsecond timestamps —
    rebased so that the stream's first event falls in half-window 0 (the
    pipeline aligns the rebase to a half-window multiple).  Returns the
    updated estimator carry and the chosen operating-point *index* into
    ``caps`` / :func:`op_point_table`.

    Bit-exact twin of the host path: the chunk runs at the Vdd chosen for
    the half-window containing its first event, whose estimate reads the two
    closed counters.  Those bins only hold events strictly earlier in the
    (time-sorted) stream, so the carry already has their full counts when
    the chunk arrives — streaming sees exactly what ``per_chunk_vdd`` sees.
    Per-bin counts saturate at ``2^counter_bits - 1`` when read, and the
    rate divide is float32 on both paths.
    """
    half = jnp.int32(cfg.half_us)
    sat = jnp.int32((1 << cfg.counter_bits) - 1)
    has = jnp.any(valid)

    # --- rotate the counters up to the chunk's first-event window ----------
    w_first = ts[0] // half
    d = w_first - rate.win
    cur = jnp.where(d == 0, rate.cur, 0)
    p1 = jnp.select([d == 0, d == 1], [rate.prev1, rate.cur], 0)
    p2 = jnp.select(
        [d == 0, d == 1, d == 2], [rate.prev2, rate.prev1, rate.cur], 0
    )

    # --- estimate + operating point (closed pair, saturating read) ---------
    pair = jnp.minimum(p1, sat) + jnp.minimum(p2, sat)
    est_meps = pair.astype(jnp.float32) / jnp.float32(cfg.tw_us)
    idx = _pick_operating_point(est_meps, caps, cfg.headroom)

    # --- integrate this chunk's events into the carry -----------------------
    # Only the last window and the two before it can ever be read again, so
    # counting those three bins in-chunk loses nothing (time-sorted stream).
    w_last = ts[-1] // half
    win_of = ts // half
    n0 = jnp.sum((valid & (win_of == w_last)).astype(jnp.int32))
    n1 = jnp.sum((valid & (win_of == w_last - 1)).astype(jnp.int32))
    n2 = jnp.sum((valid & (win_of == w_last - 2)).astype(jnp.int32))
    e = w_last - w_first
    cur2 = n0 + jnp.where(e == 0, cur, 0)
    p1b = n1 + jnp.select([e == 0, e == 1], [p1, cur], 0)
    p2b = n2 + jnp.select([e == 0, e == 1, e == 2], [p2, p1, cur], 0)

    new = RateState(
        win=jnp.where(has, w_last, rate.win).astype(jnp.int32),
        cur=jnp.where(has, cur2, rate.cur).astype(jnp.int32),
        prev1=jnp.where(has, p1b, rate.prev1).astype(jnp.int32),
        prev2=jnp.where(has, p2b, rate.prev2).astype(jnp.int32),
    )
    return new, idx.astype(jnp.int32)


def per_chunk_vdd(
    ts_us: np.ndarray,
    n_chunks: int,
    chunk: int,
    cfg: DvfsConfig = DvfsConfig(),
    *,
    n_events: int | None = None,
) -> np.ndarray:
    """Operating voltage for each fixed-size event chunk (float64, host).

    A chunk runs at the Vdd the controller chose for the half-window
    containing its *first* event (the controller is causal: estimates close
    before the chunk starts).  Precomputed on the host once per stream so
    the device-resident scan consumes it as a plain per-chunk input array —
    no host round-trip inside the fold.
    """
    ts = np.asarray(ts_us, dtype=np.int64)
    if n_events is None:
        n_events = len(ts)
    if n_chunks == 0:
        return np.zeros((0,), np.float64)
    trace = simulate_dvfs(ts, cfg)
    half = cfg.half_us
    win_of_ts = np.minimum(ts // half, len(trace.vdd) - 1)
    out = np.zeros((n_chunks,), np.float64)
    for c in range(n_chunks):
        w = int(win_of_ts[min(c * chunk, n_events - 1)]) if n_events else 0
        out[c] = float(trace.vdd[w])
    return out
