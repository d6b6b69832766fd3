"""Plain reference of the near-threshold detector: ``bench/reference.py``'s
fold, with the paper's online DVFS controller and the TOS macro's write
errors (NM-TOS, arXiv:2512.02346, §III-B and §V-C).  It imports nothing of
``repro``; the STCF and the Harris response are ``bench/reference.py``'s.

One lane's stream is folded in chunks of ``chunk`` events, as there, with
two additions:

1. **The operating point** of each chunk, from the paper's 3-counter
   round-robin rate estimator (Fig. 2(b)), written out.  Timestamps are
   rebased as a serving session rebases them (the first event's time
   rounded down to a multiple of TW/2).  Each counter integrates events
   over a half window of TW/2 and saturates at ``2^counter_bits - 1``;
   the two closed counters, those of the two half windows before the one
   that holds a chunk's first event, give the rate estimate, their sum
   over TW in events/us (a float32 divide).  The chunk runs at the lowest
   Vdd of ``OP_POINTS`` (above ``vdd_floor``) whose capacity is at least
   the estimate times ``headroom``, else at the highest.
2. **Write errors** after each chunk's TOS update.  The lane's key is
   ``jax.random.PRNGKey(lane_seed)``; every chunk splits it once
   (``key, sub = split(key)``) and draws ``bernoulli(sub, ber, (H, W,
   5))`` at the chunk's BER, one draw per stored bit.  The macro stores
   5 bits of a pixel: code 0 is value 0 and code ``c`` in 1..31 is value
   ``224 + c`` (225..255); a pixel whose code is 0 skips write-back and
   keeps 0, every other code is xor-ed with its 5 drawn bits and decoded.
   The Harris refresh then reads the corrupted surface.  ``jax.random``
   is used only as the definition of the random bits (threefry2x32, the
   contract the program states), on the CPU device where there is one.

Departures from the macro, as the program models it too: one draw per
stored bit per chunk, on the whole surface at once, rather than one at
each row write-back of each event's patch; surface values between 1 and
224 (below the threshold, never held with the default ``th`` of 225)
store as code 0 while errors are drawn.  A configuration without the
online controller or without write errors is checked by
``bench/reference.py``.
"""
# no ``from __future__ import annotations``: the harness loads this file
# without registering it in ``sys.modules``, where a dataclass with string
# annotations would look its module up
import dataclasses
import functools

import numpy as np

from bench import reference
from bench.reference import LaneResult, compare_lane, harris_at, lane_state, stcf

__all__ = ["Detector", "OP_POINTS", "run_lane", "lane_state", "compare_lane",
           "vdd_indices", "chunk_bers"]

# The operating points, ascending Vdd: (Vdd in V, capacity in Meps, BER).
# The BER is the paper's Monte-Carlo SPICE result (§V-C: 0 above 0.62 V).
# The capacity, 1 / patch latency, comes from the program's alpha-power
# fit of the paper's two endpoints (16 ns at 1.2 V, 203 ns at 0.6 V per
# 7x7 patch; Vth 0.35 V): the paper gives no intermediate points.
# Compared in float32, as the controller's hardware twin compares.
OP_POINTS = (
    (0.6, 4.9261083743842375, 0.025),
    (0.7, 10.295889791242693, 0.0),
    (0.8, 17.530962287224614, 0.0),
    (0.9, 26.51686193677398, 0.0),
    (1.0, 37.14993683222729, 0.0),
    (1.1, 49.34020625610979, 0.0),
    (1.2, 63.01020408163265, 0.0),
)
_BASE = 224            # value of code c > 0 is _BASE + c
_BITS = 5              # stored bits of a pixel


@dataclasses.dataclass(frozen=True)
class Detector(reference.Detector):
    tw_us: int = 10_000
    counter_bits: int = 20
    headroom: float = 1.25
    vdd_floor: float = 0.6

    @classmethod
    def from_config(cls, config: dict) -> "Detector":
        d = config["detector"]
        if not (d["dvfs"] and d.get("dvfs_online") and d["inject_ber"]):
            raise ValueError("this reference models the online DVFS "
                             "controller with write errors injected")
        c = d["dvfs_cfg"]
        return cls(**dataclasses.asdict(reference.Detector.from_config(
            config)), tw_us=c["tw_us"], counter_bits=c["counter_bits"],
            headroom=c["headroom"], vdd_floor=c["vdd_floor"])


def op_points(det: Detector) -> tuple:
    """``(vdd, caps float32, ber float32)`` of the points the controller
    may pick: those at or above ``vdd_floor``."""
    pts = [p for p in OP_POINTS if p[0] >= det.vdd_floor - 1e-9]
    return (np.array([p[0] for p in pts]),
            np.array([p[1] for p in pts], np.float32),
            np.array([p[2] for p in pts], np.float32))


def vdd_indices(ts: np.ndarray, det: Detector) -> np.ndarray:
    """The operating-point index (into ``op_points``) of each whole chunk
    of a time-sorted stream."""
    n = (len(ts) // det.chunk) * det.chunk
    ts = np.asarray(ts[:n], np.int64)
    if not n:
        return np.zeros(0, np.int64)
    half = det.tw_us // 2
    base = (int(ts[0]) // half) * half          # the session's rebase
    win = (ts - base) // half
    counts = np.bincount(win)                   # every event ever folded
    sat = (1 << det.counter_bits) - 1
    _, caps, _ = op_points(det)
    out = np.empty(n // det.chunk, np.int64)
    for c, w in enumerate(win[::det.chunk].tolist()):
        closed = [min(int(counts[k]), sat) if k >= 0 else 0
                  for k in (w - 1, w - 2)]
        est = np.float32(sum(closed)) / np.float32(det.tw_us)
        ok = np.flatnonzero(caps >= est * np.float32(det.headroom))
        out[c] = ok[0] if ok.size else caps.size - 1
    return out


def chunk_bers(ts: np.ndarray, det: Detector) -> np.ndarray:
    """The write BER of each whole chunk (float32)."""
    return op_points(det)[2][vdd_indices(ts, det)]


@functools.lru_cache(maxsize=None)
def _draw(shape: tuple):
    """Jitted ``(flips(key, p), split(key))`` for a surface of ``shape``."""
    import jax

    def flips(key, p):
        return jax.random.bernoulli(key, p, shape + (_BITS,))

    return jax.jit(flips), jax.jit(jax.random.split)


def _cpu():
    """The CPU device where JAX has one (the random bits are the same on
    every backend), else None: the default device."""
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def write_errors(tos: np.ndarray, flips: np.ndarray) -> np.ndarray:
    """``tos`` (int16, H x W) after one write pass whose stored bits flip
    where ``flips`` (H x W x 5 bool) is set."""
    code = np.where(tos > _BASE, tos - _BASE, 0)
    mask = (flips.astype(np.int16) << np.arange(_BITS, dtype=np.int16)).sum(-1)
    code = np.where(code > 0, code ^ mask, 0)
    return np.where(code > 0, code + _BASE, 0).astype(np.int16)


def run_lane(xy: np.ndarray, ts: np.ndarray, det: Detector,
             precision: str = "float64", *, lane_seed: int) -> LaneResult:
    """Fold one lane's stream (whole chunks only) and score every event;
    ``lane_seed`` is the seed the pool gave the lane's state, whose key
    draws the write errors."""
    import jax

    n = (len(ts) // det.chunk) * det.chunk
    xy = np.asarray(xy[:n], np.int64)
    ts = np.asarray(ts[:n], np.int64)
    keep, sae, fired = stcf(xy, ts, det)
    h, w, r, th = det.height, det.width, det.patch // 2, det.th
    tos = np.zeros((h, w), np.int16)
    dt = np.float32 if precision == "bfloat16" else np.float64
    scores = np.full(n, -np.inf, dt)
    chunks = n // det.chunk
    bers = chunk_bers(ts, det)
    flips, split = _draw((h, w))
    # a key committed to the CPU keeps every draw there
    key = jax.device_put(jax.random.PRNGKey(lane_seed), _cpu())
    ready = False
    lut_tos = tos.astype(np.uint8)
    for c in range(chunks):
        lo, hi = c * det.chunk, (c + 1) * det.chunk
        for i in np.flatnonzero(keep[lo:hi]) + lo:       # Algorithm 1
            x, y = int(xy[i, 0]), int(xy[i, 1])
            win = tos[max(0, y - r):y + r + 1, max(0, x - r):x + r + 1]
            win -= 1
            win[win < th] = 0
            tos[y, x] = 255
        key, sub = split(key)
        if bers[c] > 0:
            tos = write_errors(tos, np.asarray(flips(sub, bers[c])))
        if (c + 1) % det.lut_every_chunks == 0:
            ready = True
            lut_tos = tos.astype(np.uint8)
            nxt = slice(hi, min(n, hi + det.lut_every_chunks * det.chunk))
            idx = np.flatnonzero(keep[nxt]) + hi
            if idx.size:
                scores[idx] = harris_at(tos, xy[idx, 1], xy[idx, 0], det,
                                        precision)
    return LaneResult(keep, scores, tos.astype(np.uint8), sae, fired,
                      ready, lut_tos, chunks)
