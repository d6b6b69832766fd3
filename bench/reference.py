"""Plain reference of the detector, written from the paper's description
and independent of the code under test (it imports nothing of ``repro``).

One lane's stream is folded in chunks of ``chunk`` events, from the
stream's first event:

1. STCF, event by event: an event is kept iff at least ``support`` of its
   8 neighbouring pixels fired within the last ``tw`` microseconds; every
   event then writes its timestamp to its pixel of the SAE.
2. TOS (Algorithm 1), event by event for kept events: every pixel of the
   ``patch`` x ``patch`` window (clipped at the border) loses 1 and drops
   to 0 below ``th``; the event's own pixel becomes 255.
3. Scores: a kept event reads the Harris LUT built at the last refresh
   before its chunk (``-inf`` before the first refresh and for events the
   STCF drops).
4. After chunk ``c`` with ``(c + 1) % lut_every == 0`` the LUT is rebuilt
   from the TOS: ``img = TOS / 255``, zero-padded; ``gx``, ``gy`` are the
   normalised extended-Sobel correlations; ``a, b, c`` the box means of
   ``gx^2, gy^2, gx*gy``; ``R = a*b - c^2 - k*(a + b)^2``.

The Harris response is computed only where it is read (each scored
pixel), from the 9x9 neighbourhood that defines it.  ``precision``
"float64" is the reference; "bfloat16" rounds the image, the kernels and every intermediate of the
response to bfloat16 with float32 sums — the control, the step below the
program's float32.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_NEVER = np.iinfo(np.int64).min // 4


def _pascal(n: int) -> np.ndarray:
    row = np.array([1.0])
    for _ in range(n - 1):
        row = np.convolve(row, [1.0, 1.0])
    return row


def sobel(size: int) -> tuple[np.ndarray, np.ndarray]:
    """Extended Sobel kernels (smooth x derivative), each divided by the
    sum of its absolute taps."""
    smooth = _pascal(size)
    deriv = np.convolve(_pascal(size - 1), [1.0, -1.0])
    kx = np.outer(smooth, deriv)
    ky = np.outer(deriv, smooth)
    return kx / np.abs(kx).sum(), ky / np.abs(ky).sum()


@dataclasses.dataclass(frozen=True)
class Detector:
    height: int
    width: int
    chunk: int
    patch: int
    th: int
    lut_every_chunks: int
    stcf_tw_us: int
    stcf_support: int
    sobel_size: int
    window_size: int
    harris_k: float

    @classmethod
    def from_config(cls, config: dict) -> "Detector":
        d = config["detector"]
        return cls(config["height"], config["width"], d["chunk"], d["patch"],
                   d["th"], d["lut_every_chunks"], d["stcf_tw_us"],
                   d["stcf_support"], d["sobel_size"], d["window_size"],
                   d["harris_k"])


def _bf16(x):
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def harris_at(tos: np.ndarray, ys, xs, det: Detector,
              precision: str = "float64", block: int = 4096) -> np.ndarray:
    """Harris response of surface ``tos`` at pixels ``(ys, xs)``."""
    q = _bf16 if precision == "bfloat16" else (lambda v: v)
    dt = np.float32 if precision == "bfloat16" else np.float64
    s, w = det.sobel_size, det.window_size
    halo = s // 2 + w // 2
    img = q(tos.astype(dt) / dt(255.0))
    pad = np.pad(img, halo)
    kx, ky = (q(k.astype(dt)) for k in sobel(s))
    views = np.lib.stride_tricks.sliding_window_view(
        pad, (2 * halo + 1, 2 * halo + 1))
    ys, xs = np.asarray(ys), np.asarray(xs)
    out = np.empty(ys.size, dt)
    for lo in range(0, ys.size, block):
        nb = views[ys[lo:lo + block], xs[lo:lo + block]]   # (n, 9, 9)
        sub = np.lib.stride_tricks.sliding_window_view(nb, (s, s), axis=(1, 2))
        gx = q(np.tensordot(sub, kx, axes=([3, 4], [0, 1])))   # (n, w, w)
        gy = q(np.tensordot(sub, ky, axes=([3, 4], [0, 1])))
        a = q(q(gx * gx).sum((1, 2)) / dt(w * w))
        b = q(q(gy * gy).sum((1, 2)) / dt(w * w))
        c = q(q(gx * gy).sum((1, 2)) / dt(w * w))
        d = q(q(a * b) - q(c * c))
        tr = q(a + b)
        out[lo:lo + block] = q(d - q(dt(det.harris_k) * q(tr * tr)))
    return out


@dataclasses.dataclass
class LaneResult:
    keep: np.ndarray        # (n,) bool
    scores: np.ndarray      # (n,) float, -inf where not scored
    tos: np.ndarray         # (H, W) uint8
    sae: np.ndarray         # (H, W) int64 absolute us; fired: sae_fired
    sae_fired: np.ndarray   # (H, W) bool
    lut_ready: bool
    lut_tos: np.ndarray     # (H, W) uint8: the TOS the last LUT was built on
    chunks: int


def stcf(xy: np.ndarray, ts: np.ndarray, det: Detector):
    """Keep mask and final SAE of a time-sorted stream (event by event)."""
    h, w = det.height, det.width
    wp = w + 2
    sae = [_NEVER] * ((h + 2) * wp)       # one-pixel border never fires
    neigh = [dy * wp + dx for dy in (-1, 0, 1) for dx in (-1, 0, 1)
             if dy or dx]
    tw, support = det.stcf_tw_us, det.stcf_support
    keep = np.zeros(len(ts), bool)
    xs = xy[:, 0].tolist()
    ys = xy[:, 1].tolist()
    for i, t in enumerate(ts.tolist()):
        p = (ys[i] + 1) * wp + xs[i] + 1
        n = 0
        for o in neigh:
            if t - sae[p + o] <= tw:
                n += 1
        keep[i] = n >= support
        sae[p] = t
    full = np.array(sae, np.int64).reshape(h + 2, wp)[1:-1, 1:-1]
    return keep, full, full != _NEVER


def run_lane(xy: np.ndarray, ts: np.ndarray, det: Detector,
             precision: str = "float64", *, lane_seed: int = 0) -> LaneResult:
    """Fold one lane's stream (whole chunks only) and score every event.
    ``lane_seed`` is the seed the pool gave the lane's state: this detector
    draws nothing (no write errors), so it is unused here; a reference of a
    configuration that injects them draws them from it."""
    n = (len(ts) // det.chunk) * det.chunk
    xy = np.asarray(xy[:n], np.int64)
    ts = np.asarray(ts[:n], np.int64)
    keep, sae, fired = stcf(xy, ts, det)
    h, w, r, th = det.height, det.width, det.patch // 2, det.th
    tos = np.zeros((h, w), np.int16)
    dt = np.float32 if precision == "bfloat16" else np.float64
    scores = np.full(n, -np.inf, dt)
    chunks = n // det.chunk
    ready = False
    lut_tos = tos.astype(np.uint8)
    for c in range(chunks):
        lo, hi = c * det.chunk, (c + 1) * det.chunk
        for i in np.flatnonzero(keep[lo:hi]) + lo:
            x, y = int(xy[i, 0]), int(xy[i, 1])
            win = tos[max(0, y - r):y + r + 1, max(0, x - r):x + r + 1]
            win -= 1
            win[win < th] = 0
            tos[y, x] = 255
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


def lut_active(tos: np.ndarray, det: Detector) -> np.ndarray:
    """Pixels whose Harris response can be non-zero: some TOS pixel within
    the response's halo is non-zero."""
    halo = det.sobel_size // 2 + det.window_size // 2
    nz = np.pad((tos > 0).astype(np.int64), halo)
    cs = nz.cumsum(0).cumsum(1)
    cs = np.pad(cs, ((1, 0), (1, 0)))
    k = 2 * halo + 1
    box = cs[k:, k:] - cs[:-k, k:] - cs[k:, :-k] + cs[:-k, :-k]
    return box > 0


def lane_state(ref: LaneResult, det: Detector, precision: str) -> dict:
    """The final state a detector computing in ``precision`` would hold, in
    the program's layout (SAE relative to the first event's time, never
    fired = -2**30): what the control puts in the program's place."""
    fired = ref.sae_fired
    base = ref.sae[fired].min() if fired.any() else 0
    lut = np.full(ref.tos.shape, -np.inf)
    if ref.lut_ready:
        lut[:] = 0.0
        ys, xs = np.nonzero(lut_active(ref.lut_tos, det))
        lut[ys, xs] = harris_at(ref.lut_tos, ys, xs, det, precision)
    return {"surface": ref.tos,
            "sae": np.where(fired, ref.sae - base, -(2 ** 30)),
            "lut": lut, "lut_ready": ref.lut_ready}


def compare_lane(got_scores, got_keep, state, ref: LaneResult,
                 det: Detector, score_limit: float = 0.0) -> dict:
    """Numbers that decide one lane: mismatch counts (exact) and the widest
    float gaps of the scores and the final LUT against the reference;
    ``bad_chunks`` counts chunks holding an event that is kept, scored or
    left unscored wrongly, or whose score is more than ``score_limit`` off."""
    n = ref.keep.size
    out = {"lost_events": max(0, n - got_scores.size),
           "extra_events": max(0, got_scores.size - n)}
    m = min(n, got_scores.size)
    s = np.asarray(got_scores[:m], np.float64)
    k = np.asarray(got_keep[:m], bool)
    out["kept_mismatch"] = int(np.count_nonzero(k != ref.keep[:m]))
    fin_got, fin_ref = np.isfinite(s), np.isfinite(ref.scores[:m])
    both = fin_got & fin_ref
    out["inf_mismatch"] = int(np.count_nonzero(fin_got != fin_ref))
    gap = np.zeros(m)
    gap[both] = np.abs(s[both] - ref.scores[:m][both])
    out["score_gap"] = float(np.max(gap, initial=0.0))
    bad = (k != ref.keep[:m]) | (fin_got != fin_ref) | (gap > score_limit)
    out["bad_chunks"] = int(np.unique(np.flatnonzero(bad) // det.chunk).size)
    out["tos_mismatch"] = int(np.count_nonzero(
        np.asarray(state["surface"]) != ref.tos))
    sae = np.asarray(state["sae"], np.int64)
    prog_fired = sae > -(2 ** 29)
    off = (ref.sae[ref.sae_fired] - sae[ref.sae_fired])
    out["sae_mismatch"] = int(
        np.count_nonzero(prog_fired != ref.sae_fired)
        + (np.count_nonzero(off != off[0]) if off.size else 0))
    lut = np.asarray(state["lut"], np.float64)
    if bool(state["lut_ready"]) != ref.lut_ready:
        out["inf_mismatch"] += 1
        out["lut_gap"] = float("inf")
    elif ref.lut_ready:
        act = lut_active(ref.lut_tos, det)
        ys, xs = np.nonzero(act)
        want = harris_at(ref.lut_tos, ys, xs, det)
        gap = np.max(np.abs(lut[ys, xs] - want), initial=0.0)
        out["lut_gap"] = float(max(gap, np.max(np.abs(lut[~act]),
                                                initial=0.0)))
    else:
        out["lut_gap"] = 0.0
    return out
