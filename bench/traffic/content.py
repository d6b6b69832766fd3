"""What the benchmark's cameras see: a vectorised shapes_dof analogue.

A copy of the idea of ``repro.events.synthetic.shapes_stream`` (a few
polygons translating and rotating, events on their edges, uniform
background noise), kept here so the yardstick does not move when the
program's own generators do.  Polygon sizes and speeds scale with the
sensor, so a 1280x720 frame sees the same scene as a 180x240 one.

``LaneContent`` gives every lane a stream of its own: ``bases`` recordings
are generated, lane ``i`` replays recording ``i % bases`` with its columns
rotated by ``7 * (i // bases)`` pixels, and a recording that runs out
loops, its timestamps carried on.
"""
from __future__ import annotations

import numpy as np

NATIVE_RATE_PER_US = 0.27          # 0.25 signal + 0.02 noise events per us
_SIGNAL_PER_US = 0.25
_NOISE_PER_US = 0.02


def _polygon(n_vertices: int, radius: float, rng) -> np.ndarray:
    ang = np.sort(rng.uniform(0, 2 * np.pi, n_vertices))
    ang = ang + np.linspace(0, 2 * np.pi, n_vertices, endpoint=False)
    ang = np.sort(np.mod(ang, 2 * np.pi))
    r = radius * rng.uniform(0.75, 1.0, n_vertices)
    return np.stack([r * np.cos(ang), r * np.sin(ang)], axis=1)


def shapes(height: int, width: int, n_events: int, rng,
           n_shapes: int = 3) -> tuple[np.ndarray, np.ndarray]:
    """Exactly ``n_events`` time-sorted events: ``(xy int32 (n, 2) as
    (col, row), ts int64 (n,) microseconds from 0)``."""
    scale = min(height / 180.0, width / 240.0)
    dur = int(n_events / NATIVE_RATE_PER_US * 1.3) + 1000
    xs, ys, ts = [], [], []
    for _ in range(n_shapes):
        nv = int(rng.integers(3, 7))
        base = _polygon(nv, rng.uniform(18, 32) * scale, rng)
        c0 = np.array([rng.uniform(40 * scale, width - 40 * scale),
                       rng.uniform(30 * scale, height - 30 * scale)])
        vel = rng.uniform(-60e-6, 60e-6, 2) * scale
        omg = rng.uniform(-3e-6, 3e-6)
        n = int(rng.poisson(_SIGNAL_PER_US / n_shapes * dur))
        t = np.sort(rng.uniform(0, dur, n)).astype(np.int64)
        a = omg * t
        cos, sin = np.cos(a)[:, None], np.sin(a)[:, None]
        vx = base[None, :, 0] * cos - base[None, :, 1] * sin + c0[0] + vel[0] * t[:, None]
        vy = base[None, :, 0] * sin + base[None, :, 1] * cos + c0[1] + vel[1] * t[:, None]
        edge = rng.integers(0, nv, n)
        lam = rng.uniform(0, 1, n)
        rows = np.arange(n)
        nxt = (edge + 1) % nv
        px = vx[rows, edge] + lam * (vx[rows, nxt] - vx[rows, edge])
        py = vy[rows, edge] + lam * (vy[rows, nxt] - vy[rows, edge])
        px = px + rng.normal(0, 0.4, n)
        py = py + rng.normal(0, 0.4, n)
        xs.append(np.clip(np.round(px), 0, width - 1))
        ys.append(np.clip(np.round(py), 0, height - 1))
        ts.append(t)
    n = int(rng.poisson(_NOISE_PER_US * dur))
    ts.append(np.sort(rng.uniform(0, dur, n)).astype(np.int64))
    xs.append(rng.integers(0, width, n))
    ys.append(rng.integers(0, height, n))
    t = np.concatenate(ts)
    order = np.argsort(t, kind="stable")[:n_events]
    if order.size < n_events:
        raise RuntimeError(f"shapes gave {order.size} < {n_events} events")
    xy = np.stack([np.concatenate(xs), np.concatenate(ys)], 1)
    return xy[order].astype(np.int32), t[order]


class LaneContent:
    """Per-lane event content for a fleet of ``height`` x ``width`` cameras."""

    def __init__(self, height: int, width: int, seed: int, *,
                 bases: int = 16, length: int = 1 << 16):
        self.height, self.width = height, width
        self.bases, self.length = bases, length
        recs = [shapes(height, width, length,
                       np.random.default_rng([seed, 7, b]))
                for b in range(bases)]
        self.xy = np.stack([r[0] for r in recs])          # (bases, L, 2)
        ts = np.stack([r[1] for r in recs])               # (bases, L)
        self.ts = ts - ts[:, :1]
        # one loop of a recording lasts its span plus one mean gap
        self.period = self.ts[:, -1] + int(round(1 / NATIVE_RATE_PER_US))

    def events(self, lanes, starts, n: int):
        """Events ``starts[j] .. starts[j] + n`` of lane ``lanes[j]``, for
        every ``j`` at once: ``(xy (m, n, 2) int32, ts (m, n) int64)``, with
        ``ts`` the content's own time from the lane's first event."""
        lanes = np.asarray(lanes, np.int64).reshape(-1)
        starts = np.asarray(starts, np.int64).reshape(-1)
        b = lanes % self.bases
        k = starts[:, None] + np.arange(n)[None, :]
        idx = k % self.length
        xy = self.xy[b[:, None], idx]
        shift = (7 * (lanes // self.bases)) % self.width
        xy[..., 0] = (xy[..., 0] + shift[:, None]) % self.width
        ts = self.ts[b[:, None], idx] + (k // self.length) * self.period[b][:, None]
        return xy.astype(np.int32), ts
