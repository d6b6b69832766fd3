"""The least time a chip needs for the detector's work, counted from the
algorithm and not from any implementation of it.

Per event (``event_cost``): the event's inputs (x, y, timestamp, valid:
13 B), the 3x3 SAE window read (9 x 4 B) and its write (4 B), the
``patch`` x ``patch`` TOS read and write (1 B a pixel each way), one LUT
read (4 B) and the outputs (score 4 B, keep 1 B).  Operations: 3 per SAE
neighbour (difference, compare, count) and 3 per TOS pixel (decrement,
compare, select).

Per due LUT refresh (``refresh_cost``), only for a lane whose chunk index
hits the refresh cadence: the surface read (1 B a pixel) and the LUT
write (4 B a pixel); operations per pixel: the scaling divide, the two
Sobel correlations over their non-zero taps (multiply and add each), the
three products, the three box sums (multiply and add per tap) and the
five operations of ``det - k * trace^2``.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

EVENT_IO_BYTES = 13 + 4 + 1


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def event_cost(patch: int) -> tuple[int, int]:
    """(operations, bytes) of one event."""
    ops = 8 * 3 + patch * patch * 3
    nbytes = EVENT_IO_BYTES + 9 * 4 + 4 + 2 * patch * patch + 4
    return ops, nbytes


def _sobel_taps(size: int) -> int:
    # the derivative row of an odd extended Sobel has a zero centre tap
    return size * (size - 1) if size % 2 else size * size


def refresh_cost(height: int, width: int, sobel: int,
                 window: int) -> tuple[int, int]:
    """(operations, bytes) of one full-frame LUT refresh."""
    px = height * width
    ops = px * (1 + 2 * 2 * _sobel_taps(sobel) + 3 + 3 * 2 * window * window
                + 5)
    return ops, px * (1 + 4)


def least_time_s(events: int, refreshes: int, detector: dict,
                 height: int, width: int, peak: dict) -> float:
    """The larger of bytes over peak bandwidth and operations over peak
    rate, for ``events`` events and ``refreshes`` due refreshes."""
    e_ops, e_bytes = event_cost(detector["patch"])
    r_ops, r_bytes = refresh_cost(height, width, detector["sobel_size"],
                                  detector["window_size"])
    ops = events * e_ops + refreshes * r_ops
    nbytes = events * e_bytes + refreshes * r_bytes
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["flops_per_s"])


def due_refreshes(first_chunk: np.ndarray, n_chunks: np.ndarray,
                  lut_every: int) -> int:
    """Refreshes due over chunks ``first .. first + n`` of each lane: chunk
    ``c`` refreshes when ``(c + 1) % lut_every == 0``."""
    first = np.asarray(first_chunk, np.int64)
    end = first + np.asarray(n_chunks, np.int64)
    return int(np.sum(end // lut_every - first // lut_every))


def share(least_s: float, busy_s: float) -> float:
    """The roofline share; a share over 1 means the work is over-counted or
    the busy time misses work, and is refused."""
    if busy_s <= 0:
        raise ValueError("no device busy time to take a roofline share of")
    s = least_s / busy_s
    if s > 1.0:
        raise ValueError(f"roofline share {s} > 1: least time {least_s} s "
                         f"over busy {busy_s} s")
    return s
