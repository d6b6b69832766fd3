"""Pallas TPU kernel for the FBF Harris response over the TOS.

Strategy: the padded surface sits in VMEM (event-camera sensors are small —
1280x720 f32 is 3.7 MB, well inside a v5e core's VMEM); the grid walks
output row-strips, each instance computing separable Sobel gradients and the
windowed structure tensor with shift-and-add over static taps (pure VPU
work, no gather).  Strip overlap (halo) is read directly from the VMEM-
resident input, which Pallas allows because the input block is the whole
array.

The kernel documents its VMEM budget in ``vmem_bytes``; the wrapper
(``ops.harris_response_op``) refuses a sensor beyond it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.harris import sobel_kernels

__all__ = ["harris_call", "vmem_bytes"]

STRIP = 64  # output rows per grid step


def vmem_bytes(h: int, w: int, sobel: int, window: int) -> int:
    m = sobel // 2 + window // 2
    return 4 * (h + 2 * m) * (w + 2 * m) * 6  # img + gx/gy + a/b/c working set


def _pascal(n: int) -> np.ndarray:
    row = np.array([1.0])
    for _ in range(n - 1):
        row = np.convolve(row, [1.0, 1.0])
    return row


def _sep_taps(size: int):
    smooth = _pascal(size)
    deriv = np.convolve(_pascal(size - 1), [1.0, -1.0])
    # Normalisation matching core.harris.sobel_kernels (|outer| sums to 1).
    norm = np.abs(np.outer(smooth, deriv)).sum()
    return smooth / np.sqrt(norm), deriv / np.sqrt(norm)


def _conv1d_rows(x, taps, r):
    """Correlate along rows (axis 0) with static taps; 'valid' in axis 0."""
    out = None
    h = x.shape[0]
    for k, t in enumerate(taps):
        sl = x[k : h - 2 * r + k, :] * t
        out = sl if out is None else out + sl
    return out


def _conv1d_cols(x, taps, r):
    out = None
    w = x.shape[1]
    for k, t in enumerate(taps):
        sl = x[:, k : w - 2 * r + k] * t
        out = sl if out is None else out + sl
    return out


def _harris_kernel(img_ref, out_ref, *, sobel, window, k, strip, halo):
    si = pl.program_id(0)
    row0 = si * strip

    rs = sobel // 2
    rw = window // 2
    tile_h, tile_w = out_ref.shape

    # Input window: output strip + full halo on each side (rows), full width.
    win = img_ref[pl.ds(row0, strip + 2 * halo), :]

    smooth, deriv = _sep_taps(sobel)
    # gx = smooth over rows, deriv over cols;  gy = the transpose pairing.
    gx = _conv1d_cols(_conv1d_rows(win, smooth, rs), deriv, rs)
    gy = _conv1d_cols(_conv1d_rows(win, deriv, rs), smooth, rs)

    wtaps = np.ones(window) / window
    def box(z):
        return _conv1d_cols(_conv1d_rows(z, wtaps, rw), wtaps, rw)

    a = box(gx * gx)
    b = box(gy * gy)
    c = box(gx * gy)
    det = a * b - c * c
    tr = a + b
    out_ref[...] = (det - k * tr * tr)[:tile_h, :tile_w]


@functools.partial(
    jax.jit, static_argnames=("sobel_size", "window_size", "k", "interpret")
)
def harris_call(
    tos: jax.Array,
    *,
    sobel_size: int = 5,
    window_size: int = 5,
    k: float = 0.04,
    interpret: bool = True,
) -> jax.Array:
    """Harris response map (float32, same shape as ``tos``)."""
    h, w = tos.shape
    halo = sobel_size // 2 + window_size // 2
    img = tos.astype(jnp.float32) / 255.0
    # Pad: halo on all sides + strip alignment below.
    n_strips = pl.cdiv(h, STRIP)
    h_pad = n_strips * STRIP
    img_p = jnp.pad(img, ((halo, halo + (h_pad - h)), (halo, halo)))

    out = pl.pallas_call(
        functools.partial(
            _harris_kernel,
            sobel=sobel_size,
            window=window_size,
            k=k,
            strip=STRIP,
            halo=halo,
        ),
        grid=(n_strips,),
        in_specs=[pl.BlockSpec(img_p.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((STRIP, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h_pad, w), jnp.float32),
        interpret=interpret,
    )(img_p)
    return out[:h]
