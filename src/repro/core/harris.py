"""Frame-by-frame Harris response over the TOS (luvHarris's FBF half).

The paper runs the standard Harris operator on the latest TOS snapshot to
build a corner look-up table (LUT); incoming events are tagged as corners by
reading the LUT at their coordinates.  The paper notes this half is cheap on
a CNN accelerator (~236 Mops for 1280x720 with 5x5 Sobel/window); we make it
first-class with a Pallas conv kernel (``repro.kernels.harris_conv``) and keep
this pure-jnp version as the oracle / CPU path.

Pipeline:  g = Sobel(TOS);  M = window * [gx^2, gx*gy; gx*gy, gy^2];
           R = det(M) - k * trace(M)^2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "sobel_kernels",
    "harris_response",
    "corner_lut",
    "score_events",
]

DEFAULT_K = 0.04
DEFAULT_SOBEL = 5
DEFAULT_WINDOW = 5


def _pascal_row(n: int) -> np.ndarray:
    row = np.array([1.0])
    for _ in range(n - 1):
        row = np.convolve(row, [1.0, 1.0])
    return row


def sobel_kernels(size: int = DEFAULT_SOBEL) -> tuple[np.ndarray, np.ndarray]:
    """Separable extended Sobel: smooth (Pascal) x derivative (diff of Pascal)."""
    smooth = _pascal_row(size)
    deriv = np.convolve(_pascal_row(size - 1), [1.0, -1.0])
    gx = np.outer(smooth, deriv)
    gy = np.outer(deriv, smooth)
    # Normalise so the response scale is stable across sobel sizes.
    gx = gx / np.abs(gx).sum()
    gy = gy / np.abs(gy).sum()
    return gx.astype(np.float32), gy.astype(np.float32)


def _conv2_valid(img: jax.Array, ker: np.ndarray) -> jax.Array:
    """2-D valid correlation as an explicitly-unrolled shift-and-add.

    Deliberately NOT ``lax.conv``: the XLA runtime convolution lowers
    differently at top level vs inside a ``lax.scan`` body (different
    accumulation order), which would break the bit-exactness contract
    between the host-loop reference pipeline and the device-resident scan.
    A fixed left-fold over the (static, small) kernel taps emits identical
    HLO — hence identical floats — in both contexts.
    """
    ker = np.asarray(ker)
    kh, kw = ker.shape
    h = img.shape[0] - kh + 1
    w = img.shape[1] - kw + 1
    img = img.astype(jnp.float32)
    out = jnp.zeros((h, w), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            tap = float(ker[i, j])
            if tap == 0.0:
                continue
            out = out + tap * jax.lax.slice(img, (i, j), (i + h, j + w))
    return out


@functools.partial(jax.jit, static_argnames=("sobel_size", "window_size", "k"))
def harris_response(
    tos: jax.Array,
    *,
    sobel_size: int = DEFAULT_SOBEL,
    window_size: int = DEFAULT_WINDOW,
    k: float = DEFAULT_K,
) -> jax.Array:
    """Harris corner response map (float32, same shape as the surface).

    Boundary convention: the surface is zero-padded ONCE by the full halo
    (sobel//2 + window//2), then both conv stages are 'valid' — the exact
    semantics of the Pallas kernel (single padded VMEM image, valid taps),
    so kernel and oracle agree to float tolerance everywhere including
    borders.

    The whole response is fenced with ``optimization_barrier`` for the same
    reason ``_conv2_valid`` avoids ``lax.conv``: the shift-and-add emits
    identical HLO in every context, but XLA:CPU may still *contract* the
    ``tap * slice + acc`` chain into FMAs differently depending on what the
    surrounding program fuses in (observed: one-ULP LUT drift when the
    refresh sits next to the inlined interpret-mode fused Pallas step inside
    the pool's scan-of-cond executor).  The barriers pin the conv region's
    fusion boundary so its rounding is program-context independent — the
    property every cross-path bit-exactness test in the suite leans on.
    """
    halo = sobel_size // 2 + window_size // 2
    img = tos.astype(jnp.float32) / 255.0
    img = jax.lax.optimization_barrier(jnp.pad(img, halo))
    gxk, gyk = sobel_kernels(sobel_size)
    gx = _conv2_valid(img, gxk)
    gy = _conv2_valid(img, gyk)
    win = np.ones((window_size, window_size), np.float32) / float(window_size**2)
    a = _conv2_valid(gx * gx, win)
    b = _conv2_valid(gy * gy, win)
    c = _conv2_valid(gx * gy, win)
    det = a * b - c * c
    tr = a + b
    return jax.lax.optimization_barrier(det - k * tr * tr)


def corner_lut(
    tos: jax.Array,
    *,
    sobel_size: int = DEFAULT_SOBEL,
    window_size: int = DEFAULT_WINDOW,
    k: float = DEFAULT_K,
) -> jax.Array:
    """Alias emphasising the paper's usage: the FBF response *is* the LUT."""
    return harris_response(tos, sobel_size=sobel_size, window_size=window_size, k=k)


@jax.jit
def score_events(lut: jax.Array, xy: jax.Array, valid: jax.Array) -> jax.Array:
    """Read the Harris LUT at each event's pixel (the EBE corner tagging)."""
    scores = lut[xy[:, 1], xy[:, 0]]
    return jnp.where(valid, scores, -jnp.inf)
