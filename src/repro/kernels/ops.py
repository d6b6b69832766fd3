"""Jit'd public wrappers over the Pallas kernels.

``tos_update_op``      — chunked TOS update.  mode='nmc' streams events
                         through the VMEM-resident tile (paper-faithful);
                         mode='batched' uses the fused MXU formulation
                         (beyond-paper).
``fused_step_op``      — the whole per-chunk inner pipeline (STCF -> TOS ->
                         BER -> LUT score) in one kernel, VMEM-resident end
                         to end (``backend="pallas_fused"``; see
                         ``kernels.fused_step``).
``harris_response_op`` — Pallas Harris; a surface over its VMEM budget
                         is an error (no kernel falls back to a reference).
``compact_slots_op``   — device-side stream compaction of dense ring
                         result slots into kept-corner records
                         (``kernels.compact``; the D2H readout diet for
                         ``readout="compact"`` pools).

All auto-pad surfaces to tile multiples and crop back, so callers keep
native sensor shapes (e.g. DAVIS240's 180 x 240).

Interpret-mode resolution (every op takes ``interpret=``):

    explicit kwarg  >  REPRO_PALLAS_INTERPRET env var  >  backend auto

The env var is read per call — not at import time — so a test or a launch
script can flip it without re-importing; ``PipelineConfig.interpret`` threads
the kwarg through every backend route.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from repro.core.stcf import _NEVER
from repro.core.tos import (
    DEFAULT_PATCH,
    DEFAULT_TH,
    TOS_MAX,
    _clamp_threshold,
    _scatter_last_center_value,
    _suffix_cover_counts,
)
from repro.kernels import compact, fused_step, harris_conv, tos_update

__all__ = [
    "tos_update_op",
    "fused_step_op",
    "harris_response_op",
    "compact_slots_op",
    "default_interpret",
    "resolve_interpret",
]

_INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def default_interpret() -> bool:
    """Pallas interpret mode unless the process is actually on a TPU.

    ``REPRO_PALLAS_INTERPRET`` overrides the auto choice ("0"/"false"/""
    forces compiled, anything else forces interpret); it is consulted at
    *call* time so flipping the env mid-process takes effect.  An explicit
    ``interpret=`` kwarg on any op beats both — see ``resolve_interpret``.
    """
    env = os.environ.get(_INTERPRET_ENV)
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "no")
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Per-call interpret resolution: explicit kwarg > env > backend auto."""
    if interpret is None:
        return default_interpret()
    return bool(interpret)


def _pad_to_tiles(tos: jax.Array) -> tuple[jax.Array, tuple[int, int]]:
    h, w = tos.shape
    hp = -h % tos_update.TILE_H
    wp = -w % tos_update.TILE_W
    return jnp.pad(tos, ((0, hp), (0, wp))), (h, w)


def tos_update_op(
    tos: jax.Array,
    xy: jax.Array,
    valid: jax.Array,
    *,
    patch: int = DEFAULT_PATCH,
    th: int = DEFAULT_TH,
    mode: str = "batched",
    interpret: bool | None = None,
) -> jax.Array:
    """Chunked TOS update through the Pallas kernels (order-exact).

    ``interpret=None`` resolves via ``resolve_interpret`` (env var, then
    backend auto) so callers can stay backend-agnostic — compiled on TPU,
    interpreter elsewhere.  Resolution happens *outside* the jit cache so a
    flipped env var retraces instead of hitting a stale entry.
    """
    return _tos_update_jit(
        tos, xy, valid, patch=patch, th=th, mode=mode,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(
    jax.jit, static_argnames=("patch", "th", "mode", "interpret")
)
def _tos_update_jit(
    tos: jax.Array,
    xy: jax.Array,
    valid: jax.Array,
    *,
    patch: int,
    th: int,
    mode: str,
    interpret: bool,
) -> jax.Array:
    padded, (h, w) = _pad_to_tiles(tos)
    if mode == "nmc":
        out = tos_update.nmc_stream_call(
            padded, xy, valid, patch=patch, th=th, interpret=interpret
        )
    elif mode == "nmc_binned":
        out = tos_update.nmc_stream_binned_call(
            padded, xy, valid, patch=patch, th=th, interpret=interpret
        )
    elif mode in ("batched", "batched_binned"):
        r = (patch - 1) // 2
        k_after = _suffix_cover_counts(xy, valid, r)
        centre_vals = _clamp_threshold(TOS_MAX - k_after, th)
        centre_surf = _scatter_last_center_value(
            padded.shape, xy, valid, centre_vals
        )
        call = (tos_update.batched_fused_binned_call
                if mode == "batched_binned" else tos_update.batched_fused_call)
        out = call(
            padded, xy, valid, centre_surf, patch=patch, th=th,
            interpret=interpret,
        )
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return out[:h, :w]


def fused_step_op(
    tos: jax.Array,
    sae: jax.Array,
    lut: jax.Array,
    xy: jax.Array,
    ts: jax.Array,
    valid: jax.Array,
    ber: jax.Array | None = None,
    bits: jax.Array | None = None,
    *,
    patch: int = DEFAULT_PATCH,
    th: int = DEFAULT_TH,
    support: int = 2,
    tw: int = 5000,
    stcf_enabled: bool = True,
    inject_ber: bool = False,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused chunk step: STCF -> TOS -> BER -> LUT score in one kernel.

    Returns ``(new_tos, new_sae, keep, scores_raw)``; ``scores_raw`` is
    ``where(keep, lut[y, x], -inf)`` — the caller applies the ``lut_ready``
    gate (a scalar select), exactly like ``harris.score_events`` composition
    in the jnp step.  With ``inject_ber`` the caller supplies the Bernoulli
    ``bits`` (``ber.write_error_bits``) and the traced ``ber`` scalar, so
    the randomness discipline is shared with the oracle.
    """
    return _fused_step_jit(
        tos, sae, lut, xy, ts, valid, ber, bits,
        patch=patch, th=th, support=support, tw=tw,
        stcf_enabled=stcf_enabled, inject_ber=inject_ber,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "patch", "th", "support", "tw", "stcf_enabled", "inject_ber",
        "interpret",
    ),
)
def _fused_step_jit(
    tos, sae, lut, xy, ts, valid, ber, bits, *,
    patch, th, support, tw, stcf_enabled, inject_ber, interpret,
):
    h, w = tos.shape
    r = (patch - 1) // 2
    sub, lanes = fused_step.SUBLANES, fused_step.LANES
    hp = max(-(-h // sub) * sub, fused_step.window_rows(fused_step.RS),
             fused_step.window_rows(r))
    wp = -(-w // lanes) * lanes
    pad = ((0, hp - h), (0, wp - w))
    tos_p = jnp.pad(tos.astype(jnp.int32), pad)
    lut_p = jnp.pad(lut, pad)
    # out-of-surface neighbours read as "never fired" (== the oracle's
    # in-bounds mask)
    sae_p = jnp.pad(sae, pad, constant_values=_NEVER)
    ev = jnp.stack(
        [xy[:, 0].astype(jnp.int32), xy[:, 1].astype(jnp.int32),
         ts.astype(jnp.int32), valid.astype(jnp.int32)],
        axis=1,
    )
    if inject_ber:
        bits_p = jnp.pad(bits, pad)
        ber_arg = jnp.asarray(ber)
    else:
        bits_p, ber_arg = None, None
    tos_o, sae_o, keep, scores = fused_step.fused_chunk_step_call(
        tos_p, sae_p, lut_p, ev, bits_p, ber_arg,
        patch=patch, th=th, support=support, tw=tw,
        stcf_enabled=stcf_enabled, interpret=interpret,
    )
    return (tos_o[:h, :w].astype(tos.dtype), sae_o[:h, :w],
            keep[0].astype(bool), scores[0])


def compact_slots_op(
    scores: jax.Array,
    keep: jax.Array,
    *,
    cap: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pack dense result slots into kept-corner records on device.

    ``scores``/``keep`` carry any leading batch shape over a trailing
    event axis ``(..., E)``; returns ``(idx (..., cap) i32,
    val (..., cap) f32, count (...,) i32)`` where record ``j`` of a slot
    is its j-th kept event in stream order (``ref.compact_ref`` is the
    oracle).  ``count`` is the total kept — ``count > cap`` flags
    overflow; the records themselves stop at ``cap`` and the caller keeps
    the dense slot as the lossless fallback.
    """
    return _compact_slots_jit(
        scores, keep, cap=cap, interpret=resolve_interpret(interpret)
    )


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def _compact_slots_jit(scores, keep, *, cap, interpret):
    lead = scores.shape[:-1]
    e = scores.shape[-1]
    flat = math.prod(lead)
    idx, val, cnt = compact.compact_slots_call(
        scores.reshape(flat, e).astype(jnp.float32),
        keep.reshape(flat, e).astype(jnp.int32),
        cap=cap, interpret=interpret,
    )
    return (idx.reshape(*lead, cap), val.reshape(*lead, cap),
            cnt.reshape(lead))


def harris_response_op(
    tos: jax.Array,
    *,
    sobel_size: int = 5,
    window_size: int = 5,
    k: float = 0.04,
    interpret: bool | None = None,
) -> jax.Array:
    return _harris_response_jit(
        tos, sobel_size=sobel_size, window_size=window_size, k=k,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(
    jax.jit, static_argnames=("sobel_size", "window_size", "k", "interpret")
)
def _harris_response_jit(
    tos: jax.Array,
    *,
    sobel_size: int,
    window_size: int,
    k: float,
    interpret: bool,
) -> jax.Array:
    h, w = tos.shape
    budget = 16 * 2**20  # one v5e core's VMEM, conservative
    need = harris_conv.vmem_bytes(h, w, sobel_size, window_size)
    if need > budget:
        raise ValueError(
            f"{h}x{w} Harris needs {need} B of VMEM, over the kernel's "
            f"{budget} B budget"
        )
    return harris_conv.harris_call(
        tos, sobel_size=sobel_size, window_size=window_size, k=k,
        interpret=interpret,
    )
