"""Pallas kernels vs pure-jnp oracles: shape sweeps, interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_events, make_tos
from repro.kernels import ops, ref

TOS_CASES = [
    (64, 64, 16, 7, 225),
    (180, 240, 96, 7, 225),       # DAVIS240
    (100, 130, 33, 5, 240),
    (128, 200, 128, 9, 200),
    (260, 350, 64, 3, 225),       # > one tile each way
]


@pytest.mark.parametrize("h,w,e,patch,th", TOS_CASES)
@pytest.mark.parametrize("mode", ["nmc", "batched", "nmc_binned",
                                  "batched_binned"])
def test_tos_kernel_vs_oracle(rng, h, w, e, patch, th, mode):
    xy, valid = make_events(rng, h, w, e)
    t0 = jnp.asarray(make_tos(rng, h, w, th))
    gold = ref.tos_seq_ref(t0, jnp.asarray(xy), jnp.asarray(valid),
                           patch=patch, th=th)
    out = ops.tos_update_op(t0, jnp.asarray(xy), jnp.asarray(valid),
                            patch=patch, th=th, mode=mode)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(gold))


HARRIS_CASES = [
    (64, 96, 5, 5), (180, 240, 5, 5), (128, 128, 7, 7), (90, 150, 3, 5),
    (181, 241, 5, 3),                  # non-multiple-of-strip sizes
]


@pytest.mark.parametrize("h,w,sobel,win", HARRIS_CASES)
def test_harris_kernel_vs_oracle(rng, h, w, sobel, win):
    t = jnp.asarray(make_tos(rng, h, w))
    out = ops.harris_response_op(t, sobel_size=sobel, window_size=win)
    gold = ref.harris_ref(t, sobel_size=sobel, window_size=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               atol=1e-6, rtol=1e-5)


def test_harris_dtype_f32_path(rng):
    """uint8 and pre-scaled float inputs must agree."""
    t = make_tos(rng, 64, 64)
    a = ops.harris_response_op(jnp.asarray(t))
    b = ref.harris_ref(jnp.asarray(t))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_tos_kernel_empty_chunk(rng):
    """All-invalid chunk: surface unchanged."""
    t0 = jnp.asarray(make_tos(rng, 64, 64))
    xy = jnp.zeros((16, 2), jnp.int32)
    valid = jnp.zeros((16,), bool)
    for mode in ("nmc", "batched", "nmc_binned", "batched_binned"):
        out = ops.tos_update_op(t0, xy, valid, mode=mode)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(t0))


# ---------------------------------------------------------------------------
# Interpret-mode resolution: explicit kwarg > env var > backend auto.  The
# env is consulted at *call* time (not import time), so flipping it
# mid-process must take effect.
# ---------------------------------------------------------------------------


def test_default_interpret_env_precedence(monkeypatch):
    import jax as _jax

    auto = _jax.default_backend() != "tpu"
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert ops.default_interpret() is auto
    # falsy spellings force compiled regardless of backend
    for off in ("", "0", "false", "no", " FALSE ", " 0 "):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", off)
        assert ops.default_interpret() is False, repr(off)
    # anything else forces interpret
    for on in ("1", "true", "yes", "interpret"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", on)
        assert ops.default_interpret() is True, repr(on)


def test_resolve_interpret_kwarg_beats_env(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.resolve_interpret(False) is False
    assert ops.resolve_interpret(None) is True


def test_env_flip_takes_effect_per_call(rng, monkeypatch):
    """The op wrappers resolve interpret outside the jit cache: the same
    Python callable honours an env flip between calls (the old import-time
    read would have frozen the first value)."""
    t0 = jnp.asarray(make_tos(rng, 64, 64))
    xy = jnp.zeros((8, 2), jnp.int32)
    valid = jnp.zeros((8,), bool)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    a = ops.tos_update_op(t0, xy, valid, mode="nmc")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    # on a CPU host the compiled path would fail inside pallas_call if it
    # were actually taken with a TPU-only kernel; the nmc kernel lowers on
    # CPU interpret only — so just assert the resolver output flipped and
    # the interpret call above produced the oracle result.
    assert ops.default_interpret() is False
    np.testing.assert_array_equal(np.asarray(a), np.asarray(t0))


def test_harris_op_refuses_over_vmem_budget():
    """A surface past the kernel's VMEM budget is an error, never a silent
    fallback to the jnp oracle."""
    t = jnp.zeros((720, 1280), jnp.uint8)
    with pytest.raises(ValueError, match="VMEM"):
        ops.harris_response_op(t)
