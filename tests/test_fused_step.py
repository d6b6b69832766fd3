"""Fused Pallas chunk-step megakernel vs the composed jnp oracle.

The contract (ISSUE 7): ``kernels.ops.fused_step_op`` runs STCF support
check, TOS patch update, BER write-error injection, and the per-event
Harris-LUT read in ONE ``pallas_call`` — and is bit-exact against the
composition of the individually-tested jnp ops it replaces
(``stcf_step`` -> ``tos_update_batched`` -> ``ber.apply_write_errors`` ->
LUT gather), sharing the Bernoulli draw discipline with the oracle via
``ber.write_error_bits``.  The same property is asserted end-to-end:
``run_pipeline``, ``StreamingDetector`` (including live ``set_control``
ladder knobs), and the ``DetectorPool`` executors all match the jnp
backend on every output, including the float64 energy books.

The whole module runs the kernel in interpret mode on CPU hosts (the
``resolve_interpret`` auto rule) and is marked ``pallas`` so CI can run it
as its own parity job (``pytest -m pallas``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ber as ber_mod
from repro.core import pipeline
from repro.core import stcf as stcf_mod
from repro.core import tos as tos_mod
from repro.kernels import ops

pytestmark = pytest.mark.pallas

TW = 5000
SUPPORT = 2


def _mk_chunk(rng, h, w, e, t_hi=40_000):
    xy = np.stack([rng.integers(0, w, e), rng.integers(0, h, e)], 1)
    ts = np.sort(rng.integers(0, t_hi, e))
    return jnp.asarray(xy, jnp.int32), jnp.asarray(ts, jnp.int32)


def _mk_state(rng, h, w):
    """A busy mid-stream state: non-trivial TOS, SAE, and LUT."""
    tos = np.zeros((h, w), np.uint8)
    hot = rng.random((h, w)) < 0.3
    tos[hot] = rng.integers(225, 256, hot.sum())
    sae = np.full((h, w), stcf_mod._NEVER, np.int32)
    seen = rng.random((h, w)) < 0.4
    sae[seen] = rng.integers(0, 30_000, seen.sum())
    lut = rng.standard_normal((h, w)).astype(np.float32)
    return jnp.asarray(tos), jnp.asarray(sae), jnp.asarray(lut)


def _oracle(tos, sae, lut, xy, ts, valid, *, patch, th, stcf_enabled,
            bits=None, ber=None):
    """The unfused composition the megakernel replaces, op by op."""
    sae2, keep = stcf_mod.stcf_step(
        sae, xy, ts, valid, enabled=stcf_enabled,
        support=SUPPORT, tw=TW,
    )
    tos2 = tos_mod.tos_update_batched(tos, xy, keep, patch=patch, th=th)
    if bits is not None:
        tos2 = ber_mod.apply_write_errors(tos2, bits, ber)
    scores = jnp.where(keep, lut[xy[:, 1], xy[:, 0]], -jnp.inf)
    return tos2, sae2, keep, scores.astype(jnp.float32)


def _assert_step_equal(got, want):
    for g, w, name in zip(got, want, ("tos", "sae", "keep", "scores")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


@pytest.mark.parametrize("patch", [5, 7, 9])
def test_fused_op_matches_composed_oracle(patch):
    rng = np.random.default_rng(patch)
    h, w, e = 64, 96, 256
    tos, sae, lut = _mk_state(rng, h, w)
    xy, ts = _mk_chunk(rng, h, w, e)
    valid = jnp.arange(e) < e - 17          # padded tail rides along masked
    got = ops.fused_step_op(tos, sae, lut, xy, ts, valid,
                            patch=patch, th=225, support=SUPPORT, tw=TW)
    want = _oracle(tos, sae, lut, xy, ts, valid,
                   patch=patch, th=225, stcf_enabled=True)
    _assert_step_equal(got, want)
    assert got[0].dtype == jnp.uint8 and got[3].dtype == jnp.float32


def test_fused_op_ber_injection_shares_draws():
    """Nonzero BER (vdd ~0.61): same key -> same Bernoulli masks -> same
    corrupted surface, in-kernel xor/decode vs the jnp apply half."""
    rng = np.random.default_rng(3)
    h, w, e = 48, 80, 192
    tos, sae, lut = _mk_state(rng, h, w)
    xy, ts = _mk_chunk(rng, h, w, e)
    valid = jnp.ones((e,), bool)
    ber = jnp.float32(2e-3)
    bits = ber_mod.write_error_bits(jax.random.PRNGKey(11), (h, w), ber)
    assert int(jnp.sum(bits)) > 0           # the draw actually flips bits
    got = ops.fused_step_op(tos, sae, lut, xy, ts, valid, ber, bits,
                            patch=7, th=225, support=SUPPORT, tw=TW,
                            inject_ber=True)
    want = _oracle(tos, sae, lut, xy, ts, valid,
                   patch=7, th=225, stcf_enabled=True, bits=bits, ber=ber)
    _assert_step_equal(got, want)


def test_fused_op_stcf_disabled():
    rng = np.random.default_rng(4)
    h, w, e = 40, 56, 128
    tos, sae, lut = _mk_state(rng, h, w)
    xy, ts = _mk_chunk(rng, h, w, e)
    valid = jnp.arange(e) < e - 5
    got = ops.fused_step_op(tos, sae, lut, xy, ts, valid,
                            patch=7, th=225, stcf_enabled=False)
    want = _oracle(tos, sae, lut, xy, ts, valid,
                   patch=7, th=225, stcf_enabled=False)
    _assert_step_equal(got, want)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(valid))


# ---------------------------------------------------------------------------
# Window geometry: patches straddling 128-pixel boundaries, the kernel's
# 8-row window blocks and the surface edge (odd sizes force the padded tail
# rows and columns of ``ops.fused_step_op``).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("patch", [5, 7, 9])
@pytest.mark.parametrize("hw", [(100, 130), (260, 350)])
def test_fused_tile_straddle_and_edges(patch, hw):
    h, w = hw
    r = patch // 2
    pts = []
    # every interior tile boundary, straddled from both sides and dead-on
    for bx in range(128, w, 128):
        for off in (-r, -1, 0, 1, r):
            pts.append((bx + off, min(h - 1, 64)))
    for by in range(128, h, 128):
        for off in (-r, -1, 0, 1, r):
            pts.append((min(w - 1, 64), by + off))
    # surface corners and edges: halo clipped by the pad region
    pts += [(0, 0), (w - 1, 0), (0, h - 1), (w - 1, h - 1),
            (w - 1, h // 2), (w // 2, h - 1)]
    pts = [(x, y) for (x, y) in pts if 0 <= x < w and 0 <= y < h]
    e = len(pts)
    xy = jnp.asarray(np.array(pts, np.int32))
    ts = jnp.asarray(np.arange(e, dtype=np.int32) * 10)
    valid = jnp.ones((e,), bool)
    rng = np.random.default_rng(h * w + patch)
    tos, sae, lut = _mk_state(rng, h, w)
    got = ops.fused_step_op(tos, sae, lut, xy, ts, valid,
                            patch=patch, th=225, support=SUPPORT, tw=TW)
    want = _oracle(tos, sae, lut, xy, ts, valid,
                   patch=patch, th=225, stcf_enabled=True)
    _assert_step_equal(got, want)


def test_fused_boundary_events_cross_tile_halo():
    """An event at x=127 decrements pixels at x=128 and up, and vice
    versa — the patch write must land across the 128-column boundary."""
    h, w, patch = 256, 256, 7
    tos = jnp.full((h, w), 255, jnp.uint8)
    sae = jnp.full((h, w), stcf_mod._NEVER, jnp.int32)
    lut = jnp.zeros((h, w), jnp.float32)
    xy = jnp.asarray([[127, 60], [128, 200]], jnp.int32)
    ts = jnp.asarray([10, 20], jnp.int32)
    valid = jnp.ones((2,), bool)
    got_tos, _, keep, _ = ops.fused_step_op(
        tos, sae, lut, xy, ts, valid,
        patch=patch, th=225, stcf_enabled=False)
    want = _oracle(tos, sae, lut, xy, ts, valid,
                   patch=patch, th=225, stcf_enabled=False)[0]
    np.testing.assert_array_equal(np.asarray(got_tos), np.asarray(want))
    g = np.asarray(got_tos)
    assert (g[57:64, 124:131] != 255).any()      # halo crossed into tile B
    assert g[60, 127] == 255 and g[200, 128] == 255


# ---------------------------------------------------------------------------
# End-to-end: the pallas_fused backend through every serving surface.
# ---------------------------------------------------------------------------


def _e2e_cfgs(backend):
    return pipeline.PipelineConfig(
        height=100, width=130, chunk=64, lut_every_chunks=2,
        inject_ber=True, dvfs_online=True, backend=backend,
    )


def _e2e_events(n=6 * 64, seed=0):
    rng = np.random.default_rng(seed)
    xy = np.stack([rng.integers(0, 130, n), rng.integers(0, 100, n)], 1)
    ts = np.sort(rng.integers(0, 200_000, n))
    return xy.astype(np.int32), ts.astype(np.int32)


def test_pipeline_fused_parity_full_books():
    xy, ts = _e2e_events()
    a = pipeline.run_pipeline(xy, ts, _e2e_cfgs("jnp"))
    b = pipeline.run_pipeline(xy, ts, _e2e_cfgs("pallas_fused"))
    np.testing.assert_array_equal(a.scores, b.scores)
    np.testing.assert_array_equal(a.kept, b.kept)
    np.testing.assert_array_equal(a.tos, b.tos)
    np.testing.assert_array_equal(a.lut, b.lut)
    np.testing.assert_array_equal(a.vdd_trace, b.vdd_trace)
    assert a.energy_pj == b.energy_pj
    assert a.latency_ns_per_event == b.latency_ns_per_event


def test_streaming_fused_parity_with_ladder_knobs():
    """Live ``set_control`` moves (lut_every, vdd_cap) mid-stream: the fused
    backend tracks the jnp one through the knob change, bit-for-bit."""
    from repro.serve.streaming import StreamingDetector

    xy, ts = _e2e_events(seed=2)
    half = len(xy) // 2

    def run(backend):
        det = StreamingDetector(_e2e_cfgs(backend), seed=7)
        s1, k1 = det.feed(xy[:half], ts[:half])
        det.set_control(lut_every=1, vdd_cap=1)
        s2, k2 = det.feed(xy[half:], ts[half:])
        s3, k3 = det.flush()
        return (np.concatenate([s1, s2, s3]), np.concatenate([k1, k2, k3]))

    sj, kj = run("jnp")
    sf, kf = run("pallas_fused")
    np.testing.assert_array_equal(sj, sf)
    np.testing.assert_array_equal(kj, kf)


def test_pool_fused_parity():
    """The pool's K-round executor (scan of cond of vmapped step) with the
    fused kernel inlined == the jnp pipeline — the program context that
    historically perturbed XLA:CPU's FMA contraction around the Harris
    refresh (now fenced in ``harris_response``)."""
    from repro.serve import DetectorPool

    xy, ts = _e2e_events(seed=5)

    def run_pool(backend):
        pool = DetectorPool(_e2e_cfgs(backend), capacity=2)
        lane = pool.connect()
        pool.feed(lane, xy, ts)
        for _ in range(20):
            pool.pump_rounds()
        sc, kp = pool.poll(lane)
        return np.asarray(sc), np.asarray(kp)

    ref = pipeline.run_pipeline(xy, ts, _e2e_cfgs("jnp"))
    sc, kp = run_pool("pallas_fused")
    n = len(xy)
    np.testing.assert_array_equal(sc[:n], ref.scores)
    np.testing.assert_array_equal(kp[:n], ref.kept)
