"""Donation guards: keyed off actual state placement, never the default
backend (ISSUE 4 satellite).

The bug class: ``donate_argnames`` decisions used to key off
``jax.default_backend()``.  A session explicitly placed on CPU under a GPU
default backend would then donate host buffers (useless, and unsafe next to
zero-copy ``device_get`` views), while a session placed on an accelerator
under a CPU default backend would never donate.  The guard now keys off the
``.devices()`` of the state that will actually be donated
(``repro.core.state.donation_ok``).

On this CI host (CPU-only) the accelerator half is asserted as a strict
no-op plus fake-device unit coverage of the decision function; the
buffer-deletion (``is_deleted``) witnesses run when an accelerator is
present.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import pipeline
from repro.core import state as state_mod
from repro.events import synthetic
from repro.serve import DetectorPool, StreamingDetector
from repro.serve import streaming as streaming_mod

CFG = pipeline.PipelineConfig(chunk=256, lut_every_chunks=2)

_ON_CPU = jax.default_backend() == "cpu"


class _FakeDev:
    def __init__(self, platform):
        self.platform = platform


class _FakeLeaf:
    def __init__(self, *devs):
        self._devs = set(devs)

    def devices(self):
        return set(self._devs)


def test_donation_ok_keys_off_placement_not_backend():
    gpu, tpu, cpu = _FakeDev("gpu"), _FakeDev("tpu"), _FakeDev("cpu")
    assert state_mod.donation_ok([_FakeLeaf(gpu)])
    assert state_mod.donation_ok([_FakeLeaf(tpu), _FakeLeaf(gpu)])
    # anything CPU-resident disqualifies, even partially
    assert not state_mod.donation_ok([_FakeLeaf(cpu)])
    assert not state_mod.donation_ok([_FakeLeaf(gpu), _FakeLeaf(cpu)])
    assert not state_mod.donation_ok([_FakeLeaf(gpu, cpu)])
    # host arrays (no .devices) and empty trees: nothing to donate
    assert not state_mod.donation_ok([np.zeros(3)])
    assert not state_mod.donation_ok([])
    assert not state_mod.donation_ok(None)


def test_cpu_state_never_donates_even_under_gpu_default(monkeypatch):
    """Regression: a CPU-resident session must not donate host buffers just
    because the *default backend* claims to be an accelerator."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    det = StreamingDetector(CFG)
    assert det._donate is False
    # the step cache is keyed on the (cfg, donate) pair, not the backend
    assert det._step is streaming_mod._step_fn(det._tcfg, False)
    st = synthetic.shapes_stream(duration_us=10_000, seed=0)
    s, k = det.feed(st.xy[:512], st.ts[:512])    # still folds correctly
    assert s.size == 512

    pool = DetectorPool(CFG, capacity=1)
    assert pool._donate is False                 # same guard, pool executors
    lane = pool.connect(seed=CFG.seed)
    pool.feed(lane, st.xy[:512], st.ts[:512])
    pool.pump()
    s2, _ = pool.flush(lane)
    np.testing.assert_array_equal(s2, s)
    pool.close()


def test_run_pipeline_donation_guard(monkeypatch):
    """run_pipeline's scan keys donation off the freshly-created state's
    placement; on a CPU-resident state the backend claim is irrelevant and
    results are unchanged."""
    st = synthetic.shapes_stream(duration_us=10_000, seed=1)
    ref = pipeline.run_pipeline(st.xy, st.ts, CFG)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    out = pipeline.run_pipeline(st.xy, st.ts, CFG)
    np.testing.assert_array_equal(out.scores, ref.scores)
    np.testing.assert_array_equal(out.tos, ref.tos)


def test_real_state_donation_decision_matches_backend():
    """On this host the real stacked pool state's decision must equal
    'are we on an accelerator' — donation_ok sees real jax.Array leaves."""
    det = StreamingDetector(CFG)
    assert state_mod.donation_ok(det.state) is (not _ON_CPU)
    pool = DetectorPool(CFG, capacity=2)
    assert pool._donate is (not _ON_CPU)
    pool.close()


def test_fresh_state_leaves_own_their_buffers():
    """A donating step (StreamingDetector, run_pipeline's scan) hands every
    state leaf to XLA; the runtime refuses a buffer shared by two donated
    leaves, so a fresh state may not reuse one array for several leaves."""
    leaves = jax.tree.leaves(state_mod.detector_init(CFG))
    ptrs = [leaf.unsafe_buffer_pointer() for leaf in leaves]
    assert len(set(ptrs)) == len(ptrs)


def test_disconnect_mid_migration_discards_staged_state():
    """Regression (ISSUE 5 satellite): ``disconnect()`` of a lane whose
    migration is staged (snapshot taken, restore pending) must discard the
    staged snapshot.  Leaking it would restore the retired session's state
    into the slot's next tenant at the next pump — the migration-era twin
    of the use-after-donate bug class this file guards."""
    st = synthetic.shapes_stream(duration_us=20_000, seed=0)
    pool = DetectorPool(CFG, capacity=1, buckets=(128, 512),
                        policy="adaptive", ring_rounds=2)
    lane = pool.connect(seed=CFG.seed, chunk=128)
    pool.feed(lane, st.xy[:512], st.ts[:512])
    pool.pump()
    pool.poll(lane)
    # stage the move directly (deterministic mid-migration window: the
    # scheduler would do the same after enough drain observations)
    pool._rt.stage_migration(lane, 512)
    assert pool._rt.staged_migrations() == {lane: 512}
    assert pool.stats(lane)["migration_staged"]
    stats = pool.disconnect(lane)               # snapshot taken, restore pending
    assert stats["migrations"] == 0             # the move never applied
    assert pool._rt.staged_migrations() == {}   # nothing leaked
    # the recycled slot starts clean: same seed, fresh state, no restore
    lane2 = pool.connect(seed=CFG.seed, chunk=128)
    assert lane2 == lane
    pool.feed(lane2, st.xy[:512], st.ts[:512])
    pool.pump()                                 # apply-staged runs: no-op
    s, _ = pool.flush(lane2)
    ref = pipeline.run_pipeline(
        st.xy[:512], st.ts[:512], dataclasses.replace(CFG, chunk=128)
    )
    np.testing.assert_array_equal(s, ref.scores)
    st2 = pool.stats(lane2)
    assert st2["bucket"] == 128 and st2["migrations"] == 0
    assert pool.executors_compiled_once()
    pool.close()


def test_restage_and_cancel_migration():
    """Re-staging a lane replaces its pending move; staging its current
    bucket cancels the pending move (the scheduler's change of mind
    between drains must not leave a stale snapshot behind)."""
    st = synthetic.shapes_stream(duration_us=20_000, seed=0)
    pool = DetectorPool(CFG, capacity=1, buckets=(128, 256, 512),
                        policy="adaptive")
    lane = pool.connect(seed=CFG.seed, chunk=128)
    pool.feed(lane, st.xy[:256], st.ts[:256])
    pool.pump()
    pool._rt.stage_migration(lane, 512)
    pool._rt.stage_migration(lane, 256)         # replace
    assert pool._rt.staged_migrations() == {lane: 256}
    pool._rt.stage_migration(lane, 128)         # cancel (current bucket)
    assert pool._rt.staged_migrations() == {}
    pool.pump()
    assert pool.stats(lane)["migrations"] == 0
    pool.close()


@pytest.mark.skipif(_ON_CPU, reason="donation is a no-op on CPU")
def test_pool_executor_donates_on_accelerator():
    """Accelerator witness: the executor consumes (deletes) the donated
    stacked-state and live-ring buffers — the pool's HBM working set is
    updated in place, not doubled."""
    st = synthetic.shapes_stream(duration_us=10_000, seed=0)
    pool = DetectorPool(CFG, capacity=1, ring_rounds=2)
    assert pool._donate
    lane = pool.connect(seed=CFG.seed)
    states_before = pool._states
    ring_before = pool._rings[CFG.chunk]
    pool.feed(lane, st.xy[:512], st.ts[:512])
    pool.pump()
    assert all(x.is_deleted() for x in jax.tree.leaves(states_before))
    assert all(x.is_deleted() for x in jax.tree.leaves(ring_before))
    s, _ = pool.flush(lane)                      # results still readable
    assert s.size == 512
    pool.close()


@pytest.mark.skipif(_ON_CPU, reason="donation is a no-op on CPU")
def test_streaming_step_donates_on_accelerator():
    st = synthetic.shapes_stream(duration_us=10_000, seed=0)
    det = StreamingDetector(CFG)
    assert det._donate
    state_before = det.state
    det.feed(st.xy[:256], st.ts[:256])
    assert all(x.is_deleted() for x in jax.tree.leaves(state_before))
