"""Sharding rules validity for all archs + HLO analyzer unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro import configs
from repro.launch import sharding as sh
from repro.models import transformer as T
from repro.utils.hlo_analysis import analyze_hlo

# jax.make_mesh defaults to Explicit axes; the mesh-rules path needs Auto.
AUTO2 = (jax.sharding.AxisType.Auto,) * 2


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_rules_produce_valid_shardings(arch):
    """Every full-config param must map to a constructible NamedSharding
    (no duplicate mesh axes, no invalid specs) on a (data, model) mesh."""
    cfg = configs.get(arch)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    rules = sh.make_rules(cfg, mesh, fsdp=True)
    _, axes = T.abstract_params(cfg)
    shardings = sh.param_shardings(mesh, axes, rules)   # raises on conflict
    n_params = len(jax.tree.leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple)))
    assert len(jax.tree.leaves(shardings)) == n_params

    # Also check against the PRODUCTION mesh axis sizes (16x16) without
    # building 256 devices: validate specs never map one mesh axis twice.
    import types
    fake = types.SimpleNamespace(
        axis_names=("data", "model"), shape={"data": 16, "model": 16})
    rules16 = sh.make_rules(cfg, fake, fsdp=True)
    from repro.meshctx import logical_to_spec
    for ax in jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)):
        spec = logical_to_spec(ax, rules16)
        flat = [a for p in spec for a in
                (p if isinstance(p, tuple) else (p,)) if a]
        assert len(flat) == len(set(flat)), (ax, spec)


@pytest.mark.parametrize("arch", ["qwen2_0_5b", "olmoe_1b_7b", "mamba2_370m",
                                  "whisper_tiny", "zamba2_1_2b"])
def test_smoke_lowers_with_mesh(arch):
    """Smoke config lowers under mesh + rules on the 1-device mesh."""
    from repro.meshctx import use_mesh_rules
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import make_train_step

    cfg = configs.get_smoke(arch)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    rules = sh.make_rules(cfg, mesh)
    aparams, axes = T.abstract_params(cfg)
    psh = sh.param_shardings(mesh, axes, rules)
    opt_cfg = AdamWConfig()
    aopt = {"m": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), aparams),
            "v": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), aparams),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}
    osh = {"m": psh, "v": psh,
           "step": NamedSharding(mesh, jax.sharding.PartitionSpec())}
    batch = T.input_specs(cfg, "train", 64, 2)
    bsh = sh.batch_shardings(mesh, batch, rules)
    step = make_train_step(cfg, opt_cfg)
    with use_mesh_rules(mesh, rules):
        lowered = jax.jit(step, in_shardings=(psh, osh, bsh)).lower(
            aparams, aopt, batch)
    assert lowered is not None


def test_batch_rule_adapts_to_small_batch():
    import types
    cfg = configs.get("mamba2-370m")
    fake = types.SimpleNamespace(
        axis_names=("pod", "data", "model"),
        shape={"pod": 2, "data": 16, "model": 16})
    assert sh.make_rules(cfg, fake, global_batch=1)["batch"] == ()
    assert sh.make_rules(cfg, fake, global_batch=2)["batch"] == ("pod",)
    assert sh.make_rules(cfg, fake, global_batch=256)["batch"] == ("pod", "data")


# ---------------------------------------------------------------------------
# HLO analyzer on a canned module
# ---------------------------------------------------------------------------

_CANNED = """
HloModule test

%body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %ar = f32[8,8]{1,0} all-reduce(%x), replica_groups={}, to_apply=%sum.1
  %d = f32[8,8]{1,0} dot(%ar, %ar), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %d)
}

%cond.1 (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %i2 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(12)
  ROOT %cmp = pred[] compare(%i2, %n), direction=LT
}

%sum.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (arg: f32[8,8]) -> f32[8,8] {
  %arg = f32[8,8]{1,0} parameter(0)
  %c = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%c, %arg)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_hlo_analyzer_trip_counts_and_collectives():
    s = analyze_hlo(_CANNED)
    # all-reduce: 8*8*4 bytes * 12 trips
    assert s.collective_bytes["all-reduce"] == 8 * 8 * 4 * 12
    # dot: 2 * 64 elems * 8 contraction * 12 trips
    assert s.dot_flops == 2 * 64 * 8 * 12
    assert not s.unresolved_loops
    assert any(v == 12 for v in s.trip_counts.values())


_CANNED_A2A = """
HloModule t2

ENTRY %main (arg: f32[16,8]) -> f32[16,8] {
  %arg = f32[16,8]{1,0} parameter(0)
  %a2a = f32[16,8]{1,0} all-to-all(%arg), replica_groups={}, dimensions={0}
  %rs = f32[4,8]{1,0} reduce-scatter(%a2a), replica_groups={}, dimensions={0}, to_apply=%sum.9
  %cp = f32[4,8]{1,0} collective-permute(%rs), source_target_pairs={{0,1}}
  %ags = (f32[4,8]{1,0}, f32[16,8]{1,0}) all-gather-start(%cp), replica_groups={}, dimensions={0}
  %agd = f32[16,8]{1,0} all-gather-done(%ags)
  ROOT %out = f32[16,8]{1,0} add(%agd, %a2a)
}

%sum.9 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}
"""


def test_hlo_analyzer_all_collective_kinds():
    s = analyze_hlo(_CANNED_A2A)
    assert s.collective_bytes["all-to-all"] == 16 * 8 * 4
    assert s.collective_bytes["reduce-scatter"] == 4 * 8 * 4
    assert s.collective_bytes["collective-permute"] == 4 * 8 * 4
    # -start counted once (tuple incl. aliased input buffer), -done skipped
    assert s.collective_bytes["all-gather"] == (4 * 8 + 16 * 8) * 4
    assert s.n_collectives == 4


def test_hlo_analyzer_counts_real_dump():
    """The analyzer must find dots + trip counts in a real compiled module
    (regression for the nested-paren header format)."""
    import os
    path = "/tmp/hlo_dump.txt"
    if not os.path.exists(path):
        pytest.skip("no dump available")
    s = analyze_hlo(open(path).read())
    assert s.dot_flops > 0
    assert s.trip_counts
    assert not s.unresolved_loops


# ---------------------------------------------------------------------------
# Pinned-host H2D staging (ISSUE 7): capability probe + transparent fallback.
# ---------------------------------------------------------------------------


class _Mem:
    def __init__(self, kind):
        self.kind = kind


class _StubDev:
    """Duck-typed device for the pinned-host capability probe."""

    def __init__(self, platform, kinds=(), raises=False):
        self.platform = platform
        self._kinds = kinds
        self._raises = raises

    def addressable_memories(self):
        if self._raises:
            raise RuntimeError("memory query failed")
        return [_Mem(k) for k in self._kinds]


class _NoMemoriesDev:
    """Device object without the memory-space API."""

    platform = "gpu"


def test_pinned_host_sharding_probe():
    # CPU devices never stage (jnp.asarray is already host memory)
    assert sh.pinned_host_sharding(_StubDev("cpu", ("pinned_host",))) is None
    # accelerator without the memory-space API: fall back, don't crash
    assert sh.pinned_host_sharding(_NoMemoriesDev()) is None
    # a failing memory query is a fault, not "no pinned memory"
    with pytest.raises(RuntimeError, match="memory query failed"):
        sh.pinned_host_sharding(_StubDev("gpu", raises=True))
    # accelerator without a pinned_host space: fall back
    assert sh.pinned_host_sharding(_StubDev("gpu", ("device",))) is None
    # real device objects are required to build a SingleDeviceSharding, so
    # the positive case uses the actual local device: on CPU hosts the
    # probe must still answer None (platform gate fires first)
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        assert sh.pinned_host_sharding(dev) is None


def test_host_stager_cpu_fallback_roundtrip():
    """On hosts without a pinned_host space the stager degrades to a plain
    jnp.asarray: same values, uploads counted, zero staged bytes."""
    st = sh.HostStager()
    arr = np.arange(12, dtype=np.int32).reshape(3, 4)
    out = st.put(arr)
    np.testing.assert_array_equal(np.asarray(out), arr)
    assert st.uploads == 1
    if not st.pinned:
        assert st.staged_bytes == 0
    st.put(arr)
    assert st.uploads == 2


def test_host_stager_pinned_hop_lands_in_device_memory():
    """The staged path itself: host -> pinned_host -> device memory.  CPU
    devices expose a pinned_host space too, so the two hops run here with
    the probe's platform gate bypassed."""
    from jax.sharding import SingleDeviceSharding

    dev = jax.devices()[0]
    if "pinned_host" not in {m.kind for m in dev.addressable_memories()}:
        pytest.skip("device exposes no pinned_host memory")
    st = sh.HostStager(dev)
    st._pinned = SingleDeviceSharding(dev, memory_kind="pinned_host")
    arr = np.arange(12, dtype=np.int32).reshape(3, 4)
    out = st.put(arr)
    np.testing.assert_array_equal(np.asarray(out), arr)
    assert out.sharding.memory_kind == "device"
    assert st.staged_bytes == arr.nbytes


def test_pinned_staging_pool_never_recompiles(monkeypatch):
    """Staged uploads arrive committed to the device; the pool's carried
    states and rings must be committed the same way on every call, or each
    flip compiles a new executor (seen on the chip, where staging is on)."""
    from jax.sharding import SingleDeviceSharding

    from repro.core import pipeline as pipeline_mod
    from repro.events import synthetic
    from repro.serve import DetectorPool

    dev = jax.devices()[0]
    if "pinned_host" not in {m.kind for m in dev.addressable_memories()}:
        pytest.skip("device exposes no pinned_host memory")
    monkeypatch.setattr(
        sh, "pinned_host_sharding",
        lambda d: SingleDeviceSharding(d, memory_kind="pinned_host"))
    cfg = pipeline_mod.PipelineConfig(height=64, width=96, chunk=32)
    s = synthetic.shapes_stream(height=64, width=96, duration_us=4000)
    xy, ts = s.xy[:600], s.ts[:600]
    for readout in ("dense", "compact"):
        pool = DetectorPool(cfg, 3, readout=readout, ring_rounds=4)
        assert pool.pool_stats()["h2d_pinned_staging"]
        lanes = [pool.connect() for _ in range(3)]
        for start in range(0, 600, 100):
            for lane in lanes:
                pool.feed(lane, xy[start:start + 100], ts[start:start + 100])
            pool.pump()
            pool.poll(lanes[0], wait=False)
        for lane in lanes:
            pool.flush(lane)
        assert pool.executors_compiled_once(), pool.compile_cache_sizes()
        _assert_donated_leaves_distinct(pool)
        pool.close()


def _assert_donated_leaves_distinct(pool):
    """On an accelerator the executors donate the stacked states and the
    live ring; a buffer shared by two donated leaves is refused there
    ("donate the same buffer twice"), so every leaf must own its buffer."""
    for bucket in pool.buckets:
        for ring in [pool._rings[bucket], *pool._spares[bucket]]:
            leaves = jax.tree.leaves((pool._states, ring))
            ptrs = [leaf.unsafe_buffer_pointer() for leaf in leaves]
            assert len(set(ptrs)) == len(ptrs)


def test_pool_donated_leaves_own_their_buffers():
    """Rings recycled through drains (reset cursors) and lanes re-placed
    through connect keep one buffer per donated leaf."""
    from repro.core import pipeline as pipeline_mod
    from repro.events import synthetic
    from repro.serve import DetectorPool

    cfg = pipeline_mod.PipelineConfig(height=64, width=96, chunk=32)
    s = synthetic.shapes_stream(height=64, width=96, duration_us=4000)
    for readout in ("dense", "compact"):
        pool = DetectorPool(cfg, 2, readout=readout, ring_rounds=2)
        lanes = [pool.connect() for _ in range(2)]
        for start in range(0, 400, 100):
            for lane in lanes:
                pool.feed(lane, s.xy[start:start + 100],
                          s.ts[start:start + 100])
            pool.pump()
            pool.poll(lanes[1])
        _assert_donated_leaves_distinct(pool)
        pool.close()


def test_pool_reports_staging_stats():
    from repro.core import pipeline as pipeline_mod
    from repro.serve import DetectorPool

    pool = DetectorPool(
        pipeline_mod.PipelineConfig(height=48, width=64, chunk=64),
        capacity=1,
    )
    ps = pool.pool_stats()
    assert "h2d_pinned_staging" in ps and "h2d_staged_uploads" in ps
    assert ps["h2d_pinned_staging"] in (True, False)
    pool.close()
