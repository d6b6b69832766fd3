"""Compile the serving main path's kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
``v5e:2x2`` topology that is described, not attached, and raises what the
chip's compiler would raise — unaligned VMEM slices, scoped-VMEM overruns,
unlowerable primitives.  Interpret mode on CPU sees none of these.  Sizes
are the deployments ``chip_smoke.py`` serves: DAVIS240 (180x240, a
1,024-lane fleet) and 1280x720 (16 lanes), chunk 256, default compact cap.

The topology is described inside a module fixture (never at import time:
only one process may load the TPU library, and test workers all import
this module), and the persistent compilation cache is off around the
compiles, since an entry written here could not be read back without a
chip.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import pipeline
from repro.core import state as state_mod
from repro.kernels import ops

CHUNK = 256
CAP = CHUNK // 8              # DetectorPool's default compact_cap
GEOMETRIES = {"davis240": (180, 240, 1024), "gen4_720p": (720, 1280, 16)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fused_args(sharding, h, w, lanes, inject):
    lead = () if lanes is None else (lanes,)

    def s(shape, dtype):
        return _spec(sharding, lead + shape, dtype)

    return [s((h, w), jnp.uint8), s((h, w), jnp.int32),
            s((h, w), jnp.float32), s((CHUNK, 2), jnp.int32),
            s((CHUNK,), jnp.int32), s((CHUNK,), jnp.bool_),
            s((), jnp.float32) if inject else None,
            s((h, w), jnp.int32) if inject else None]


def _fused(inject):
    return functools.partial(
        ops.fused_step_op, patch=7, th=225, support=2, tw=5000,
        stcf_enabled=True, inject_ber=inject, interpret=False,
    )


@pytest.mark.parametrize("inject", [False, True], ids=["no_ber", "ber"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_fused_step_compiles(one_chip, geometry, inject):
    h, w, _ = GEOMETRIES[geometry]
    compiled = jax.jit(_fused(inject)).lower(
        *_fused_args(one_chip, h, w, None, inject)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_fused_step_vmapped_over_lanes_compiles(one_chip, geometry):
    """The pool executors vmap the step over lanes: Pallas adds a lane grid
    axis, and every trailing block dim must stay whole."""
    h, w, _ = GEOMETRIES[geometry]
    jax.jit(jax.vmap(_fused(True))).lower(
        *_fused_args(one_chip, h, w, 8, True)).compile()


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_compaction_compiles(one_chip, geometry):
    lanes = GEOMETRIES[geometry][2]
    f = functools.partial(ops.compact_slots_op, cap=CAP, interpret=False)
    compiled = jax.jit(f).lower(
        _spec(one_chip, (lanes, CHUNK), jnp.float32),
        _spec(one_chip, (lanes, CHUNK), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _vmapped_step(one_chip, cfg, lanes):
    """The step as the pool executors vmap it over ``lanes`` lanes,
    compiled for one described chip."""
    tcfg = pipeline._trace_cfg(cfg)
    one = jax.eval_shape(lambda: state_mod.detector_init(cfg))
    states = jax.tree.map(
        lambda a: _spec(one_chip, (lanes,) + a.shape, a.dtype), one)
    chunk = state_mod.ChunkInput(
        xy=_spec(one_chip, (lanes, CHUNK, 2), jnp.int32),
        ts=_spec(one_chip, (lanes, CHUNK), jnp.int32),
        valid=_spec(one_chip, (lanes, CHUNK), jnp.bool_),
        ber=_spec(one_chip, (lanes,), jnp.float32),
        energy_coef=_spec(one_chip, (lanes,), jnp.float32),
        latency_coef=_spec(one_chip, (lanes,), jnp.float32),
    )
    step = jax.vmap(lambda s, c: state_mod.detector_step(tcfg, s, c))
    return jax.jit(step).lower(states, chunk).compile()


def test_vmapped_detector_step_jnp_compiles(one_chip):
    cfg = pipeline.PipelineConfig(height=180, width=240, chunk=CHUNK)
    compiled = _vmapped_step(one_chip, cfg, 64)
    assert compiled.memory_analysis() is not None


def test_vmapped_near_threshold_step_compiles(one_chip):
    """The 0.6 V fleet's step: the online DVFS pick and the write errors'
    threefry draws over every lane's (180, 240, 5) bits."""
    from repro.core.dvfs import DvfsConfig

    cfg = pipeline.PipelineConfig(height=180, width=240, chunk=CHUNK,
                                  dvfs=True, dvfs_online=True,
                                  inject_ber=True, dvfs_cfg=DvfsConfig())
    assert "ber_inject" in _vmapped_step(one_chip, cfg, 8).as_text()
