"""``chip_smoke.py`` at a tiny size on CPU, so the chip script cannot rot.

The phases run with Pallas in interpret mode here; on the chip they run at
deployment sizes with compiled kernels.  ``main()`` itself must refuse any
device that is not a TPU and print no ``ok`` line.
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.core import pipeline
from repro.core import state as state_mod
from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               REPO / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

TINY = (dict(height=64, width=96), dict(height=72, width=128))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_main_refuses_non_tpu(capsys, restore_cache_dir):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out


def test_require_compiled_kernels_refuses_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="interpret"):
        cs.require_compiled_kernels([])
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cs.require_compiled_kernels([pipeline.PipelineConfig()])
    with pytest.raises(RuntimeError, match="interpret"):
        cs.require_compiled_kernels(
            [pipeline.PipelineConfig(backend="pallas_fused", interpret=True)])


def test_compile_cache_env_wins(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_fleet_streams_distinct_and_sorted():
    streams = cs.fleet_streams(TINY[0], 5, 300, seed=0, bases=2)
    assert len(streams) == 5
    for xy, ts in streams:
        assert xy.shape == (300, 2) and ts.shape == (300,)
        assert np.all(np.diff(ts) >= 0)
        assert xy[:, 0].max() < 96 and xy[:, 1].max() < 64
    # lanes 0 and 2 replay one recording, shifted: never the same surface
    assert not np.array_equal(streams[0][0], streams[2][0])
    np.testing.assert_array_equal(streams[0][1], streams[2][1])


def test_chunks_for_refreshes_gives_two_refreshes():
    cfg = pipeline.PipelineConfig(chunk=32)
    n = cs.chunks_for_refreshes(cfg)
    assert n // cfg.chunk >= 2 * cfg.lut_every_chunks


@pytest.fixture(params=[False, True], ids=["cpu_placement", "donating"])
def donating(request, monkeypatch):
    """``donating``: every jitted step donates its state as on the chip,
    where a buffer shared by two donated leaves is refused; the CPU runtime
    refuses it too once donation is on."""
    if request.param:
        monkeypatch.setattr(state_mod, "donation_ok", lambda tree: True)
    return request.param


def test_one_chip_phases_tiny(capsys, donating):
    cs.run_one_chip(davis_lanes=8, gen4_lanes=2, pallas_lanes=4, sample=2,
                    slab=100, geometry=TINY, chunk=32)
    out = capsys.readouterr().out
    for phase in ("davis240", "720p", "backends", "reference"):
        assert f"phase {phase}:" in out
    assert "snapshot/restore bit-equal" in out


def test_four_chip_phase_tiny(capsys, donating):
    cs.run_four_chips(lanes=4, slab=100, chunk=32, geometry=TINY[0])
    out = capsys.readouterr().out
    assert "== one device, every lane" in out


def test_reference_diff_catches_a_wrong_lane():
    cfg = pipeline.PipelineConfig(**TINY[0], chunk=32)
    (xy, ts), (xy2, ts2) = cs.fleet_streams(TINY[0], 2, 300, seed=3,
                                            bases=2)
    run = cs.serve_fleet(cfg, [(xy, ts), (xy2, ts2)], slab=100,
                         readout="dense", shard=False, keep_states=[0, 1])
    assert cs.reference_diff(cfg, xy, ts, run.scores[0], run.kept[0],
                             run.states[0]) == 0.0
    with pytest.raises(AssertionError):
        cs.reference_diff(cfg, xy, ts, run.scores[1], run.kept[1],
                          run.states[1])
