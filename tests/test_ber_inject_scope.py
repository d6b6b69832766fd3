"""The write-error injection's own named scope and the pool's
``ber_chunks`` counter: the chunks handed back that were folded at an
operating point whose write BER is above 0."""
import functools
import re

import jax
import numpy as np
import pytest

import repro.obs
from repro.core import dvfs, pipeline
from repro.core import state as state_mod
from repro.events import synthetic
from repro.serve import DetectorPool

OP_NAME = re.compile(r'op_name="([^"]*)"')
DRAWS = ("threefry", "_bernoulli", "_uniform")
CHUNK = 64
DVFS = dvfs.DvfsConfig(tw_us=150)       # half windows of 75 us
# per half window: 0.8 events/us picks 0.6 V, then 6.7 events/us 0.7 V
RAMP = [60] * 4 + [500] * 4


def _cfg(inject_ber=True, **kw):
    return pipeline.PipelineConfig(height=24, width=32, chunk=CHUNK,
                                   dvfs_cfg=DVFS, inject_ber=inject_ber, **kw)


def _serve(cfg, lanes=2):
    """Fold the ramp on every lane of a pool; ``(pool_stats, ts)``."""
    st = synthetic.ramp_stream(RAMP, DVFS.half_us, height=24, width=32,
                               seed=3)
    pool = DetectorPool(cfg, capacity=lanes, ring_rounds=3)
    try:
        ids = [pool.connect() for _ in range(lanes)]
        for lane in ids:
            pool.feed(lane, st.xy, st.ts)
        want = st.ts.size // CHUNK
        got = dict.fromkeys(ids, 0)
        for _ in range(want):
            pool.pump()
            for lane in ids:
                got[lane] += pool.poll(lane)[0].size // CHUNK
            if min(got.values()) == want:
                break
        assert min(got.values()) == want
        return pool.pool_stats(), st.ts
    finally:
        pool.close()


def test_ber_chunks_count_the_chunks_folded_at_0_6_volts():
    stats, ts = _serve(_cfg(dvfs=True, dvfs_online=True))
    chunks = ts.size // CHUNK
    vdd = dvfs.per_chunk_vdd(ts, chunks, CHUNK, DVFS)
    at_06 = int(np.count_nonzero(vdd < 0.605))
    assert 0 < at_06 < chunks and {0.6, 0.7} == set(vdd.tolist())
    assert stats["chunks_returned"] == 2 * chunks
    assert stats["ber_chunks"] == 2 * at_06


@pytest.mark.parametrize("vdd", [0.6, 1.2])
def test_a_fixed_vdd_pool_counts_no_ber_chunks(vdd):
    stats, ts = _serve(_cfg(vdd=vdd))
    assert stats["chunks_returned"] == 2 * (ts.size // CHUNK)
    assert stats["ber_chunks"] == 0


def _scope(op_name: str) -> str:
    """The innermost of ``tos_update`` and ``ber_inject`` in an op_name."""
    for part in reversed(op_name.split("/")):
        name = part[part.find("(") + 1:].rstrip(")") if "(" in part else part
        if name in ("tos_update", "ber_inject"):
            return name
    return ""


def _op_names(texts) -> list:
    return [m.group(1) for t in texts for m in OP_NAME.finditer(t)]


def test_ber_inject_scope_holds_the_draws_in_the_executors():
    """The pool's executors (``repro.obs.latest_hlo_texts``) draw and apply
    the write errors under ``ber_inject``; ``tos_update`` holds no draw."""
    _serve(_cfg(dvfs=True, dvfs_online=True), lanes=1)
    names = _op_names(repro.obs.latest_hlo_texts())
    ber = [n for n in names if _scope(n) == "ber_inject"]
    tos = [n for n in names if _scope(n) == "tos_update"]
    assert tos and ber
    assert any("threefry" in n for n in ber)
    assert any("xor" in n for n in ber)
    assert not [n for n in tos if any(d in n for d in DRAWS)]


def _step_hlo(cfg) -> str:
    state = state_mod.detector_init(cfg, seed=1)
    e = cfg.chunk
    chunk = state_mod.ChunkInput(
        xy=np.zeros((e, 2), np.int32), ts=np.arange(e, dtype=np.int32),
        valid=np.ones(e, bool), ber=np.float32(0.025),
        energy_coef=np.float32(0.0), latency_coef=np.float32(0.0))
    step = jax.jit(functools.partial(state_mod.detector_step, cfg))
    return step.lower(state, chunk).compile().as_text()


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_ber_inject_scope_in_each_step(backend):
    """Both steps split the key and draw under ``ber_inject``; without
    write errors neither has the scope nor a draw."""
    kw = {"interpret": True} if backend == "pallas_fused" else {}
    names = _op_names([_step_hlo(_cfg(vdd=0.6, backend=backend, **kw))])
    ber = [n for n in names if _scope(n) == "ber_inject"]
    assert any("threefry" in n for n in ber)
    assert not [n for n in names if _scope(n) == "tos_update"
                and any(d in n for d in DRAWS)]
    plain = _op_names([_step_hlo(_cfg(inject_ber=False, backend=backend,
                                      **kw))])
    assert not [n for n in plain if "ber_inject" in n
                or any(d in n for d in DRAWS)]
