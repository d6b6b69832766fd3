"""shard_map all-to-all MoE (EXPERIMENTS.md §Perf): exactness vs the pjit
dispatch path, single-process (1-device mesh) and multi-device (subprocess
with 8 forced host devices — kept out-of-process so the main pytest run
stays on 1 device)."""
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import sharding as sh
from repro.meshctx import use_mesh_rules
from repro.models.common import init_dense
from repro.models.mlp import moe_apply, moe_apply_a2a, moe_spec

# jax.make_mesh defaults to Explicit axes; the mesh-rules path needs Auto.
AUTO2 = (jax.sharding.AxisType.Auto,) * 2
REPO = pathlib.Path(__file__).resolve().parents[1]


def test_a2a_equals_pjit_single_shard():
    cfg = configs.get_smoke("olmoe_1b_7b")
    p, _ = init_dense(jax.random.PRNGKey(0), moe_spec(cfg), jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(1).normal(0, 1, (2, 32, cfg.d_model)), jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    rules = sh.make_rules(cfg, mesh)
    with use_mesh_rules(mesh, rules):
        y1, a1 = jax.jit(lambda p, x: moe_apply(p, x, cfg))(p, x)
        y2, a2 = jax.jit(lambda p, x: moe_apply_a2a(p, x, cfg))(p, x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert abs(float(a1) - float(a2)) < 1e-6


def test_a2a_grads_match_single_shard():
    cfg = configs.get_smoke("olmoe_1b_7b")
    p, _ = init_dense(jax.random.PRNGKey(0), moe_spec(cfg), jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(2).normal(0, 1, (2, 16, cfg.d_model)), jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    rules = sh.make_rules(cfg, mesh)
    with use_mesh_rules(mesh, rules):
        g1 = jax.jit(jax.grad(lambda p, x: jnp.sum(moe_apply(p, x, cfg)[0] ** 2)))(p, x)
        g2 = jax.jit(jax.grad(lambda p, x: jnp.sum(moe_apply_a2a(p, x, cfg)[0] ** 2)))(p, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro import configs
    from repro.models.mlp import moe_apply, moe_apply_a2a, moe_spec
    from repro.models.common import init_dense
    from repro.meshctx import use_mesh_rules
    from repro.launch import sharding as sh
    AUTO2 = (jax.sharding.AxisType.Auto,) * 2
    cfg = configs.get_smoke("olmoe_1b_7b")
    p, _ = init_dense(jax.random.PRNGKey(0), moe_spec(cfg), jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (4, 32, cfg.d_model)),
                    jnp.float32)
    m1 = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    with use_mesh_rules(m1, sh.make_rules(cfg, m1)):
        y_ref, _ = jax.jit(
            lambda p, x: moe_apply(p, x, cfg, capacity_factor=8.0))(p, x)
    y_ref = np.asarray(y_ref)
    m = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
    with use_mesh_rules(m, sh.make_rules(cfg, m)):
        y2, _ = jax.jit(
            lambda p, x: moe_apply_a2a(p, x, cfg, capacity_factor=8.0))(p, x)
    d = float(np.max(np.abs(y_ref - np.asarray(y2))))
    assert d == 0.0, d
    print("OK", d)
""")


@pytest.mark.slow
def test_a2a_exact_on_8_devices():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
