"""Production meshes.

Factory functions only — importing this module never touches jax device
state; the dry-run sets XLA_FLAGS *before* any jax import and then calls
``make_production_mesh``.

Axes:
  pod   — inter-pod data parallelism (DCN-connected; gradient all-reduce
          crosses this axis once per step)
  data  — intra-pod data parallel + FSDP (optimizer/param shards)
  model — tensor / expert / head parallelism (highest-bandwidth ICI ring)
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "HW"]


def _auto(n: int) -> tuple:
    """``Auto`` axis types: ``jax.make_mesh`` defaults to ``Explicit`` axes,
    which ``with_sharding_constraint`` (``meshctx.shard_act``) rejects."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


class HW:
    """TPU v5e per-chip constants used by the roofline (per the brief)."""

    PEAK_BF16_FLOPS = 197e12          # FLOP/s
    HBM_BW = 819e9                    # B/s
    ICI_BW = 50e9                     # B/s per link
    HBM_BYTES = 16 * 2**30
    VMEM_BYTES = 16 * 2**20
