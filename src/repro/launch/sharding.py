"""Sharding rules: logical axis names -> mesh axes, per architecture family.

The model code annotates every parameter (ParamSpec.axes) and the key
activations (``shard_act``) with *logical* names.  A rules table maps those
names onto mesh axes; strategies are data:

  * ``base_rules``     — TP on 'model', DP(+pod) on batch, FSDP off.
  * ``fsdp_rules``     — adds FSDP: 'embed' (the axis every weight matrix
    shares) is sharded over 'data', so param + optimizer-state memory scales
    1/(data*model).  XLA inserts the all-gather before use (prefetchable).
  * per-arch adjustments: MoE experts on 'model' (EP), kv_heads replicated
    when n_kv < model-axis size (MQA), SSM inner dim on 'model'.

``param_shardings(cfg, mesh, axes_tree, rules)`` maps a logical-axes pytree
to NamedShardings for pjit in_shardings.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

from repro.meshctx import logical_to_spec
from repro.models.common import ModelConfig
from repro.obs.d2h import leaves_nbytes

__all__ = [
    "make_rules", "param_shardings", "batch_shardings", "data_axes",
    "local_lane_mesh", "lane_padded_capacity", "lane_spec", "lane_put",
    "HostStager", "pinned_host_sharding",
]


def data_axes(mesh: Mesh) -> tuple:
    """The mesh axes carrying the batch: ('pod','data') or ('data',)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# Event-serving lane sharding (DetectorPool): a 1-D 'lanes' mesh over the
# local devices.  Lane->device placement is pure data — lane i lives at a
# fixed offset of the stacked state pytree, so membership churn (join/leave)
# moves no arrays and triggers no recompiles; the detector step has no
# cross-lane term, so the sharded pool needs no collectives at all.
# ---------------------------------------------------------------------------


def local_lane_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D ``('lanes',)`` mesh over the local devices (or the first
    ``n_devices`` of them).  A single-device host yields a 1-wide mesh, so
    sharded and unsharded pools share every code path."""
    import numpy as np

    devs = jax.local_devices()
    if n_devices is not None:
        devs = devs[: int(n_devices)]
    return Mesh(np.asarray(devs), ("lanes",))


def lane_padded_capacity(capacity: int, mesh: Mesh) -> int:
    """Physical lane count: ``capacity`` rounded up so the lane axis splits
    evenly across the mesh (the padding lanes just ride along masked)."""
    n = mesh.shape["lanes"]
    return ((int(capacity) + n - 1) // n) * n


def lane_spec(lane_axis: int = 0) -> P:
    """PartitionSpec placing ``lane_axis`` on the 'lanes' mesh axis (all
    other dims replicated; rank-deficient leaves — scalars next to a
    lane-stacked tree — should use ``P()`` instead)."""
    return P(*([None] * lane_axis), "lanes")


def lane_put(mesh: Mesh, tree, lane_axis: int = 0):
    """device_put a lane-stacked pytree with the lane axis sharded across
    the mesh; leaves with too few dims (shared scalars) stay replicated."""
    def one(leaf):
        spec = lane_spec(lane_axis) if leaf.ndim > lane_axis else P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(one, tree)


def pinned_host_sharding(device) -> Optional[SingleDeviceSharding]:
    """``pinned_host`` sharding for ``device``, or None when the runtime
    doesn't expose one (CPU devices, or backends without memory spaces).

    Factored out of ``HostStager`` so the capability probe is unit-testable
    with stub devices on CPU-only hosts.
    """
    if getattr(device, "platform", "cpu") == "cpu":
        return None
    memories = getattr(device, "addressable_memories", None)
    if memories is None:
        return None
    # a runtime error from the probe propagates: it is a fault, not an
    # answer of "no pinned memory"
    kinds = {m.kind for m in memories()}
    if "pinned_host" not in kinds:
        return None
    return SingleDeviceSharding(device, memory_kind="pinned_host")


class HostStager:
    """Pinned (page-locked) host staging for H2D event uploads.

    A plain ``jnp.asarray(host_array)`` upload gives the driver a pageable
    buffer, so every copy pays a hidden pageable -> pinned bounce and the
    DMA cannot overlap compute.  On runtimes that expose a ``pinned_host``
    memory space (CUDA, TPU) this stager device_puts the host array into
    pinned memory first and then issues the device copy from there — the
    second hop reads locked pages directly, making the pool's H2D event
    uploads async-copy-capable.  On hosts without a pinned space
    (CPU-only CI) ``put`` degrades transparently to ``jnp.asarray``: same
    values, same device, no staging — so every caller keeps one code path.

    ``depth`` sizes the in-flight double buffer: the stager keeps the last
    ``depth`` pinned slabs alive (a bounded deque), so a caller that stages
    upload *i+1* while upload *i*'s device copy is still in flight never
    races the source pages — depth 2 is the pump pipeline's stage-ahead
    window (one block staging while one executes).
    """

    def __init__(self, device=None, *, depth: int = 2):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.device = jax.devices()[0] if device is None else device
        self._pinned = pinned_host_sharding(self.device)
        # the second hop names the device memory space: a plain device
        # target refuses a buffer that lives in pinned_host memory
        self._on_device = SingleDeviceSharding(self.device,
                                               memory_kind="device")
        self.depth = int(depth)
        self._inflight = collections.deque(maxlen=self.depth)
        self.uploads = 0          # put() calls routed through this stager
        self.staged_bytes = 0     # bytes that went via pinned memory

    @property
    def pinned(self) -> bool:
        """True iff uploads actually stage through pinned host memory."""
        return self._pinned is not None

    def put(self, arr) -> jax.Array:
        self.uploads += 1
        if self._pinned is None:
            return jnp.asarray(arr)
        staged = jax.device_put(arr, self._pinned)
        # byte math lives in repro.obs (the CI metrics-ownership lint
        # bans ad-hoc nbytes arithmetic in serve/ and launch/)
        self.staged_bytes += leaves_nbytes(staged)
        # retain the pinned slab until `depth` newer uploads have staged:
        # the second-hop copy may still be reading these locked pages when
        # the caller moves on to stage the next block
        self._inflight.append(staged)
        return jax.device_put(staged, self._on_device)


def make_rules(cfg: ModelConfig, mesh: Mesh, *, fsdp: bool = True,
               global_batch: Optional[int] = None,
               overrides: Optional[dict] = None) -> dict:
    """Logical-axis -> mesh-axis rules for (cfg, mesh).

    ``global_batch``: when given, the batch axes shrink to the largest prefix
    of ('pod','data') whose product divides it (batch=1 long-context decode
    replicates the batch instead of failing to shard).
    """
    batch = data_axes(mesh)
    if global_batch is not None:
        chosen = []
        prod = 1
        for a in batch:
            if global_batch % (prod * mesh.shape[a]) == 0:
                chosen.append(a)
                prod *= mesh.shape[a]
        batch = tuple(chosen)
    model_size = mesh.shape.get("model", 1)

    rules: dict = {
        # --- activations ---------------------------------------------------
        "batch": batch,
        "seq": None,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "expert": "model",       # EP
        "expert_cap": batch,     # token groups stay data-sharded
        # --- params ----------------------------------------------------------
        "embed": "data" if fsdp else None,     # FSDP shard axis
        "embed2": "model",                     # concat-input projections (TP)
        "layers": None,
        "head_dim": None,
        "q_lora": None,
        "kv_lora": None,
        # SSM
        "inner": "model",
        "inner_all": "model",
        "ssm_heads": None,
    }

    # Experts take the model axis (EP); the expert FF dim then stays local.
    # If experts don't divide the axis, fall back to TP inside experts.
    rules["expert_mlp"] = None
    if cfg.n_experts and cfg.n_experts % model_size != 0:
        rules["expert"] = None
        rules["expert_mlp"] = "model"
    # MQA / small-KV: replicating KV heads beats padding a size-<16 axis.
    if 0 < cfg.n_kv < model_size:
        rules["kv_heads"] = None
    # Heads not divisible by the model axis (e.g. qwen2-0.5b's 14 heads):
    # GSPMD would pad; replicate instead and keep TP on the MLP only.
    if cfg.n_heads and cfg.n_heads % model_size != 0:
        rules["heads"] = None
    if cfg.vocab % model_size != 0:
        rules["vocab"] = None

    if overrides:
        rules.update(overrides)
    return rules


def param_shardings(mesh: Mesh, axes_tree, rules: dict):
    """Pytree of logical-axes tuples -> pytree of NamedShardings."""
    def one(axes):
        spec = logical_to_spec(axes, rules)
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        one, axes_tree, is_leaf=lambda x: isinstance(x, tuple)
    )


def batch_shardings(mesh: Mesh, batch_tree, rules: dict):
    """Input batches: leading dim on the batch axes, rest replicated."""
    batch = rules.get("batch")

    def one(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(batch, *([None] * (nd - 1))))

    return jax.tree.map(one, batch_tree)


def cache_shardings(mesh: Mesh, cache_tree, rules: dict, cfg: ModelConfig):
    """Decode caches: (layers/sites, batch, ...) -> batch on axis 1; the
    kv-head axis (if present and sharded) follows the rules.  ``enc_out``
    (whisper's encoder output) is the one un-stacked leaf: batch-first."""
    batch = rules.get("batch")

    def one(path, leaf):
        names = "/".join(str(getattr(k, "key", k)) for k in path)
        nd = len(leaf.shape)
        if "enc_out" in names:
            return NamedSharding(mesh, P(batch, *([None] * (nd - 1))))
        if nd >= 4 and cfg.n_kv and leaf.shape[-2] == cfg.n_kv:
            kv = rules.get("kv_heads")
            return NamedSharding(
                mesh, P(None, batch, *([None] * (nd - 4)), kv, None)
            )
        if nd >= 2:
            return NamedSharding(mesh, P(None, batch, *([None] * (nd - 2))))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(one, cache_tree)
