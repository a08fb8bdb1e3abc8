"""Parameter / activation sharding rules (DESIGN.md §5).

Conventions on the production mesh (("pod",) "data", "model"):
  * TP over "model": attention heads, FFN hidden, vocab, MoE experts.
  * FSDP over `fsdp_axes` (usually ("data",), plus "pod" for the 1T MoE):
    the remaining large dimension of each weight.
  * batch over dp_axes = ("pod", "data") when multi-pod.

Rules are name-based over the param tree; scanned stacks (leading
n_steps axis) get a None prepended automatically. Everything funnels
through `param_specs` / `batch_specs` so train/serve/dry-run agree.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.moe import MeshContext

__all__ = [
    "ShardingRules",
    "param_specs",
    "batch_specs",
    "cache_specs",
    "make_mesh_context",
    "named",
    "STREAM_AXIS",
    "stream_mesh",
    "stream_shardings",
    "replicated_shardings",
    "surviving_devices",
]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    dp_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    fsdp_axes: Tuple[str, ...] = ("data",)
    fsdp: bool = True

    @property
    def fsdp_spec(self):
        if not self.fsdp:
            return None
        return self.fsdp_axes if len(self.fsdp_axes) > 1 else self.fsdp_axes[0]

    @property
    def dp_spec(self):
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]


def make_mesh_context(rules: ShardingRules) -> MeshContext:
    return MeshContext(
        mesh=rules.mesh,
        dp_axes=rules.dp_axes,
        model_axis=rules.model_axis,
        fsdp_axes=rules.fsdp_axes if rules.fsdp else (),
    )


# expected trailing ndims for each named weight class
_RULES = {
    # name: (base_ndim, spec builder)
    "embed": (2, lambda r: P(r.model_axis, r.fsdp_spec)),
    "head": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "wq": (3, lambda r: P(r.fsdp_spec, r.model_axis, None)),
    "wk": (3, lambda r: P(r.fsdp_spec, r.model_axis, None)),
    "wv": (3, lambda r: P(r.fsdp_spec, r.model_axis, None)),
    "wo": (3, lambda r: P(r.model_axis, None, r.fsdp_spec)),
    "w_up": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "w_gate": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "w_down": (2, lambda r: P(r.model_axis, r.fsdp_spec)),
    "router": (2, lambda r: P(None, None)),
    # mamba2 projections (column-parallel inner dim / heads over model)
    "in_proj": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "out_proj": (2, lambda r: P(r.model_axis, r.fsdp_spec)),
    "w_z": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "w_x": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "w_dt": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "conv_w": (2, lambda r: P(None, r.model_axis)),  # (K, d_inner)
    # rwkv6 time-mix (channels == heads x head_dim over model)
    "w_r": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "w_k": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "w_v": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "w_g": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "w_o": (2, lambda r: P(r.model_axis, r.fsdp_spec)),
    # rwkv6 channel-mix
    "cm_w_k": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
    "cm_w_v": (2, lambda r: P(r.model_axis, r.fsdp_spec)),
    "cm_w_r": (2, lambda r: P(r.fsdp_spec, r.model_axis)),
}

# MoE expert banks: one extra leading expert axis sharded over model
_EXPERT_RULES = {
    "w_up": lambda r: P(r.model_axis, r.fsdp_spec, None),
    "w_gate": lambda r: P(r.model_axis, r.fsdp_spec, None),
    "w_down": lambda r: P(r.model_axis, None, r.fsdp_spec),
}


def _axes_size(entry, mesh: Mesh) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        return mesh.shape[entry]
    n = 1
    for ax in entry:
        n *= mesh.shape[ax]
    return n


def _fit(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec entries that don't divide the dim size (explicit
    in_shardings require exact divisibility). The systematic case is GQA
    kv heads (8) on the 16-way model axis: KV projections replicate
    under wide TP (Megatron convention — attention then runs fully local
    per rank); the KV *cache* stays distributed by sharding its sequence
    axis instead (see cache_specs)."""
    dims = list(spec) + [None] * (len(shape) - len(spec))
    for i, entry in enumerate(dims):
        if entry is None:
            continue
        if shape[i] % _axes_size(entry, mesh) != 0:
            dims[i] = None
    return P(*dims)


def _leaf_spec(path, leaf, rules: ShardingRules) -> P:
    keys = [
        k.key if isinstance(k, jax.tree_util.DictKey) else None
        for k in path
    ]
    names = [k for k in keys if isinstance(k, str)]
    name = names[-1] if names else ""
    # int8 serving weights: {"q","s"} dicts under the weight's name —
    # q inherits the weight rule; s drops the (now size-1) last-dim entry
    is_q = is_s = False
    if name in ("q", "s") and len(names) >= 2:
        is_q, is_s = name == "q", name == "s"
        name = names[-2]
    in_moe = "moe" in names or "experts" in names
    ndim = leaf.ndim

    if in_moe and name in _EXPERT_RULES:
        base = 3
        spec = _EXPERT_RULES[name](rules)
    elif name in _RULES:
        base, builder = _RULES[name]
        spec = builder(rules)
    else:
        # norms, biases, small vectors: replicated
        base = ndim
        spec = P(*([None] * ndim))
    extra = ndim - base
    if extra < 0:
        return P(*([None] * ndim))
    dims = [None] * extra + list(spec)
    if is_s:
        dims = dims[:-1] + [None]
    del is_q
    return _fit(P(*dims), leaf.shape, rules.mesh)


def param_specs(params_shape: Any, rules: ShardingRules):
    """Pytree of PartitionSpec matching a params (shape) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _leaf_spec(p, l, rules), params_shape
    )


def named(tree_specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs)


def batch_specs(batch_shape: Any, rules: ShardingRules):
    """Input batch: leading dim is the global batch -> dp axes; if the
    batch doesn't divide the dp axes (long-context batch=1), replicate."""
    dp_total = 1
    for ax in rules.dp_axes:
        dp_total *= rules.mesh.shape[ax]

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        if leaf.shape[0] % dp_total == 0:
            return P(*([rules.dp_spec] + [None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(spec, batch_shape)


# --------------------------------------------------------------------------
# Stream-parallel serving mesh (KWS)
# --------------------------------------------------------------------------
#
# The KWS server's unit of parallelism is the stream SLOT: every
# `ServerState` leaf, input slab, and submitted mask leads with the
# (max_streams,) slot axis, and slots are computationally independent
# (per-stream GRU state, filter carry, scores — no cross-slot reduction
# anywhere in the tick). That makes the slot axis embarrassingly
# shardable: a 1-D ("stream",) mesh splits it block-wise over devices
# while the classifier/frontend parameters replicate.

STREAM_AXIS = "stream"


def stream_mesh(
    devices: Union[int, Sequence[Any], None] = None
) -> Mesh:
    """A 1-D ``("stream",)`` mesh for stream-parallel serving.

    devices: an int (the first N visible devices), an explicit device
    sequence, or None for every visible device. An int larger than the
    visible device count is an error — serving capacity planning must
    not silently degrade.
    """
    if devices is None:
        devs = list(jax.devices())
    elif isinstance(devices, int):
        visible = list(jax.devices())
        if devices < 1 or devices > len(visible):
            raise ValueError(
                f"stream_mesh(devices={devices}) but only "
                f"{len(visible)} device(s) visible"
            )
        devs = visible[:devices]
    else:
        devs = list(devices)
    # Auto axis type: the serving programs shard by in/out shardings,
    # and a slot reset's scatter must not need an explicit out_sharding
    # (`jax.make_mesh` defaults to Explicit axes)
    return Mesh(np.asarray(devs), (STREAM_AXIS,))


def surviving_devices(mesh: Mesh, lost_index: int) -> list:
    """Devices of a 1-D stream mesh minus the lost shard's device, in
    mesh order — the pool a shard-loss recovery rebuilds its (smaller)
    mesh from (`StreamingKWSServer.recover_shard_loss` hands this to
    `ElasticMeshManager`, whose power-of-two shrink takes a prefix)."""
    devs = list(np.ravel(mesh.devices))
    if not 0 <= lost_index < len(devs):
        raise ValueError(
            f"lost_index {lost_index} outside mesh of {len(devs)} "
            "device(s)"
        )
    return [d for i, d in enumerate(devs) if i != lost_index]


def stream_shardings(tree: Any, mesh: Mesh):
    """NamedShardings sharding every leaf's LEADING axis over
    ``"stream"`` (scalars replicate) — the layout of `ServerState`
    leaves and per-tick slot-major slabs. Scanned replay slabs
    ``(n_ticks, max_streams, ...)`` shard their second axis instead;
    the serving loop spells those specs out at its jit boundary."""

    def spec(leaf):
        ndim = getattr(leaf, "ndim", 0)
        if ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(STREAM_AXIS, *([None] * (ndim - 1))))

    return jax.tree.map(spec, tree)


def replicated_shardings(tree: Any, mesh: Mesh):
    """Fully replicated NamedShardings (classifier params, frontend
    calibration state, scalars)."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def cache_specs(cache_shape: Any, rules: ShardingRules, batch: int):
    """Serving-state sharding, keyed by leaf name.

    KV caches ("k"/"v", shape (..., B, S, KV, hd)): batch over dp when it
    divides; the SEQUENCE axis shards over "model" (plus "data" when the
    batch cannot shard — long-context batch=1). Decode attention then
    reduces its softmax over the sharded seq dim: flash-decoding, with
    GSPMD inserting the cross-shard max/sum. Recurrent states shard
    their head/channel axis over "model" to match the column-parallel
    projections that produce them."""
    dp_total = 1
    for ax in rules.dp_axes:
        dp_total *= rules.mesh.shape[ax]
    batch_ok = batch % dp_total == 0
    seq_axes = (
        rules.model_axis if batch_ok else ("data", rules.model_axis)
    )

    def spec(path, leaf):
        names = [
            k.key for k in path if isinstance(k, jax.tree_util.DictKey)
        ]
        name = names[-1] if names else ""
        dims = [None] * leaf.ndim
        bidx = None
        for i, d in enumerate(leaf.shape[:2]):
            if d == batch:
                bidx = i
                break
        if bidx is None:
            return P(*dims)
        if batch_ok:
            dims[bidx] = rules.dp_spec
        if name in ("k", "v") and leaf.ndim >= bidx + 4:
            dims[bidx + 1] = seq_axes  # sequence axis
        elif name in ("wkv", "ssd") and leaf.ndim >= bidx + 3:
            dims[bidx + 1] = rules.model_axis  # heads
        elif name == "conv":
            dims[-1] = rules.model_axis  # d_inner (column-parallel)
        return _fit(P(*dims), leaf.shape, rules.mesh)

    return jax.tree_util.tree_map_with_path(spec, cache_shape)
