"""Logical-axis partitioning: map parameter logical axes -> mesh axes.

This replaces the reference's partitioned-tensor bookkeeping (`ds_tensor`,
`ds_id`, partition/allgather primitives — ``runtime/zero/partition_parameters.py``)
with declarative sharding: every parameter carries a tuple of *logical* axis
names (e.g. ("embed", "mlp")), and a rules table maps logical names to mesh
axis names. GSPMD then inserts the all-gathers/reduce-scatters the reference
implements by hand.

t5x/flax use the same idea; the implementation here is our own and tuned to the
ZeRO-stage semantics described in zero/config.
"""

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = Union[str, Tuple[str, ...], None]


@dataclasses.dataclass
class ShardingRules:
    """Ordered logical->mesh rules; first match wins (like t5x rule lists)."""
    rules: Tuple[Tuple[str, MeshAxis], ...]

    def mesh_axes(self, logical_axes: Optional[Tuple[Optional[str], ...]]):
        if logical_axes is None:
            return P()
        table = dict(self.rules)
        out = []
        used = set()
        for name in logical_axes:
            axis = table.get(name) if name is not None else None
            # one mesh axis can only be used once per spec
            key = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
            if axis is not None and any(a in used for a in key):
                axis = None
            if axis is not None:
                used.update(key)
            out.append(tuple(axis) if isinstance(axis, list) else axis)
        while out and out[-1] is None:
            out.pop()
        return P(*out)


# Default logical-axis vocabulary used by deepspeed_tpu.models:
#   "embed"    — model hidden dim
#   "vocab"    — vocabulary dim
#   "mlp"      — MLP intermediate dim
#   "heads"    — attention heads dim
#   "kv"       — per-head dim
#   "qkv"      — fused qkv output dim
#   "expert"   — expert index dim (MoE stacked experts)
#   "unmodeled"— small params (biases, norms)
#   "layers"   — scanned-layer stacking dim

def make_rules(zero_stage: int, tp: bool = True, pipe: bool = False,
               fsdp_axis: str = "fsdp", tensor_axis: str = "tensor") -> ShardingRules:
    """Build the rules table realizing a ZeRO stage + optional TP + PP.

    stage <= 2: params replicated across DP — logical axes map only to tensor.
    stage == 3: the largest logical dim additionally shards over `fsdp`
    (all-gather-on-use inserted by GSPMD = ZeRO-3 fetch/release).
    pipe: the stacked `layers` dim shards over `pipe` (= the reference's
    PipelineModule layer partitioning, as a sharding choice).
    """
    t = tensor_axis if tp else None
    layers_axis = "pipe" if pipe else None
    if zero_stage >= 3:
        rules = (
            ("vocab", (fsdp_axis, t) if t else fsdp_axis),
            ("embed", fsdp_axis),
            ("mlp", t if t else fsdp_axis),
            ("heads", t if t else fsdp_axis),
            ("qkv", t if t else fsdp_axis),
            ("kv", None),
            ("expert", "expert"),
            ("layers", layers_axis),
            ("unmodeled", None),
        )
    else:
        rules = (
            ("vocab", t),
            ("embed", None),
            ("mlp", t),
            ("heads", t),
            ("qkv", t),
            ("kv", None),
            ("expert", "expert"),
            ("layers", layers_axis),
            ("unmodeled", None),
        )
    return ShardingRules(rules=tuple((k, v) for k, v in rules))


# --------------------------------------------------------------------------
# Param metadata pytrees
# --------------------------------------------------------------------------

def logical_to_sharding(logical_tree, mesh: Mesh, rules: ShardingRules):
    """Map a pytree of logical-axis tuples to a pytree of NamedSharding."""
    def one(axes):
        return NamedSharding(mesh, rules.mesh_axes(axes))
    return jax.tree.map(one, logical_tree, is_leaf=lambda x: x is None or isinstance(x, tuple))


def spec_tree(logical_tree, rules: ShardingRules):
    def one(axes):
        return rules.mesh_axes(axes)
    return jax.tree.map(one, logical_tree, is_leaf=lambda x: x is None or isinstance(x, tuple))


def shard_params(params, shardings):
    return jax.tree.map(lambda p, s: jax.device_put(p, s), params, shardings)


def num_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def params_bytes(params) -> int:
    return sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in jax.tree.leaves(params))


def sharded_bytes(tree) -> int:
    """PER-DEVICE resident bytes of a pytree of committed jax Arrays: each
    leaf is priced at its shard shape (``sharding.shard_shape``), so a
    tensor-sharded KV block pool is counted once per chip, not once per
    logical array. Leaves without a sharding (host numpy, abstract shapes)
    fall back to their full size — on a 1-device mesh the two agree.
    This is what the serving engine's ``pool_bytes`` reports: the HBM a
    chip actually spends, the number the memory law is written against."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            try:
                shape = sharding.shard_shape(tuple(shape))
            except Exception:  # pragma: no cover - exotic shardings
                pass
        total += int(np.prod(shape)) * np.dtype(leaf.dtype).itemsize
    return total
