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
import jax.numpy as jnp
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


# --------------------------------------------------------------------------
# Chunked tensor-parallel collective-matmul overlap
# --------------------------------------------------------------------------

TENSOR_AXIS = "tensor"

_OVERLAP_WARNED = False


def _warn_overlap_unhosted(chunks):
    global _OVERLAP_WARNED
    if _OVERLAP_WARNED:
        return
    _OVERLAP_WARNED = True
    from deepspeed_tpu.utils.logging import logger
    logger.warning(
        f"tp_overlap_chunks={chunks}: a '{TENSOR_AXIS}' mesh axis is active "
        "but the trace context cannot host the chunked collective-matmul "
        "overlap (a surrounding manual shard_map region — e.g. "
        "comm.deferred_grad_sync — owns the partitioning); the row-parallel "
        "projections fall back to the serial matmul with an exposed "
        "boundary all-reduce")


def _tp_degree_for_overlap():
    """(mesh, active tensor-parallel degree) usable for the chunked
    decomposition — degree 0 when the current context cannot host it: no
    mesh, tensor absent or size 1, tensor already manual (nested shard_map
    regions own it), or any partially-manual region (the nested shard_map
    cannot be established from inside another manual region)."""
    from deepspeed_tpu.parallel.context import (in_manual_region,
                                                physical_mesh_env)
    env_mesh, shape, _ = physical_mesh_env()
    if env_mesh is None:
        return None, 0
    tp = shape.get(TENSOR_AXIS, 1)
    if tp <= 1 or in_manual_region():
        return env_mesh, 0
    return env_mesh, tp


def row_parallel_matmul(x, w, *, chunks: int = 0):
    """``x @ w`` for a row-parallel weight (contraction dim sharded over the
    ``tensor`` mesh axis) with the tensor-axis reduction DECOMPOSED into
    ``chunks`` independent psums.

    GSPMD compiles the plain matmul to one local matmul + ONE all-reduce of
    the whole [B, S, H] output — a serial wire bubble at the end of every
    row-parallel projection. Chunking the rows makes chunk i's all-reduce
    and chunk i+1's matmul independent ops the latency-hiding scheduler can
    interleave (the collective-matmul overlap the reference gets from
    ``overlap_comm`` CUDA streams). Bit-identical to the unchunked path:
    each output element still sums the same per-shard partials in the same
    order — only the *grouping* of elements per collective changes. The
    BACKWARD is pinned to the plain matmul's own vjp via ``jax.custom_vjp``:
    auto-transposing the chunked region would split the weight-grad's
    sequence contraction per chunk (partial sums of partials — a genuine
    float reordering), whereas the plain vjp is the exact program the
    unchunked path compiles, so end-to-end training parity stays exact.

    Expressed as a partial-auto ``shard_map`` manual over ``tensor`` only
    (the deferred-grad-sync machinery, comm/schedule.py): batch axes stay
    auto, so GSPMD keeps partitioning the chunk matmuls over data/fsdp.
    Falls back to the plain matmul whenever the context can't host the
    decomposition (no tensor axis, nested manual region, indivisible
    shapes) — enabling the config on a 1-chip run changes nothing.
    """
    env_mesh, tp = _tp_degree_for_overlap()
    if not tp:
        if chunks and chunks > 1 and env_mesh is not None \
                and dict(env_mesh.shape).get(TENSOR_AXIS, 1) > 1:
            # a tensor axis EXISTS but the context can't host the overlap
            # (manual region owns it, e.g. comm.deferred_grad_sync's
            # shard_map) — say so once instead of silently serializing the
            # projection, the exact defect the serialized-backward corpus
            # entry plants
            _warn_overlap_unhosted(chunks)
        return x @ w
    if not chunks or chunks <= 1 or w.ndim != 2 \
            or x.shape[-1] != w.shape[0] or w.shape[0] % tp or x.ndim < 2:
        return x @ w
    # chunk along the second-to-last (sequence) dim; largest divisor <= chunks
    dim = x.ndim - 2
    c = min(int(chunks), int(x.shape[dim]))
    while c > 1 and x.shape[dim] % c:
        c -= 1
    if c <= 1:
        return x @ w
    from jax import lax as _lax
    from jax.sharding import PartitionSpec as _P
    from deepspeed_tpu.comm.schedule import shard_map_compat
    size = x.shape[dim] // c

    def body(xl, wl):
        parts = []
        for i in range(c):
            xc = _lax.slice_in_dim(xl, i * size, (i + 1) * size, axis=dim)
            parts.append(_lax.psum(
                jnp.matmul(xc, wl), TENSOR_AXIS))
        return jnp.concatenate(parts, axis=dim)

    in_x = _P(*([None] * (x.ndim - 1) + [TENSOR_AXIS]))

    @jax.custom_vjp
    def chunked(x, w):
        fn = shard_map_compat(body, env_mesh,
                              in_specs=(in_x, _P(TENSOR_AXIS, None)),
                              out_specs=_P(),
                              manual_axes=(TENSOR_AXIS,))
        return fn(x, w)

    def chunked_fwd(x, w):
        return chunked(x, w), (x, w)

    def chunked_bwd(res, g):
        xr, wr = res
        _, vjp = jax.vjp(lambda a, b: jnp.matmul(a, b), xr, wr)
        return vjp(g)

    chunked.defvjp(chunked_fwd, chunked_bwd)
    try:
        return chunked(x, w)
    except Exception as e:  # noqa: BLE001 — composition contexts we can't host
        # loud fallback: a silently-serialized projection is exactly the
        # defect the serialized-backward corpus entry plants — if the
        # overlap the config asked for can't be hosted, say so
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            f"tp_overlap_chunks={chunks}: chunked collective-matmul overlap "
            f"fell back to the serial matmul ({type(e).__name__}: {e}); the "
            "boundary all-reduce will be exposed")
        return x @ w


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
