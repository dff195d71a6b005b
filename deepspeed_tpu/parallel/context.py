"""Ambient parallel context.

The engine publishes its mesh/plan here so that model-internal ops (ring
attention over the `seq` axis, MoE dispatch) can build shard_maps without
threading the mesh through every model signature. Mirrors how the reference
publishes process groups via the global ``deepspeed.utils.groups`` registry
(``utils/groups.py``) rather than passing them explicitly.
"""

from typing import Optional

from jax._src import core as _core
from jax._src import mesh as _mesh_lib
from jax.sharding import AxisType, Mesh, get_abstract_mesh

_MESH: Optional[Mesh] = None
_PLAN = None


def set_parallel_context(mesh: Mesh, plan) -> None:
    global _MESH, _PLAN
    _MESH = mesh
    _PLAN = plan


def current_mesh() -> Optional[Mesh]:
    return _MESH


def current_plan():
    return _PLAN


def seq_parallel_degree() -> int:
    return getattr(_PLAN, "seq", 1) if _PLAN is not None else 1


def physical_mesh_env():
    """(physical mesh | None, {axis: size}, shard_map-bound axis names) of
    the ambient trace context.

    The one sanctioned home for the jax._src introspection the model-internal
    sharding hints need: ``thread_resources.env.physical_mesh`` is the mesh
    the surrounding ``with mesh:`` / jit established; the bound set is the
    axes a surrounding ``shard_map`` has already made manual (constraining
    over those would double-partition). Imported plainly at module scope
    (resolved on jax 0.9.0): a rename in a later jax must fail the import,
    not silently drop every sharding hint."""
    env_mesh = _mesh_lib.thread_resources.env.physical_mesh
    if env_mesh.empty:
        return None, {}, set()
    return env_mesh, dict(env_mesh.shape), set(_core.get_axis_env().axis_sizes)


def in_manual_region() -> bool:
    """True inside a (fully or partially) manual ``shard_map`` body: the
    body sees per-shard views, so sharding constraints and nested
    shard_maps over the ambient mesh must not be applied there."""
    return any(t is AxisType.Manual
               for t in get_abstract_mesh().axis_types)


def kernel_mesh():
    """(mesh, {axis: size}) an opaque kernel call must be ``shard_map``-ped
    over in the ambient trace context, or ``(None, {})`` when nothing would
    partition it (no mesh, one device, or every axis already manual).

    A Pallas (Mosaic) custom call carries no partitioning rule: left bare
    under ``jit`` with operands sharded over a multi-device mesh, jax 0.9.0
    refuses to lower it ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" — met on a 2x2 v5e
    mesh, PR 21). Callers map the call over the returned axes —
    ALL of them, so the region is fully manual — and name in their specs
    the ones their operands are sharded on. Inside a partially-manual
    region (deferred grad sync) the mesh is the context's abstract mesh
    and the axes are the ones still auto."""
    env_mesh, shape, _ = physical_mesh_env()
    if env_mesh is None or env_mesh.size == 1:
        return None, {}
    am = get_abstract_mesh()
    if am.empty:
        return env_mesh, shape
    auto = {a: n for a, n, t in zip(am.axis_names, am.axis_sizes,
                                    am.axis_types)
            if t is not AxisType.Manual}
    return (am, auto) if any(n > 1 for n in auto.values()) else (None, {})
