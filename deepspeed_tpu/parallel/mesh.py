"""Mesh planner: world size + parallelism degrees -> a named `jax.sharding.Mesh`.

Reference equivalents: ``deepspeed/utils/groups.py:45`` (DP/MP/EP group
factory), ``runtime/pipe/topology.py:9`` (ProcessTopology rank grid). On TPU
the rank grid IS the mesh: process groups become named mesh axes, and group
collectives become `jax.lax` ops over those axis names.

Axis names (fixed vocabulary):
  pipe   — pipeline stages (outermost: cross-slice/DCN friendly)
  data   — pure data parallel (replicated params)
  fsdp   — ZeRO/FSDP data parallel (params/grads/opt sharded)
  seq    — sequence/context parallelism (ring attention)
  tensor — tensor-model parallelism (megatron-style col/row)
  expert — expert parallelism for MoE (folded from data×fsdp at dispatch time)

ZeRO stages map onto (data, fsdp): stage 0-2 put all DP on "data"; stage 3
puts it on "fsdp" (params sharded there). Stage 1/2 shard optimizer
state/grads over "data" without sharding params — see zero/partition rules.
"""

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np
from jax.sharding import Mesh

# canonical axis order, outermost first — pipe outermost so that PP crosses
# the slowest links (DCN) and tensor innermost so TP rides fastest ICI links.
AXIS_ORDER = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

# the axes that together carry the global batch dim (engine._batch_spec and
# the model-side activation constraint must agree on this set)
BATCH_AXES = ("data", "fsdp", "expert")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Resolved parallelism degrees for the current world size."""
    pipe: int = 1
    data: int = 1
    fsdp: int = 1
    expert: int = 1    # expert parallelism: carved out of the dp degree
    seq: int = 1
    tensor: int = 1

    @property
    def world_size(self) -> int:
        return (self.pipe * self.data * self.fsdp * self.expert * self.seq
                * self.tensor)

    @property
    def dp_world_size(self) -> int:
        """Total data-parallel degree: expert groups also consume distinct
        data (= the reference's expert-data-parallel groups)."""
        return self.data * self.fsdp * self.expert

    def axis_sizes(self) -> Dict[str, int]:
        return {"pipe": self.pipe, "data": self.data, "fsdp": self.fsdp,
                "expert": self.expert, "seq": self.seq, "tensor": self.tensor}

    def describe(self) -> str:
        return "x".join(f"{k}={v}" for k, v in self.axis_sizes().items() if v > 1) or "single"


def plan_from_config(config, world_size: int) -> MeshPlan:
    """Derive the mesh plan from config + world size.

    Explicit `mesh.axes` wins; otherwise degrees come from
    pipeline.stages / tensor_parallel.tp_size / sequence_parallel.sp_size /
    moe.expert_parallel_size, and the remaining factor becomes data or fsdp
    depending on the ZeRO stage (stage>=3 -> fsdp, else data).
    """
    explicit = dict(config.mesh.axes or {})
    if explicit:
        ep_default = (config.moe.expert_parallel_size
                      if config.moe.enabled else 1)
        plan = MeshPlan(
            pipe=explicit.get("pipe", 1), data=explicit.get("data", 1),
            fsdp=explicit.get("fsdp", 1),
            expert=explicit.get("expert", ep_default),
            seq=explicit.get("seq", 1), tensor=explicit.get("tensor", 1))
        if plan.world_size != world_size:
            raise ValueError(f"mesh.axes product {plan.world_size} != world size {world_size}")
        return plan

    pp = max(1, config.pipeline.stages)
    tp = max(1, config.tensor_parallel.tp_size)
    sp = max(1, config.sequence_parallel.sp_size)
    denom = pp * tp * sp
    if world_size % denom != 0:
        raise ValueError(f"world size {world_size} not divisible by pipe({pp})*tensor({tp})*seq({sp})")
    dp = world_size // denom
    ep = max(1, config.moe.expert_parallel_size) if config.moe.enabled else 1
    if dp % ep != 0:
        raise ValueError(f"expert_parallel_size {ep} must divide dp degree {dp}")
    dp //= ep
    stage = config.zero_optimization.stage
    if stage >= 3:
        data, fsdp = 1, dp
    else:
        data, fsdp = dp, 1
    return MeshPlan(pipe=pp, data=data, fsdp=fsdp, expert=ep, seq=sp, tensor=tp)


def build_mesh(plan: MeshPlan, devices: Optional[List] = None) -> Mesh:
    """Build the device mesh.

    On real TPUs `jax.experimental.mesh_utils.create_device_mesh` assigns
    devices so the innermost axes land on the fastest ICI rings of the
    torus; elsewhere (CPU, one device) the order is a plain reshape. A
    failure of the torus assignment propagates — a mesh silently built in
    naive device order would run every collective over the wrong links.
    """
    import jax
    from jax.experimental import mesh_utils
    devices = devices if devices is not None else jax.devices()
    shape = tuple(getattr(plan, ax) for ax in AXIS_ORDER)
    n = int(np.prod(shape))
    if n != len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    if len(devices) > 1 and devices[0].platform == "tpu":
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def single_device_mesh() -> Mesh:
    import jax
    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,) * len(AXIS_ORDER)), AXIS_ORDER)


# --------------------------------------------------------------------------
# Topology queries (reference: runtime/pipe/topology.py ProcessTopology API)
# --------------------------------------------------------------------------

class Topology:
    """Rank-grid queries over the mesh, mirroring the reference's
    ``ProcessTopology`` (``runtime/pipe/topology.py:9``): get_rank(axis=coord),
    get_axis_comm_lists, filter_match."""

    def __init__(self, plan: MeshPlan):
        self.plan = plan
        self.axes = [ax for ax in AXIS_ORDER]
        self.dims = [getattr(plan, ax) for ax in AXIS_ORDER]

    def world_size(self) -> int:
        return int(np.prod(self.dims))

    def get_rank(self, **coords) -> int:
        idx = [coords.get(ax, 0) for ax in self.axes]
        return int(np.ravel_multi_index(idx, self.dims))

    def get_coord(self, rank: int) -> Dict[str, int]:
        unraveled = np.unravel_index(rank, self.dims)
        return {ax: int(c) for ax, c in zip(self.axes, unraveled)}

    def get_dim(self, axis: str) -> int:
        return self.dims[self.axes.index(axis)]

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """Groups of ranks that differ only along `axis` (the reference builds
        torch process groups from these; we only need them for tests/tools)."""
        ai = self.axes.index(axis)
        groups = {}
        for rank in range(self.world_size()):
            coord = list(np.unravel_index(rank, self.dims))
            key = tuple(c for i, c in enumerate(coord) if i != ai)
            groups.setdefault(key, []).append(rank)
        return [sorted(v) for _, v in sorted(groups.items())]

    def filter_match(self, **coords) -> List[int]:
        out = []
        for rank in range(self.world_size()):
            c = self.get_coord(rank)
            if all(c[k] == v for k, v in coords.items()):
                out.append(rank)
        return out
