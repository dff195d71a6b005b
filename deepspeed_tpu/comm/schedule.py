"""Collective scheduling: deferred gradient sync.

Reference: ``runtime/zero/stage_1_and_2.py`` — DeepSpeed's headline ZeRO
throughput comes as much from *when* collectives run as from sharding
itself: ``overlap_comm`` overlaps grad reduction with backward compute,
``no_sync`` defers it across accumulation boundaries.

TPU-native design: GSPMD owns collective *placement*, so scheduling policy
is expressed structurally —

* **deferred sync** (``comm.deferred_grad_sync``): the microbatch grad
  accumulation runs inside a ``shard_map`` that is *manual* over the
  ``data`` mesh axis (every other axis stays auto/GSPMD). Per-device grads
  accumulate locally across the whole scan — no data-axis collective can
  exist inside the loop because the axis is manual and nothing asks for
  one — and a single explicit ``psum``/``psum_scatter`` at the step
  boundary produces exactly the reduction the eager path spreads over every
  microbatch. Stage-1/2 dp-sync collective counts become independent of
  ``gradient_accumulation_steps`` (DeepSpeed ``no_sync`` semantics).

Everything here is pure spec/tree surgery plus the in-``shard_map``
boundary reduction; the engine wires it into the dense GSPMD step, the
fused K-step program, and (trivially — it is already deferred by
construction) the 1-bit shard_map step.
"""

from typing import Optional, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

DATA_AXIS = "data"


def shard_map_compat(f, mesh, *, in_specs, out_specs, manual_axes):
    """Partial-auto shard_map: only `manual_axes` become manual; every
    other mesh axis stays auto — GSPMD keeps partitioning the body over
    them (param all-gathers, TP reductions, fsdp constraints)."""
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=set(manual_axes), check_vma=False)


def _entries(spec: P):
    """PartitionSpec -> list of per-dim axis tuples."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(())
        elif isinstance(entry, (tuple, list)):
            out.append(tuple(entry))
        else:
            out.append((entry,))
    return out


def _from_entries(entries) -> P:
    out = [tuple(e) if len(e) > 1 else (e[0] if e else None) for e in entries]
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def axis_dim(spec: P, axis: str) -> Optional[int]:
    """Dim index carrying `axis`, or None."""
    for i, e in enumerate(_entries(spec)):
        if axis in e:
            return i
    return None


def drop_axis(spec: P, axis: str) -> P:
    """Remove every reference to `axis` from a spec (the LOCAL view of a
    tensor inside a region that is manual over `axis`)."""
    return _from_entries([tuple(a for a in e if a != axis)
                          for e in _entries(spec)])


def local_tree(spec_tree, axis: str = DATA_AXIS):
    """grad_specs -> their local (manual-over-`axis`) counterparts."""
    return jax.tree.map(lambda s: drop_axis(s, axis), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def deferred_supported(plan) -> Tuple[bool, str]:
    """Whether the deferred-sync shard_map region composes with this mesh.

    The region is manual over `data` only — params are never data-sharded,
    so they enter replicated and the model body runs unmodified (fsdp/
    tensor stay auto: GSPMD still inserts the per-use param all-gathers and
    TP reductions inside). Axes that restructure the step itself can't
    nest: pipeline's manual region, ring attention's seq collectives, and
    MoE's expert-data routing.
    """
    if plan.pipe > 1:
        return False, "pipeline parallelism wraps the step in its own " \
                      "manual mesh region"
    if plan.seq > 1:
        return False, "ring attention's seq-axis collectives cannot nest " \
                      "inside a manual-data region"
    if plan.expert > 1:
        return False, "expert-data routing folds the data axis at dispatch " \
                      "time"
    return True, ""


def boundary_reduce(grads, grad_specs, plan, *, mean: bool = True):
    """The ONE data-axis reduction of the deferred path, applied to the
    locally-accumulated grad tree inside the manual-over-`data` region.

    Per leaf: grad specs carrying `data` on a dim get a ``psum_scatter``
    (reduce-scatter) on that dim — the output lands exactly where ZeRO
    stage >= 2 wants it; replicated-over-data leaves get a ``psum``
    (all-reduce). ``mean=True`` folds the 1/data normalization in after the
    sum (an exponent-only scale for power-of-two meshes), matching the
    eager path's global-mean gradient bit-for-bit when the sums themselves
    are exact.
    """
    inv = np.float32(1.0 / plan.data)

    def one(g, spec):
        dim = axis_dim(spec, DATA_AXIS)
        if dim is None:
            g = lax.psum(g, DATA_AXIS)
        else:
            g = lax.psum_scatter(g, DATA_AXIS, scatter_dimension=dim,
                                 tiled=True)
        return g * inv if mean else g

    # grad_sync scope: the perf doctor's trace join attributes the boundary
    # collectives' device time to the grad-sync phase by this op_name path
    with jax.named_scope("grad_sync"):
        return jax.tree.map(one, grads, grad_specs,
                            is_leaf=lambda x: isinstance(x, P))


def manual_out_spec(grad_specs):
    """shard_map out_specs for the reduced grad tree: only the manual
    (`data`) placement is named; auto-axis sharding (fsdp/tensor) rides
    through from the constraints inside the body."""
    def one(spec):
        dim = axis_dim(spec, DATA_AXIS)
        if dim is None:
            return P()
        return P(*([None] * dim + [DATA_AXIS]))
    return jax.tree.map(one, grad_specs, is_leaf=lambda x: isinstance(x, P))
