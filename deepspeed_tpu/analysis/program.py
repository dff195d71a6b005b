"""Lowering step programs to analyzable artifacts — without executing them.

The whole pass is static: ``jax.jit(...).lower(abstract args).compile()``
produces the partitioned program XLA would run, on any backend, with no data
and no step executed. A 2-device CPU process therefore audits the same
collective structure an N-chip slice would get from GSPMD for that mesh
shape.

Also home to the runtime SPMD-warning capture absorbed from
``utils/hlo_check`` (the one check that needs fd-level interception rather
than program text: XLA's partitioner logs its replication fallback on fd 2
from C++).
"""

import contextlib
import dataclasses
import os
import sys
import tempfile
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.memory import abstractify  # noqa: F401  (its home)


@dataclasses.dataclass
class ProgramArtifacts:
    """Every representation of one lowered step program the analyzers read."""
    name: str                          # e.g. "train_step"
    optimized_hlo: str                 # post-GSPMD/fusion (collectives, aliases)
    pre_hlo: str = ""                  # pre-optimization HLO (sharding annots)
    stablehlo: str = ""                # per-arg aliasing/sharding attributes
    # donation contract: flat tree paths + bytes of the buffers the program
    # is expected to alias in-place (empty when the program doesn't own them,
    # e.g. the NVMe-swapper grad program where state persists host-side)
    donatable_paths: Tuple[str, ...] = ()
    donatable_bytes: Tuple[int, ...] = ()
    donation_expected: bool = True
    compute_dtype: str = "f32"         # "f32" | "bf16" | "f16"
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


def tree_leaf_paths(tree) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """("/params/layers/wq", ...), (nbytes, ...) in jit flattening order."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    paths, sizes = [], []
    for path, leaf in leaves:
        paths.append("/" + "/".join(_path_key(k) for k in path))
        sizes.append(int(getattr(leaf, "size", 0))
                     * np.dtype(leaf.dtype).itemsize
                     if hasattr(leaf, "dtype") else 0)
    return tuple(paths), tuple(sizes)


def _path_key(k) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def lower_program(jitted, *abstract_args, name: str = "program",
                  mesh=None, donatable=None, donation_expected: bool = True,
                  compute_dtype: str = "f32",
                  meta: Optional[Dict[str, Any]] = None) -> ProgramArtifacts:
    """Lower + compile a jitted callable on abstract args and collect every
    text representation the analyzers need.

    donatable: optional pytree (usually the state argument's abstract tree)
    whose leaves the program is expected to donate.

    Compilation runs under the SPMD-warning capture: any involuntary full
    rematerialization the partitioner logs on fd 2 lands structured in
    ``meta["spmd_warnings"]`` (RematAudit turns them into findings). XLA's
    own buffer-assignment stats, where the backend exposes them, land in
    ``meta["xla_memory"]`` as a cross-check for the textual liveness model.
    """
    ctx = mesh if mesh is not None else contextlib.nullcontext()
    spmd_matches: list = []
    with ctx, capture_spmd_warnings(spmd_matches):
        lowered = jitted.lower(*abstract_args)
        compiled = lowered.compile()
    xla_memory = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            xla_memory = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
            }
    except Exception:  # pragma: no cover - backend-dependent surface
        pass
    stablehlo = ""
    pre_hlo = ""
    try:
        stablehlo = lowered.as_text()
    except Exception:  # pragma: no cover - text emission is best-effort
        pass
    try:
        pre_hlo = lowered.as_text(dialect="hlo")
    except Exception:  # pragma: no cover - dialect arg drifts across jax
        pass
    paths: Tuple[str, ...] = ()
    sizes: Tuple[int, ...] = ()
    if donatable is not None:
        paths, sizes = tree_leaf_paths(donatable)
    full_meta = dict(meta or {})
    if spmd_matches:
        from deepspeed_tpu.analysis.hlo_parse import parse_spmd_remat_warning
        full_meta["spmd_warnings"] = [parse_spmd_remat_warning(w)
                                      for w in spmd_matches]
    if xla_memory:
        full_meta["xla_memory"] = xla_memory
    return ProgramArtifacts(
        name=name,
        optimized_hlo=compiled.as_text(),
        pre_hlo=pre_hlo,
        stablehlo=stablehlo,
        donatable_paths=paths,
        donatable_bytes=sizes,
        donation_expected=donation_expected,
        compute_dtype=compute_dtype,
        meta=full_meta)


# --------------------------------------------------------------------------
# Jaxpr-level census (pre-lowering): which primitives survive tracing.
# Used e.g. to assert the flash-attention kernel (pallas_call) survives for
# global layers when per-layer attention windows are configured.
# --------------------------------------------------------------------------

def jaxpr_primitive_census(fn, *args, **kwargs) -> Dict[str, int]:
    """{primitive_name: count} over the traced jaxpr of fn(*args), recursing
    into nested jaxprs (scan/cond/remat/custom-vjp bodies)."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    counts: Dict[str, int] = {}
    _walk_jaxpr(closed.jaxpr, counts)
    return counts


def _walk_jaxpr(jaxpr, counts: Dict[str, int]):
    from jax.extend import core as jex_core  # noqa: F401  (import guard)
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _walk_jaxpr(sub, counts)


def _sub_jaxprs(v):
    if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):                              # raw Jaxpr
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _sub_jaxprs(item)


# --------------------------------------------------------------------------
# Runtime SPMD fallback capture (absorbed from utils/hlo_check)
# --------------------------------------------------------------------------

# spmd_partitioner.cc fallback lines worth failing a build over.
_SPMD_PATTERNS = (
    "Involuntary full rematerialization",
    "involuntary full rematerialization",
)


@contextlib.contextmanager
def capture_spmd_warnings(matches: list):
    """Capture fd-2 output (XLA C++ logs) while compiling; append any SPMD
    full-rematerialization warning lines to `matches`.

    Everything captured is re-emitted to the real stderr afterwards so no
    diagnostics are swallowed. Use around `.lower().compile()` or the first
    traced call of a jitted function.
    """
    sys.stderr.flush()
    saved_fd = os.dup(2)
    with tempfile.TemporaryFile(mode="w+b") as tmp:
        os.dup2(tmp.fileno(), 2)
        try:
            yield matches
        finally:
            sys.stderr.flush()
            os.dup2(saved_fd, 2)
            os.close(saved_fd)
            tmp.seek(0)
            text = tmp.read().decode("utf-8", errors="replace")
            if text:
                sys.stderr.write(text)
                sys.stderr.flush()
            for line in text.splitlines():
                if any(p in line for p in _SPMD_PATTERNS):
                    matches.append(line)


def assert_no_spmd_replication(compile_fn, *args, **kwargs):
    """Run `compile_fn(*args, **kwargs)` (something that triggers XLA SPMD
    compilation) and raise RuntimeError if the partitioner reported an
    involuntary full rematerialization. Returns compile_fn's result."""
    from deepspeed_tpu.analysis.hlo_parse import parse_spmd_remat_warning
    matches: list = []
    with capture_spmd_warnings(matches):
        result = compile_fn(*args, **kwargs)
    real = [m for m in matches
            if not parse_spmd_remat_warning(m).get("trivial")]
    if real:
        raise RuntimeError(
            "XLA SPMD involuntary full rematerialization during compile "
            f"({len(real)} site(s)) — a tensor is being replicated in the "
            "hot loop:\n" + "\n".join(real[:8]))
    return result
