"""Static x runtime join: compiled-program costs priced by the observed rate.

graft-lint (``deepspeed_tpu/analysis``) already reads the compiled step
program's collective census statically; XLA's ``cost_analysis`` knows the
program's post-fusion FLOPs. Neither says anything about TIME — and the
runtime telemetry knows the observed step rate but nothing about what a step
*is*. Multiplying the two yields first-class monitor events no single layer
could produce:

  * ``modeled_comm_bytes_per_sec`` — census bytes/step x steps/sec: the wire
    load this config puts on ICI/DCN at the observed rate (the reference can
    only estimate this by watching NCCL with the comms logger)
  * ``window_mfu`` — compiled flops/step x steps/sec / chip peak: achieved
    MFU per steps_per_print window, continuously, not just when the flops
    profiler runs its one-shot report

The static half is computed ONCE (lazily, at the first window boundary) from
the same jitted callable the engine dispatches, lowered on the abstract args
captured at dispatch time — off the steady-state path, no execution, no
extra fetch.
"""

from typing import Any, Dict, Optional

from deepspeed_tpu.utils.logging import logger


def static_step_cost(jitted, abstract_args, *, mesh=None,
                     divisor: int = 1) -> Optional[Dict[str, Any]]:
    """Lower+compile ``jitted`` on ``abstract_args`` and read XLA's cost
    analysis plus the collective census. ``divisor`` normalizes a fused
    K-step program back to per-step costs. Returns None when the backend
    can't answer (no cost model, lowering failure)."""
    import contextlib
    from deepspeed_tpu.telemetry.tracing import build_log
    try:
        ctx = mesh if mesh is not None else contextlib.nullcontext()
        # a second build of the step, off the hot path: in the build log
        with ctx, build_log().program("other", "static_join"):
            compiled = jitted.lower(*abstract_args).compile()
        flops = 0
        bytes_accessed = 0
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            if ca:
                flops = int(ca.get("flops", 0))
                bytes_accessed = int(ca.get("bytes accessed", 0))
        except Exception:  # noqa: BLE001 - cost model is backend-dependent
            pass
        from deepspeed_tpu.analysis.hlo_parse import (collective_census,
                                                      estimate_peak_hbm,
                                                      overlap_summary,
                                                      parse_overlap)
        # ONE text dump feeds everything: the collective census
        # (kind/bytes), the scheduled-HLO overlap classification (how much
        # of that wire load is hidden under compute vs exposed step
        # latency), and the static peak-HBM liveness model
        text = compiled.as_text()
        overlap_ops = parse_overlap(text)
        census = collective_census(overlap_ops)
        comm_bytes = sum(c["bytes"] for c in census.values())
        overlap = overlap_summary(overlap_ops)
        # NOT divided by k: a correctly-fused K-step program carries its
        # inter-step state at boundary shardings, so its peak stays ~1x
        # the single step's — dividing would claim K-fused uses 1/K the
        # memory of one step, which is exactly backwards
        peak_hbm = estimate_peak_hbm(text).peak_bytes
        k = max(1, int(divisor))
        return {
            "modeled_peak_hbm": peak_hbm,
            "flops_per_step": flops // k,
            "bytes_accessed_per_step": bytes_accessed // k,
            "comm_bytes_per_step": comm_bytes // k,
            "exposed_comm_bytes_per_step": overlap["exposed"]["bytes"] // k,
            "overlapped_comm_bytes_per_step":
                overlap["overlapped"]["bytes"] // k,
            "census": {kind: dict(c) for kind, c in census.items()},
            "overlap": overlap,
            "fuse_steps": k,
        }
    except Exception as e:  # noqa: BLE001 - telemetry must never kill a run
        logger.debug(f"telemetry: static step cost unavailable: {e!r}")
        return None


def joined_rates(static: Dict[str, Any], steps_per_sec: float,
                 peak_flops: float,
                 interconnect_bytes_per_sec: float = 0.0) -> Dict[str, float]:
    """Price the static per-step costs at the observed rate."""
    out = {
        "modeled_comm_bytes_per_sec":
            static["comm_bytes_per_step"] * steps_per_sec,
    }
    if static.get("modeled_peak_hbm"):
        # not a rate, but it rides the same window join so every consumer
        # (bench, dryrun, monitors) sees modeled peak next to measured
        out["modeled_peak_hbm"] = float(static["modeled_peak_hbm"])
    if static.get("flops_per_step") and peak_flops > 0:
        out["window_mfu"] = (static["flops_per_step"] * steps_per_sec
                             / peak_flops)
    exposed = static.get("exposed_comm_bytes_per_step")
    if exposed is not None and interconnect_bytes_per_sec > 0:
        # modeled serial wire time of the exposed collectives per step —
        # the comm the scheduler is NOT hiding behind compute
        out["exposed_comm_ms"] = exposed / interconnect_bytes_per_sec * 1e3
    total = static.get("comm_bytes_per_step") or 0
    if total and "overlapped_comm_bytes_per_step" in static:
        out["overlap_efficiency"] = (
            static["overlapped_comm_bytes_per_step"] / total)
    return out
