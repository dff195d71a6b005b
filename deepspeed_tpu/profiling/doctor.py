"""Perf doctor: machine-readable diagnosis + CI gate over a traced step.

Turns a captured trace artifact (profiling/capture.py) into the same
finding/baseline machinery graft-lint uses, so a perf regression gates a
pipeline exactly like a collective-census drift does::

    python -m deepspeed_tpu.profiling.doctor --trace bench_artifacts/trace_seq2048.json.gz
    python -m deepspeed_tpu.profiling.doctor --trace T --write-baseline doctor_baseline.json
    python -m deepspeed_tpu.profiling.doctor --trace T --baseline doctor_baseline.json
    python -m deepspeed_tpu.profiling.doctor --corpus exposed-collective-trace

Rules:

  * ``stall-regression``      — a bucket's fraction of step time grew past
                                the baseline by more than the tolerance
  * ``exposed-collective-measured`` — measured exposed-comm time exceeds
                                the allowed fraction of the step (the
                                default gate; fires with no baseline)
  * ``modeled-measured-divergence`` — measured exposed-comm ms diverges
                                from the static OverlapAudit's modeled
                                ``exposed_comm_ms`` by > 25% (warning: one
                                of the two models is lying)
  * ``offload-overlap``       — the layer-streamed step left too much of
                                its storage IO exposed (``--offload-decomp``)
  * ``serving-phase-stall``   — a NON-fetch serving round phase dominates
                                round wall time (``--serving-decomp``;
                                ISSUE 18 — fetch-bound is the healthy
                                "device is the bottleneck" state)
  * ``tracing-sync-leak``     — request tracing performed device syncs

Exit status: non-zero when any error finding survives — the CI gate.
"""

import argparse
import gzip
import json
import os
import sys
from typing import Any, Dict, List, Optional

from deepspeed_tpu.analysis.report import Finding, Report
from deepspeed_tpu.profiling import trace_analysis
from deepspeed_tpu.profiling.trace_analysis import (classify_bounds,
                                                    join_census,
                                                    stall_ranking, stall_top2)

# measured exposed collective time above this fraction of the step is an
# error even without a baseline — wire latency the scheduler is not hiding
MAX_EXPOSED_COMM_FRACTION = 0.15
# modeled (OverlapAudit) vs measured exposed-comm divergence warning bar
DIVERGENCE_TOLERANCE = 0.25
# baseline gating: a bucket must grow BOTH 20% relative and 2 points of
# step fraction before stall-regression fires (absolute floor keeps noise
# on tiny buckets from gating)
REGRESSION_REL = 0.20
REGRESSION_ABS = 0.02
# offload pipeline gate: the measured share of the streamed step's storage
# IO the executor hid under compute (bench: offload_overlap_fraction).
# Below this the capacity rung is paying serialized wire/host time the
# three-way read || update || write schedule exists to hide.
OFFLOAD_MIN_OVERLAP = 0.8


def diagnose(trace: Any, hlo_text: str = "", *,
             cost: Optional[Dict[str, Any]] = None,
             steps: int = 1,
             modeled_exposed_comm_ms: Optional[float] = None,
             accel=None) -> Dict[str, Any]:
    """Full attribution + roofline + census join + top-2 stalls for one
    traced step. Pure host work — no jax import on the happy path."""
    if accel is None:
        from deepspeed_tpu.accelerator import get_accelerator
        accel = get_accelerator()
    scope_map = (trace_analysis.parse_hlo_scopes(hlo_text)
                 if hlo_text else None)
    attr = trace_analysis.attribute(trace, scope_map, steps=steps)
    bounds = classify_bounds(
        attr, cost,
        peak_flops=accel.peak_flops_per_device("bf16"),
        hbm_bytes_per_sec=accel.hbm_bytes_per_sec())
    out = {
        "step_span_ms": round(attr.step_span_ms, 4),
        "device_busy_ms": round(attr.device_busy_ms, 4),
        "fwd_ms": round(attr.fwd_ms, 4),
        "bwd_ms": round(attr.bwd_ms, 4),
        "buckets": attr.buckets,
        "bounds": bounds,
        "by_scope_ms": {k: round(v, 4) for k, v in sorted(
            attr.by_scope_ms.items(), key=lambda kv: -kv[1])},
        "exposed_comm_ms": round(attr.exposed_comm_ms, 4),
        "stalls": stall_ranking(attr, bounds),
        "stall_top2": stall_top2(attr, bounds),
        "joined_ops": attr.joined_ops,
        "total_ops": attr.total_ops,
    }
    if cost and cost.get("census"):
        out["collective_join"] = join_census(attr, cost["census"])
    if modeled_exposed_comm_ms is not None:
        out["modeled_exposed_comm_ms"] = round(modeled_exposed_comm_ms, 4)
        hi = max(attr.exposed_comm_ms, modeled_exposed_comm_ms)
        div = (abs(attr.exposed_comm_ms - modeled_exposed_comm_ms) / hi
               if hi > 0 else 0.0)
        out["exposed_comm_divergence"] = round(div, 4)
    return out


def gate(diag: Dict[str, Any], *,
         baseline: Optional[Dict[str, Any]] = None,
         max_exposed_fraction: float = MAX_EXPOSED_COMM_FRACTION,
         program: str = "traced_step") -> Report:
    """Apply the doctor's gating rules to a diagnosis. Returns a Report in
    the graft-lint mold: ``report.ok`` is the exit status, findings carry
    rule/ident for baseline suppression."""
    report = Report(meta={"tool": "perf-doctor", "program": program,
                          "step_span_ms": diag.get("step_span_ms")})
    span = diag.get("step_span_ms") or 0.0
    exposed = diag.get("exposed_comm_ms") or 0.0
    if span > 0 and exposed / span > max_exposed_fraction:
        report.extend([Finding(
            rule="exposed-collective-measured",
            message=(f"measured exposed collective time {exposed:.3f} ms is "
                     f"{exposed / span:.1%} of the {span:.3f} ms step "
                     f"(budget {max_exposed_fraction:.0%}) — the scheduler "
                     "is not hiding this wire time under compute"),
            program=program, ident="exposed",
            data={"exposed_comm_ms": exposed, "step_span_ms": span})])
    div = diag.get("exposed_comm_divergence")
    if div is not None and div > DIVERGENCE_TOLERANCE:
        report.extend([Finding(
            rule="modeled-measured-divergence", severity="warning",
            message=(f"measured exposed-comm {exposed:.3f} ms vs modeled "
                     f"{diag.get('modeled_exposed_comm_ms'):.3f} ms diverge "
                     f"{div:.0%} (> {DIVERGENCE_TOLERANCE:.0%}) — the "
                     "overlap model or the interconnect pricing is off"),
            program=program, ident="divergence",
            data={"divergence": div})])
    if baseline:
        base_buckets = baseline.get("buckets", {})
        for name, stat in diag.get("buckets", {}).items():
            base = base_buckets.get(name)
            if base is None:
                continue
            cur_f, base_f = stat["fraction"], base.get("fraction", 0.0)
            if (cur_f - base_f > REGRESSION_ABS
                    and cur_f > base_f * (1 + REGRESSION_REL)):
                report.extend([Finding(
                    rule="stall-regression",
                    message=(f"bucket '{name}' grew to {cur_f:.1%} of the "
                             f"step (baseline {base_f:.1%}) — attribution "
                             "regression"),
                    program=program, ident=name,
                    data={"fraction": cur_f, "baseline": base_f})])
    return report


def diagnose_offload(decomp: Dict[str, Any],
                     step_ms: Optional[float] = None) -> Dict[str, Any]:
    """Host-stall attribution for the offload phases of a layer-streamed
    step, from the measured decomposition
    (``InfinityExecutor.measure_decomposition``) plus a measured step time.

    Attribution: compute = L x (layer fwd+bwd) + L x (chunk Adam) + the
    embed/CE-head top; io = 2L param-chunk fetches + L opt-chunk
    round-trips; everything the step spent beyond compute is EXPOSED io/
    host stall (clamped to the io budget), and
    ``offload_overlap_fraction = 1 - exposed/io`` prices how much of the
    storage traffic the pipeline actually hid under compute."""
    compute = (float(decomp.get("offload_compute_ms", 0.0))
               + float(decomp.get("offload_update_sweep_ms", 0.0))
               + float(decomp.get("offload_top_ms", 0.0)))
    io = float(decomp.get("offload_io_ms")
               or decomp.get("offload_dma_ms") or 0.0)
    out: Dict[str, Any] = {
        "offload_compute_total_ms": round(compute, 2),
        "offload_io_ms": round(io, 2),
        "offload_pipeline": decomp.get("offload_pipeline"),
    }
    if step_ms is None:
        step_ms = decomp.get("offload_step_ms")
    if step_ms:
        exposed = max(0.0, min(float(step_ms) - compute, io))
        out["offload_step_ms"] = round(float(step_ms), 2)
        out["offload_exposed_io_ms"] = round(exposed, 2)
        out["offload_overlap_fraction"] = (round(1.0 - exposed / io, 4)
                                           if io > 0 else 1.0)
        # which phase dominates the step — the "turn this knob" signal
        phases = {"layer-compute": float(decomp.get("offload_compute_ms",
                                                    0.0)),
                  "host-adam": float(decomp.get("offload_update_sweep_ms",
                                                0.0)),
                  "top-compute": float(decomp.get("offload_top_ms", 0.0)),
                  "exposed-io-stall": exposed}
        out["offload_dominant_phase"] = max(phases, key=phases.get)
    elif "offload_overlap_fraction" in decomp:
        out["offload_overlap_fraction"] = decomp["offload_overlap_fraction"]
    return out


def gate_offload(diag: Dict[str, Any], *,
                 min_overlap: float = OFFLOAD_MIN_OVERLAP,
                 program: str = "offload_step") -> Report:
    """The ``offload-overlap`` rule: the streamed step left more than
    (1 - min_overlap) of its storage IO exposed — the executor is running
    fetch -> compute -> host-Adam -> write-back serially instead of the
    three-way pipeline. Report in the graft-lint mold (exit status = CI
    gate); the corpus twin is ``offload-serial-pipeline``."""
    report = Report(meta={"tool": "perf-doctor", "program": program,
                          "offload": diag})
    frac = diag.get("offload_overlap_fraction")
    if frac is None:
        # fail CLOSED: a gate that cannot price the overlap (no
        # offload_step_ms / offload_overlap_fraction in the input) must
        # not certify the pipeline it never measured
        report.extend([Finding(
            rule="offload-overlap",
            message="offload overlap cannot be priced: the decomposition "
                    "carries no offload_overlap_fraction and no "
                    "offload_step_ms (pass the measured step time "
                    "alongside the measure_decomposition fields)",
            program=program, ident="unpriced", data=dict(diag))])
        return report
    if frac < min_overlap:
        exposed = diag.get("offload_exposed_io_ms", 0.0)
        io = diag.get("offload_io_ms", 0.0)
        report.extend([Finding(
            rule="offload-overlap",
            message=(f"offload pipeline hid only {frac:.0%} of the streamed "
                     f"step's {io:.1f} ms storage IO under compute (budget "
                     f"{min_overlap:.0%}; {exposed:.1f} ms exposed host "
                     f"stall, dominant phase "
                     f"{diag.get('offload_dominant_phase', 'unknown')}) — "
                     "check offload_param/offload_optimizer "
                     "pipeline_read/pipeline_write and the aio "
                     "read_queue_depth/write_queue_depth"),
            program=program, ident="offload-overlap",
            data={"stall": "host-io", **diag})])
    return report


def offload_fields(diag: Dict[str, Any]) -> Dict[str, Any]:
    """The bench-JSON fields for the offload attribution."""
    keys = ("offload_overlap_fraction", "offload_exposed_io_ms",
            "offload_io_ms", "offload_dominant_phase")
    return {k: diag[k] for k in keys if k in diag}


# --------------------------------------------------------------------------
# serving doctor (ISSUE 18)
# --------------------------------------------------------------------------

# a NON-fetch phase of the serving round loop above this fraction of round
# wall time is a stall the knob table names; fetch is exempt — the round's
# ONE sync legitimately waits on the device, so fetch-dominant means "the
# accelerator is the bottleneck", which is the healthy steady state
SERVING_MAX_PHASE_FRACTION = 0.5
# phase -> which resource the round is actually bound on
SERVING_BOUND = {
    "schedule": "host-scheduling-bound",
    "commit": "host-scheduling-bound",
    "prefill_dispatch": "dispatch-bound",
    "decode_dispatch": "dispatch-bound",
    "fetch": "fetch-bound",
    "housekeeping": "paging-bound",
}
# the "turn this knob" message per dominant phase
SERVING_KNOBS = {
    "schedule": "raise decode_quantum (fewer scheduling boundaries per "
                "token) or cap max_seqs — the Python scheduler is the "
                "bottleneck",
    "commit": "raise decode_quantum or thin the per-token host "
              "bookkeeping — round-boundary commit work dominates",
    "prefill_dispatch": "set/raise prefill_token_budget so long prompts "
                        "chunk instead of monopolizing rounds, and check "
                        "prompt_bucket for compile churn",
    "decode_dispatch": "fewer, larger steps: raise decode_quantum, or "
                       "hunt per-step recompiles (decode_backend/bucket "
                       "drift)",
    "fetch": "healthy: the device is the bottleneck (the host has the "
             "next round dispatched before the last is through, and waits) "
             "— scale the mesh or shrink the model, not the host loop",
    "housekeeping": "adapter paging / CoW fork traffic dominates: more "
                    "adapter_slots (or adapter-affinity routing) so hot "
                    "adapters stay resident instead of re-paging",
}


def diagnose_serving(decomp: Dict[str, Any]) -> Dict[str, Any]:
    """Round-phase attribution for the serving loop, from
    ``ServingEngine.phase_decomposition()`` output: per-phase fractions of
    round wall time, the dominant phase and its bound
    (host-scheduling-bound / dispatch-bound / fetch-bound / paging-bound),
    the top-2 phases for the bench, per-token round cost, and the tracing
    evidence (device-sync self-report + measured overhead) passed through
    for ``gate_serving``.

    The loop looks ahead (``ServingEngine._round``): a call's fetch
    leaves the round's last steps on the device's queue, so the non-fetch
    phases of the next call are host time the chip does not wait for as
    long as they are shorter than those steps. The reading holds: while
    the device is the slower side the fetch absorbs the difference and
    dominates (fetch-bound: health); once the host is the slower side the
    tokens are ready when it asks, the fetch shrinks to the copy, and the
    dominant phase names the host work to cut."""
    phases = {
        "schedule": float(decomp.get("serve_schedule_ms", 0.0)),
        "housekeeping": float(decomp.get("serve_housekeeping_ms", 0.0)),
        "prefill_dispatch": float(decomp.get("serve_prefill_dispatch_ms",
                                             0.0)),
        "decode_dispatch": float(decomp.get("serve_decode_dispatch_ms",
                                            0.0)),
        "fetch": float(decomp.get("serve_fetch_ms", 0.0)),
        "commit": float(decomp.get("serve_commit_ms", 0.0)),
    }
    round_ms = float(decomp.get("serve_round_ms", 0.0))
    tokens = float(decomp.get("serve_tokens", 0.0))
    out: Dict[str, Any] = {
        "serve_rounds": float(decomp.get("serve_rounds", 0.0)),
        "serve_phases_ms": {k: round(v, 3) for k, v in phases.items()},
        "serve_round_ms": round(round_ms, 3),
        "serve_tokens": tokens,
    }
    if round_ms > 0 and out["serve_rounds"] > 0:
        fr = {k: v / round_ms for k, v in phases.items()}
        top = sorted(phases, key=phases.get, reverse=True)
        out["serve_phase_fractions"] = {k: round(v, 4)
                                        for k, v in fr.items()}
        out["serve_dominant_phase"] = top[0]
        out["serve_bound"] = SERVING_BOUND[top[0]]
        out["serve_phase_top2"] = [
            {"phase": k, "ms": round(phases[k], 3),
             "fraction": round(fr[k], 4)} for k in top[:2]]
        if tokens > 0:
            out["serve_ms_per_token"] = round(round_ms / tokens, 4)
    for k in ("trace_armed", "trace_device_syncs",
              "serve_phase_stall_events", "serve_trace_overhead_pct"):
        if k in decomp:
            out[k] = decomp[k]
    return out


def gate_serving(diag: Dict[str, Any], *,
                 max_phase_fraction: float = SERVING_MAX_PHASE_FRACTION,
                 program: str = "serving_round") -> Report:
    """The serving rules, in the graft-lint mold (exit status = CI gate):

    * ``serving-phase-stall`` — a NON-fetch phase exceeds
      ``max_phase_fraction`` of round wall time (corpus twin:
      ``serving-blind-stall``). Fails CLOSED when the decomposition
      carries no priced rounds — a gate that never saw a round must not
      certify the loop.
    * ``tracing-sync-leak`` — the tracer self-reports device syncs (a
      ``device_get`` per span — the defect its host-clock contract
      forbids; corpus twin: ``tracing-sync-leak``). The measured
      ``serve_trace_overhead_pct`` is reported in ``meta`` and gates
      nothing: it is a wall-clock reading of a busy host."""
    report = Report(meta={"tool": "perf-doctor", "program": program,
                          "serving": diag})
    fr = diag.get("serve_phase_fractions")
    if not fr:
        report.extend([Finding(
            rule="serving-phase-stall",
            message="serving phases cannot be priced: the decomposition "
                    "carries no rounds / round wall time (serve some "
                    "load, then pass phase_decomposition() output)",
            program=program, ident="unpriced", data=dict(diag))])
        return report
    for phase, f in sorted(fr.items(), key=lambda kv: -kv[1]):
        if phase == "fetch":
            continue      # the one sync: device-bound is health
        if f > max_phase_fraction:
            report.extend([Finding(
                rule="serving-phase-stall",
                message=(f"serving rounds are {SERVING_BOUND[phase]}: "
                         f"phase '{phase}' takes {f:.0%} of round wall "
                         f"time (budget {max_phase_fraction:.0%}) — "
                         f"{SERVING_KNOBS[phase]}"),
                program=program, ident=phase,
                data={"phase": phase, "fraction": round(f, 4),
                      "phases_ms": diag.get("serve_phases_ms")})])
            break         # name the dominant stall, not every echo of it
    syncs = diag.get("trace_device_syncs") or 0
    if syncs:
        report.extend([Finding(
            rule="tracing-sync-leak",
            message=(f"request tracing performed {int(syncs)} device "
                     "syncs — span bookkeeping must be host-wall-clock "
                     "only (a device_get per span serializes the exact "
                     "dispatch pipeline tracing exists to observe)"),
            program=program, ident="device-syncs",
            data={"trace_device_syncs": syncs})])
    return report


def serving_fields(diag: Dict[str, Any]) -> Dict[str, Any]:
    """The bench-JSON fields for the serving attribution (the doctor's
    bound + top-2 phases ride next to the SLO numbers)."""
    keys = ("serve_bound", "serve_dominant_phase", "serve_phase_top2",
            "serve_ms_per_token")
    return {k: diag[k] for k in keys if k in diag}


def baseline_dict(diag: Dict[str, Any]) -> Dict[str, Any]:
    return {"buckets": diag.get("buckets", {}),
            "stall_top2": diag.get("stall_top2", []),
            "exposed_comm_ms": diag.get("exposed_comm_ms", 0.0),
            "step_span_ms": diag.get("step_span_ms", 0.0)}


def stall_fields(diag: Dict[str, Any], suffix: str) -> Dict[str, Any]:
    """The bench-JSON fields: stall_top2_<suffix> = [{bucket, ms,
    fraction}, ...] (fraction is of step_span_ms)."""
    return {f"stall_top2_{suffix}": [
        {"bucket": s["bucket"], "ms": s["ms"], "fraction": s["fraction"]}
        for s in diag.get("stall_top2", [])]}


# --------------------------------------------------------------------------
# seeded corpus
# --------------------------------------------------------------------------

def synthetic_exposed_collective_trace() -> Dict[str, Any]:
    """A trace with an artificially exposed collective: 10 ms of matmul,
    then an 8 ms all-reduce with NOTHING scheduled under it. Attribution
    must price the full 8 ms as exposed and the doctor gate must fire."""
    evs = [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10_000.0,
         "name": "dot.1", "args": {"hlo_op": "dot.1"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 2_000.0, "dur": 1_000.0,
         "name": "fusion.2", "args": {"hlo_op": "fusion.2"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 10_050.0, "dur": 8_000.0,
         "name": "all-reduce.3", "args": {"hlo_op": "all-reduce.3"}},
    ]
    return {"displayTimeUnit": "ms", "traceEvents": evs}


def simulate_serving_decomp(stalled: bool = False) -> Dict[str, Any]:
    """A synthetic 64-round phase decomposition in the ring's schema.
    Healthy: fetch-dominant (the round's one sync waits ~3.2 ms of a
    ~5.7 ms round on the device — the steady state the gate must PASS).
    ``stalled``: every other round pays an ~18 ms cold adapter page-in,
    so housekeeping swamps the round — the ``serving-blind-stall`` face
    the gate must name as paging-bound."""
    rounds = 64
    per = {"schedule": 0.35, "housekeeping": 0.15, "prefill_dispatch": 0.45,
           "decode_dispatch": 1.1, "fetch": 3.2, "commit": 0.25}
    totals = {k: v * rounds for k, v in per.items()}
    if stalled:
        totals["housekeeping"] += 18.0 * (rounds // 2)
    round_ms = sum(totals.values()) + 0.02 * rounds   # loop overhead
    return {
        "serve_rounds": float(rounds),
        "serve_schedule_ms": totals["schedule"],
        "serve_housekeeping_ms": totals["housekeeping"],
        "serve_prefill_dispatch_ms": totals["prefill_dispatch"],
        "serve_decode_dispatch_ms": totals["decode_dispatch"],
        "serve_fetch_ms": totals["fetch"],
        "serve_commit_ms": totals["commit"],
        "serve_round_ms": round_ms,
        "serve_tokens": float(rounds * 24),
    }


def audit_serving(stalled: bool = True) -> Report:
    """Corpus face of the serving gate: the stalled decomposition MUST
    fire ``serving-phase-stall`` naming housekeeping/paging; the healthy
    twin MUST pass (fetch-dominant is the certified steady state)."""
    diag = diagnose_serving(simulate_serving_decomp(stalled=stalled))
    return gate_serving(diag, program=("serving_blind_stall" if stalled
                                       else "serving_instrumented"))


def audit_tracing(leaky: bool = True) -> Report:
    """Corpus face of the tracing gate, driven through the REAL
    ``RequestTracer`` over a simulated request load. The leaky twin
    plants the defect the host-clock contract forbids: an ``on_span``
    hook that round-trips the device per span (one ``device_get`` each,
    self-reported on ``tracer.device_syncs`` per the hook contract) —
    the gate fires on the sync count, deterministically; the measured
    per-span cost priced against the synthetic healthy round is the
    reported ``serve_trace_overhead_pct`` only (a wall-clock reading: on a
    busy host it passed 1 % without a defect). The host-clock twin's hook
    is pure host work and MUST pass."""
    import time

    from deepspeed_tpu.telemetry.request_trace import RequestTracer

    tracer = RequestTracer(replica="audit")
    if leaky:
        import jax
        import jax.numpy as jnp

        def leak(ev):
            jax.device_get(jnp.zeros(()))   # the defect: a sync per span
            tracer.device_syncs += 1        # the hook self-report contract
        tracer.on_span = leak
    t0 = time.perf_counter()
    for rid in range(8):
        tracer.begin(rid)
        with tracer.span(rid, "prefill"):
            pass
        for _ in range(24):
            with tracer.span(rid, "decode_quantum"):
                pass
        tracer.instant(rid, "finish")
        tracer.end(rid)
    span_ms = (time.perf_counter() - t0) * 1e3
    decomp = simulate_serving_decomp(stalled=False)
    decomp["trace_armed"] = 1.0
    decomp["trace_device_syncs"] = float(tracer.device_syncs)
    decomp["serve_trace_overhead_pct"] = round(
        100.0 * span_ms / decomp["serve_round_ms"], 3)
    diag = diagnose_serving(decomp)
    return gate_serving(diag, program=("tracing_sync_leak" if leaky
                                       else "tracing_host_clock"))


DOCTOR_CORPUS = {
    "exposed-collective-trace": (synthetic_exposed_collective_trace,
                                 "exposed_collective_trace"),
}

# serving-tier entries run their own audit (decomp/tracer-driven, not a
# Chrome trace) — run_corpus_entry dispatches on membership
SERVING_CORPUS = {
    "serving-blind-stall": (lambda: audit_serving(stalled=True),
                            "serving_blind_stall"),
    "tracing-sync-leak": (lambda: audit_tracing(leaky=True),
                          "tracing_sync_leak"),
}


def run_corpus_entry(name: str = "exposed-collective-trace") -> Report:
    """A ``doctor`` corpus entry (analysis.corpus wires them into the lint
    --corpus runner): the seeded defect MUST fire its gate."""
    if name in SERVING_CORPUS:
        run, _program = SERVING_CORPUS[name]
        return run()
    make_trace, program = DOCTOR_CORPUS[name]
    diag = diagnose(make_trace())
    return gate(diag, program=program)


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def _load_json(path: str) -> Dict[str, Any]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _load_text(path: str) -> str:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return f.read()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.profiling.doctor",
        description="Stall attribution + CI gate over a jax.profiler traced "
                    "step (see profiling/capture.py for producing one).")
    p.add_argument("--trace", help="trace artifact (.json or .json.gz, "
                                   "Chrome-trace format)")
    p.add_argument("--hlo", help="compiled step program text (the "
                                 "trace_<tag>.hlo.txt.gz written next to "
                                 "the artifact) for the scope/census join")
    p.add_argument("--steps", type=int, default=None,
                   help="engine steps inside the capture window (default: "
                        "the artifact's recorded metadata.steps, else 1)")
    p.add_argument("--modeled-exposed-ms", type=float, default=None,
                   help="modeled exposed_comm_ms from the telemetry overlap "
                        "join, for the divergence cross-check")
    p.add_argument("--max-exposed-frac", type=float,
                   default=MAX_EXPOSED_COMM_FRACTION)
    p.add_argument("--json", dest="json_out", metavar="PATH",
                   help="write the diagnosis JSON to PATH ('-' for stdout)")
    p.add_argument("--baseline", help="baseline JSON: gate bucket fractions "
                                      "against it")
    p.add_argument("--write-baseline", metavar="PATH",
                   help="accept the current attribution and exit 0")
    p.add_argument("--corpus", help="run a seeded known-bad entry instead "
                                    "of a trace (doctor gate self-test)")
    p.add_argument("--offload-decomp", metavar="PATH",
                   help="offload decomposition JSON (the "
                        "measure_decomposition fields + offload_step_ms, "
                        "e.g. cut from the bench JSON): run the "
                        "offload-overlap gate instead of a trace")
    p.add_argument("--min-offload-overlap", type=float,
                   default=OFFLOAD_MIN_OVERLAP)
    p.add_argument("--serving-decomp", metavar="PATH",
                   help="serving round-phase decomposition JSON "
                        "(ServingEngine.phase_decomposition() output, e.g. "
                        "cut from the bench JSON): run the "
                        "serving-phase-stall / tracing-sync-leak gates "
                        "instead of a trace")
    p.add_argument("--max-phase-fraction", type=float,
                   default=SERVING_MAX_PHASE_FRACTION)
    args = p.parse_args(argv)

    if args.serving_decomp:
        decomp = _load_json(args.serving_decomp)
        diag = diagnose_serving(decomp)
        report = gate_serving(
            diag, max_phase_fraction=args.max_phase_fraction,
            program=os.path.basename(args.serving_decomp))
        print(report.summary(), file=sys.stderr)
        top = ", ".join(f"{s['phase']}={s['ms']:.2f}ms({s['fraction']:.0%})"
                        for s in diag.get("serve_phase_top2", [])) or "none"
        print(f"doctor: {diag.get('serve_rounds', 0):.0f} serving rounds, "
              f"bound {diag.get('serve_bound', 'unpriced')}, top phases: "
              f"{top}", file=sys.stderr)
        if args.json_out:
            payload = dict(diag)
            payload["findings"] = [f.to_dict() for f in report.findings]
            payload["ok"] = report.ok
            text = json.dumps(payload, indent=2, default=str)
            if args.json_out == "-":
                print(text)
            else:
                with open(args.json_out, "w") as f:
                    f.write(text + "\n")
        return 0 if report.ok else 1

    if args.offload_decomp:
        decomp = _load_json(args.offload_decomp)
        diag = diagnose_offload(decomp)
        report = gate_offload(diag,
                              min_overlap=args.min_offload_overlap,
                              program=os.path.basename(args.offload_decomp))
        print(report.summary(), file=sys.stderr)
        if args.json_out:
            payload = dict(diag)
            payload["findings"] = [f.to_dict() for f in report.findings]
            payload["ok"] = report.ok
            text = json.dumps(payload, indent=2, default=str)
            if args.json_out == "-":
                print(text)
            else:
                with open(args.json_out, "w") as f:
                    f.write(text + "\n")
        return 0 if report.ok else 1

    if args.corpus:
        name = ("exposed-collective-trace" if args.corpus == "doctor"
                else args.corpus)
        if name not in DOCTOR_CORPUS and name not in SERVING_CORPUS:
            p.error(f"unknown doctor corpus entry '{args.corpus}' — one of "
                    f"{sorted({**DOCTOR_CORPUS, **SERVING_CORPUS})}")
        report = run_corpus_entry(name)
        print(report.summary(), file=sys.stderr)
        return 0 if report.ok else 1
    if not args.trace:
        p.error("--trace (or --corpus) is required")

    trace = _load_json(args.trace)
    hlo_path = args.hlo
    if hlo_path is None:
        guess = args.trace.replace(".json.gz", ".hlo.txt.gz") \
                          .replace(".json", ".hlo.txt.gz")
        hlo_path = guess if os.path.exists(guess) else None
    hlo_text = _load_text(hlo_path) if hlo_path else ""
    steps = args.steps
    if steps is None:   # an explicit --steps wins over the recorded value
        meta = trace.get("metadata") if isinstance(trace, dict) else None
        steps = int(meta["steps"]) if meta and meta.get("steps") else 1
    diag = diagnose(trace, hlo_text, steps=steps,
                    modeled_exposed_comm_ms=args.modeled_exposed_ms)
    baseline = _load_json(args.baseline) if args.baseline else None
    report = gate(diag, baseline=baseline,
                  max_exposed_fraction=args.max_exposed_frac,
                  program=os.path.basename(args.trace))

    print(report.summary(), file=sys.stderr)
    top = ", ".join(f"{s['bucket']}={s['ms']:.2f}ms({s['fraction']:.0%})"
                    for s in diag["stall_top2"]) or "none"
    print(f"doctor: step {diag['step_span_ms']:.3f} ms, device busy "
          f"{diag['device_busy_ms']:.3f} ms, top stalls: {top}",
          file=sys.stderr)
    if args.json_out:
        payload = dict(diag)
        payload["findings"] = [f.to_dict() for f in report.findings]
        payload["ok"] = report.ok
        text = json.dumps(payload, indent=2, default=str)
        if args.json_out == "-":
            print(text)
        else:
            with open(args.json_out, "w") as f:
                f.write(text + "\n")
    if args.write_baseline:
        with open(args.write_baseline, "w") as f:
            json.dump(baseline_dict(diag), f, indent=2)
        print(f"doctor: baseline written to {args.write_baseline}",
              file=sys.stderr)
        return 0
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
