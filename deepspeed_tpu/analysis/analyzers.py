"""The seven graft-lint analyzers.

Each analyzer is ``analyze(artifacts, settings) -> [Finding]`` over one
lowered program (analysis/program.py). They are pure text/structure passes —
no execution, no device state — so the same code audits a 2-device CPU
lowering in CI and a 256-chip lowering on a real pod.

1. CollectiveAudit    — census of all-reduce/all-gather/reduce-scatter/
                        all-to-all/collective-permute ops vs the kind policy
                        for the config (expectations.py) and any exact pin
                        (config analysis.expect_collectives or a baseline).
                        Guards the reference's canonical silent failure: an
                        extra allreduce nobody notices until the bill.
2. OverlapAudit       — classifies each collective of the *scheduled* HLO
                        as overlapped (async start/done pair separated by
                        compute) or exposed; gates on
                        analysis.max_exposed_collectives when set.
3. DonationLint       — every state buffer the step was given to donate must
                        alias an output; a missed donation is double memory
                        for that buffer at peak.
4. DtypePromotionLint — bf16/f16 configs must not widen activation-sized
                        tensors to f32 beyond the configured floor.
5. ReplicationBudget  — explicitly-replicated float tensors above the floor
                        must fit the per-config byte budget (promotes the
                        old utils/hlo_check.replicated_tensor_bytes scan).
6. MemoryLint         — static peak-HBM liveness over the scheduled module
                        (params/grads/opt/activations breakdown, gated by
                        analysis.max_hbm_bytes) + the ZeRO memory law: the
                        per-device bytes of each persistent state class must
                        be ~logical/dp per the configured stage.
7. RematAudit         — rematerialization: flags involuntary SPMD full
                        rematerialization captured at compile time, and a
                        configured-but-inert remat policy (no recomputed ops
                        in the scheduled backward).
"""

import dataclasses
import re
from typing import Any, Dict, List, Optional

from deepspeed_tpu.analysis import hlo_parse
from deepspeed_tpu.analysis.expectations import CollectivePolicy
from deepspeed_tpu.analysis.report import Finding, compare_census


@dataclasses.dataclass
class AnalysisSettings:
    """Knobs for one lint run — built from config ``analysis`` section."""
    # collectives smaller than this are control-plane sync (loss means,
    # overflow flags) and exempt from the kind policy
    min_collective_bytes: int = 1024
    # exact census pin: {kind: count | {"count": n, "bytes": b}}; empty ->
    # kind policy only
    expect_collectives: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # donation: buffers below the floor are noise (scalars, counters)
    min_donation_bytes: int = 1024
    # dtype promotion: smallest f32-widened result worth flagging
    min_upcast_bytes: int = 1 << 20
    # replication: smallest replicated tensor scanned / total budget allowed
    min_replicated_bytes: int = 1 << 20
    max_replicated_bytes: int = 0
    # overlap audit: max exposed (synchronous or back-to-back-scheduled)
    # collectives tolerated before "collective-exposed" fires. None =
    # report-only (the overlap census still lands in the report) — CPU
    # lowerings never emit async pairs, so the gate is opt-in.
    max_exposed_collectives: Optional[int] = None
    min_exposed_bytes: int = 1024
    # memory lint: statically-modeled peak HBM a program may reach before
    # "memory-peak" fires. None = report-only (the estimate still lands in
    # Report.memory) — absolute peaks are model/mesh-specific.
    max_hbm_bytes: Optional[int] = None
    # memory law: a state class expected to shard 1/dp may exceed
    # logical/dp by this factor (small unshardable leaves, persistence
    # thresholds, padding) before "memory-law" fires...
    memory_law_tolerance: float = 1.5
    # ...and the absolute excess must also clear this floor (tiny test
    # models never trip the law by rounding)
    min_law_bytes: int = 1 << 20
    # rule ids / finding-key prefixes to suppress
    suppress: List[str] = dataclasses.field(default_factory=list)
    baseline: Optional[str] = None

    @classmethod
    def from_config(cls, config) -> "AnalysisSettings":
        a = getattr(config, "analysis", None)
        if a is None:
            return cls()
        return cls(min_collective_bytes=a.min_collective_bytes,
                   expect_collectives=dict(a.expect_collectives),
                   min_donation_bytes=a.min_donation_bytes,
                   min_upcast_bytes=a.min_upcast_bytes,
                   min_replicated_bytes=a.min_replicated_bytes,
                   max_replicated_bytes=a.max_replicated_bytes,
                   max_exposed_collectives=a.max_exposed_collectives,
                   min_exposed_bytes=a.min_exposed_bytes,
                   max_hbm_bytes=a.max_hbm_bytes,
                   memory_law_tolerance=a.memory_law_tolerance,
                   min_law_bytes=a.min_law_bytes,
                   suppress=list(a.suppress),
                   baseline=a.baseline)


# --------------------------------------------------------------------------

class CollectiveAudit:
    """Kind policy + optional exact count pin over the collective census."""

    rule_forbidden = "collective-forbidden-kind"
    rule_missing = "collective-missing"

    def __init__(self, policy: CollectivePolicy):
        self.policy = policy

    def analyze(self, art, settings: AnalysisSettings,
                ops=None) -> List[Finding]:
        # callers that already parsed the module (lint.analyze_programs
        # reuses the ops for the report census) pass them in — the optimized
        # HLO of a real model is tens of MB, one regex pass is enough
        if ops is None:
            ops = hlo_parse.parse_collectives(art.optimized_hlo)
        large = hlo_parse.collective_census(ops,
                                            settings.min_collective_bytes)
        full = hlo_parse.collective_census(ops)
        findings = []
        for kind, c in sorted(large.items()):
            if kind not in self.policy.allowed:
                findings.append(Finding(
                    rule=self.rule_forbidden, program=art.name, ident=kind,
                    nbytes=c["bytes"],
                    message=(f"{c['count']} {kind} op(s) moving "
                             f"{c['bytes']} bytes, but this config allows "
                             f"{sorted(self.policy.allowed) or 'none'} "
                             f"({self.policy.reason})"),
                    data={"census": c,
                          "allowed": sorted(self.policy.allowed)}))
        # presence checks run against the full census: the required op may
        # legitimately be small (tiny shard sizes in tests). Synthetic
        # single-purpose programs (corpus) opt out — the policy's required
        # ops describe a full train step, not a fragment.
        required = () if art.meta.get("skip_required") else self.policy.required
        for group in required:
            if not any(k in full for k in group):
                findings.append(Finding(
                    rule=self.rule_missing, program=art.name,
                    ident="|".join(group), severity="warning",
                    message=(f"expected at least one of {list(group)} "
                             f"({self.policy.reason}) but the compiled "
                             "program has none — the config's parallelism "
                             "may not have materialized"),
                    data={"required": list(group),
                          "present": sorted(full)}))
        if settings.expect_collectives:
            # exact pins are PER STEP; a fused K-step program (unrolled
            # loop, meta fuse_steps=K) must carry exactly K of each — fewer
            # means a collective was hoisted out of the loop, more means one
            # was duplicated into it
            k = int(art.meta.get("fuse_steps", 1) or 1)
            expected = {
                kind: ({f: n[f] * k for f in n} if isinstance(n, dict)
                       else n * k)
                for kind, n in settings.expect_collectives.items()}
            findings.extend(compare_census(
                full, expected, art.name,
                source="config analysis.expect_collectives"
                       + (f" (x{k} fused steps)" if k > 1 else "")))
        return findings


class OverlapAudit:
    """Overlap classification of the *scheduled* step HLO: every collective
    is either overlapped (async start/done pair separated by scheduled
    compute — the wire runs under the math) or exposed (synchronous, or a
    pair scheduled back-to-back). The latency-hiding scheduler is the whole
    reason ZeRO-3's per-use all-gathers are affordable; this pins that it
    actually fired. Findings only when ``analysis.max_exposed_collectives``
    is set (CPU lowerings never async-lower, so the default is
    report-only — the overlap census still reaches the report/JSON)."""

    rule_exposed = "collective-exposed"

    def analyze(self, art, settings: AnalysisSettings,
                overlap_ops=None) -> List[Finding]:
        if settings.max_exposed_collectives is None:
            return []
        if overlap_ops is None:
            overlap_ops = hlo_parse.parse_overlap(art.optimized_hlo)
        exposed = [op for op in overlap_ops
                   if not op.overlapped
                   and op.nbytes >= settings.min_exposed_bytes]
        if len(exposed) <= settings.max_exposed_collectives:
            return []
        by_kind: Dict[str, List] = {}
        for op in exposed:
            by_kind.setdefault(op.kind, []).append(op)
        findings = []
        for kind, ops in sorted(by_kind.items()):
            nbytes = sum(op.nbytes for op in ops)
            sync = sum(1 for op in ops if not op.is_async)
            findings.append(Finding(
                rule=self.rule_exposed, program=art.name, ident=kind,
                nbytes=nbytes,
                message=(f"{len(ops)} exposed {kind} op(s) moving {nbytes} "
                         f"bytes ({sync} synchronous, "
                         f"{len(ops) - sync} async-but-back-to-back) — "
                         f"the config allows at most "
                         f"{settings.max_exposed_collectives} exposed "
                         "collective(s); the scheduler is not hiding this "
                         "latency behind compute"),
                data={"count": len(ops), "sync": sync,
                      "budget": settings.max_exposed_collectives,
                      "lines": [op.line[:160] for op in ops[:4]]}))
        return findings


class DonationLint:
    """Each donatable state leaf must appear in the compiled module's
    input_output_alias map (state is argument 0, so its leaves are entry
    parameters 0..N-1 in jit flattening order)."""

    rule = "donation-missing"

    def analyze(self, art, settings: AnalysisSettings) -> List[Finding]:
        if not art.donation_expected or not art.donatable_paths:
            return []
        donated = set(hlo_parse.parse_donated_params(art.optimized_hlo))
        # the pre-XLA view: which args jit marked donatable at all —
        # distinguishes "never donated" (fix donate_argnums) from "donation
        # requested but XLA could not honor it" (fix the output
        # shape/layout so the buffer is reusable)
        requested = set(hlo_parse.parse_aliased_args_stablehlo(art.stablehlo))
        findings = []
        for idx, (path, nbytes) in enumerate(
                zip(art.donatable_paths, art.donatable_bytes)):
            if idx in donated or nbytes < settings.min_donation_bytes:
                continue
            if idx in requested:
                why = ("donation was requested but XLA could not honor it — "
                       "make the output reuse the input's shape/dtype/layout")
            elif requested:
                why = "it was never marked donatable — check donate_argnums"
            else:  # no stablehlo text or no aliasing attrs at all
                why = ("check donate_argnums and that the output reuses the "
                       "input's shape/layout")
            findings.append(Finding(
                rule=self.rule, program=art.name, ident=path, nbytes=nbytes,
                message=(f"state buffer {path} ({nbytes} bytes) is not "
                         "aliased input->output — it is held live alongside "
                         f"its updated copy (double memory at peak); {why}"),
                data={"arg_index": idx,
                      "donation_requested": idx in requested}))
        return findings


class DtypePromotionLint:
    """bf16/f16 programs must not widen big tensors to f32: an f32 copy of
    an activation-sized tensor doubles its HBM footprint and bandwidth."""

    rule = "dtype-upcast"

    def analyze(self, art, settings: AnalysisSettings) -> List[Finding]:
        if art.compute_dtype not in ("bf16", "f16"):
            return []
        ups = hlo_parse.parse_upcasts(art.optimized_hlo,
                                      settings.min_upcast_bytes)
        findings = []
        seen = set()
        for up in ups:
            if up.shape in seen:  # one finding per distinct widened shape
                continue
            seen.add(up.shape)
            count = sum(1 for u in ups if u.shape == up.shape)
            findings.append(Finding(
                rule=self.rule, program=art.name, ident=up.shape,
                nbytes=up.nbytes,
                message=(f"{count} convert(s) widen {up.from_dtype} to "
                         f"{up.shape} ({up.nbytes} bytes) in a "
                         f"{art.compute_dtype} program — an intended master/"
                         "loss-path upcast belongs in the baseline; anything "
                         "else is paying f32 bandwidth for a "
                         f"{art.compute_dtype} model"),
                data={"count": count, "from": up.from_dtype}))
        return findings


class ReplicationBudget:
    """Explicitly-replicated float tensors >= the floor must fit the
    config's byte budget."""

    rule = "replication-over-budget"

    def analyze(self, art, settings: AnalysisSettings) -> List[Finding]:
        if art.meta.get("world_size", 2) <= 1:
            # on a single device every tensor is trivially "replicated" —
            # the budget only means something across >= 2 devices
            return []
        text = art.pre_hlo or art.stablehlo
        if not text:
            return []
        hits = hlo_parse.replicated_tensor_bytes(
            text, settings.min_replicated_bytes)
        if art.meta.get("params_replicated_by_design"):
            # ZeRO stages 0-2 replicate parameters on purpose; only computed
            # tensors (resharding, broadcasts) count against the budget.
            # Filter DECLARATION lines only ("%argN :" / "parameter(") — an
            # op merely referencing an argument operand ("(%arg0)") is a
            # computed tensor and stays in scope
            hits = [(b, l) for b, l in hits
                    if " parameter(" not in l
                    and not re.search(r"%arg\d+\s*:", l)]
        total = sum(b for b, _ in hits)
        if total <= settings.max_replicated_bytes:
            return []
        worst = hits[0]
        return [Finding(
            rule=self.rule, program=art.name,
            ident=f"total={total}", nbytes=total,
            message=(f"{len(hits)} replicated tensor(s) totalling {total} "
                     f"bytes exceed the budget of "
                     f"{settings.max_replicated_bytes} bytes (largest: "
                     f"{worst[0]} bytes — `{worst[1][:120]}`); shard it or "
                     "raise analysis.max_replicated_bytes"),
            data={"tensors": [{"bytes": b, "line": l} for b, l in hits[:8]],
                  "budget": settings.max_replicated_bytes})]


class MemoryLint:
    """Static peak-HBM liveness + the ZeRO memory law.

    The liveness pass (hlo_parse.estimate_peak_hbm) models every scheduled
    top-level buffer's live range and reports the peak with a per-class
    breakdown: entry parameters are classified by their state-tree path
    (/params vs /opt vs other state), temporaries by shape provenance
    (state-shaped temps are gradients/moment updates, the rest are
    activations). The memory law compares the per-device (post-SPMD) bytes
    of each persistent class against logical/dp for the configured ZeRO
    stage: a silently replicated opt-state leaf in a stage>=1 config shows
    up here even when no explicit sharding annotation names it."""

    rule_peak = "memory-peak"
    rule_law = "memory-law"

    def __init__(self, law):
        self.law = law   # expectations.MemoryLaw

    @staticmethod
    def measure(art) -> Dict[str, Any]:
        """The per-program memory summary recorded in Report.memory —
        computed once per program, shared by analyze() and the report."""
        entry = hlo_parse.parse_entry_params(art.optimized_hlo)
        n_state = len(art.donatable_paths)
        param_classes: Dict[int, str] = {}
        temp_shapes: Dict[str, str] = {}
        per_device: Dict[str, int] = {}
        logical: Dict[str, int] = {}
        for p in entry:
            if n_state and p.number < n_state:
                path = art.donatable_paths[p.number]
                cls = ("params" if path.startswith("/params")
                       else "opt" if path.startswith("/opt") else "state")
                temp_shapes[f"{p.dtype}[{p.dims}]"] = "grads"
                logical[cls] = (logical.get(cls, 0)
                                + art.donatable_bytes[p.number])
            else:
                # batch/rng/scalar inputs: data, not state
                cls = "activations"
            param_classes[p.number] = cls
            per_device[cls] = per_device.get(cls, 0) + p.nbytes
        est = hlo_parse.estimate_peak_hbm(
            art.optimized_hlo, param_classes=param_classes,
            temp_class_shapes=temp_shapes)
        breakdown = {c: est.breakdown.get(c, 0)
                     for c in ("params", "grads", "opt", "activations")}
        for c, b in est.breakdown.items():   # extra classes (misc state)
            if c not in breakdown:
                breakdown[c] = b
        out: Dict[str, Any] = {
            "peak_hbm_bytes": est.peak_bytes,
            "peak_breakdown": breakdown,
            "state_bytes": {
                cls: {"logical": logical.get(cls, 0),
                      "per_device": per_device.get(cls, 0)}
                for cls in sorted(set(logical) | set(per_device)
                                  - {"activations"})},
            "boundary_activation_bytes": est.boundary_bytes,
            "remat": hlo_parse.parse_remat_census(art.optimized_hlo),
            "largest_at_peak": [
                {"bytes": b, "class": c, "line": l} for b, c, l in
                est.largest[:4]],
        }
        if art.meta.get("xla_memory"):
            out["xla_memory"] = dict(art.meta["xla_memory"])
        return out

    def analyze(self, art, settings: AnalysisSettings,
                memory: Optional[Dict[str, Any]] = None) -> List[Finding]:
        if memory is None:
            memory = self.measure(art)
        findings = []
        peak = memory["peak_hbm_bytes"]
        if settings.max_hbm_bytes is not None \
                and peak > settings.max_hbm_bytes:
            bd = ", ".join(f"{c}={b}" for c, b in
                           memory["peak_breakdown"].items())
            worst = memory["largest_at_peak"][:2]
            findings.append(Finding(
                rule=self.rule_peak, program=art.name,
                ident=f"peak={peak}", nbytes=peak,
                message=(f"statically modeled peak HBM {peak} bytes exceeds "
                         f"analysis.max_hbm_bytes={settings.max_hbm_bytes} "
                         f"(at peak: {bd}; largest live: "
                         + "; ".join(f"{w['bytes']}B {w['class']} "
                                     f"`{w['line'][:80]}`" for w in worst)
                         + ")"),
                data={"breakdown": memory["peak_breakdown"],
                      "budget": settings.max_hbm_bytes,
                      "largest": memory["largest_at_peak"]}))
        # the memory law needs the donation contract to know which entry
        # params are which state class; programs without one opt out
        if not art.donatable_paths:
            return findings
        for cls, factor in (("params", self.law.params),
                            ("opt", self.law.opt)):
            if factor <= 1:
                continue
            sb = memory["state_bytes"].get(cls)
            if not sb or not sb["logical"]:
                continue
            expected = sb["logical"] / factor
            excess = sb["per_device"] - expected
            if sb["per_device"] > expected * settings.memory_law_tolerance \
                    and excess >= settings.min_law_bytes:
                findings.append(Finding(
                    rule=self.rule_law, program=art.name, ident=cls,
                    nbytes=int(excess),
                    message=(f"{cls} state holds {sb['per_device']} bytes "
                             f"per device but the ZeRO memory law expects "
                             f"~{int(expected)} (logical {sb['logical']} / "
                             f"{factor}; {self.law.reason}) — a leaf this "
                             "config should shard is replicated"),
                    data={"per_device": sb["per_device"],
                          "logical": sb["logical"],
                          "expected_factor": factor,
                          "measured_factor": round(
                              sb["logical"] / max(1, sb["per_device"]), 3)}))
        return findings


class RematAudit:
    """Rematerialization audit of the scheduled module.

    Involuntary remat: the SPMD partitioner's 'Involuntary full
    rematerialization' fallback (captured on fd 2 during compile,
    structured in meta["spmd_warnings"]) means a tensor is replicated+
    recomputed in the hot loop at every step — an error at any scale.
    Inert policy: the config asked for activation checkpointing but the
    compiled backward contains no rematerialized op (jax stamps recomputed
    regions with /rematted_computation/ metadata) — the activations the
    policy was meant to drop are being carried across the fwd/bwd boundary
    instead (the liveness pass prices exactly that set as
    Report.memory[...]["boundary_activation_bytes"])."""

    rule_involuntary = "involuntary-remat"
    rule_inert = "remat-policy-inert"

    def analyze(self, art, settings: AnalysisSettings,
                memory: Optional[Dict[str, Any]] = None) -> List[Finding]:
        findings = []
        for w in art.meta.get("spmd_warnings", ()):
            if w.get("trivial"):
                # broadcast/iota-from-scalar: recomputation is free, the
                # partitioner's fallback costs nothing — not a finding
                continue
            findings.append(Finding(
                rule=self.rule_involuntary, program=art.name,
                ident=str(w.get("op", w.get("raw", ""))[:80]),
                nbytes=int(w.get("nbytes", 0)),
                message=("XLA SPMD fell back to involuntary full "
                         "rematerialization"
                         + (f" of {w['shape']}" if "shape" in w else "")
                         + (f" at {w['source_file']}:{w['source_line']}"
                            if "source_file" in w else "")
                         + (f" (resharding {w['from_sharding']} -> "
                            f"{w['to_sharding']})"
                            if "from_sharding" in w else "")
                         + " — the tensor is replicated and recomputed "
                         "every step; enrich its sharding annotations"),
                data=dict(w)))
        policy = art.meta.get("remat_policy")
        if policy and policy != "none":
            census = (memory or {}).get("remat") \
                or hlo_parse.parse_remat_census(art.optimized_hlo)
            if census["bwd_ops"] and not census["remat_ops"]:
                boundary = (memory or {}).get("boundary_activation_bytes", 0)
                findings.append(Finding(
                    rule=self.rule_inert, program=art.name, ident=policy,
                    severity="warning", nbytes=int(boundary),
                    message=(f"remat policy '{policy}' is configured but "
                             "the compiled backward recomputes nothing "
                             f"(0 rematerialized ops, {census['bwd_ops']} "
                             "backward ops) — checkpointed activations "
                             + (f"({boundary} bytes) " if boundary else "")
                             + "are carried across the fwd/bwd boundary "
                             "instead of being recomputed"),
                    data={"remat_census": census,
                          "boundary_activation_bytes": boundary}))
        return findings


def default_analyzers(policy: CollectivePolicy, law=None):
    if law is None:
        # standalone callers (tests, corpus) default to "nothing sharded":
        # the law gate stays quiet unless the caller supplies expectations
        from deepspeed_tpu.analysis.expectations import MemoryLaw
        law = MemoryLaw(params=1, opt=1, reason="no law expectations")
    return [CollectiveAudit(policy), OverlapAudit(), DonationLint(),
            DtypePromotionLint(), ReplicationBudget(), MemoryLint(law),
            RematAudit()]
