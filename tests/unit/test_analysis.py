"""graft-lint static analysis (deepspeed_tpu/analysis) — grown from
test_spmd_clean.py per the analysis-subsystem issue.

Reference counterpart: DeepSpeed has no compiler to interrogate — its
canonical silent failure is an extra allreduce nobody notices until the
bill. Here each analyzer is exercised on a clean config AND a seeded
violation, and the collective census for ZeRO stage 2 vs stage 3 is pinned
to exact counts on a 2-device mesh: a silently added/removed collective is
a hard test failure. This module is the CI gate for the lint subsystem
(the CLI exit-code tests at the bottom are what a pipeline would run).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis import (AnalysisSettings, Finding, Report,
                                    capture_spmd_warnings, collective_census,
                                    estimate_peak_hbm,
                                    jaxpr_primitive_census, lower_program,
                                    parse_collectives, parse_donated_params,
                                    parse_entry_params, parse_remat_census,
                                    parse_spmd_remat_warning,
                                    parse_upcasts, replicated_tensor_bytes,
                                    shape_bytes)
from deepspeed_tpu.models import TransformerConfig, make_model

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def tiny_model(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=64, dtype=jnp.float32, attention_impl="xla")
    base.update(kw)
    return make_model(TransformerConfig(**base), name="lint-tiny")


def stage_config(stage, axes, **overrides):
    cfg = {"train_batch_size": 4,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "bf16": {"enabled": False},
           "zero_optimization": {"stage": stage,
                                 "stage3_param_persistence_threshold": 0},
           "mesh": {"axes": axes},
           "steps_per_print": 100}
    cfg.update(overrides)
    return cfg


BATCH = {"input_ids": np.zeros((4, 16), np.int32)}


def audit_stage(stage, axes, model=None, devices=None, **overrides):
    engine, *_ = deepspeed_tpu.initialize(
        model=model or tiny_model(),
        config=stage_config(stage, axes, **overrides),
        devices=devices or jax.devices()[:2])
    return engine.audit(batch=BATCH)


# plain-config audits are deterministic per (stage, axes): cache them so the
# clean-config gate and the memory-law pins share one lowering per stage
# instead of re-compiling the engine per test (quick-tier wall budget)
_AUDIT_CACHE = {}


def cached_audit(stage, axes, devices):
    key = (stage, tuple(sorted(axes.items())))
    if key not in _AUDIT_CACHE:
        _AUDIT_CACHE[key] = audit_stage(stage, axes, devices=devices)
    return _AUDIT_CACHE[key]


# --------------------------------------------------------------------------
# parsers (pure text, no compilation)
# --------------------------------------------------------------------------

class TestHloParsers:
    def test_shape_bytes(self):
        assert shape_bytes("f32", "2,32,32") == 8192
        assert shape_bytes("bf16", "1024") == 2048
        assert shape_bytes("pred", "") == 1  # scalar

    def test_parse_collectives_with_decoys(self):
        hlo = "\n".join([
            # real ops: plain, async pair (tuple wraps operand+result: the
            # op size is the LARGEST element, not the double-counting sum),
            # variadic tuple result
            "  %all-reduce.1 = f32[16]{0} all-reduce(f32[16]{0} %x), "
            "channel_id=1, to_apply=%add",
            "  %ag = (f32[2,32]{1,0}, f32[2,64]{1,0}) "
            "all-gather-start(f32[2,32]{1,0} %y), channel_id=2",
            "  %agd = f32[2,64]{1,0} all-gather-done(%ag)",
            "  %rs = (f32[8]{0}, f32[8]{0}) reduce-scatter(%a, %b), "
            "channel_id=3",
            # decoys: operand reference, metadata op_name (underscored)
            "  %copy.1 = f32[2,64]{1,0} copy(f32[2,64]{1,0} %all-gather.9)",
            '  %fusion.2 = f32[4]{0} fusion(%z), metadata={op_name='
            '"jit(f)/all_gather"}',
        ])
        ops = parse_collectives(hlo)
        kinds = sorted(op.kind for op in ops)
        assert kinds == ["all-gather", "all-reduce", "reduce-scatter"]
        by_kind = {op.kind: op for op in ops}
        assert by_kind["all-reduce"].nbytes == 64
        assert by_kind["all-gather"].nbytes == 512   # max, not 256+512
        assert by_kind["all-gather"].is_async
        assert by_kind["reduce-scatter"].nbytes == 64  # variadic summed

    def test_census_min_bytes(self):
        ops = parse_collectives(
            "  %r = f32[4]{0} all-reduce(%x), channel_id=1\n"
            "  %big = f32[1024,1024]{1,0} all-reduce(%y), channel_id=2\n")
        assert collective_census(ops)["all-reduce"]["count"] == 2
        big = collective_census(ops, min_bytes=1 << 20)
        assert big["all-reduce"] == {"count": 1, "bytes": 4 << 20}

    def test_stablehlo_alias_attribution_per_arg(self):
        """tf.aliasing_output must be charged to ITS argument, not an
        earlier undecorated one (attr dicts contain commas/quoted braces)."""
        from deepspeed_tpu.analysis import hlo_parse
        st = ('func.func public @main(%arg0: tensor<256x256xf32>, '
              '%arg1: tensor<256x256xf32> {mhlo.sharding = '
              '"{devices=[2]<=[2]}", tf.aliasing_output = 0 : i32}) '
              '-> (tensor<256x256xf32>) {')
        assert hlo_parse.parse_aliased_args_stablehlo(st) == [1]

    def test_parse_donated_params(self):
        hlo = ("HloModule jit_f, input_output_alias={ {0}: (0, {}, "
               "may-alias), {1}: (3, {}, must-alias) }, "
               "entry_computation_layout={...}\n  body\n")
        assert parse_donated_params(hlo) == [0, 3]
        assert parse_donated_params("HloModule jit_g\n  body\n") == []

    def test_parse_upcasts(self):
        hlo = "\n".join([
            "  %c1 = f32[512,512]{1,0} convert(bf16[512,512]{1,0} %x)",
            "  %c2 = f32[4]{0} convert(bf16[4]{0} %y)",       # tiny
            "  %c3 = bf16[512,512]{1,0} convert(f32[512,512]{1,0} %z)",  # down
        ])
        ups = parse_upcasts(hlo, min_bytes=1 << 20)
        assert len(ups) == 1 and ups[0].nbytes == 1 << 20
        assert ups[0].from_dtype == "bf16"

    def test_replicated_tensor_scanner(self):
        """replicated_tensor_bytes flags large replicated float tensors and
        ignores small/sharded ones (kept from test_spmd_clean)."""
        hlo = "\n".join([
            "  %big = f32[1024,1024] broadcast(%x), sharding={replicated}",
            "  %small = f32[4,4] broadcast(%x), sharding={replicated}",
            "  %sharded = f32[1024,1024] add(%a, %b), "
            "sharding={devices=[4,1]<=[4]}",
            "  %bigbf = bf16[2048,1024]{1,0} copy(%c), sharding={replicated}",
        ])
        hits = replicated_tensor_bytes(hlo, min_bytes=1 << 20)
        assert len(hits) == 2
        assert {h[0] for h in hits} == {1024 * 1024 * 4, 2048 * 1024 * 2}
        # only the RESULT shape is charged: a tiny replicated result with a
        # big float operand must not be billed for the operand
        decoy = ("  %p = pred[4]{0} compare(f32[1024,1024]{1,0} %a, %b), "
                 "sharding={replicated}")
        assert replicated_tensor_bytes(decoy, min_bytes=1 << 20) == []

    def test_replicated_scanner_stablehlo(self):
        st = ('    %0 = stablehlo.custom_call @Sharding(%arg0) '
              '{mhlo.sharding = "{replicated}"} : (tensor<512x512xf32>) '
              '-> tensor<512x512xf32>')
        hits = replicated_tensor_bytes(st, min_bytes=1 << 20)
        assert hits == [(512 * 512 * 4, st.strip()[:200])]

    def test_capture_helper_sees_fd2_writes(self):
        # must capture C-level fd-2 writes, not just sys.stderr
        # (kept from test_spmd_clean)
        matches = []
        with capture_spmd_warnings(matches):
            os.write(2, b"[SPMD] Involuntary full rematerialization line\n")
        assert len(matches) == 1


# a real spmd_partitioner.cc line (captured from the 8-dev fsdp=4xtensor=2
# dryrun — the pre-existing involuntary-remat failure this audit diagnoses)
_SPMD_WARN_LINE = (
    "2026-08-03 10:11:21.614278: E external/xla/xla/service/spmd/"
    "spmd_partitioner.cc:613] [spmd] Involuntary full rematerialization. "
    "The compiler was not able to go from sharding {devices=[1,8]<=[8]} to "
    "{devices=[2,1,4]<=[4,2]T(1,0) last_tile_dim_replicate} without doing a "
    "full rematerialization of the tensor for HLO operation: %transpose.11 "
    "= f32[128,64]{0,1} transpose(f32[64,128]{1,0} %get-tuple-element), "
    "dimensions={1,0}, sharding={devices=[1,8]<=[8]}, metadata={op_name="
    '"jit(train_step)/jit(main)/while/body/transpose" source_file='
    '"/root/repo/deepspeed_tpu/models/transformer.py" source_line=1215}. '
    "You probably want to enrich the sharding annotations to prevent this "
    "from happening.")


class TestMemoryParsers:
    """Pure-text liveness/remat parsers — no compilation."""

    # 4 MiB param (donated), 32 KiB batch arg, one 4 MiB temp; the updated
    # output writes into the donated param's buffer
    _HLO = "\n".join([
        "HloModule jit_step, is_scheduled=true, input_output_alias="
        "{ {0}: (0, {}, may-alias) }",
        "",
        "ENTRY %main (p0: f32[1024,1024], p1: f32[8,1024]) -> "
        "(f32[1024,1024]) {",
        "  %p0 = f32[1024,1024]{1,0} parameter(0)",
        "  %p1 = f32[8,1024]{1,0} parameter(1)",
        "  %big = f32[1024,1024]{1,0} multiply(%p0, %p0)",
        "  %t = f32[8,1024]{1,0} dot(%p1, %big)",
        "  %upd = f32[1024,1024]{1,0} add(%big, %p0)",
        "  ROOT %out = (f32[1024,1024]{1,0}) tuple(%upd)",
        "}",
    ])

    def test_entry_params_per_device_shapes(self):
        ps = parse_entry_params(self._HLO)
        assert [(p.number, p.nbytes) for p in ps] == [(0, 1 << 22),
                                                      (1, 32768)]
        assert ps[0].dtype == "f32" and ps[0].dims == "1024,1024"

    def test_peak_honors_donation_alias(self):
        est = estimate_peak_hbm(self._HLO,
                                param_classes={0: "params",
                                               1: "activations"})
        # peak at the %t dot: p0 + p1 + %big + %t; %upd reuses p0's buffer
        # (input_output_alias) so the update adds nothing
        assert est.peak_bytes == 2 * (1 << 22) + 2 * 32768
        assert est.param_bytes == {"params": 1 << 22,
                                   "activations": 32768}
        # a missed donation is double memory: same module without the
        # header alias map holds %upd as a second 4 MiB allocation
        # alongside p0 and %big
        undonated = self._HLO.replace(
            ", input_output_alias={ {0}: (0, {}, may-alias) }", "")
        est2 = estimate_peak_hbm(undonated)
        assert est2.peak_bytes == 3 * (1 << 22) + 32768

    def test_gte_selects_one_tuple_element(self):
        """Element-level aliasing: a gte of one small tuple element must
        not keep the big sibling alive (else every fused K-step carry
        would model as Kx memory)."""
        hlo = "\n".join([
            "HloModule jit_g, is_scheduled=true",
            "",
            "ENTRY %main (p0: f32[1024,1024], p1: f32[4]) -> f32[4] {",
            "  %p0 = f32[1024,1024]{1,0} parameter(0)",
            "  %p1 = f32[4]{0} parameter(1)",
            "  %a = f32[1024,1024]{1,0} exponential(%p0)",
            "  %b = f32[4]{0} ceil(%p1)",
            "  %tup = (f32[1024,1024]{1,0}, f32[4]{0}) tuple(%a, %b)",
            "  %sel = f32[4]{0} get-tuple-element(%tup), index=1",
            "  %c = f32[1024,1024]{1,0} cosine(%p0)",
            "  %d = f32[4]{0} reduce(%c, %p1), to_apply=%add",
            "  ROOT %use = f32[4]{0} add(%sel, %d)",
            "}",
        ])
        est = estimate_peak_hbm(hlo)
        # %a dies at %tup (only %b flows on through %sel): peak holds ONE
        # 4 MiB temp at a time, params + max(a, c) + scalars
        assert est.peak_bytes < (1 << 22) + (1 << 22) + (1 << 22)
        assert est.peak_bytes >= (1 << 22) + (1 << 22)

    def test_boundary_skips_hoisted_backward_leaves(self):
        """The fwd/bwd boundary is where the backward first READS a forward
        temporary: 0.9 schedules backward-stamped leaves (partition-id,
        constants, zero accumulators) at the top of the entry computation,
        before any forward op."""
        bwd = ', metadata={op_name="jit(s)/transpose(jvp(f))/mul"}'
        hlo = "\n".join([
            "HloModule jit_s, is_scheduled=true",
            "",
            "ENTRY %main (p0: f32[1024,1024]) -> f32[1024,1024] {",
            "  %pid = u32[] partition-id()" + bwd,
            "  %zero = f32[1024,1024]{1,0} broadcast(%pid)" + bwd,
            "  %p0 = f32[1024,1024]{1,0} parameter(0)",
            "  %act = f32[1024,1024]{1,0} tanh(%p0)",
            "  %out = f32[1024,1024]{1,0} exponential(%act)",
            "  %g = f32[1024,1024]{1,0} multiply(%act, %zero)" + bwd,
            "  ROOT %r = f32[1024,1024]{1,0} add(%g, %out)" + bwd,
            "}",
        ])
        est = estimate_peak_hbm(hlo, param_classes={0: "params"})
        assert est.boundary_index == 5           # %g, not %pid
        # live across it: %act, %out and the hoisted accumulator %zero
        assert est.boundary_bytes == 3 * (1 << 22)

    def test_remat_census_markers(self):
        hlo = "\n".join([
            '  %f = f32[4]{0} fusion(%x), metadata={op_name="jit(s)/'
            'transpose(jvp(checkpoint))/rematted_computation/dot_general"}',
            '  %g = f32[4]{0} fusion(%y), metadata={op_name="jit(s)/'
            'transpose(jvp(checkpoint))/mul"}',
            '  %h = f32[4]{0} fusion(%z), metadata={op_name="jit(s)/tanh"}',
        ])
        census = parse_remat_census(hlo)
        assert census == {"remat_ops": 1, "bwd_ops": 2, "total_ops": 3}

    def test_spmd_warning_structured(self):
        w = parse_spmd_remat_warning(_SPMD_WARN_LINE)
        assert w["op"] == "%transpose.11"
        assert w["shape"] == "f32[128,64]" and w["nbytes"] == 32768
        assert w["from_sharding"] == "{devices=[1,8]<=[8]}"
        assert w["source_file"].endswith("models/transformer.py")
        assert w["source_line"] == 1215
        assert "while/body/transpose" in w["op_name"]

    def test_remat_audit_findings_from_artifacts(self):
        """RematAudit is a pure structure pass: involuntary remat comes
        from the compile-time capture in meta, the inert-policy warning
        from the metadata census — no lowering needed to test either."""
        from deepspeed_tpu.analysis import ProgramArtifacts, RematAudit
        art = ProgramArtifacts(
            name="p", optimized_hlo="",
            meta={"spmd_warnings": [parse_spmd_remat_warning(
                _SPMD_WARN_LINE)]})
        fs = RematAudit().analyze(art, AnalysisSettings())
        assert [f.rule for f in fs] == ["involuntary-remat"]
        assert fs[0].severity == "error" and fs[0].nbytes == 32768
        assert fs[0].data["source_line"] == 1215
        # configured policy, backward present, nothing rematerialized
        hlo = ("HloModule m, is_scheduled=true\n\n"
               "ENTRY %e (a: f32[4]) -> f32[4] {\n"
               "  %a = f32[4]{0} parameter(0)\n"
               "  ROOT %x = f32[4]{0} negate(%a), metadata={op_name="
               '"jit(s)/transpose(jvp(f))/neg"}\n}\n')
        art2 = ProgramArtifacts(name="p", optimized_hlo=hlo,
                                meta={"remat_policy": "dots_saveable"})
        fs2 = RematAudit().analyze(art2, AnalysisSettings())
        assert [f.rule for f in fs2] == ["remat-policy-inert"]
        assert fs2[0].severity == "warning"


# --------------------------------------------------------------------------
# seeded-violation corpus: every analyzer must flag its planted defect
# --------------------------------------------------------------------------

_CORPUS_RULES = {
    "undonated-state": "donation-missing",
    "extra-collective": "collective-census-drift",
    "f32-upcast": "dtype-upcast",
    "replicated-budget": "replication-over-budget",
    "census-drift": "collective-census-drift",
    "fused-hoist": "collective-census-drift",
    "telemetry-leak": "donation-missing",
    "deferred-sync-regression": "collective-census-drift",
    "remat-missing": "memory-peak",
    "stage3-replicated-opt": "memory-law",
    "paged-cache-leak": "memory-peak",
    "tp-serving-replicated-pool": "replication-over-budget",
    "quantized-weight-replicated": "replication-over-budget",
    "adapter-slot-leak": "pool-growth",
    "handoff-recompute": "ttft-growth",
    "serving-blind-stall": "serving-phase-stall",
    "tracing-sync-leak": "tracing-sync-leak",
    "staging-buffer-alias": "buffer-alias",
    "allocator-unlocked-share": "refcount-race",
    "drain-schema-skew": "reader-writer-skew",
    "fenceless-failover": "double-serve",
}


class TestSeededCorpus:
    @pytest.mark.parametrize("name", sorted(_CORPUS_RULES))
    def test_corpus_entry_flagged(self, name, devices8):
        from deepspeed_tpu.analysis.corpus import run_corpus
        report = run_corpus(name, devices=devices8[:2])
        assert not report.ok, f"{name}: seeded violation not flagged"
        rules = {f.rule for f in report.findings}
        assert _CORPUS_RULES[name] in rules, (name, rules)

    @pytest.mark.parametrize("name", ["extra-collective", "f32-upcast",
                                      "telemetry-leak"])
    def test_defect_free_twin_is_ok(self, name, devices8):
        """The same program without its planted defect, under the same pin
        and settings: the analyzer tells the two apart."""
        from deepspeed_tpu.analysis.corpus import CORPUS
        report = CORPUS[name](devices8[:2], seeded=False)
        assert report.ok and not report.findings, report.summary()

    def test_deferred_sync_regression_reports_exposed(self, devices8):
        """The gas=4 per-microbatch reduce-scatter corpus entry must be
        flagged BOTH ways: census drift (gas x inflation vs the deferred
        1-per-step pin) AND exposed collectives from the overlap audit."""
        from deepspeed_tpu.analysis.corpus import run_corpus
        report = run_corpus("deferred-sync-regression", devices=devices8[:2])
        rules = {f.rule for f in report.findings}
        assert "collective-census-drift" in rules
        assert "collective-exposed" in rules
        ov = report.overlap["deferred_step"]
        assert ov["exposed"]["count"] == 4 and ov["overlapped"]["count"] == 0

    def test_stage3_replicated_opt_fires_both_rules(self, devices8):
        """The replicated-moments defect must be caught from BOTH ends:
        the ZeRO memory law (per-device opt bytes = logical instead of
        logical/dp) and the replication budget (explicit replicated
        shardings over the floor)."""
        from deepspeed_tpu.analysis.corpus import run_corpus
        report = run_corpus("stage3-replicated-opt", devices=devices8[:2])
        rules = {f.rule for f in report.findings}
        assert {"memory-law", "replication-over-budget"} <= rules, rules
        sb = report.memory["stage3_step"]["state_bytes"]
        assert sb["opt"]["per_device"] == sb["opt"]["logical"]   # defect
        assert sb["params"]["per_device"] == sb["params"]["logical"] // 2

    def test_remat_fix_stays_under_the_corpus_budget(self, devices8):
        """The remat-missing entry's defect is the MISSING checkpoint: the
        same long-scan program with the body checkpointed must clear the
        identical 18 MiB budget, with recomputation visible in the remat
        census."""
        from deepspeed_tpu.analysis.corpus import (_FakePlan,
                                                   _long_scan_program,
                                                   _stage0_config)
        from deepspeed_tpu.analysis.lint import analyze_programs
        art = _long_scan_program(remat=True, devices=devices8[:2])
        report = analyze_programs(
            [art], _stage0_config(), _FakePlan(),
            settings=AnalysisSettings(max_hbm_bytes=18 << 20))
        assert report.ok, report.summary()
        mem = report.memory["long_scan_step"]
        assert mem["peak_hbm_bytes"] <= 18 << 20
        assert mem["remat"]["remat_ops"] > 0   # recomputation happened

    def test_suppression_accepts_known_finding(self, devices8):
        from deepspeed_tpu.analysis.corpus import run_corpus
        report = run_corpus("f32-upcast", devices=devices8[:2])
        report.suppress(["dtype-upcast"])
        assert report.ok and report.suppressed

    def test_baseline_roundtrip(self):
        rep = Report(findings=[Finding(rule="dtype-upcast", program="p",
                                       ident="f32[512,512]", message="x")],
                     census={"p": {"all-reduce": {"count": 2, "bytes": 64}}})
        base = rep.baseline_dict()
        rep2 = Report(findings=[Finding(rule="dtype-upcast", program="p",
                                        ident="f32[512,512]", message="x")])
        rep2.apply_baseline(base)
        assert rep2.ok and len(rep2.suppressed) == 1

    def test_baseline_never_suppresses_census_drift(self):
        """Accepting a drifted state must re-pin the census, not suppress
        drift-by-key — a FUTURE extra collective of the same kind has the
        same key and would sail through the gate it exists for."""
        from deepspeed_tpu.analysis import compare_census
        census = {"all-reduce": {"count": 3, "bytes": 96}}
        drift = compare_census(census, {"all-reduce": 2}, "p", source="pin")
        rep = Report(findings=list(drift), census={"p": census})
        base = rep.baseline_dict()
        assert base["findings"] == []           # drift keys not recorded
        assert base["census"]["p"]["all-reduce"]["count"] == 3  # re-pinned
        # a later run with one MORE all-reduce still fails against the
        # accepted baseline
        worse = {"all-reduce": {"count": 4, "bytes": 128}}
        rep2 = Report(findings=compare_census(worse, base["census"]["p"],
                                              "p", source="baseline"))
        rep2.apply_baseline(base)
        assert not rep2.ok


# --------------------------------------------------------------------------
# clean configs: ZeRO stages 0-3 lint clean; stage 2 vs 3 census is PINNED
# --------------------------------------------------------------------------

# exact collective censuses for the tiny model / 4x16 batch / 2-device mesh,
# adamw, f32 (measured; stable across xla_backend_optimization_level).
# If a deliberate program change shifts these, re-measure with:
#   python -m deepspeed_tpu.analysis.lint --config <cfg> --write-baseline
# An UNEXPLAINED shift is the bug this test exists to catch.
# Pinned on jax/jaxlib 0.9.0 (PR 21). The previous stack's pins were
# {ar 41, ag 22, a2a 2} / {ag 45, ar 30, a2a 17}: 0.9's CPU pipeline
# combines the per-parameter grad all-reduces (41 -> 4), so a single extra
# all-reduce now merges into a combined one and no longer moves the COUNT
# (see test_extra_allreduce_in_model_fails_pin)...
STAGE2_CENSUS = {"all-reduce": 4, "all-gather": 20}
STAGE3_CENSUS = {"all-gather": 19, "all-reduce": 4, "all-to-all": 6}
# ...so the stage-2 pin holds the four all-reduces' BYTES beside their count
STAGE2_PIN = {**STAGE2_CENSUS, "all-reduce": {"count": 4, "bytes": 69592}}


class TestCleanConfigs:
    @pytest.mark.parametrize("stage,axes", [
        (0, {"data": 2}), (1, {"data": 2}),
        (2, {"data": 2}), (3, {"fsdp": 2})])
    def test_zero_stage_lints_clean(self, stage, axes, devices8):
        report = cached_audit(stage, axes, devices8[:2])
        assert report.ok and not report.findings, report.summary()
        assert report.census["train_step"], "no collectives parsed"

    def test_stage2_vs_stage3_census_pinned(self, devices8):
        """The collective-audit acceptance gate: exact counts per stage on a
        2-device mesh; an extra (or vanished) collective is a hard failure."""
        for stage, axes, pin, want in (
                (2, {"data": 2}, STAGE2_PIN, STAGE2_CENSUS),
                (3, {"fsdp": 2}, STAGE3_CENSUS, STAGE3_CENSUS)):
            report = audit_stage(stage, axes, devices=devices8[:2],
                                 analysis={"expect_collectives": pin})
            assert report.ok, f"stage {stage}:\n{report.summary()}"
            got = {k: c["count"]
                   for k, c in report.census["train_step"].items()}
            assert got == want, f"stage {stage} census drifted: {got}"

    @pytest.mark.slow
    def test_fused_program_census_scales_by_k(self, devices8):
        """pipeline.fuse_steps=K lowers a second artifact (train_step_fused)
        whose census must be EXACTLY Kx the single-step pins: a collective
        hoisted out of (or duplicated into) the unrolled loop is drift.
        Its MEMORY must not scale with K: the inter-step state stays at
        boundary shardings in the loop carry, so the modeled peak HBM of
        the K-fused program pins ~1x the single step's, not Kx."""
        report = audit_stage(2, {"data": 2}, devices=devices8[:2],
                             pipeline={"fuse_steps": 2},
                             analysis={"expect_collectives": STAGE2_CENSUS})
        assert report.ok, report.summary()
        single = {k: c["count"] for k, c in report.census["train_step"].items()}
        fused = {k: c["count"]
                 for k, c in report.census["train_step_fused"].items()}
        assert single == STAGE2_CENSUS
        assert fused == {k: 2 * v for k, v in STAGE2_CENSUS.items()}, fused
        peak1 = report.memory["train_step"]["peak_hbm_bytes"]
        peakk = report.memory["train_step_fused"]["peak_hbm_bytes"]
        assert peak1 > 0
        # K=2: Kx would be >= 2.0; the carried state models ~1.3x (XLA's
        # own buffer assignment says 1.16x for this program pair)
        assert peakk < 1.6 * peak1, (peak1, peakk)

    def test_extra_allreduce_in_model_fails_pin(self, devices8):
        """A model-level silently-added cross-replica reduction must break
        the stage-2 pin — the reference's unnoticeable extra allreduce is a
        hard failure here. XLA combines it into one of the four existing
        all-reduces, so it is the pinned BYTES that move (+64: the [16]
        f32 statistic); the clean model passes the same pin
        (test_stage2_vs_stage3_census_pinned)."""
        from deepspeed_tpu.analysis.corpus import NoisyLossModel
        report = audit_stage(
            2, {"data": 2}, model=NoisyLossModel(tiny_model()),
            devices=devices8[:2],
            analysis={"expect_collectives": STAGE2_PIN})
        assert not report.ok
        drift = [f for f in report.findings
                 if f.rule == "collective-census-drift"
                 and f.ident == "all-reduce"]
        assert drift, report.summary()
        assert drift[0].data["got_bytes"] \
            == drift[0].data["expected_bytes"] + 16 * 4

    def test_donation_covers_whole_state(self, devices8):
        """Every param/optimizer buffer of the stage-2 step aliases an
        output (missed donation = double memory)."""
        from deepspeed_tpu.analysis import lower_engine_programs
        engine, *_ = deepspeed_tpu.initialize(
            model=tiny_model(), config=stage_config(2, {"data": 2}),
            devices=devices8[:2])
        art = lower_engine_programs(engine, batch=BATCH)[0]
        donated = parse_donated_params(art.optimized_hlo)
        assert len(donated) == len(art.donatable_paths)
        assert donated == list(range(len(art.donatable_paths)))


# --------------------------------------------------------------------------
# memory lint: ZeRO memory law + peak breakdown on the 2-dev mesh
# --------------------------------------------------------------------------

class TestMemoryLintEngine:
    def test_memory_law_stage0_vs_stage3_pinned(self, devices8):
        """The acceptance pin: per-device opt-state bytes verify the ZeRO
        memory law on the 2-dev mesh — stage 0 holds the FULL optimizer
        state on every device (per-device == logical, exactly), stage 3
        shards it ~1/dp (slack only from unshardable small leaves), and
        the stage-3/stage-0 per-device ratio is ~1/dp. Same law for
        stage-3 params. The numbers come from the compiled modules' entry
        parameter shapes — post-SPMD fact, not configuration intent."""
        rep0 = cached_audit(0, {"data": 2}, devices8[:2])
        rep3 = cached_audit(3, {"fsdp": 2}, devices8[:2])
        s0 = rep0.memory["train_step"]["state_bytes"]
        s3 = rep3.memory["train_step"]["state_bytes"]
        # identical logical state across stages (same model/optimizer)
        assert s3["opt"]["logical"] == s0["opt"]["logical"]
        assert s3["params"]["logical"] == s0["params"]["logical"]
        # stage 0: everything replicated — exact equality
        assert s0["opt"]["per_device"] == s0["opt"]["logical"]
        assert s0["params"]["per_device"] == s0["params"]["logical"]
        # stage 3: ~1/dp with dp=2; <=5% slack for unshardable leaves
        for cls in ("opt", "params"):
            half = s3[cls]["logical"] / 2
            assert half <= s3[cls]["per_device"] <= 1.05 * half, \
                (cls, s3[cls])
        ratio = s3["opt"]["per_device"] / s0["opt"]["per_device"]
        assert abs(ratio - 0.5) < 0.02, ratio

    def test_audit_reports_peak_with_class_breakdown(self, devices8):
        """engine.audit() must report per-program peak_hbm_bytes with the
        params/grads/opt/activations breakdown (the acceptance surface
        the CLI JSON exposes)."""
        report = cached_audit(2, {"data": 2}, devices8[:2])
        mem = report.memory["train_step"]
        assert mem["peak_hbm_bytes"] > 0
        bd = mem["peak_breakdown"]
        assert {"params", "grads", "opt", "activations"} <= set(bd)
        # the donated state is resident at peak: params are exact
        assert bd["params"] == mem["state_bytes"]["params"]["per_device"]
        assert sum(bd.values()) == mem["peak_hbm_bytes"]
        # fwd/bwd boundary liveness + remat census ride the same measure
        assert mem["boundary_activation_bytes"] > 0   # no remat configured
        assert mem["remat"]["remat_ops"] == 0
        assert mem["remat"]["bwd_ops"] > 0

    @pytest.mark.slow
    def test_memory_lint_changes_no_numerics(self, devices8):
        """Bit-for-bit: auditing with the memory gate armed is a pure
        read of the compiled artifact — training with audit() calls and
        analysis.max_hbm_bytes set produces byte-identical params to
        training without. Slow tier: numerical-parity suites run with
        production codegen (two engine builds + 6 steps, ~9s; re-tiered
        with the PR-6 quick additions to hold the 180s tier budget)."""
        def run(with_lint):
            overrides = ({"analysis": {"max_hbm_bytes": 1 << 40}}
                         if with_lint else {})
            engine, *_ = deepspeed_tpu.initialize(
                model=tiny_model(),
                config=stage_config(2, {"data": 2}, **overrides),
                devices=devices8[:2])
            rng = np.random.default_rng(7)
            for i in range(3):
                batch = {"input_ids": rng.integers(
                    0, 64, size=(4, 16), dtype=np.int32)}
                engine.train_batch(batch)
                if with_lint and i == 1:
                    report = engine.audit(batch=BATCH)
                    assert report.ok, report.summary()
            return jax.device_get(engine.state["params"])
        base = run(False)
        linted = run(True)
        flat_b = jax.tree_util.tree_leaves(base)
        flat_l = jax.tree_util.tree_leaves(linted)
        assert len(flat_b) == len(flat_l)
        for a, b in zip(flat_b, flat_l):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# dtype/flash satellites
# --------------------------------------------------------------------------

class TestDtypeAndFlash:
    def test_bf16_clean_config_no_upcast_findings(self, devices8):
        report = audit_stage(2, {"data": 2},
                             model=tiny_model(dtype=jnp.bfloat16),
                             devices=devices8[:2])
        assert not [f for f in report.findings if f.rule == "dtype-upcast"], \
            report.summary()

    def test_flash_survives_static_windows_unrolled(self):
        """attn_windows=(0, w): the unrolled path passes STATIC windows, so
        the global layer keeps the flash/Pallas kernel and (since PR 44) the
        windowed layer takes its banded forward; under scan the traced
        window pushes every layer to the XLA path (documented cost).
        Confirmed at jaxpr level via the analysis census."""
        counts = {}
        for scan in (False, True):
            cfg = TransformerConfig(
                vocab_size=64, hidden_size=128, num_layers=2, num_heads=2,
                max_seq_len=128, dtype=jnp.float32, attention_impl="pallas",
                attn_windows=(0, 8), scan_layers=scan)
            model = make_model(cfg, name="win")
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            batch = {"input_ids": jax.ShapeDtypeStruct((2, 128), jnp.int32)}
            census = jaxpr_primitive_census(
                lambda p, b: model.loss_fn(p, b, None, True), params, batch)
            counts[scan] = census.get("pallas_call", 0)
        assert counts[False] == 2, counts  # flash + its banded forward
        assert counts[True] == 0, counts   # scan: traced window, XLA path


# --------------------------------------------------------------------------
# CLI — the CI gate a pipeline runs
# --------------------------------------------------------------------------

def _run_cli(*args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["DSTPU_LOG_LEVEL"] = "error"
    # replace any inherited XLA_FLAGS with just the compile-speed flag:
    # the CLI appends its own virtual-device count, and census pins are
    # stable across optimization levels (see STAGE2_CENSUS note) while
    # full-opt compile costs ~2x the wall of the whole test
    env["XLA_FLAGS"] = "--xla_backend_optimization_level=0"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis.lint", *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO_ROOT)


class TestLintCLI:
    def test_clean_config_exits_zero_with_census(self, tmp_path):
        cfg = tmp_path / "cfg.json"
        cfg.write_text(json.dumps(stage_config(2, {"data": 2})))
        out = tmp_path / "report.json"
        proc = _run_cli("--config", str(cfg), "--json", str(out))
        assert proc.returncode == 0, proc.stderr[-2000:]
        report = json.loads(out.read_text())
        assert report["ok"] and not report["findings"]
        census = report["census"]["train_step"]
        for kind, c in census.items():
            assert c["count"] > 0 and c["bytes"] > 0
        assert "all-reduce" in census
        # the memory-lint surface rides the same JSON report
        mem = report["memory"]["train_step"]
        assert mem["peak_hbm_bytes"] > 0
        assert {"params", "grads", "opt", "activations"} \
            <= set(mem["peak_breakdown"])
        assert mem["state_bytes"]["opt"]["per_device"] > 0

    def test_seeded_violation_exits_nonzero(self, tmp_path):
        proc = _run_cli("--corpus", "f32-upcast")
        assert proc.returncode == 1, proc.stderr[-2000:]
        assert "dtype-upcast" in proc.stderr

    @pytest.mark.slow
    def test_baseline_gate(self, tmp_path):
        """--write-baseline then --baseline passes; a different config
        against the same baseline fails with census drift."""
        cfg2 = tmp_path / "s2.json"
        cfg2.write_text(json.dumps(stage_config(2, {"data": 2})))
        base = tmp_path / "base.json"
        assert _run_cli("--config", str(cfg2), "--write-baseline",
                        str(base)).returncode == 0
        assert _run_cli("--config", str(cfg2), "--baseline",
                        str(base)).returncode == 0
        cfg3 = tmp_path / "s3.json"
        cfg3.write_text(json.dumps(stage_config(3, {"fsdp": 2})))
        proc = _run_cli("--config", str(cfg3), "--baseline", str(base))
        assert proc.returncode == 1
        assert "collective-census-drift" in proc.stderr
