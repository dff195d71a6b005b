"""Collective overlap & comm deferral (comm.schedule + analysis overlap).

Pins the ISSUE-4 tentpole contracts:
  * deferred gradient sync (comm.deferred_grad_sync) trains BIT-FOR-BIT
    identically to the per-microbatch path over 20 fp16 steps with a forced
    overflow at step 7 (mirroring test_dataloader_prefetch's parity idiom),
    across ZeRO stages 1/2/3 on a 2-dev mesh, including the fused K-step
    program and a data=2 x fsdp=4 mesh;
  * the stage-2 collective census is INDEPENDENT of
    gradient_accumulation_steps when deferral is on (exact pin), and the
    per-microbatch grad sync scales exactly gas x when it is off
    (microbatch-unrolled lowering makes each sync a distinct static site);
  * the overlap analyzer classifies scheduled collectives as
    overlapped/exposed and gates on analysis.max_exposed_collectives;
  * the 1/gas scaling is folded into the scan accumulator update — no
    post-scan full-grad-tree division sweep (jaxpr op-count pin).

Bit-parity methodology: deferred sync REORDERS the gradient summation
(per-device partials sum across microbatches before crossing the wire), so
float parity is bitwise exactly when the sums themselves are exact. The
parity model uses integer-valued data with a loss whose per-step gradient
arithmetic stays exact (integer column sums scaled by powers of two), which
makes every step's reduced gradient bit-identical by construction — any bit
difference in the trained state is a real defect in the deferred path, not
rounding. A quadratic-loss first-step check covers the grad computation at
exact inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis import OverlapAudit, AnalysisSettings
from deepspeed_tpu.analysis.hlo_parse import overlap_summary, parse_overlap
from deepspeed_tpu.comm import schedule as comm_sched
from jax.sharding import PartitionSpec as P


# --------------------------------------------------------------------------
# parity models (exact-arithmetic by construction)
# --------------------------------------------------------------------------

class IntLinearMean:
    """loss = mean(x @ w): the gradient is an integer column-sum of x scaled
    by powers of two — exact under ANY summation order, so eager and
    deferred reductions must agree bit-for-bit every step."""

    name = "int-linear-mean"

    def __init__(self, d=8):
        self.d = d

    def init(self, rng):
        return {"w": ((jnp.arange(self.d * self.d) % 5 - 2)
                      .reshape(self.d, self.d).astype(jnp.float32)) * 0.5}

    @property
    def logical_axes(self):
        return {"w": None}

    def loss_fn(self, params, batch, rng, deterministic):
        y = batch["x"] @ params["w"].astype(batch["x"].dtype)
        return jnp.mean(y.astype(jnp.float32))


class IntLinearSq(IntLinearMean):
    """loss = mean((x @ w)^2): grads depend on w (exact only at integer
    params) — used for the first-step bitwise check of the deferred grad
    computation itself."""

    name = "int-linear-sq"

    def loss_fn(self, params, batch, rng, deterministic):
        y = batch["x"] @ params["w"].astype(batch["x"].dtype)
        return jnp.mean(jnp.square(y).astype(jnp.float32))


def fp16_cfg(stage, axes, deferred, gas=4, batch=16, **overrides):
    cfg = {"train_batch_size": batch,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "fp16": {"enabled": True, "initial_scale_power": 8},
           "bf16": {"enabled": False},
           "zero_optimization": {"stage": stage},
           "mesh": {"axes": axes},
           "comm": {"deferred_grad_sync": deferred},
           "steps_per_print": 100}
    for k, v in overrides.items():
        if isinstance(v, dict) and isinstance(cfg.get(k), dict):
            cfg[k] = {**cfg[k], **v}
        else:
            cfg[k] = v
    return cfg


def int_batches(n=20, boost_at=7, rows=16, d=8):
    """Integer-valued batches; the boosted batch pushes the fp16-scaled grad
    products past f32 max (2 * 2^126 * 2 = 2^128 -> inf) for a forced
    overflow at `boost_at` on every path."""
    rng = np.random.default_rng(0)
    batches = [{"x": rng.integers(-2, 3, size=(rows, d)).astype(np.float32)}
               for _ in range(n)]
    boost = np.full((rows, d), 2.0, np.float32) * np.float32(2.0 ** 126)
    batches[boost_at] = {"x": boost}
    return batches


def w_bits(engine):
    w = np.asarray(jax.device_get(engine.state["params"]["w"]))
    return w.view(np.uint32)


def run_steps(engine, batches):
    for b in batches:
        engine.train_batch(b)
    return engine


# --------------------------------------------------------------------------
# deferred vs per-microbatch: bit-for-bit over 20 fp16 steps
# --------------------------------------------------------------------------

class TestDeferredParity:
    @pytest.mark.parametrize("stage,axes", [
        (1, {"data": 2}), (2, {"data": 2}), (3, {"fsdp": 2})])
    def test_bit_for_bit_20_steps_with_overflow(self, stage, axes, devices8):
        batches = int_batches()
        eager, *_ = deepspeed_tpu.initialize(
            model=IntLinearMean(), config=fp16_cfg(stage, axes, False),
            devices=devices8[:2])
        deferred, *_ = deepspeed_tpu.initialize(
            model=IntLinearMean(), config=fp16_cfg(stage, axes, True),
            devices=devices8[:2])
        run_steps(eager, batches)
        run_steps(deferred, batches)
        assert eager.global_steps == deferred.global_steps == 20
        assert eager.skipped_steps == deferred.skipped_steps == 1
        assert eager.get_loss_scale() == deferred.get_loss_scale()
        np.testing.assert_array_equal(w_bits(eager), w_bits(deferred))
        # the applied-update counter skipped exactly the overflow step
        applied = np.asarray(jax.device_get(deferred.state["step"]))
        assert int(applied.reshape(-1)[0]) == 19

    def test_fused_k_steps_deferred_bit_for_bit(self, devices8):
        """pipeline.fuse_steps=4 x deferred sync: 5 dispatches cover 20
        steps; the shard_map region threads through the unrolled program."""
        batches = int_batches()
        ref, *_ = deepspeed_tpu.initialize(
            model=IntLinearMean(), config=fp16_cfg(2, {"data": 2}, False),
            devices=devices8[:2])
        run_steps(ref, batches)
        fused, *_ = deepspeed_tpu.initialize(
            model=IntLinearMean(),
            config=fp16_cfg(2, {"data": 2}, True,
                            pipeline={"fuse_steps": 4, "in_flight": 2}),
            devices=devices8[:2])
        fused.train_batches(iter(batches), 20)
        assert fused.global_steps == 20
        assert fused.skipped_steps == ref.skipped_steps == 1
        np.testing.assert_array_equal(w_bits(ref), w_bits(fused))

    def test_deferred_2d_bit_for_bit(self, devices8):
        """data=2 x fsdp=4: the deferred region is manual over `data` only
        and unrolls its microbatch loop beside the auto fsdp axis; it
        trains bit-identically to the eager path."""
        batches = int_batches(n=10, boost_at=3)
        axes = {"data": 2, "fsdp": 4}
        eager, *_ = deepspeed_tpu.initialize(
            model=IntLinearMean(), config=fp16_cfg(2, axes, False, gas=2),
            devices=devices8)
        deferred, *_ = deepspeed_tpu.initialize(
            model=IntLinearMean(),
            config=fp16_cfg(2, axes, True, gas=2), devices=devices8)
        run_steps(eager, batches)
        run_steps(deferred, batches)
        assert eager.skipped_steps == deferred.skipped_steps == 1
        np.testing.assert_array_equal(w_bits(eager), w_bits(deferred))

    def test_quadratic_first_step_bitwise(self, devices8):
        """Grad computation parity at exact (integer) params: the very first
        optimizer step of a quadratic loss must match bitwise — this pins
        the deferred path's normalization (1/gas, 1/data, loss scale)
        exactly; later steps reorder sums over irrational params and are
        rounding-, not correctness-, different."""
        batches = int_batches(n=1, boost_at=0)
        batches[0] = {"x": np.random.default_rng(1).integers(
            -2, 3, size=(16, 8)).astype(np.float32)}
        eager, *_ = deepspeed_tpu.initialize(
            model=IntLinearSq(), config=fp16_cfg(2, {"data": 2}, False),
            devices=devices8[:2])
        deferred, *_ = deepspeed_tpu.initialize(
            model=IntLinearSq(), config=fp16_cfg(2, {"data": 2}, True),
            devices=devices8[:2])
        me = eager.train_batch(batches[0])
        md = deferred.train_batch(batches[0])
        assert float(me["grad_norm"]) == float(md["grad_norm"])
        np.testing.assert_array_equal(w_bits(eager), w_bits(deferred))


# --------------------------------------------------------------------------
# census pins: gas-independence (deferred) vs exactly-gas-x (eager)
# --------------------------------------------------------------------------

def tiny_model():
    from deepspeed_tpu.models import TransformerConfig, make_model
    return make_model(TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=64, dtype=jnp.float32, attention_impl="xla"),
        name="lint-tiny")


BATCH16 = {"input_ids": np.zeros((16, 16), np.int32)}

# exact censuses for the tiny model / 16x16 batch / 2-device data mesh
# (measured; re-measure with engine.audit() if a deliberate change shifts
# them). DEFERRED is the same dict for EVERY gas; the eager per-microbatch
# grad sync adds exactly EAGER_AR_PER_MB all-reduces per extra microbatch.
# Pinned on jax/jaxlib 0.9.0 (PR 21; the previous stack's values were
# deferred {ar 21, rs 20, ag 20}, eager gas-1 ar 41, +21 per microbatch):
# 0.9's CPU pipeline combines each microbatch's per-parameter grad
# all-reduces into ONE, so the eager path now grows by exactly 1 per extra
# microbatch (measured 4 / 5 / 7 at gas 1 / 2 / 4).
STAGE2_DEFERRED_CENSUS = {"all-reduce": 1, "reduce-scatter": 20,
                          "all-gather": 20}
STAGE2_EAGER_GAS1_AR = 4        # = test_analysis.STAGE2_CENSUS["all-reduce"]
EAGER_AR_PER_MB = 1             # per-microbatch grad sync all-reduces


def census_of(stage, axes, devices, gas, *, deferred, unroll=0,
              expect=None, fuse=0):
    cfg = {"train_batch_size": 16,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "bf16": {"enabled": False},
           "zero_optimization": {"stage": stage,
                                 "stage3_param_persistence_threshold": 0},
           "mesh": {"axes": axes},
           "comm": {"deferred_grad_sync": deferred,
                    "microbatch_unroll": unroll},
           "steps_per_print": 100}
    if fuse:
        cfg["pipeline"] = {"fuse_steps": fuse}
    if expect is not None:
        cfg["analysis"] = {"expect_collectives": expect}
    engine, *_ = deepspeed_tpu.initialize(model=tiny_model(), config=cfg,
                                          devices=devices)
    report = engine.audit(batch=BATCH16)
    return report


class TestDeferredCensus:
    def test_stage2_census_independent_of_gas(self, devices8):
        """The acceptance pin: with deferral on, the stage-2 collective
        census is IDENTICAL for gas=1 and gas=4 — one data-axis sync per
        step, period — and matches the exact pin (enforced through
        analysis.expect_collectives so the report gate itself fires)."""
        censuses = {}
        for gas in (1, 4):
            rep = census_of(2, {"data": 2}, devices8[:2], gas, deferred=True,
                            expect=STAGE2_DEFERRED_CENSUS)
            assert rep.ok, f"gas={gas}:\n{rep.summary()}"
            censuses[gas] = {k: c["count"]
                             for k, c in rep.census["train_step"].items()}
        assert censuses[1] == censuses[4] == STAGE2_DEFERRED_CENSUS, censuses

    def test_stage2_eager_grad_sync_scales_exactly_gas_x(self, devices8):
        """With deferral OFF and the microbatch loop unrolled (each sync a
        distinct static site), the per-microbatch grad all-reduce count is
        exactly linear in gas: ar(gas) = ar(1) + EAGER_AR_PER_MB*(gas-1)."""
        rep = census_of(2, {"data": 2}, devices8[:2], 4, deferred=False,
                        unroll=4)
        assert rep.ok, rep.summary()
        got = {k: c["count"] for k, c in rep.census["train_step"].items()}
        assert got["all-reduce"] == STAGE2_EAGER_GAS1_AR \
            + EAGER_AR_PER_MB * 3, got
        # no reduce-scatter sites vanish into the deferred shape by accident
        assert got["all-reduce"] > STAGE2_DEFERRED_CENSUS["all-reduce"]

    @pytest.mark.slow
    def test_stage2_eager_linearity_at_gas2(self, devices8):
        rep = census_of(2, {"data": 2}, devices8[:2], 2, deferred=False,
                        unroll=2)
        got = {k: c["count"] for k, c in rep.census["train_step"].items()}
        assert got["all-reduce"] == STAGE2_EAGER_GAS1_AR + EAGER_AR_PER_MB

    @pytest.mark.slow
    def test_fused_deferred_census_scales_by_k(self, devices8):
        """The fused K-step program threads the deferred shard_map region K
        times: its census must be exactly K x the deferred single-step pin
        (CollectiveAudit scales expect_collectives by meta fuse_steps).
        Slow tier: the K-step lowering was the quick tier's single most
        expensive compile (~13s on a 1-core box); the fuse_steps pin
        scaling it exercises is also covered (slow) by test_analysis's
        test_fused_program_census_scales_by_k."""
        rep = census_of(2, {"data": 2}, devices8[:2], 2, deferred=True,
                        expect=STAGE2_DEFERRED_CENSUS, fuse=2)
        assert rep.ok, rep.summary()
        single = {k: c["count"] for k, c in rep.census["train_step"].items()}
        fused = {k: c["count"]
                 for k, c in rep.census["train_step_fused"].items()}
        assert single == STAGE2_DEFERRED_CENSUS
        assert fused == {k: 2 * v
                         for k, v in STAGE2_DEFERRED_CENSUS.items()}, fused


# --------------------------------------------------------------------------
# overlap analyzer (scheduled-HLO classification)
# --------------------------------------------------------------------------

SCHED_HLO = """\
HloModule jit_step, is_scheduled=true

ENTRY %main (p0: f32[1024,1024]) -> f32[1024,1024] {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %ag = (f32[512,1024]{1,0}, f32[1024,1024]{1,0}) all-gather-start(f32[512,1024]{1,0} %x), channel_id=1
  %fused = f32[1024,1024]{1,0} fusion(f32[1024,1024]{1,0} %p0), kind=kLoop, calls=%fc
  %agd = f32[1024,1024]{1,0} all-gather-done((f32[512,1024]{1,0}, f32[1024,1024]{1,0}) %ag)
  %rs = (f32[1024,1024]{1,0}, f32[512,1024]{1,0}) reduce-scatter-start(f32[1024,1024]{1,0} %fused), channel_id=2
  %rsd = f32[512,1024]{1,0} reduce-scatter-done((f32[1024,1024]{1,0}, f32[512,1024]{1,0}) %rs)
  %ar = f32[1024,1024]{1,0} all-reduce(f32[1024,1024]{1,0} %agd), channel_id=3, to_apply=%add
  %tiny = f32[4]{0} all-reduce(f32[4]{0} %small), channel_id=4, to_apply=%add
  %pp = (f32[1024,1024]{1,0}, f32[1024,1024]{1,0}, u32[], u32[]) collective-permute-start(f32[1024,1024]{1,0} %agd), channel_id=5
  %w = (s32[], f32[1024,1024]{1,0}) while(s32[] %c, f32[1024,1024]{1,0} %agd), condition=%cond, body=%wbody
  %ppd = f32[1024,1024]{1,0} collective-permute-done((f32[1024,1024]{1,0}, f32[1024,1024]{1,0}, u32[], u32[]) %pp)
}
"""


class TestOverlapAnalyzer:
    def test_classification(self):
        ops = parse_overlap(SCHED_HLO)
        by = {}
        for op in ops:
            by.setdefault(op.kind, []).append(op)
        # async pair with a fusion scheduled between start/done: overlapped
        ag = by["all-gather"][0]
        assert ag.is_async and ag.overlapped and ag.gap_ops == 1
        assert ag.nbytes == 1024 * 1024 * 4  # max tuple element, not sum
        # async pair scheduled back-to-back: exposed
        rs = by["reduce-scatter"][0]
        assert rs.is_async and not rs.overlapped
        # synchronous collective: exposed by construction
        ar = by["all-reduce"][0]
        assert not ar.is_async and not ar.overlapped
        # a TUPLE-result compute op (while loops, multi-output fusions)
        # between start/done still counts as overlap
        pp = by["collective-permute"][0]
        assert pp.is_async and pp.overlapped and pp.gap_ops == 1

    def test_classification_without_name_sigils(self):
        """Some XLA dump styles print instruction names without the '%'
        sigil; start/done pairing must still resolve (boundary-anchored
        matching, no substring collisions)."""
        ops = parse_overlap(SCHED_HLO.replace("%", ""))
        by = {}
        for op in ops:
            by.setdefault(op.kind, []).append(op)
        assert by["all-gather"][0].overlapped
        assert not by["reduce-scatter"][0].overlapped
        assert by["collective-permute"][0].overlapped

    def test_summary_respects_min_bytes(self):
        summary = overlap_summary(parse_overlap(SCHED_HLO), min_bytes=1024)
        assert summary["overlapped"]["count"] == 2
        assert summary["exposed"]["count"] == 2  # tiny all-reduce exempt
        assert summary["exposed"]["bytes"] == (1024 * 1024 * 4) * 2

    def test_gate_fires_only_when_configured(self):
        from deepspeed_tpu.analysis.program import ProgramArtifacts
        art = ProgramArtifacts(name="p", optimized_hlo=SCHED_HLO)
        audit = OverlapAudit()
        assert audit.analyze(art, AnalysisSettings()) == []  # report-only
        findings = audit.analyze(
            art, AnalysisSettings(max_exposed_collectives=0,
                                  min_exposed_bytes=1024))
        rules = {f.rule for f in findings}
        assert rules == {"collective-exposed"}
        kinds = {f.ident for f in findings}
        assert kinds == {"all-reduce", "reduce-scatter"}
        # budget of 2 tolerates both exposed ops
        assert audit.analyze(
            art, AnalysisSettings(max_exposed_collectives=2,
                                  min_exposed_bytes=1024)) == []

    def test_engine_report_carries_overlap_census(self, devices8):
        rep = census_of(2, {"data": 2}, devices8[:2], 1, deferred=False)
        ov = rep.overlap["train_step"]
        total = ov["overlapped"]["count"] + ov["exposed"]["count"]
        assert total > 0  # every parsed collective is classified
        assert "overlap" in rep.to_dict()

    def test_static_join_prices_exposed_comm(self):
        from deepspeed_tpu.telemetry import joined_rates
        static = {"comm_bytes_per_step": 1000,
                  "exposed_comm_bytes_per_step": 250,
                  "overlapped_comm_bytes_per_step": 750,
                  "flops_per_step": 0}
        rates = joined_rates(static, steps_per_sec=2.0, peak_flops=1.0,
                             interconnect_bytes_per_sec=1e6)
        assert rates["exposed_comm_ms"] == pytest.approx(250 / 1e6 * 1e3)
        assert rates["overlap_efficiency"] == pytest.approx(0.75)
        # no interconnect estimate -> no modeled wire time, no crash
        rates = joined_rates(static, 2.0, 1.0)
        assert "exposed_comm_ms" not in rates


# --------------------------------------------------------------------------
# satellite: 1/gas folded into the scan accumulator update
# --------------------------------------------------------------------------

class TestGasFold:
    def test_no_post_scan_division_sweep(self):
        """The mean scaling rides the accumulator update inside the scan;
        the OUTER jaxpr must not contain one div per grad leaf after the
        scan (the single remaining div is the loss mean)."""
        from deepspeed_tpu.runtime.engine import Engine
        params = {"a": jnp.ones((8, 8)), "b": jnp.ones((4,)),
                  "c": jnp.ones((8, 4))}
        batch = {"x": jnp.ones((16, 8))}

        def micro(p, mb, r):
            loss = jnp.mean((mb["x"] @ p["a"] @ p["c"]) ** 2) \
                + jnp.sum(p["b"])
            return loss, jax.tree.map(lambda q: q * 0 + loss, p)

        jaxpr = jax.make_jaxpr(
            lambda p, b, r: Engine._accum_micro_grads(micro, p, b, 4, r))(
                params, batch, jax.random.PRNGKey(0))
        outer = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
        assert outer.count("div") == 1, outer  # loss mean only
        assert "scan" in outer

    def test_folded_mean_matches_reference(self):
        from deepspeed_tpu.runtime.engine import Engine
        params = {"w": jnp.arange(8.0)}
        batch = {"x": jnp.arange(32.0).reshape(32, 1)}

        def micro(p, mb, r):
            return jnp.sum(mb["x"]), jax.tree.map(
                lambda q: q + jnp.sum(mb["x"]), jax.tree.map(
                    jnp.zeros_like, p))

        grads, loss = Engine._accum_micro_grads(
            micro, params, batch, 4, jax.random.PRNGKey(0))
        # sum over microbatches / gas
        per_mb = [np.sum(np.arange(32.0).reshape(4, 8, 1)[i])
                  for i in range(4)]
        np.testing.assert_allclose(np.asarray(grads["w"]),
                                   np.mean(per_mb), rtol=1e-6)

    def test_unrolled_scan_matches_loop(self):
        """comm.microbatch_unroll >= gas fully unrolls; values match the
        scan path exactly (same op order per element)."""
        from deepspeed_tpu.runtime.engine import Engine
        params = {"w": jnp.ones((4,))}
        batch = {"x": jnp.arange(16.0).reshape(16, 1)}

        def micro(p, mb, r):
            s = jnp.sum(mb["x"])
            return s, {"w": p["w"] * s}

        g1, l1 = Engine._accum_micro_grads(micro, params, batch, 4,
                                           jax.random.PRNGKey(0))
        g2, l2 = Engine._accum_micro_grads(micro, params, batch, 4,
                                           jax.random.PRNGKey(0), unroll=4)
        np.testing.assert_array_equal(np.asarray(g1["w"]),
                                      np.asarray(g2["w"]))
        assert float(l1) == float(l2)


# --------------------------------------------------------------------------
# comm.schedule spec surgery
# --------------------------------------------------------------------------

class TestScheduleSpecs:
    def test_drop_axis(self):
        assert comm_sched.drop_axis(P("data", None), "data") == P()
        assert comm_sched.drop_axis(P(("data", "fsdp"), None), "data") \
            == P("fsdp")
        assert comm_sched.drop_axis(P(None, "data"), "data") == P()
        assert comm_sched.drop_axis(P("fsdp"), "data") == P("fsdp")

    def test_axis_dim(self):
        assert comm_sched.axis_dim(P(None, "data"), "data") == 1
        assert comm_sched.axis_dim(P(("data", "fsdp")), "fsdp") == 0
        assert comm_sched.axis_dim(P("fsdp"), "data") is None

    def test_deferred_supported_gates(self):
        from deepspeed_tpu.parallel.mesh import MeshPlan
        ok, _ = comm_sched.deferred_supported(MeshPlan(data=2, fsdp=4))
        assert ok
        for plan in (MeshPlan(data=2, pipe=2), MeshPlan(data=2, seq=2),
                     MeshPlan(data=2, expert=2)):
            ok, why = comm_sched.deferred_supported(plan)
            assert not ok and why


# --------------------------------------------------------------------------
# satellite: AIOHandle.__del__ must not raise after a failed init
# --------------------------------------------------------------------------

class TestAIOHandleDel:
    def test_del_without_handle_attr(self):
        from deepspeed_tpu.ops.aio import AIOHandle
        h = AIOHandle.__new__(AIOHandle)  # __init__ "failed" before _h
        h.close()   # no AttributeError
        h.__del__()  # no noise at collection either
        assert h._h is None

    def test_close_idempotent_without_lib(self):
        from deepspeed_tpu.ops.aio import AIOHandle
        h = AIOHandle.__new__(AIOHandle)
        h._h = 123          # handle present but _lib missing (mid-init)
        h.close()
        assert h._h is None
        h.close()
