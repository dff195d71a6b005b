"""Fleet observability (ISSUE 18): request tracing, serving doctor, rollup.

The three contracts pinned here:

  - tracing is FREE where it counts: a tracing-armed engine produces
    BIT-IDENTICAL outputs to an untraced one (zero added device syncs,
    self-reported through ``tracer.device_syncs``), and a 2-replica
    failover under tracing stays bit-identical to the fault-free run
    while the merged Chrome trace shows ONE trace id spanning both
    replica process rows (drain-state v3 stitching);
  - the serving doctor prices the round-phase decomposition fail-closed
    and names the dominant phase with a knob (``serving-blind-stall`` /
    ``tracing-sync-leak`` corpus twins, both directions);
  - the router's fleet rollup is exactly the sum of per-replica truth,
    survives the Prometheus text round-trip, and ``reset_stats`` clears
    every counter it exposes (the PR-12 pinned-reset contract at fleet
    scope).
"""

import collections
import json
import os

import numpy as np
import pytest

from deepspeed_tpu.robustness import events as rb_events
from deepspeed_tpu.robustness import faults as rb_faults
from deepspeed_tpu.telemetry.exposition import (DEFAULT_EDGES_MS, Histogram,
                                                parse_exposition,
                                                render_prometheus)
from deepspeed_tpu.telemetry.request_trace import (RequestTracer,
                                                   merge_chrome_trace)


@pytest.fixture(autouse=True)
def _clean_robustness_state():
    rb_faults.clear()
    rb_events.clear()
    yield
    rb_faults.clear()
    rb_events.clear()


# ---------------------------------------------------------------------------
# RequestTracer (pure host)
# ---------------------------------------------------------------------------

class TestRequestTracer:
    def test_begin_idempotent_and_sequenced(self):
        tr = RequestTracer(replica="rA")
        tid = tr.begin(7)
        assert tid == "rA/7.0"
        assert tr.begin(7) == tid              # re-begin keeps the id
        assert tr.begin(8) == "rA/8.1"         # fresh rid, next seq
        tr.end(7)
        assert tr.trace_id(7) is None
        assert tr.begin(7) == "rA/7.2"         # resubmission = new trace

    def test_span_context_adopt_stitch(self):
        """The migration stitching rule end to end: the destination
        inherits the trace id and re-appends the source's spans with
        their ORIGINAL replica tags, so one merged export shows the
        request in two process rows under one trace id."""
        src = RequestTracer(replica="r0")
        tid = src.begin(3)
        with src.span(3, "prefill", tokens=4):
            pass
        src.instant(3, "drained", tag="t")
        ctx = src.context(3)
        assert ctx["id"] == tid
        assert [e["name"] for e in ctx["spans"]] == ["prefill", "drained"]

        dst = RequestTracer(replica="r1")
        assert dst.adopt(3, ctx) == tid        # id survives migration
        dst.instant(3, "migrated_in")
        with dst.span(3, "decode_quantum"):
            pass
        # history keeps r0's tag; new activity is tagged r1
        reps = [e["replica"] for e in dst.events]
        assert reps == ["r0", "r0", "r1", "r1"]
        assert all(e["trace"] == tid for e in dst.events)

        merged = merge_chrome_trace([dst.export()])
        evs = [e for e in merged["traceEvents"] if e["ph"] != "M"]
        pids = {e["pid"] for e in evs}
        assert len(pids) == 2                  # two process rows
        assert {e["args"]["trace"] for e in evs} == {tid}
        names = {e["name"] for e in merged["traceEvents"] if e["ph"] == "M"}
        assert names == {"process_name"}

    def test_adopt_empty_ctx_begins_fresh(self):
        tr = RequestTracer(replica="r1")
        assert tr.adopt(5, None) == "r1/5.0"   # v2 record: no trace ctx

    def test_ring_bounded(self):
        tr = RequestTracer(replica="r0", max_events=64)
        tr.begin(1)
        for i in range(500):
            tr.instant(1, f"e{i}")
        assert len(tr.events) == 64
        assert tr.events[-1]["name"] == "e499"

    def test_leaky_hook_is_self_reported(self):
        """The documented defect seam: whatever on_span does is on the
        caller, and the sync count it self-reports is the evidence the
        doctor's tracing-sync-leak gate prices."""
        tr = RequestTracer(replica="r0")

        def leaky(ev):
            tr.device_syncs += 1

        tr.on_span = leaky
        tr.begin(1)
        tr.instant(1, "a")
        with tr.span(1, "b"):
            pass
        assert tr.device_syncs == 2
        # adopted history is NOT new activity: the hook must not fire
        tr2 = RequestTracer(replica="r1", on_span=leaky)
        tr2.adopt(1, tr.context(1))
        assert tr.device_syncs == 2


# ---------------------------------------------------------------------------
# Histogram + exposition (pure host)
# ---------------------------------------------------------------------------

class TestExposition:
    def test_merge_requires_matching_edges(self):
        a, b = Histogram([1, 2, 4]), Histogram([1, 2, 4])
        a.observe_many([0.5, 3.0, 100.0])      # under, mid, overflow
        b.observe(1.5)
        a.merge(b)
        assert a.count == 4 and a.counts[-1] == 1   # overflow bucket
        with pytest.raises(ValueError):
            a.merge(Histogram([1, 2, 8]))

    def test_from_dict_rejects_malformed(self):
        h = Histogram([1, 2])
        h.observe(1.5)
        rt = Histogram.from_dict(h.to_dict())
        assert rt is not None and rt.counts == h.counts
        # version-skew rule: malformed payloads are ignored, not fatal
        assert Histogram.from_dict(None) is None
        assert Histogram.from_dict({"edges": [1, 2]}) is None
        assert Histogram.from_dict({"edges": [1], "counts": [1]}) is None

    def test_render_parse_roundtrip(self):
        h = Histogram(DEFAULT_EDGES_MS)
        h.observe_many([0.5, 3.0, 3.5, 900.0, 1e6])
        text = render_prometheus({"ttft_ms": h, "live": 2,
                                  "ok": True}, prefix="dstpu")
        assert "# TYPE dstpu_ttft_ms histogram" in text
        assert 'le="+Inf"' in text
        parsed = parse_exposition(text)
        assert parsed["dstpu_live"] == 2.0
        assert parsed["dstpu_ok"] == 1.0
        back = parsed["dstpu_ttft_ms"]
        assert back.count == h.count and back.counts == h.counts
        assert back.sum == pytest.approx(h.sum)

    def test_quantile_upper_edge(self):
        h = Histogram([1, 2, 4, 8])
        h.observe_many([1.5] * 9 + [7.0])
        assert h.quantile(0.5) == 2.0          # upper edge of the bucket
        assert h.quantile(0.99) == 8.0
        assert Histogram([1, 2]).quantile(0.5) == 0.0   # empty window


# ---------------------------------------------------------------------------
# Round-phase ring + stall event (host rig over the REAL methods)
# ---------------------------------------------------------------------------

def _entry(round_ms=1.0, **phases):
    e = {"index": 0, "t_s": 0.0, "running_before": 2, "prefills": 0,
         "prefill_tokens": 0, "shape": (2, 2), "ahead_covered": True,
         "empty_before_ms": 0.0, "gc_ms": 0.0, "build_ms": 0.0,
         "schedule_ms": 0.1, "housekeeping_ms": 0.1, "prefill_ms": 0.1,
         "decode_ms": 0.2, "fetch_ms": 0.3, "commit_ms": 0.1,
         "round_ms": round_ms, "tokens": 8.0}
    e.update(phases)
    return e


class _PhaseRig:
    """The ServingEngine's round-record surface, host-only: the REAL
    ``_note_phases`` / ``phase_decomposition`` bound to a stub so the
    stall-event state machine is pinned without a jit compile."""
    from deepspeed_tpu.inference.serving import ServingEngine as _SE
    _STALL_MIN_ROUND_MS = _SE._STALL_MIN_ROUND_MS
    _STALL_FRACTION = _SE._STALL_FRACTION
    _RING_ROUNDS, _SLOW_ROUNDS = _SE._RING_ROUNDS, _SE._SLOW_ROUNDS
    _PHASES, _PHASE_OUT = _SE._PHASES, _SE._PHASE_OUT
    _reset_round_records = _SE._reset_round_records
    _decode_dominated = staticmethod(_SE._decode_dominated)
    _note_phases = _SE._note_phases
    phase_decomposition = _SE.phase_decomposition

    def __init__(self, warm=True):
        self._reset_round_records()
        self._quantum_warm = warm
        self._tracer = None


class TestPhaseStallEvent:
    def test_stall_fires_once_naming_the_phase(self):
        rig = _PhaseRig()
        for _ in range(9):
            rig._note_phases(_entry())
        rig._note_phases(_entry(round_ms=200.0, housekeeping_ms=150.0))
        evs = rb_events.history("serving_phase_stall")
        assert len(evs) == 1
        assert evs[0]["phase"] == "housekeeping"
        assert evs[0]["round_ms"] == pytest.approx(200.0)
        # latched: a second stall in the same window does not re-emit
        rig._note_phases(_entry(round_ms=300.0, housekeeping_ms=250.0))
        assert len(rb_events.history("serving_phase_stall")) == 1
        assert rig.phase_decomposition()["serve_phase_stall_events"] == 1.0

    def test_a_fetch_three_times_the_median_is_a_stall(self):
        """A round's device time is constant, so a fetch that dominates a
        round three times the median is the device, its runtime or a
        descheduled host: the event fires, once a window, and carries the
        round's whole record. (Fetch-bound TOTALS are still health: the
        doctor's reading, TestServingDoctor.)"""
        rig = _PhaseRig()
        for _ in range(9):
            rig._note_phases(_entry(round_ms=100.0, fetch_ms=80.0))
        assert rb_events.history("serving_phase_stall") == []   # steady
        slow = _entry(round_ms=400.0, fetch_ms=390.0, index=9, gc_ms=3.5)
        rig._note_phases(slow)
        (ev,) = rb_events.history("serving_phase_stall")
        assert ev["phase"] == "fetch" and ev["phase_ms"] == 390.0
        assert ev["record"] == slow
        rig._note_phases(_entry(round_ms=900.0, fetch_ms=890.0))
        assert len(rb_events.history("serving_phase_stall")) == 1
        rig._reset_round_records()              # a new stats window
        for _ in range(9):
            rig._note_phases(_entry(round_ms=100.0, fetch_ms=80.0))
        rig._note_phases(_entry(round_ms=400.0, fetch_ms=390.0))
        assert len(rb_events.history("serving_phase_stall")) == 2

    def test_cold_engine_and_thin_baseline_stay_quiet(self):
        cold = _PhaseRig(warm=False)
        for _ in range(12):
            cold._note_phases(_entry(round_ms=200.0, housekeeping_ms=150.0))
        assert rb_events.history("serving_phase_stall") == []
        thin = _PhaseRig()                     # warm but < 9 rounds of
        for _ in range(5):                     # baseline: compile noise
            thin._note_phases(_entry(round_ms=200.0, housekeeping_ms=150.0))
        assert rb_events.history("serving_phase_stall") == []

    def test_decomposition_sums_the_ring(self):
        rig = _PhaseRig()
        for _ in range(4):
            rig._note_phases(_entry())
        d = rig.phase_decomposition()
        assert d["serve_rounds"] == 4.0
        assert d["serve_tokens"] == 32.0
        assert d["serve_fetch_ms"] == pytest.approx(1.2)
        assert d["trace_armed"] == 0.0 and d["trace_device_syncs"] == 0.0


# ---------------------------------------------------------------------------
# Serving doctor (host-only)
# ---------------------------------------------------------------------------

class TestServingDoctor:
    def test_blind_stall_corpus_both_directions(self):
        from deepspeed_tpu.profiling import doctor
        bad = doctor.audit_serving(stalled=True)
        assert not bad.ok
        f = next(f for f in bad.findings if f.rule == "serving-phase-stall")
        assert "paging-bound" in f.message       # the bound is named
        assert "adapter_slots" in f.message      # ... with a knob
        good = doctor.audit_serving(stalled=False)
        assert good.ok and good.findings == []

    def test_sync_leak_corpus_both_directions(self):
        from deepspeed_tpu.profiling import doctor
        bad = doctor.audit_tracing(leaky=True)
        assert not bad.ok
        f = next(f for f in bad.findings if f.rule == "tracing-sync-leak")
        assert f.ident == "device-syncs"
        good = doctor.audit_tracing(leaky=False)
        assert good.ok and good.findings == []
        # the gate is the sync count; the overhead is reported, not gated
        assert good.meta["serving"]["trace_device_syncs"] == 0
        assert "serve_trace_overhead_pct" in good.meta["serving"]

    def test_gate_fails_closed_when_unpriced(self):
        from deepspeed_tpu.profiling import doctor
        r = doctor.gate_serving(doctor.diagnose_serving({}))
        assert not r.ok and r.findings[0].ident == "unpriced"

    def test_diagnose_attributes_bound_and_top2(self):
        from deepspeed_tpu.profiling import doctor
        d = doctor.diagnose_serving(doctor.simulate_serving_decomp())
        assert d["serve_bound"] == "fetch-bound"
        top2 = d["serve_phase_top2"]
        assert [p["phase"] for p in top2] == ["fetch", "decode_dispatch"]
        assert top2[0]["fraction"] > top2[1]["fraction"]
        fields = doctor.serving_fields(d)
        assert set(fields) == {"serve_bound", "serve_dominant_phase",
                               "serve_phase_top2", "serve_ms_per_token"}

    def test_corpus_registry_wiring(self):
        """Both twins ride the shared corpus registry (lint --corpus)."""
        from deepspeed_tpu.analysis.corpus import CORPUS
        assert "serving-blind-stall" in CORPUS
        assert "tracing-sync-leak" in CORPUS


# ---------------------------------------------------------------------------
# Engine end-to-end: tracing bit-parity + drain-v3 stitching
# ---------------------------------------------------------------------------

def _tiny_model():
    import jax.numpy as jnp
    from deepspeed_tpu.models import TransformerConfig, make_model
    return make_model(TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=1, num_heads=4,
        num_kv_heads=2, max_seq_len=64, position_type="rotary",
        activation="silu_glu", norm_type="rmsnorm", tie_embeddings=False,
        dtype=jnp.float32, attention_impl="xla"))


def _serving(model, params=None, **kw):
    import jax.numpy as jnp
    import deepspeed_tpu
    d = dict(max_seqs=2, block_size=16, max_model_len=64, decode_quantum=2,
             prompt_bucket=16, decode_backend="xla")
    d.update(kw)
    return deepspeed_tpu.init_serving(model, config={}, serving=d,
                                      dtype=jnp.float32, params=params)


def _load(seed=0, n=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 128, size=(int(k),)).astype(np.int32), int(m))
            for k, m in zip(rng.integers(4, 14, n), rng.integers(3, 6, n))]


class TestTracedEngineParity:
    def test_tracing_on_off_bit_identical(self):
        """The zero-sync contract: same params, same load — the traced
        engine's outputs are byte-for-byte the untraced engine's, the
        tracer self-reports zero device syncs, and the lifecycle spans
        are all present."""
        import jax
        model = _tiny_model()
        reqs = _load()
        plain = _serving(model)
        base = plain.run(list(reqs))
        params = jax.device_get(plain.engine.params)

        traced = _serving(model, params=params, request_trace=True,
                          trace_replica="rA")
        outs = traced.run(list(reqs))
        for i in base:
            np.testing.assert_array_equal(base[i], outs[i])
        tr = traced.tracer
        assert tr is not None and tr.device_syncs == 0
        names = {e["name"] for e in tr.events}
        assert {"admitted", "queue_wait", "prefill", "decode_quantum",
                "finish"} <= names
        assert all(e.get("replica") == "rA" for e in tr.events)
        # every request got one trace id, admission through finish
        per_rid = collections.defaultdict(set)
        for e in tr.events:
            per_rid[e["rid"]].add(e["trace"])
        assert len(per_rid) == len(reqs)
        assert all(len(tids) == 1 for tids in per_rid.values())

        d = traced.phase_decomposition()
        assert d["serve_rounds"] > 0 and d["serve_tokens"] > 0
        assert d["trace_armed"] == 1.0 and d["trace_device_syncs"] == 0.0

        meta = traced.obs_meta()
        assert meta["completed"] == len(reqs)
        assert Histogram.from_dict(meta["ttft_ms_hist"]).count == len(reqs)

        # pinned reset, fleet scope: every exposed counter clears — but for
        # exactly one key of stats(): ``setup`` is the ENGINE's life (what
        # it built, what that cost: ISSUE 55), not a window's, and the
        # reset that follows a warm-up must not wipe what the warm-up did
        lived = traced.stats()["setup"]
        assert lived["programs_built"] > 0 and lived["engine_init_s"] > 0.0
        traced.reset_stats()
        st = traced.stats()
        assert st["setup"] == lived
        assert st["build_ms_total"] == st["gc_ms_total"] == 0.0
        assert st["completed"] == 0.0 and "generated_tokens" not in st
        d = traced.phase_decomposition()
        assert d["serve_rounds"] == 0.0 and d["serve_tokens"] == 0.0
        assert d["serve_phase_stall_events"] == 0.0
        meta = traced.obs_meta()
        assert meta["completed"] == 0 and meta["generated_tokens"] == 0
        assert Histogram.from_dict(meta["ttft_ms_hist"]).count == 0
        assert Histogram.from_dict(meta["itl_ms_hist"]).count == 0

    def test_drain_v3_carries_trace_and_v2_interops(self, tmp_path):
        """Drain-state v3: each record carries the trace context and the
        drain marker rides it; adoption on the destination preserves the
        id. A v2 record (no "trace" key) still restores."""
        model = _tiny_model()
        src = _serving(model, request_trace=True, trace_replica="r0")
        for p, k in _load(seed=1, n=2):
            src.add_request(p, k)
        tag_dir = src.drain(str(tmp_path), tag="t0", source="r0")
        state = json.load(open(os.path.join(tag_dir, "state.json")))
        assert state["version"] == 3
        assert len(state["requests"]) == 2
        for rec in state["requests"]:
            ctx = rec["trace"]
            assert ctx["id"].startswith("r0/")
            names = [e["name"] for e in ctx["spans"]]
            assert "admitted" in names and names[-1] == "drained"

        import jax
        dst = _serving(model, params=jax.device_get(src.engine.params),
                       request_trace=True, trace_replica="r1")
        recs = state["requests"]
        recs[1] = {k: v for k, v in recs[1].items() if k != "trace"}  # v2
        rids = dst.accept_migration(recs, rng_counter=state["rng_counter"],
                                    source="r0",
                                    geometry=state["engine"])
        assert len(rids) == 2
        assert dst.tracer.trace_id(rids[0]) == state["requests"][0][
            "trace"]["id"]                     # stitched
        assert dst.tracer.trace_id(rids[1]).startswith("r1/")   # fresh
        ev_names = [e["name"] for e in dst.tracer.events
                    if e["rid"] == rids[0]]
        assert "migrated_in" in ev_names and "drained" in ev_names


# ---------------------------------------------------------------------------
# Router: fleet rollup + traced failover stitching
# ---------------------------------------------------------------------------

def _router(tmp_path, clock, **kw):
    from deepspeed_tpu.inference.router import RouterConfig, ServingRouter
    cfg = RouterConfig(store_dir=str(tmp_path / "store"),
                       drain_dir=str(tmp_path / "drains"),
                       dead_after_s=2.0, clock=clock, **kw)
    return ServingRouter(cfg)


def _drive(router, reqs, t):
    from deepspeed_tpu.inference.scheduler import AdmissionRejected
    pending = collections.deque(reqs)
    outs, rounds = {}, 0
    while pending or not router.done:
        while pending:
            p, k = pending[0]
            try:
                router.add_request(p, k)
            except AdmissionRejected:
                break
            pending.popleft()
        for r in router.step():
            outs[r.rid] = r.output
        t[0] += 1.0
        rounds += 1
        assert rounds < 200, "router test did not converge"
    return outs


@pytest.mark.slow
class TestFleetRollup:
    def test_rollup_matches_per_replica_truth_and_resets(self, tmp_path):
        import jax
        model = _tiny_model()
        t = [0.0]
        router = _router(tmp_path, clock=lambda: t[0])
        e0 = _serving(model, max_queue=4)
        e1 = _serving(model, params=jax.device_get(e0.engine.params),
                      max_queue=4)
        router.register("r0", e0)
        router.register("r1", e1)
        _drive(router, _load(seed=2, n=5), t)

        fs = router.fleet_stats()
        truth = [e0.obs_meta(), e1.obs_meta()]
        assert fs["fleet_replicas"] == 2 and fs["fleet_live"] == 2
        for key in ("completed", "cancelled", "generated_tokens"):
            assert fs[f"fleet_{key}"] == sum(m[key] for m in truth), key
        assert fs["fleet_completed"] == 5
        # merged histogram = per-replica histograms, bucket for bucket
        want = Histogram(DEFAULT_EDGES_MS)
        for m in truth:
            want.merge(Histogram.from_dict(m["ttft_ms_hist"]))
        assert fs["fleet_ttft_ms"].counts == want.counts
        assert fs["fleet_ttft_ms"].count == 5
        # gauges cover the live fleet
        assert fs["fleet_queue_depth"].count == 2
        assert fs["fleet_pool_occupancy"].count == 2

        # scrape round-trip: text exposition reconstructs the rollup
        parsed = parse_exposition(router.exposition(prefix="dstpu"))
        assert parsed["dstpu_fleet_completed"] == 5.0
        assert parsed["dstpu_fleet_ttft_ms"].counts == want.counts
        assert parsed["dstpu_fleet_live"] == 2.0

        # pinned reset at FLEET scope: every rollup counter clears
        router.reset_stats()
        fs = router.fleet_stats()
        assert fs["fleet_completed"] == 0 and fs["fleet_generated_tokens"] \
            == 0
        assert fs["fleet_ttft_ms"].count == 0
        assert fs["fleet_itl_ms"].count == 0
        assert fs["fleet_live"] == 2           # liveness is not history

    def test_traced_failover_bit_identical_and_stitched(self, tmp_path):
        """The acceptance gate: a 2-replica fleet with tracing armed,
        replica 0 killed mid-load — outputs bit-identical to a fault-free
        untraced single-replica run, and the merged Chrome trace shows
        the migrated requests' ids spanning BOTH replica process rows."""
        import jax
        from deepspeed_tpu.robustness.faults import (FaultInjector,
                                                     FaultSchedule)
        model = _tiny_model()
        reqs = _load(seed=3, n=6)
        plain = _serving(model, max_seqs=4)
        base = plain.run(list(reqs))
        params = jax.device_get(plain.engine.params)

        t = [0.0]
        router = _router(tmp_path, clock=lambda: t[0])
        e0 = _serving(model, params=params, max_queue=4, request_trace=True)
        e1 = _serving(model, params=params, max_queue=4, request_trace=True)
        router.register("r0", e0)
        router.register("r1", e1)
        # register() retags each engine's default-"r0" tracer to its
        # replica name — otherwise both streams land on one process row
        assert e0.tracer.replica == "r0" and e1.tracer.replica == "r1"
        rb_faults.install(FaultInjector(FaultSchedule([
            {"kind": "replica_kill", "at": 2, "replica": 0},
        ], seed=0)))
        outs = _drive(router, reqs, t)

        st = router.stats()
        assert st["failovers"] == 1.0 and st["migrated"] >= 1.0
        assert st["lost_requests"] == 0.0
        assert set(outs) == set(base)
        for rid in base:
            np.testing.assert_array_equal(
                base[rid], outs[rid],
                err_msg=f"request {rid} diverged under tracing")
        assert e0.tracer.device_syncs == 0 and e1.tracer.device_syncs == 0

        merged = merge_chrome_trace(
            [e0.tracer.export(), e1.tracer.export()],
            path=str(tmp_path / "fleet.json"))
        evs = [e for e in merged["traceEvents"] if e["ph"] != "M"]
        trace_pids = collections.defaultdict(set)
        for e in evs:
            trace_pids[e["args"]["trace"]].add(e["pid"])
        spanning = [tid for tid, pids in trace_pids.items()
                    if len(pids) >= 2]
        assert spanning, "no trace id spans both replica process rows"
        # the on-disk merge emitted its export event
        assert json.load(open(tmp_path / "fleet.json"))["traceEvents"]
        assert rb_events.history("trace_export")
