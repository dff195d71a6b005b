"""Serving chaos soak: a mixed continuous-batching load under every
serving-seam fault must end BIT-IDENTICAL to the fault-free run.

The schedule exercises the four injected conditions the reliability tier
exists for, in one soak:

  * ``decode_dispatch`` (fail)  — a failed quantum dispatch: recovery
    preempts every running request, rebuilds the pool, re-prefills from
    host cursors and retries the round;
  * ``pool_exhaust``            — a 2-round allocator exhaustion storm: the
    scheduler queues/preempts through it, nothing OOMs, nothing is lost;
  * ``backend_fault``           — a Pallas kernel failure mid-serve: the
    engine degrades to the XLA gather backend (``backend_degraded``) and
    keeps every sequence's tokens identical (the gather is the same math
    the kernel-parity tests pin);
  * ``decode_dispatch`` (hang)  — a hung dispatch: the round watchdog times
    it out and the same recovery path heals it;
  * ``preempt`` (round-keyed)   — a real SIGTERM: the engine drains through
    the integrity chain and a RESTARTED engine resumes the in-flight
    requests with byte-identical continuations.

Shed and deadline-miss events ride along via two canary requests (outside
the compared set), so the telemetry JSONL ends up carrying the full event
schema. Slow tier: three engine builds on interpret-mode Pallas. Runs
under tests/run_slow.sh with its own budget (SERVING_CHAOS_BUDGET).

ISSUE 12 extends the soak with the latency tier ARMED: the same fault
schedule runs with the copy-on-write prefix cache, token-budget chunked
prefill and speculative decoding all on, over a load where most prompts
share a prefix — so recoveries rebuild pools with refcounted tables in
play (the cache's references are cleared with the pool), the SIGTERM
drain serializes mid-chunk prefills and preemption re-prefills re-match
the cache on resume. The acceptance bar is the same and stricter: outputs
bit-identical to the PLAIN fault-free engine (latency features and
faults both invisible in the token stream).

ISSUE 36: the loop dispatches a round before it has fetched the one before,
so every fault after the first call finds a round in flight. The quick
tier holds one case per fault (``TestFaultsWithARoundInFlight``): the round
in flight is discarded or committed whole, never half, and the tokens are
the undisturbed run's.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.scheduler import AdmissionRejected
from deepspeed_tpu.models import TransformerConfig, make_model
from deepspeed_tpu.robustness import events as rb_events
from deepspeed_tpu.robustness import faults as rb_faults
from deepspeed_tpu.robustness.faults import FaultInjector, FaultSchedule
from deepspeed_tpu.robustness.preemption import Preempted, PreemptionHandler

N_REQUESTS = 32


@pytest.fixture(autouse=True)
def _clean_robustness_state():
    rb_faults.clear()
    rb_events.clear()
    yield
    rb_faults.clear()
    rb_events.clear()


def _model():
    # head_dim 64: paged-kernel eligible, so the soak can run FORCED pallas
    # and the backend_fault degradation ladder (pallas -> XLA gather) is
    # exercised for real (interpret mode on CPU)
    return make_model(TransformerConfig(
        vocab_size=128, hidden_size=256, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=256, position_type="rotary",
        activation="silu_glu", norm_type="rmsnorm", tie_embeddings=False,
        dtype=jnp.float32, attention_impl="xla"))


def _load():
    rng = np.random.default_rng(7)
    return [(rng.integers(0, 128, size=(int(n),)).astype(np.int32), int(k))
            for n, k in zip(rng.integers(5, 40, N_REQUESTS),
                            rng.integers(8, 15, N_REQUESTS))]


def _serving(model, params, jsonl=None, **kw):
    d = dict(max_seqs=4, block_size=16, max_model_len=128,
             decode_quantum=2, prompt_bucket=16, num_blocks=20,
             decode_backend="pallas", telemetry_jsonl=jsonl)
    d.update(kw)
    return deepspeed_tpu.init_serving(model, config={}, serving=d,
                                      dtype=jnp.float32,
                                      params=jax.device_get(params))


@pytest.mark.slow
class TestServingChaosSoak:
    def test_soak_bit_identical_to_fault_free(self, tmp_path):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        reqs = _load()

        # ---- fault-free baseline (same forced-pallas config) ----------
        srv = _serving(model, params)
        base = srv.run(list(reqs))
        assert len(base) == N_REQUESTS
        del srv

        # ---- chaos run ------------------------------------------------
        # round-indexed schedule (see module docstring); the SIGTERM at
        # round 16 drains mid-load and a fresh engine resumes
        inj = rb_faults.install(FaultInjector(FaultSchedule([
            {"kind": "decode_dispatch", "at": 2},
            {"kind": "pool_exhaust", "at": 5, "times": 2},
            {"kind": "backend_fault", "at": 8},
            {"kind": "decode_dispatch", "at": 12, "mode": "hang",
             "hang_s": 2.5},
            {"kind": "preempt", "round": 16},
        ], seed=3)))
        rb_events.clear()
        jsonl = str(tmp_path / "tel" / "serving_events.jsonl")
        drain_dir = str(tmp_path / "drain")
        handler = PreemptionHandler().install()
        outs, rounds, engines = {}, 0, []
        try:
            srv1 = _serving(model, params, jsonl=jsonl,
                            dispatch_timeout_s=1.0)
            engines.append(srv1)
            srv1.attach_preemption(handler, drain_dir)
            for p, k in reqs:
                srv1.add_request(p, k)
            resumed = False
            srv_cur = srv1
            while not srv_cur.scheduler.done:
                try:
                    for r in srv_cur.step():
                        outs[r.rid] = r.output
                    rounds += 1
                except Preempted:
                    assert not resumed, "preempted twice"
                    resumed = True
                    # the drained engine checkpointed through the
                    # integrity chain; a FRESH engine resumes the work
                    handler.reset()
                    srv2 = _serving(model, params, jsonl=jsonl,
                                    dispatch_timeout_s=1.0)
                    engines.append(srv2)
                    rids = srv2.resume(drain_dir)
                    assert rids, "nothing was in flight at the drain"
                    # canaries (outside the compared set): a shed and a
                    # deadline miss, so those events reach the JSONL too
                    srv2.scheduler.max_queue = 0
                    with pytest.raises(AdmissionRejected):
                        srv2.add_request(np.arange(4, dtype=np.int32), 4)
                    srv2.scheduler.max_queue = None
                    srv2.add_request(np.arange(4, dtype=np.int32), 4,
                                     ttft_deadline_ms=1e-3)
                    srv_cur = srv2
            assert resumed, "the SIGTERM preemption never fired"
        finally:
            handler.restore()
            rb_faults.clear()
        for srv in engines:          # requests finished before the drain
            for r in srv._finished:
                outs.setdefault(r.rid, r.output)

        # every scheduled fault actually fired
        fired = {f["kind"] for f in inj.fired}
        assert fired == {"decode_dispatch", "pool_exhaust", "backend_fault",
                         "preempt"}, fired
        modes = {f.get("mode") for f in inj.fired
                 if f["kind"] == "decode_dispatch"}
        assert modes == {"fail", "hang"}          # both dispatch shapes

        # degradation happened mid-serve and was evented; recoveries ran;
        # the soak is a REAL 40-round mixed load
        assert srv1.decode_backend == "xla"       # pallas -> gather ladder
        assert srv1.stats()["degraded"] == 1.0
        st = [e.stats() for e in engines]
        assert sum(s["recoveries"] for s in st) >= 3   # fail + hang + fault
        assert rounds >= 40, rounds

        # the canaries produced shed + deadline evidence without touching
        # the compared set
        assert srv_cur.stats()["shed"] == 1.0
        assert srv_cur.stats()["deadline_misses"] == 1.0

        # ---- the acceptance bar: BIT-IDENTICAL outputs ----------------
        assert set(outs) >= set(base)
        for rid in base:
            np.testing.assert_array_equal(
                base[rid], outs[rid],
                err_msg=f"request {rid} diverged under chaos")

        # ---- events visible in the telemetry JSONL --------------------
        types = set()
        for p in glob.glob(os.path.join(os.path.dirname(jsonl), "*")):
            with open(p) as f:
                for line in f:
                    try:
                        types.add(json.loads(line).get("type"))
                    except ValueError:
                        pass
        assert {"fault_injected", "serving_recovered", "backend_degraded",
                "serving_drained", "serving_resumed", "request_shed",
                "deadline_miss"} <= types, types


def _shared_load(n=24):
    """Mostly-shared-prefix mix: ~2/3 of the requests extend one long
    system prompt (the prefix cache's target traffic), the rest are
    unique — so the soak exercises hits, forks AND cold paths."""
    rng = np.random.default_rng(17)
    shared = rng.integers(0, 128, size=(34,)).astype(np.int32)
    reqs = []
    for i in range(n):
        if i % 3 < 2:
            p = np.concatenate([shared, rng.integers(0, 128, size=(
                int(rng.integers(2, 8)),)).astype(np.int32)])
        else:
            p = rng.integers(0, 128, size=(
                int(rng.integers(5, 30)),)).astype(np.int32)
        reqs.append((p, int(rng.integers(8, 14))))
    return reqs


@pytest.mark.slow
class TestLatencyTierChaosSoak:
    def test_soak_with_prefix_cache_and_speculation_armed(self, tmp_path):
        """ISSUE 12: the fault schedule replayed with CoW prefix cache +
        chunked prefill + speculation armed ends bit-identical to the
        PLAIN fault-free run — shared (refcounted) block tables survive
        recovery pool-rebuilds, drain/resume and preemption re-prefill."""
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        reqs = _shared_load()
        latency = dict(enable_prefix_cache=True, prefill_token_budget=48,
                       spec_tokens=2, decode_backend="auto")

        # plain fault-free baseline: no latency features, no faults — the
        # strictest possible reference (greedy parity makes the features
        # invisible; the soak proves the faults are too)
        srv = _serving(model, params, decode_backend="auto")
        base = srv.run(list(reqs))
        del srv

        inj = rb_faults.install(FaultInjector(FaultSchedule([
            {"kind": "decode_dispatch", "at": 2},
            {"kind": "pool_exhaust", "at": 5, "times": 2},
            {"kind": "decode_dispatch", "at": 9},
            {"kind": "preempt", "round": 14},
        ], seed=5)))
        rb_events.clear()
        drain_dir = str(tmp_path / "drain_lat")
        handler = PreemptionHandler().install()
        outs, engines = {}, []
        try:
            srv1 = _serving(model, params, **latency)
            engines.append(srv1)
            srv1.attach_preemption(handler, drain_dir)
            for p, k in reqs:
                srv1.add_request(p, k)
            resumed = False
            srv_cur = srv1
            while not srv_cur.scheduler.done:
                try:
                    for r in srv_cur.step():
                        outs[r.rid] = r.output
                except Preempted:
                    assert not resumed, "preempted twice"
                    resumed = True
                    handler.reset()
                    srv2 = _serving(model, params, **latency)
                    engines.append(srv2)
                    rids = srv2.resume(drain_dir)
                    assert rids, "nothing was in flight at the drain"
                    srv_cur = srv2
            assert resumed, "the SIGTERM preemption never fired"
        finally:
            handler.restore()
            rb_faults.clear()
        for srv in engines:
            for r in srv._finished:
                outs.setdefault(r.rid, r.output)

        fired = {f["kind"] for f in inj.fired}
        assert fired == {"decode_dispatch", "pool_exhaust", "preempt"}, \
            fired
        # the latency tier actually engaged: cache hits with forks on the
        # shared prompts, chunked prefills, speculation verify steps —
        # across both engines (the resumed one re-prefills via ITS cache)
        st = [e.stats() for e in engines]
        assert sum(s.get("prefix_hits", 0) for s in st) >= 6
        assert sum(s.get("cow_forks", 0) for s in st) >= 1
        assert sum(s.get("spec_steps", 0) for s in st) > 0
        assert sum(s.get("prefill_chunks", 0) for s in st) >= 1
        assert sum(s["recoveries"] for s in st) >= 2

        # the acceptance bar: BIT-IDENTICAL to the plain engine
        assert set(outs) >= set(base)
        for rid in base:
            np.testing.assert_array_equal(
                base[rid], outs[rid],
                err_msg=f"request {rid} diverged under latency-tier chaos")
        # refcount hygiene after the storm: every surviving engine's held
        # blocks are exactly its cache's (nothing leaked through the
        # recoveries and the drain)
        for e in engines:
            if e.scheduler.done:
                assert e.allocator.used_blocks == \
                    e._prefix_cache.held_blocks


class TestFaultsWithARoundInFlight:
    """Each fault hits while the round before is still unfetched; what the
    host holds stays authoritative and the outputs are the undisturbed
    run's. Quick tier: a short load, one fault a case."""

    LOAD = [(5, 14), (23, 11), (12, 17), (30, 9), (8, 13)]

    def _reqs(self):
        rng = np.random.default_rng(36)
        return [(rng.integers(0, 128, size=(n,)).astype(np.int32), k)
                for n, k in self.LOAD]

    @pytest.mark.parametrize("fault,serving", [
        ({"kind": "decode_dispatch", "at": 2, "mode": "hang", "hang_s": 2.0},
         dict(dispatch_timeout_s=0.6, decode_backend="xla")),
        ({"kind": "backend_fault", "at": 2}, dict(decode_backend="pallas")),
    ], ids=["dispatch-hang", "backend-fault"])
    def test_recovery_discards_the_round_in_flight(self, fault, serving):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        reqs = self._reqs()
        base = _serving(model, params, max_seqs=2, num_blocks=None,
                        **serving).run(list(reqs))
        inj = rb_faults.install(FaultInjector(FaultSchedule([fault], seed=1)))
        srv = _serving(model, params, max_seqs=2, num_blocks=None, **serving)
        found = []
        recover = srv._recover

        def spy(reason):
            rec = srv._inflight
            found.append(rec is not None and len(rec.live()))
            recover(reason)
            # discarded WHOLE: nothing of it is left to commit, every
            # request stands at the tokens the host held
            assert srv._inflight is None and not srv.scheduler.running
            assert all(r.inflight_rows == 0 and r.cached_rows == 0
                       for r in srv.scheduler.waiting)

        srv._recover = spy
        outs = srv.run(list(reqs))
        assert [f["kind"] for f in inj.fired] == [fault["kind"]]
        assert found and found[0] > 0            # a round WAS in flight
        if fault["kind"] == "backend_fault":
            assert srv.decode_backend == "xla"
            assert rb_events.history("backend_degraded")
        assert srv.stats()["recoveries"] == len(found)
        for i in base:
            np.testing.assert_array_equal(base[i], outs[i],
                                          err_msg=f"request {i}")
        assert srv.close()

    def test_a_preemption_drops_the_victims_tokens_in_flight(self):
        """A pool below full residency: growth preempts the newest request
        while its quantum's last step is still on the device's queue. That
        token is dropped when it arrives (never appended to a context that
        was re-prefilled without it) and computed again."""
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(4)
        reqs = [(rng.integers(0, 128, size=(26,)).astype(np.int32), 40)
                for _ in range(4)]
        base = _serving(model, params, max_seqs=2, num_blocks=None,
                        decode_backend="xla").run(list(reqs))
        srv = _serving(model, params, max_seqs=2, num_blocks=9,
                       decode_backend="xla")
        hit = []
        preempt = srv.scheduler.preempt

        def spy(req):
            rec = srv._inflight
            hit.append((rec is not None
                        and req in [r for r, _ in rec.live()],
                        req.inflight_rows, len(req.generated)))
            return preempt(req)

        srv.scheduler.preempt = spy
        outs = srv.run(list(reqs))
        st = srv.stats()
        assert st["preemptions"] >= 1 and st["recoveries"] == 0
        # hit WITH steps in flight: the victim restarts from what the host
        # held, the step behind it is computed again
        assert hit and all(inflight and rows > 0 for inflight, rows, _ in hit)
        for i in base:
            np.testing.assert_array_equal(base[i], outs[i],
                                          err_msg=f"request {i}")
        assert srv.allocator.used_blocks == 0 and srv._inflight is None
