"""One span vocabulary on the profiler's clock (ISSUE 23).

``telemetry.tracing.span`` is the only way the program opens a host span: a
``jax.profiler.TraceAnnotation`` named ``ds:<layer>.<phase>`` plus the
elapsed seconds. Pinned here, with a REAL profiler session on the CPU
backend (the host plane works without a chip):

  - the primitive lands in the trace, returns elapsed time, and nests;
  - a serving round is one ``ds:serve.round`` holding each phase once;
  - ``phase_decomposition()`` sums the whole stats window, not a ring;
  - a round leaves ONE record (ISSUE 37): what it was, its phases, the
    probe, its collections, the empty interval it ended — and the window
    keeps the slowest with their followers, and how long the engine held
    nothing (``ds:serve.drained`` -> ``ds:serve.submit``);
  - the request lifecycle (``admit_t``, ``max_gap_ms``) and the six
    ``stats()`` keys, a preempted request included, and ``admit_t``
    agreeing with ``RequestTracer``'s ``queue_wait`` span;
  - the train loop's host phases are in the trace WITHOUT telemetry;
  - set-up says where it went (ISSUE 55): ``ds:setup.weights|pools|state``
    and a ``ds:setup.program`` around every build, ONE record a program in
    ``stats()["setup"]`` from JAX's own compile events through one
    process-wide listener, kept across ``reset_stats()``, and a round's
    ``build_ms``.
"""

import collections
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, make_model
from deepspeed_tpu.telemetry import RequestTracer, StepTracer, span

SERVE_PHASES = ["ds:serve.schedule", "ds:serve.housekeeping",
                "ds:serve.prefill_dispatch", "ds:serve.decode_dispatch",
                "ds:serve.fetch", "ds:serve.commit"]


class _Session:
    """A profiler session; afterwards ``.spans`` holds every ``ds:`` event
    of the host plane as (name, start_ns, end_ns, stats)."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "trace")
        self.spans = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                            recursive=True)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("ds:"):
                        self.spans.append((e.name, e.start_ns,
                                           e.start_ns + e.duration_ns,
                                           dict(e.stats)))
        self.spans.sort(key=lambda s: s[1])
        return False

    def named(self, name):
        return [s for s in self.spans if s[0] == name]


def _tiny_model(layers=1, seq=64):
    return make_model(TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=layers, num_heads=4,
        num_kv_heads=2, max_seq_len=seq, position_type="rotary",
        activation="silu_glu", norm_type="rmsnorm", tie_embeddings=False,
        dtype=jnp.float32, attention_impl="xla"))


def _serving(model=None, **kw):
    d = dict(max_seqs=2, block_size=16, max_model_len=64, decode_quantum=2,
             prompt_bucket=16, decode_backend="xla")
    d.update(kw)
    return deepspeed_tpu.init_serving(model or _tiny_model(), config={},
                                      serving=d, dtype=jnp.float32)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 128, (n,)).astype(np.int32)


def _add(srv, n, new, seed=0):
    """add_request, and the Request the scheduler made of it."""
    srv.add_request(_prompt(n, seed), new)
    return srv.scheduler.waiting[-1]


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

class TestSpanPrimitive:
    def test_returns_elapsed_time_without_a_session(self):
        with span("ds:test.outer") as sp:
            time.sleep(0.01)
        assert 0.01 <= sp.seconds < 1.0
        assert sp.t0 <= time.perf_counter() - sp.seconds

    def test_opens_a_trace_annotation_and_nests(self, tmp_path):
        with _Session(tmp_path) as ses:
            with span("ds:test.outer", index=3) as outer:
                with span("ds:test.inner") as inner:
                    time.sleep(0.005)
                outer.note(tokens=17)
        (o,), (i,) = ses.named("ds:test.outer"), ses.named("ds:test.inner")
        assert o[1] <= i[1] and i[2] <= o[2]            # nested, one clock
        assert o[3] == {"index": 3, "tokens": 17} and i[3] == {}
        # the profiler's duration and the caller's seconds are one span
        assert (i[2] - i[1]) / 1e9 == pytest.approx(inner.seconds, abs=2e-3)
        assert outer.seconds >= inner.seconds >= 0.005

    def test_an_exception_passes_through_and_still_times(self):
        with pytest.raises(KeyError):
            with span("ds:test.raises") as sp:
                raise KeyError("x")
        assert sp.seconds >= 0.0

    def test_step_tracer_times_through_it(self, tmp_path):
        tr = StepTracer()
        with _Session(tmp_path) as ses:
            with tr.span("dispatch"):
                time.sleep(0.002)
        (ev,) = list(tr.events)
        assert ev["name"] == "dispatch" and ev["dur"] >= 2000.0
        assert tr.drain_window()["dispatch_count"] == 1
        (s,) = ses.named("ds:train.dispatch")
        assert (s[2] - s[1]) / 1e3 == pytest.approx(ev["dur"], abs=2000.0)

    def test_request_tracer_times_through_it(self, tmp_path):
        tr = RequestTracer(replica="rA")
        tr.begin(7)
        with _Session(tmp_path) as ses:
            with tr.span(7, "prefill", tokens=5):
                time.sleep(0.002)
        (ev,) = [e for e in tr.events if e["name"] == "prefill"]
        assert ev["dur"] >= 2000.0 and ev["args"] == {"tokens": 5}
        assert abs(ev["ts"] / 1e6 - time.time()) < 60.0  # unix-epoch anchored
        (s,) = ses.named("ds:request.prefill")
        assert s[3] == {"rid": 7}


# ---------------------------------------------------------------------------
# the serving round
# ---------------------------------------------------------------------------

class TestServingRoundSpans:
    def test_each_phase_once_per_round_inside_one_round_span(self, tmp_path):
        srv = _serving()
        srv.run([(_prompt(9), 4)])                  # compiles, off the trace
        srv.reset_stats()
        for k in (5, 11, 7):
            srv.add_request(_prompt(k, seed=k), 5)
        rounds = 0
        with _Session(tmp_path) as ses:
            while srv.scheduler.running or srv.scheduler.num_waiting:
                srv.step()
                rounds += 1
        outer = ses.named("ds:serve.round")
        assert len(outer) == rounds >= 3
        assert [o[3]["index"] for o in outer] == list(range(rounds))
        assert sum(o[3]["tokens"] for o in outer) == 15
        for name in SERVE_PHASES:
            assert len(ses.named(name)) == rounds, name
        for _, lo, hi, _ in outer:
            inside = [s for s in ses.spans if lo <= s[1] and s[2] <= hi
                      and s[0] != "ds:serve.round"]
            assert [s[0] for s in inside] == SERVE_PHASES  # once each, in order
            assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
        # the same rounds, as the host totals the doctor reads
        d = srv.phase_decomposition()
        assert d["serve_rounds"] == rounds and d["serve_tokens"] == 15.0
        phases = sum(d[f"serve_{p}_ms"] for p in
                     ("schedule", "housekeeping", "prefill_dispatch",
                      "decode_dispatch", "fetch", "commit"))
        assert 0.0 < phases <= d["serve_round_ms"]
        traced_ms = sum(o[2] - o[1] for o in outer) / 1e6
        assert d["serve_round_ms"] == pytest.approx(traced_ms, rel=0.05,
                                                    abs=2.0)
        srv.close()

    def test_a_round_with_nothing_running_still_closes_its_span(self, tmp_path):
        srv = _serving()
        with _Session(tmp_path) as ses:
            assert srv.step() == []
        assert len(ses.named("ds:serve.round")) == 1
        assert len(ses.named("ds:serve.schedule")) == 1
        assert ses.named("ds:serve.fetch") == []
        assert srv.phase_decomposition()["serve_rounds"] == 1.0
        srv.close()

    def test_fetch_runs_under_the_watchdog_and_keeps_its_span(self, tmp_path):
        srv = _serving(dispatch_timeout_s=30.0)
        srv.run([(_prompt(9), 4)])                  # arms the watchdog
        srv.add_request(_prompt(6), 4)
        with _Session(tmp_path) as ses:
            srv.step()
        assert len(ses.named("ds:serve.decode_dispatch")) == 1
        assert len(ses.named("ds:serve.fetch")) == 1
        assert srv.close() is True


class TestHybridRound:
    """A model with recurrent blocks adds no phase to a round: a prefill is
    a whole prompt from a zero state that overwrites the slot's rows of the
    state pool, so a slot given again needs no separate reset dispatch."""

    def _hybrid(self):
        import sys
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        sys.path.insert(0, root)
        from benchmark.families.nemotron_h import TOY
        from deepspeed_tpu.models.hf_import import hf_config_to_transformer
        hf = {"model_type": "nemotron_h", "n_shared_experts": 1,
              "routed_scaling_factor": 2.5, "max_position_embeddings": 256,
              "num_experts_per_tok": 2, **TOY}
        model = make_model(hf_config_to_transformer(hf, dtype=jnp.float32))
        return deepspeed_tpu.init_serving(
            model, config={"kv_cache_bits": 0}, dtype=jnp.float32,
            serving=dict(max_seqs=2, block_size=16, max_model_len=128,
                         decode_quantum=4, prompt_bucket=16))

    def test_a_round_holds_the_phases_every_model_has(self, tmp_path):
        srv = self._hybrid()
        srv.run([(_prompt(9), 4)])                  # compiles, off the trace
        for k in (5, 11, 7):                        # one slot is given again
            srv.add_request(_prompt(k, seed=k), 5)
        with _Session(tmp_path) as ses:
            while srv.scheduler.running or srv.scheduler.num_waiting:
                srv.step()
        rounds = ses.named("ds:serve.round")
        assert rounds
        for r in rounds:
            inside = [s[0] for s in ses.spans
                      if s[0] != "ds:serve.round"
                      and r[1] <= s[1] and s[2] <= r[2]]
            assert inside == SERVE_PHASES
        assert {s[0] for s in ses.spans} == set(SERVE_PHASES) | {
            "ds:serve.round", "ds:serve.drained"}
        srv.close()

    def test_stats_tell_the_two_kinds_of_state_apart(self):
        srv = self._hybrid()
        st = srv.stats()
        assert st["state_pool_bytes"] > 0 and st["state_slots_live"] == 0
        assert st["state_pool_bytes"] + st["kv_pool_bytes"] == st["pool_bytes"]
        srv.close()
        srv = _serving()
        st = srv.stats()
        assert "state_pool_bytes" not in st and "state_slots_live" not in st
        assert st["kv_pool_bytes"] == st["pool_bytes"]
        srv.close()


def _record(round_ms=4.0, **over):
    """A round's record as ``step()`` hands it to ``_note_phases``."""
    e = {"index": 0, "t_s": 0.0, "running_before": 2, "prefills": 0,
         "prefill_programs": 0, "prefill_tokens": 0, "shape": (2, 2),
         "ahead_covered": True,
         "empty_before_ms": 0.0, "gc_ms": 0.0, "build_ms": 0.0,
         "schedule_ms": 0.1, "housekeeping_ms": 0.1, "prefill_ms": 0.2,
         "decode_ms": 0.4, "fetch_ms": 3.0, "commit_ms": 0.2,
         "round_ms": round_ms, "tokens": 8.0}
    e.update(over)
    return e


RECORD_KEYS = set(_record())


class TestPhaseTotals:
    def _rig(self):
        from deepspeed_tpu.inference.serving import ServingEngine as SE

        class Rig:
            _STALL_MIN_ROUND_MS = SE._STALL_MIN_ROUND_MS
            _STALL_FRACTION = SE._STALL_FRACTION
            _RING_ROUNDS, _SLOW_ROUNDS = SE._RING_ROUNDS, SE._SLOW_ROUNDS
            _PHASES, _PHASE_OUT = SE._PHASES, SE._PHASE_OUT
            _reset_round_records = SE._reset_round_records
            _decode_dominated = staticmethod(SE._decode_dominated)
            _note_phases = SE._note_phases
            phase_decomposition = SE.phase_decomposition

            def __init__(self):
                self._reset_round_records()
                self._quantum_warm = True
                self._tracer = None
        return Rig()

    def test_sums_more_rounds_than_the_ring_holds(self):
        rig = self._rig()
        for _ in range(600):
            rig._note_phases(_record())
        assert len(rig._phases) == 512              # the one bounded store
        d = rig.phase_decomposition()
        assert d["serve_rounds"] == 600.0 and d["serve_tokens"] == 4800.0
        assert d["serve_round_ms"] == pytest.approx(2400.0)
        assert d["serve_fetch_ms"] == pytest.approx(1800.0)
        assert d["serve_prefill_dispatch_ms"] == pytest.approx(120.0)
        assert d["serve_decode_dispatch_ms"] == pytest.approx(240.0)

    def test_reset_stats_clears_the_totals(self):
        srv = _serving()
        srv.run([(_prompt(9), 4)])
        assert srv.phase_decomposition()["serve_round_ms"] > 0.0
        before = srv.stats()
        assert before["build_ms_total"] > 0.0       # the run built programs
        srv.reset_stats()
        d = srv.phase_decomposition()
        assert d["serve_rounds"] == 0.0
        assert all(d[k] == 0.0 for k in srv._PHASE_OUT.values())
        after = srv.stats()
        assert after["build_ms_total"] == 0.0 == after["gc_ms_total"]
        # exactly ONE key is exempt: ``setup`` is the engine's life — what it
        # built and what that cost — and a window's reset must not wipe what
        # the warm-up before it recorded (TestSetupRecord)
        assert after["setup"] == before["setup"]
        assert after["setup"]["programs_built"] > 0
        srv.close()


# ---------------------------------------------------------------------------
# the round's record (ISSUE 37)
# ---------------------------------------------------------------------------

def _drain(srv):
    """Step until the engine holds nothing; the records of those rounds."""
    n0 = srv._rounds
    while not srv.scheduler.done or srv._inflight is not None:
        srv.step()
    return list(srv._phases)[n0 - srv._rounds:] if srv._rounds > n0 else []


class _Probe:
    """Stands for the newest array on the device's queue."""

    def __init__(self, ready):
        self.ready, self.asked = ready, 0

    def is_ready(self):
        self.asked += 1
        return self.ready


def _stub_probe(monkeypatch, srv, probe):
    real = srv._dispatch_round
    monkeypatch.setattr(
        srv, "_dispatch_round", lambda spec, t0, newest=None: real(
            spec, t0, newest if newest is None else probe))


class TestRoundRecord:
    def test_every_field_on_every_kind_of_round(self):
        srv = _serving(decode_quantum=4)
        srv.add_request(_prompt(9), 12)
        srv.step()                                  # the window's first
        srv.step()                                  # a plain round
        srv.add_request(_prompt(5, seed=1), 6)
        srv.step()                                  # one with an admission
        first, plain, admit = list(srv._phases)
        for rec in (first, plain, admit):
            assert set(rec) == RECORD_KEYS
        assert [r["index"] for r in (first, plain, admit)] == [0, 1, 2]
        assert 0.0 <= first["t_s"] < plain["t_s"] < admit["t_s"]
        assert (first["running_before"], first["prefills"],
                first["prefill_programs"], first["prefill_tokens"]) == (
                    0, 1, 1, 16)
        assert first["ahead_covered"] is None       # nothing was in flight
        assert (plain["running_before"], plain["prefills"],
                plain["prefill_programs"], plain["prefill_tokens"]) == (
                    1, 0, 0, 0)
        assert plain["shape"] == (2, 2) and plain["ahead_covered"] in (
            True, False)
        assert (admit["running_before"], admit["prefills"],
                admit["prefill_tokens"]) == (1, 1, 16)
        assert admit["ahead_covered"] in (True, False)
        assert all(r["gc_ms"] >= 0.0 and r["empty_before_ms"] == 0.0
                   for r in (first, plain, admit))
        assert not srv._decode_dominated(first)
        assert srv._decode_dominated(plain)
        assert not srv._decode_dominated(admit)     # 1 prompt, 1 decoding
        _drain(srv)
        # the empty engine's early return leaves a whole record too
        assert srv.step() == []
        empty = srv._phases[-1]
        assert set(empty) == RECORD_KEYS
        assert (empty["shape"], empty["ahead_covered"], empty["prefills"],
                empty["running_before"]) == (None, None, 0, 0)
        assert not srv._decode_dominated(empty)
        srv.close()

    def test_a_speculation_round_has_no_probe(self):
        srv = _serving(spec_tokens=2)
        srv.add_request(_prompt(9), 8)
        recs = _drain(srv)
        assert len(recs) >= 2 and srv.stats()["spec_steps"] >= 1
        for rec in recs:
            assert set(rec) == RECORD_KEYS and rec["ahead_covered"] is None
        assert recs[1]["shape"] == (2, 4)           # the full tables
        st = srv.stats()
        assert st["ahead_covered_rounds"] == st["ahead_dry_rounds"] == 0.0
        srv.close()

    @pytest.mark.parametrize("ready", [False, True])
    def test_the_probe_is_counted_both_ways(self, monkeypatch, ready):
        """Not ready: the chip still had work when the host came to issue
        the round's first step. What a CPU backend's timing would say
        decides nothing here: the array is a stub."""
        srv = _serving(decode_quantum=4)
        probe = _Probe(ready)
        _stub_probe(monkeypatch, srv, probe)
        srv.add_request(_prompt(9), 14)
        recs = _drain(srv)
        ahead = [r for r in recs if r["ahead_covered"] is not None]
        assert recs[0]["ahead_covered"] is None     # an empty engine before
        assert len(ahead) >= 2 and probe.asked == len(ahead)  # once a round
        assert all(r["ahead_covered"] is (not ready) for r in ahead)
        st = srv.stats()
        assert st["rounds_ahead"] == len(ahead)
        assert st["ahead_dry_rounds" if ready
                  else "ahead_covered_rounds"] == len(ahead)
        assert st["ahead_covered_rounds" if ready
                  else "ahead_dry_rounds"] == 0.0
        srv.reset_stats()
        st = srv.stats()
        assert st["ahead_covered_rounds"] == st["ahead_dry_rounds"] == 0.0
        srv.close()

    def test_the_probe_asks_the_last_prefills_first_token(self, monkeypatch):
        """With steps in flight AND a prompt dispatched in this call, the
        newest array on the queue is that prompt's first token; without a
        prompt, the stacked tokens of the steps in flight."""
        srv = _serving(decode_quantum=4)
        asked = []
        real = srv._dispatch_round

        def spy(spec, t0, newest=None):
            asked.append(newest)
            return real(spec, t0, newest)
        monkeypatch.setattr(srv, "_dispatch_round", spy)
        srv.add_request(_prompt(9), 12)
        srv.step()
        tail = srv._inflight.tail[0]
        srv.step()
        srv.add_request(_prompt(5, seed=1), 6)
        late = srv.scheduler.waiting[-1]
        srv.step()
        assert asked[0] is None and asked[1] is tail
        assert asked[2].shape == () and late.first_token_t is not None
        assert hasattr(asked[1], "is_ready") and hasattr(asked[2], "is_ready")
        _drain(srv)
        srv.close()


class TestSlowRounds:
    def test_keeps_the_slowest_with_their_followers(self):
        """Twelve rounds of a warm engine are made slow through the
        dispatch seam (a hang with no watchdog armed is a sleep): the
        window keeps the eight slowest, slowest first, each with the
        record of the round that followed it."""
        from deepspeed_tpu.robustness import faults as rb_faults
        from deepspeed_tpu.robustness.faults import (FaultInjector,
                                                     FaultSchedule)
        srv = _serving(decode_quantum=2, max_model_len=128,
                       model=_tiny_model(seq=128))
        srv.run([(_prompt(9), 100)])                # every table width
        srv.reset_stats()
        # four short sleeps and eight long ones, far enough apart that a
        # loaded host's jitter cannot reorder the two groups
        sleeps = {3 + 2 * i: 0.010 if i % 3 == 0 else 0.100 + 0.005 * i
                  for i in range(12)}
        rb_faults.clear()
        rb_faults.install(FaultInjector(FaultSchedule(
            [{"kind": "decode_dispatch", "at": at, "mode": "hang",
              "hang_s": s} for at, s in sleeps.items()], seed=0)))
        try:
            srv.add_request(_prompt(9), 70)
            srv.add_request(_prompt(7, seed=1), 70)
            recs = _drain(srv)
        finally:
            rb_faults.clear()
        st = srv.stats()
        pairs = st["slow_rounds"]
        assert len(pairs) == 8
        by_ms = sorted((r for r in recs if srv._decode_dominated(r)),
                       key=lambda r: -r["round_ms"])
        assert [p[0]["index"] for p in pairs] == [r["index"]
                                                  for r in by_ms[:8]]
        # ... which are the eight long sleeps
        assert {p[0]["index"] for p in pairs} == {
            at for at, s in sleeps.items() if s >= 0.1}
        for rec, nxt in pairs:
            assert set(rec) == set(nxt) == RECORD_KEYS
            assert nxt["index"] == rec["index"] + 1
            assert rec["decode_ms"] >= 1e3 * sleeps[rec["index"]]
        assert st["round_ms_max"] == pairs[0][0]["round_ms"]
        assert st["phase_ms_max"]["decode"] == max(
            r["decode_ms"] for r in by_ms)
        assert st["round_ms_median"] < st["round_ms_max"] / 3
        assert set(st["phase_ms_max"]) == {"schedule", "housekeeping",
                                           "prefill", "decode", "fetch",
                                           "commit"}
        import json
        json.dumps(st["slow_rounds"])               # rides out in a run's JSON
        srv.reset_stats()
        st = srv.stats()
        assert st["slow_rounds"] == [] and st["gc_ms_total"] == 0.0
        assert "round_ms_max" not in st and "phase_ms_max" not in st
        srv.close()

    def test_the_slowest_round_waits_for_its_follower(self):
        rig = TestPhaseTotals()._rig()
        rig._note_phases(_record(5.5, index=0))
        assert rig._slow == [[_record(5.5, index=0), None]]
        rig._note_phases(_record(9.0, index=1, prefills=2))  # not dominated
        assert [p[0]["index"] for p in rig._slow] == [0]
        assert rig._slow[0][1]["index"] == 1        # ... but a follower
        assert rig._phase_max["round_ms"] == 5.5
        for i in range(2, 12):
            rig._note_phases(_record(float(i), index=i, gc_ms=0.5))
        assert [p[0]["index"] for p in rig._slow] == [11, 10, 9, 8, 7, 6, 0,
                                                      5]
        assert rig._slow[0][1] is None and rig._slow[1][1]["index"] == 11
        assert rig._gc_ms_total == pytest.approx(5.0)


class TestGcClock:
    def test_one_hook_times_every_collection(self):
        import gc
        from deepspeed_tpu.inference import serving
        a, b = _serving(), _serving()
        assert a._gc is b._gc is serving._GC_CLOCK
        assert gc.callbacks.count(serving._GC_CLOCK) == 1
        before = a._gc.seconds
        gc.collect()
        assert a._gc.seconds > before
        a.close(), b.close()

    def test_a_collection_inside_a_round_is_in_its_record(self, monkeypatch):
        import gc
        srv = _serving()
        real = srv._land

        def land(*args):
            gc.collect()
            return real(*args)
        monkeypatch.setattr(srv, "_land", land)
        srv.add_request(_prompt(9), 4)
        recs = _drain(srv)
        assert all(0.0 < r["gc_ms"] < r["round_ms"] for r in recs)
        assert srv.stats()["gc_ms_total"] == pytest.approx(
            sum(r["gc_ms"] for r in recs))
        srv.close()


class TestEmptyEngine:
    def test_seconds_the_engine_held_nothing(self):
        srv = _serving()
        srv.run([(_prompt(9), 4)])                  # warm; ends empty
        srv.reset_stats()
        time.sleep(0.02)                            # before the window
        srv.add_request(_prompt(5), 4)
        st = srv.stats()
        assert st["engine_empty_s"] == 0.0 and st["stats_window_s"] >= 0.0
        recs = _drain(srv)
        assert recs[0]["empty_before_ms"] == 0.0
        assert srv._empty_since is not None
        time.sleep(0.05)
        srv.add_request(_prompt(6, seed=1), 4)      # add -> drain -> wait -> add
        assert srv._empty_since is None
        closed = srv.stats()["engine_empty_s"]
        assert 0.05 <= closed < 5.0
        recs = _drain(srv)
        assert recs[0]["empty_before_ms"] == pytest.approx(closed * 1e3)
        assert all(r["empty_before_ms"] == 0.0 for r in recs[1:])
        time.sleep(0.03)                            # an interval still open
        st = srv.stats()
        assert st["engine_empty_s"] >= closed + 0.03
        assert st["engine_empty_s"] < st["stats_window_s"]
        assert srv.step() == []                     # does not stamp again
        assert srv.stats()["engine_empty_s"] >= st["engine_empty_s"]
        srv.reset_stats()
        st = srv.stats()
        assert st["engine_empty_s"] == 0.0 == st["stats_window_s"]
        srv.close()

    def test_a_request_that_came_by_another_door_ends_the_interval(self):
        srv = _serving()
        srv.add_request(_prompt(5), 3)
        _drain(srv)
        time.sleep(0.02)
        # not add_request: the scheduler directly, as resume / migration do
        srv.scheduler.submit(_prompt(6, seed=1), 3)
        recs = _drain(srv)
        assert recs[0]["empty_before_ms"] >= 20.0
        assert srv.stats()["engine_empty_s"] >= 0.02
        srv.close()

    def test_drained_and_submit_on_the_profilers_clock(self, tmp_path):
        srv = _serving()
        srv.run([(_prompt(9), 4)])                  # compiles, off the trace
        srv.reset_stats()
        with _Session(tmp_path) as ses:
            srv.add_request(_prompt(5), 4)
            srv.add_request(_prompt(7, seed=1), 4)
            _drain(srv)
            time.sleep(0.03)
            srv.add_request(_prompt(6, seed=2), 3)
            _drain(srv)
            with pytest.raises(ValueError):
                srv.add_request(_prompt(6), 0)      # refused inside the span
        submits, drained = ses.named("ds:serve.submit"), ses.named(
            "ds:serve.drained")
        rounds = ses.named("ds:serve.round")
        assert len(submits) == 4 and len(drained) == 2
        # every span of the session is either inside a round or one of the
        # two top-level names: none overlaps another, none is left open
        top = sorted(submits + drained + rounds, key=lambda x: x[1])
        assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
        for x in ses.spans:
            if x not in top:
                assert any(r[1] <= x[1] and x[2] <= r[2] for r in rounds)
        # a drained marker follows its round at once; the empty interval
        # is [its end, the next submit's start]
        for d in drained:
            r = max((r for r in rounds if r[2] <= d[1]), key=lambda r: r[2])
            assert d[1] - r[2] < 5e6 and d[2] - d[1] < 1e6
        gap_ns = submits[2][1] - drained[0][2]
        assert gap_ns / 1e9 == pytest.approx(
            srv.stats()["engine_empty_s"] - (
                time.perf_counter() - srv._empty_since), abs=5e-3)
        assert gap_ns >= 0.03e9
        srv.close()

    def test_the_vocabulary_lists_the_two_names(self):
        from deepspeed_tpu.telemetry import tracing
        assert "``ds:serve.submit``" in tracing.__doc__
        assert "``ds:serve.drained``" in tracing.__doc__


# ---------------------------------------------------------------------------
# set-up: spans, ONE build record a program, the round's build_ms (ISSUE 55)
# ---------------------------------------------------------------------------

PROGRAM_KEYS = {"kind", "shape", "name", "trace_s", "lower_s",
                "compile_or_load_s", "cache_load_s", "cache_hit", "builds",
                "wall_s", "built_at_s", "round"}
SETUP_KEYS = {"engine_init_s", "weights_s", "pools_s", "init_build_s",
              "programs", "programs_built", "trace_lower_s",
              "compile_or_load_s", "overlap_s", "cache_hits",
              "built_after_first_reset"}


def _programs(srv, kind=None):
    return [r for r in srv.stats()["setup"]["programs"]
            if kind is None or r["kind"] == kind]


class TestSetupRecord:
    def test_spans_on_the_profilers_clock_with_kind_and_shape(self, tmp_path):
        """A whole cold start under a session: the weights, the pool, and a
        ``ds:setup.program`` around every build — a step shape twice under
        ONE key, its lowering on the worker thread and its compile on the
        caller's."""
        with _Session(tmp_path) as ses:
            srv = _serving(_tiny_model(seq=128), max_model_len=128)
            srv.run([(_prompt(9), 4), (_prompt(20, seed=1), 4)])
        (w,), (p,) = ses.named("ds:setup.weights"), ses.named("ds:setup.pools")
        assert w[2] <= p[1]                         # one clock, in order
        progs = ses.named("ds:setup.program")
        assert all(set(x[3]) == {"kind", "shape"} for x in progs)
        # (the profile reads an argument that looks like a number as one)
        by_key = collections.Counter((x[3]["kind"], str(x[3]["shape"]))
                                     for x in progs)
        shapes = [f"{S}x{W}" for S, W in srv._step_shapes()]
        assert len(shapes) >= 2
        assert by_key == {("prefill", "16"): 1, ("prefill", "32"): 1,
                          **{("step", sh): 2 for sh in shapes}}
        # every build of a round lies inside that round's span
        rounds = ses.named("ds:serve.round")
        for x in progs:
            assert any(r[1] <= x[1] and x[2] <= r[2] for r in rounds)
        # the record agrees with the spans: their seconds are its wall_s
        for rec in _programs(srv, "step") + _programs(srv, "prefill"):
            mine = [x for x in progs if (x[3]["kind"], str(x[3]["shape"]))
                    == (rec["kind"], rec["shape"])]
            assert sum(x[2] - x[1] for x in mine) / 1e9 == pytest.approx(
                rec["wall_s"], rel=0.15, abs=0.03)
        srv.close()

    def test_every_program_built_is_in_the_record_once(self):
        srv = _serving(_tiny_model(seq=128), max_model_len=128)
        srv.run([(_prompt(9), 4), (_prompt(20, seed=1), 4)])
        setup = srv.stats()["setup"]
        assert set(setup) == SETUP_KEYS
        recs = setup["programs"]
        assert all(set(r) == PROGRAM_KEYS for r in recs)
        named = [(r["kind"], r["shape"]) for r in recs if r["kind"] != "other"]
        assert sorted(named) == sorted(
            [("prefill", "16"), ("prefill", "32")]
            + [("step", f"{S}x{W}") for S, W in srv._step_shapes()])
        assert len(set(named)) == len(named)
        for r in recs:
            if r["kind"] == "other":
                continue
            # lowered on the worker thread, compiled here: ONE record, one
            # build, each of JAX's three events in it
            assert r["builds"] == 1, r
            assert r["trace_s"] > 0.0 and r["lower_s"] > 0.0
            assert r["compile_or_load_s"] > 0.0
            assert r["name"] == ("step" if r["kind"] == "step" else "prefill")
            # tracing reports the jitted functions a program calls inside
            # the program's own time: counted once, the three fit the spans
            assert r["trace_s"] + r["lower_s"] + r["compile_or_load_s"] \
                <= r["wall_s"] + 1e-3
            assert r["round"] == 0 and r["built_at_s"] > 0.0
        # what nobody named (the pool's init, the scatter of a first token:
        # whatever this process had not built before) goes by function name
        assert all(r["shape"] == r["name"] for r in recs
                   if r["kind"] == "other")
        assert [r["built_at_s"] for r in recs] == sorted(
            r["built_at_s"] for r in recs)
        # the sums are over the records
        assert setup["programs_built"] == sum(r["builds"] for r in recs)
        assert setup["trace_lower_s"] == pytest.approx(
            sum(r["trace_s"] + r["lower_s"] for r in recs))
        assert setup["compile_or_load_s"] == pytest.approx(
            sum(r["compile_or_load_s"] for r in recs))
        assert setup["cache_hits"] == 0             # the suite has no cache
        # a shape's lowering beside another's compile: twice in the records
        assert 0.0 <= setup["overlap_s"] < setup["trace_lower_s"]
        # the constructors: weights and pool inside them, and the programs
        # they built (the init program, the pool's) counted in both
        assert 0.0 < setup["weights_s"] + setup["pools_s"] \
            <= setup["engine_init_s"]
        assert 0.0 <= setup["init_build_s"] <= setup["engine_init_s"]
        assert setup["weights_s"] == srv.engine.setup["weights_s"]
        import json
        json.dumps(setup)                           # rides out in a run's JSON
        srv.close()

    def test_the_record_survives_reset_stats(self):
        srv = _serving()
        srv.run([(_prompt(9), 4)])                  # the warm-up
        before = srv.stats()["setup"]
        assert before["built_after_first_reset"] == 0
        srv.reset_stats()
        assert srv.stats()["setup"] == before
        srv.run([(_prompt(20, seed=1), 4)])         # a bucket it did not warm
        srv.reset_stats()                           # only the FIRST is marked
        after = srv.stats()["setup"]
        new = [r for r in after["programs"] if r not in before["programs"]]
        assert ("prefill", "32") in [(r["kind"], r["shape"]) for r in new]
        assert after["built_after_first_reset"] == sum(
            r["builds"] for r in after["programs"]) - before["programs_built"]
        assert after["built_after_first_reset"] >= 1
        for key in ("engine_init_s", "weights_s", "pools_s", "init_build_s"):
            assert after[key] == before[key]
        srv.close()

    def test_a_bucket_first_met_in_a_round_is_in_its_build_ms(self):
        srv = _serving(_tiny_model(seq=128), max_model_len=128)
        srv.run([(_prompt(9), 60)])                 # bucket 16, every step
        srv.reset_stats()
        srv.add_request(_prompt(5), 12)             # warm: builds nothing
        warm = _drain(srv)
        assert warm and all(r["build_ms"] == 0.0 for r in warm)
        assert srv.stats()["build_ms_total"] == 0.0
        srv.add_request(_prompt(20, seed=1), 12)    # bucket 32: a build
        recs = _drain(srv)
        assert recs[0]["build_ms"] > 0.0 and recs[0]["prefills"] == 1
        assert recs[0]["build_ms"] < recs[0]["round_ms"]
        assert all(r["build_ms"] == 0.0 for r in recs[1:]) and len(recs) > 2
        (rec,) = [r for r in _programs(srv, "prefill") if r["shape"] == "32"]
        assert rec["round"] == recs[0]["index"]
        assert recs[0]["build_ms"] >= 1e3 * (
            rec["trace_s"] + rec["lower_s"] + rec["compile_or_load_s"]) - 1e-6
        st = srv.stats()
        assert st["build_ms_total"] == pytest.approx(recs[0]["build_ms"])
        assert st["setup"]["built_after_first_reset"] >= 1
        srv.close()

    def test_two_engines_share_one_listener(self):
        """As ``TestGcClock::test_one_hook_times_every_collection`` for the
        collector's hook: the listener is the process's, each engine's
        records are its own."""
        from jax._src import monitoring
        from deepspeed_tpu.telemetry import tracing
        a, b = _serving(), _serving()
        clock = tracing.build_clock()
        assert a._clock is b._clock is clock
        assert [cb for cb in monitoring.get_event_time_span_listeners()
                if getattr(cb, "__self__", None) is clock] == [clock._on_span]
        assert len([cb for cb in monitoring.get_event_duration_listeners()
                    if getattr(cb, "__self__", None) is clock]) == 1
        assert len([cb for cb in monitoring.get_event_listeners()
                    if getattr(cb, "__self__", None) is clock]) == 1
        before = clock.seconds
        a.run([(_prompt(9), 4)])
        assert clock.seconds > before
        b.run([(_prompt(20), 4)])
        mine = {(r["kind"], r["shape"]) for r in _programs(a, "prefill")}
        theirs = {(r["kind"], r["shape"]) for r in _programs(b, "prefill")}
        assert mine == {("prefill", "16")} and theirs == {("prefill", "32")}
        assert all(r["builds"] == 1 for r in _programs(a) + _programs(b)
                   if r["kind"] != "other")
        a.close(), b.close()

    def test_what_is_built_under_no_span_goes_by_its_name(self):
        """... into the process's own log, and from there into the record of
        every engine that was alive: the small programs nobody named are
        counted, not lost."""
        from deepspeed_tpu.telemetry import tracing

        def built_before_the_engine(x):
            return jnp.cos(x) - 3.0

        def nobody_named_me(x):
            return jnp.sin(x) * 2.0

        jax.jit(built_before_the_engine)(jnp.ones((3, 5)))
        srv = _serving()
        srv.add_request(_prompt(5), 2)
        real = srv._land

        def land(*args):                    # built inside a round
            jax.jit(nobody_named_me)(jnp.ones((3, 5)))
            return real(*args)
        srv._land = land
        srv.step()
        (rec,) = [r for r in tracing.build_log().records()
                  if r["name"] == "nobody_named_me"]
        (seen,) = [r for r in _programs(srv, "other")
                   if r["name"] == "nobody_named_me"]
        assert seen["round"] == rec["round"] == 0
        assert 0.0 < seen["built_at_s"] < rec["built_at_s"]   # its own t0
        assert {k: v for k, v in seen.items() if k != "built_at_s"} == {
            k: v for k, v in rec.items() if k != "built_at_s"}
        assert "built_before_the_engine" not in [
            r["name"] for r in _programs(srv)]
        srv.close()
        assert (rec["kind"], rec["shape"]) == ("other", "nobody_named_me")
        assert rec["builds"] == 1 and rec["wall_s"] == 0.0
        assert rec["trace_s"] > 0.0 and rec["lower_s"] > 0.0
        assert rec["compile_or_load_s"] > 0.0
        # ... and a trace alone (nothing lowered) waits for a span's edge
        jax.eval_shape(jax.jit(lambda x: jnp.cos(x) + 1.0), jnp.ones((7,)))
        log = tracing.BuildLog()
        with log.program("other", "toy"):
            pass
        (toy,) = log.records()
        assert toy["trace_s"] == 0.0 and toy["builds"] == 0   # not the span's

    def test_a_chunk_width_and_the_verify_step_are_builds_too(self):
        srv = _serving(prefill_token_budget=16, max_model_len=64)
        srv.run([(_prompt(40), 4)])                 # 16 + 16 + 8: two widths
        assert sorted(r["shape"] for r in _programs(srv, "span")) == [
            "16"]                                   # 8 pads to a block: 16
        assert all(r["builds"] == 1 and r["name"] == "chunk"
                   for r in _programs(srv, "span"))
        srv.close()
        srv = _serving(spec_tokens=2)
        srv.run([(_prompt(9), 8)])
        (rec,) = _programs(srv, "spec_step")
        assert rec["shape"] == "2x3" and rec["builds"] == 1
        assert rec["trace_s"] > 0.0 and rec["round"] is not None
        assert _programs(srv, "step") == []         # it never ran a plain one
        srv.close()

    def test_a_recovery_rebuilds_the_pool_under_its_span(self, tmp_path):
        srv = _serving()
        srv.run([(_prompt(9), 4)])
        first = srv.stats()["setup"]["pools_s"]
        with _Session(tmp_path) as ses:
            srv._recover("test")
        assert len(ses.named("ds:setup.pools")) == 1
        assert srv.stats()["setup"]["pools_s"] > first
        srv.close()

    def test_the_train_engines_state_and_step(self, tmp_path):
        from deepspeed_tpu.telemetry import tracing
        model = make_model(TransformerConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=24))
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, 64, (8, 24), dtype=np.int32)}
        with _Session(tmp_path) as ses:
            engine, *_ = deepspeed_tpu.initialize(model=model, config={
                "train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
            engine.train_batch(batch)
            engine.train_batch(batch)
        assert len(ses.named("ds:setup.state")) == 1
        (prog,) = ses.named("ds:setup.program")     # the first call alone
        assert prog[3] == {"kind": "train_step", "shape": "8x24"}
        (disp, _) = ses.named("ds:train.dispatch")
        assert disp[1] <= prog[1] and prog[2] <= disp[2]
        (rec,) = [r for r in tracing.build_log().records()
                  if (r["kind"], r["shape"]) == ("train_step", "8x24")]
        assert rec["builds"] >= 1 and rec["trace_s"] > 0.0
        assert rec["lower_s"] > 0.0 and rec["compile_or_load_s"] > 0.0
        engine.close()

    def test_the_vocabulary_lists_the_four_names(self):
        from deepspeed_tpu.telemetry import tracing
        for name in ("weights", "pools", "state", "program"):
            assert f"``ds:setup.{name}``" in tracing.__doc__


# ---------------------------------------------------------------------------
# the request lifecycle
# ---------------------------------------------------------------------------

NEW_STATS = ["queue_wait_p50_ms", "queue_wait_p90_ms",
             "first_token_wait_p50_ms", "first_token_wait_p90_ms",
             "token_gap_max_p50_ms", "token_gap_max_p90_ms"]


class TestRequestLifecycle:
    def test_fields_and_stats_with_a_preempted_request(self):
        """2 slots and a pool below full residency: uniform long
        generations collide in growth and the newest is preempted. Its
        ``admit_t`` stays the FIRST admission's; the gap it waited to be
        re-admitted shows in ``max_gap_ms``."""
        srv = _serving(_tiny_model(seq=128), max_model_len=128, num_blocks=9)
        reqs = [_add(srv, 26, 40, seed=i) for i in range(4)]
        first_admit = {}
        done = []
        while srv.scheduler.running or srv.scheduler.num_waiting:
            done += srv.step()
            for r in srv.scheduler.running:
                first_admit.setdefault(r.rid, r.admit_t)
        assert len(done) == 4 and sum(r.preemptions for r in done) >= 1
        for r in reqs:
            assert r.submit_t <= r.admit_t <= r.first_token_t <= r.finish_t
            assert r.admit_t == first_admit[r.rid]      # never re-stamped
            assert r.max_gap_ms is not None and r.max_gap_ms > 0.0
            assert r.max_gap_ms <= (r.finish_t - r.first_token_t) * 1e3 + 1e-6
        st = srv.stats()
        for key in NEW_STATS:
            assert key in st and st[key] >= 0.0, key
        # the queue holds two of the four until a slot frees: p90 sees it
        waits = sorted((r.admit_t - r.submit_t) * 1e3 for r in reqs)
        assert st["queue_wait_p90_ms"] == pytest.approx(
            float(np.percentile(waits, 90)))
        assert st["queue_wait_p90_ms"] > st["queue_wait_p50_ms"] >= 0.0
        gaps = [r.max_gap_ms for r in reqs]
        assert st["token_gap_max_p90_ms"] == pytest.approx(
            float(np.percentile(gaps, 90)))
        ftw = [(r.first_token_t - r.admit_t) * 1e3 for r in reqs]
        assert st["first_token_wait_p50_ms"] == pytest.approx(
            float(np.percentile(ftw, 50)))
        srv.reset_stats()
        assert not any(k in srv.stats() for k in NEW_STATS)
        srv.close()

    @pytest.mark.parametrize("budget,deliveries", [(3, 1), (12, 2)])
    def test_one_delivery_has_no_gap(self, budget, deliveries):
        """A quantum of 8: the call that dispatches a prompt's prefill
        fetches its first token and six steps' tokens together; the two
        steps behind them, and every later round, are deliveries of their
        own."""
        srv = _serving(decode_quantum=8)
        req = _add(srv, 5, budget)
        while srv.scheduler.running or srv.scheduler.num_waiting:
            srv.step()
        assert len(req.generated) == budget
        assert (req.max_gap_ms is None) == (deliveries == 1)
        st = srv.stats()
        assert "queue_wait_p90_ms" in st
        assert ("token_gap_max_p90_ms" not in st) == (deliveries == 1)
        srv.close()

    def test_admit_t_agrees_with_the_tracers_queue_wait_span(self):
        srv = _serving(request_trace=True)
        reqs = {r.rid: r for r in (_add(srv, k, 6, seed=k)
                                   for k in (5, 9, 7))}   # 2 slots: one waits
        while srv.scheduler.running or srv.scheduler.num_waiting:
            srv.step()
        tr = srv.tracer
        waits = [e for e in tr.events if e["name"] == "queue_wait"]
        assert len(waits) == 3
        for e in waits:
            r = reqs[e["rid"]]
            assert e["ts"] / 1e6 == pytest.approx(tr.epoch(r.submit_t),
                                                  abs=1e-6)
            assert (e["ts"] + e["dur"]) / 1e6 == pytest.approx(
                tr.epoch(r.admit_t), abs=1e-6)
        late = max(waits, key=lambda e: e["dur"])
        assert late["dur"] / 1e3 == pytest.approx(
            srv.stats()["queue_wait_p90_ms"], rel=0.25)
        srv.close()


# ---------------------------------------------------------------------------
# the train loop's host phases
# ---------------------------------------------------------------------------

class TestTrainHostPhases:
    def test_spans_without_telemetry(self, tmp_path):
        model = make_model(TransformerConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=16))
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "pipeline": {"in_flight": 1, "prefetch": True}})
        assert engine._tracer is None               # telemetry is off
        rng = np.random.default_rng(0)
        batches = [{"input_ids": rng.integers(0, 64, (8, 16), dtype=np.int32)}
                   for _ in range(4)]
        engine.train_batches(iter(batches[:1]), 1)  # compile, off the trace
        with _Session(tmp_path) as ses:
            engine.train_batches(iter(batches), 4)
        assert len(ses.named("ds:train.dispatch")) == 4
        assert len(ses.named("ds:train.prefetch")) == 4
        assert len(ses.named("ds:train.data_wait")) >= 4
        assert len(ses.named("ds:train.block")) == 3     # in_flight = 1
        assert {s[0] for s in ses.spans} == {
            "ds:train.dispatch", "ds:train.prefetch", "ds:train.data_wait",
            "ds:train.block"}
        engine.close()
