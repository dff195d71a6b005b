"""Disaggregated prefill/decode serving (ISSUE 19): KV-byte handoff +
role-aware routing + the autoscaling fleet controller.

The load-bearing contracts pinned here:

  - ``export_kv``/``accept_migration(kv=)`` hands a prefill-done request
    across engines by SHIPPING THE POOL BYTES (one gather + one scatter)
    and the continuation is TOKEN-IDENTICAL to the colocated engine —
    f32 exact, int8-KV exact too (quantized blocks + scales travel
    together, so the receiver's pool state is bit-equal);
  - any payload the receiver cannot scatter bit-faithfully (geometry /
    kv-bits / torn checksum) refuses with the typed
    ``ResumeIncompatible`` BEFORE anything is enqueued, and the ordinary
    re-prefill migration (the path old drain records take) still lands
    the continuation token-identically;
  - a ``role="prefill"`` engine never decodes; the router routes new
    requests to prefill-capable replicas, sweeps prefill-done work onto
    the decode tier, and old no-role heartbeats interop as "both";
  - the ``kv_handoff`` fault seam (fail / corrupt) degrades to
    re-prefill — a torn payload is caught by the crc, never decoded;
  - the FleetController scales the tier up under sustained SLO pressure
    and drains it on lull through ``decommission`` (integrity-chain
    drain + failover + heartbeat retirement) with ZERO lost requests.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.fleet import FleetConfig, FleetController
from deepspeed_tpu.inference.kv_cache import kv_payload_nbytes
from deepspeed_tpu.inference.router import (ReplicaHandle, RouterConfig,
                                            ServingRouter)
from deepspeed_tpu.inference.scheduler import AdmissionRejected
from deepspeed_tpu.inference.serving import (ResumeIncompatible,
                                             kv_payload_crc)
from deepspeed_tpu.models import TransformerConfig, make_model
from deepspeed_tpu.robustness import events as rb_events
from deepspeed_tpu.robustness import faults as rb_faults
from deepspeed_tpu.robustness.faults import FaultInjector, FaultSchedule


@pytest.fixture(autouse=True)
def _clean_robustness_state():
    rb_faults.clear()
    rb_events.clear()
    yield
    rb_faults.clear()
    rb_events.clear()


def _cfg(**overrides):
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, max_seq_len=128, position_type="rotary",
                activation="silu_glu", norm_type="rmsnorm",
                tie_embeddings=False, dtype=jnp.float32,
                attention_impl="xla")
    base.update(overrides)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    return make_model(_cfg())


@pytest.fixture(scope="module")
def params(model):
    return jax.device_get(model.init(jax.random.PRNGKey(0)))


def _serving(model, params, config=None, mesh=None, **kw):
    d = dict(max_seqs=3, block_size=16, max_model_len=128,
             decode_quantum=2, prompt_bucket=16, decode_backend="xla",
             num_blocks=24)
    d.update(kw)
    return deepspeed_tpu.init_serving(model, config=config or {},
                                     serving=d, dtype=jnp.float32,
                                     params=params, mesh=mesh)


def _reqs(seed=0, n=3, lens=(7, 21, 12), news=(8, 6, 9), vocab=128):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, size=(lens[i % len(lens)],)
                          ).astype(np.int32), news[i % len(news)])
            for i in range(n)]


def _prefill_all(srv, reqs):
    """Admit ``reqs`` and step until every one is prefill-done with its
    first token sampled (the handoff-ready state)."""
    rids = [srv.add_request(p, max_new_tokens=k) for p, k in reqs]
    for _ in range(200):
        srv.step()
        live = {r.rid: r for r in srv.scheduler.running}
        if all(rid in live and live[rid].prefill_done
               and live[rid].generated for rid in rids):
            return rids
    raise AssertionError("prefill never completed on the source engine")


def _run_to_done(srv, rids, budget=400):
    outs = {}
    for _ in range(budget):
        for r in srv.step():
            outs[r.rid] = r.output
        if set(outs) >= set(rids):
            return outs
    raise AssertionError(f"requests {set(rids) - set(outs)} never finished")


# ---------------------------------------------------------------------------
# the KV-byte handoff: token-identical, typed refusals, payload hygiene
# ---------------------------------------------------------------------------

class TestKvHandoff:
    def test_handoff_token_identical_f32(self, model, params):
        """The headline contract: export -> release -> accept(kv=) on a
        second engine continues every request EXACTLY as the colocated
        engine would have — the receiver re-computes only the pending
        token's row (one tail span), not the prompt."""
        reqs = _reqs()
        base = _serving(model, params).run([(p.copy(), k) for p, k in reqs])

        src = _serving(model, params, role="prefill")
        dst = _serving(model, params, role="decode")
        rids = _prefill_all(src, reqs)
        payloads = src.export_kv(rids)
        assert sorted(payloads) == sorted(rids)
        for (p, _), rid in zip(reqs, rids):
            # pending-token protocol: the prefill sampled the first
            # token, so exported rows == full prompt — strictly inside
            # the (prompt + first token) context
            assert payloads[rid]["rows"] == len(p)
        recs = src.release_requests(rids)
        assert src.scheduler.done and not src._requests
        dst.accept_migration(recs, source="src", kv=payloads)
        outs = _run_to_done(dst, rids)
        assert set(outs) == set(base)
        for rid in base:
            np.testing.assert_array_equal(
                base[rid], outs[rid],
                err_msg=f"request {rid} diverged across the handoff")
        # the fast path really ran: no fallback on either side
        assert src.stats()["handoff_fallbacks"] == 0
        assert dst.stats()["handoff_fallbacks"] == 0
        assert dst.stats()["handoffs"] == len(rids)

    def test_payload_schema_staging_and_counters(self, model, params):
        """Payload carries schema/rows/blocks/geometry/crc; the staged
        bytes are priced into ``pool_bytes``/``kv_staging_bytes`` until
        the hop completes; ``reset_stats`` clears the counters."""
        src = _serving(model, params, role="prefill")
        dst = _serving(model, params, role="decode")
        (rid,) = _prefill_all(src, _reqs(n=1))
        pool_before = src.stats()["pool_bytes"]
        payloads = src.export_kv([rid])
        pl = payloads[rid]
        assert pl["schema"] == 1
        assert pl["geometry"]["block_size"] == 16
        assert pl["geometry"]["num_layers"] == 2
        assert pl["geometry"]["kv_bits"] == 0
        assert pl["crc"] == kv_payload_crc(pl["data"])
        nbytes = kv_payload_nbytes(pl["data"])
        assert nbytes > 0
        st = src.stats()
        assert st["kv_staging_bytes"] == nbytes
        assert st["pool_bytes"] == pool_before + nbytes
        assert st["handoffs"] == 1 and st["handoff_bytes"] == nbytes
        recs = src.release_requests([rid])
        assert src.stats()["kv_staging_bytes"] == 0   # hop consumed it
        dst.accept_migration(recs, source="src", kv=payloads)
        assert dst.stats()["kv_staging_bytes"] == nbytes
        _run_to_done(dst, [rid])
        st = dst.stats()
        assert st["kv_staging_bytes"] == 0            # scatter consumed it
        assert st["handoffs"] == 1 and st["handoff_bytes"] == nbytes
        dst.reset_stats()
        st = dst.stats()
        assert st["handoffs"] == 0 and st["handoff_bytes"] == 0
        assert st["handoff_fallbacks"] == 0

    def test_export_skips_requests_without_rows(self, model, params):
        """A request with nothing cached (still waiting) or an unknown
        rid exports nothing — the caller's fallback is the ordinary
        re-prefill migration, never a malformed payload."""
        src = _serving(model, params)
        rid = src.add_request(np.arange(9, dtype=np.int32),
                              max_new_tokens=4)
        assert src.export_kv([rid, 777]) == {}   # no step yet: no rows

    def test_geometry_mismatch_refuses_typed_then_fallback(
            self, model, params):
        """A block-size-mismatched payload refuses with the typed
        ``ResumeIncompatible`` BEFORE anything is enqueued
        (all-or-nothing), and the same records land token-identically
        through the re-prefill path — old drain records keep working."""
        reqs = _reqs(n=2)
        base = _serving(model, params).run([(p.copy(), k) for p, k in reqs])
        src = _serving(model, params, role="prefill")
        dst = _serving(model, params, block_size=8, num_blocks=48)
        rids = _prefill_all(src, reqs)
        payloads = src.export_kv(rids)
        recs = src.release_requests(rids)
        with pytest.raises(ResumeIncompatible, match="block_size"):
            dst.accept_migration(recs, source="src", kv=payloads)
        assert not dst._requests                 # nothing half-landed
        assert dst.stats()["handoff_fallbacks"] >= 1
        dst.accept_migration(recs, source="src")  # the re-prefill path
        outs = _run_to_done(dst, rids)
        for rid in base:
            np.testing.assert_array_equal(base[rid], outs[rid])

    def test_torn_payload_refused_by_checksum(self, model, params):
        """Size-preserving bitrot in the payload buffers fails the crc —
        typed refusal, then the fallback serves the exact tokens. The
        receiver must never scatter (and decode from) garbage."""
        reqs = _reqs(n=1)
        base = _serving(model, params).run([(p.copy(), k) for p, k in reqs])
        src = _serving(model, params, role="prefill")
        dst = _serving(model, params, role="decode")
        rids = _prefill_all(src, reqs)
        payloads = src.export_kv(rids)
        flat = payloads[rids[0]]["data"]["k"].reshape(-1).view(np.uint8)
        flat[: max(1, flat.size // 16)] ^= 0xFF
        recs = src.release_requests(rids)
        with pytest.raises(ResumeIncompatible, match="checksum"):
            dst.accept_migration(recs, source="src", kv=payloads)
        dst.accept_migration(recs, source="src")
        outs = _run_to_done(dst, rids)
        np.testing.assert_array_equal(base[rids[0]], outs[rids[0]])

    def test_rows_outside_pending_token_protocol_refused(
            self, model, params):
        """rows must sit strictly inside (0, ctx): the receiver's tail
        span computes the row AT cached_rows, so a full-context payload
        is as malformed as an empty one."""
        src = _serving(model, params, role="prefill")
        dst = _serving(model, params, role="decode")
        (rid,) = _prefill_all(src, _reqs(n=1))
        payloads = src.export_kv([rid])
        recs = src.release_requests([rid])
        ctx = len(recs[0]["prompt"]) + len(recs[0]["generated"])
        bad = dict(payloads[rid], rows=ctx)
        with pytest.raises(ResumeIncompatible, match="rows"):
            dst.accept_migration(recs, source="src", kv={rid: bad})
        assert not dst._requests

    def test_int8_kv_handoff_token_identical(self, model, params):
        """int8-KV pools ship payload + scales (the payload tree mirrors
        the pool tree) and the handed-off continuation matches the
        colocated int8 engine — the quantized blocks travel bit-exactly,
        so even the weaker int8 parity bar is met exactly. A kv-bits
        mismatch (int8 payload into an f32 pool) refuses typed."""
        reqs = _reqs(n=2)
        q = {"kv_cache_bits": 8}
        base = _serving(model, params, config=q).run(
            [(p.copy(), k) for p, k in reqs])
        src = _serving(model, params, config=q, role="prefill")
        dst = _serving(model, params, config=q, role="decode")
        rids = _prefill_all(src, reqs)
        payloads = src.export_kv(rids)
        pl = payloads[rids[0]]
        assert pl["geometry"]["kv_bits"] == 8
        assert {"k", "v", "k_scale", "v_scale"} <= set(pl["data"])
        recs = src.release_requests(rids)
        dst.accept_migration(recs, source="src", kv=payloads)
        outs = _run_to_done(dst, rids)
        agree = exact = total = 0
        for (p, _), rid in zip(reqs, rids):
            # outputs are prompt + generated: score only the GENERATED
            # tail. int8 bar: first tokens exact, >0.9 greedy agreement
            # — the byte-exact handoff clears the exact bar today, the
            # weaker floor is the contract
            a = np.asarray(base[rid])[len(p):]
            b = np.asarray(outs[rid])[len(p):]
            np.testing.assert_array_equal(a[:4], b[:4])
            n = min(len(a), len(b))
            agree += int((a[:n] == b[:n]).sum())
            exact += int(np.array_equal(a, b))
            total += n
        assert agree / total > 0.9
        assert exact == len(base)       # today: bit-exact state, exact
        # cross-bits: the f32 engine's pool tree has no scale leaves
        f32 = _serving(model, params)
        rids2 = _prefill_all(src, reqs)
        payloads2 = src.export_kv(rids2)
        recs2 = src.release_requests(rids2)
        with pytest.raises(ResumeIncompatible):
            f32.accept_migration(recs2, source="src", kv=payloads2)

    def test_payload_keeps_the_head_major_byte_order(self, model, params):
        """The pool is stored token-major ([L, NB, bs, nkv, hd], scale
        planes [L, NB, nkv*bs]: ISSUE 24); the PAYLOAD is not — its
        ``data`` bytes, ``geometry`` and crc are those of the head-major
        arrays [L, n, nkv, bs, hd] / [L, n, nkv, bs] that every payload
        written before the change carries. Pinned on a hand-filled int8
        pool whose logical content is built here, in the payload's
        order."""
        from deepspeed_tpu.models.transformer import (
            paged_blocks_from_logical)
        src = _serving(model, params, config={"kv_cache_bits": 8},
                       role="prefill")
        (rid,) = _prefill_all(src, _reqs(n=1, lens=(21,)))
        L, NB, bs, nkv, hd = src.pools["k"].shape
        assert (bs, nkv, hd) == (16, 2, 16)
        rng = np.random.default_rng(7)
        logical = {
            "k": rng.integers(-127, 128, (L, NB, nkv, bs, hd), np.int8),
            "v": rng.integers(-127, 128, (L, NB, nkv, bs, hd), np.int8),
            "k_scale": rng.random((L, NB, nkv, bs), np.float32),
            "v_scale": rng.random((L, NB, nkv, bs), np.float32)}
        filled = paged_blocks_from_logical(
            {n: jnp.asarray(a) for n, a in logical.items()})
        assert {n: a.shape for n, a in filled.items()} == \
            {n: a.shape for n, a in src.pools.items()}
        src.pools = filled
        pl = src.export_kv([rid])[rid]
        req = src._requests[rid]
        assert (pl["rows"], pl["blocks"]) == (21, 2)
        want = {n: np.ascontiguousarray(a[:, req.block_ids[:2]])
                for n, a in logical.items()}
        assert set(pl["data"]) == set(want)
        for n in want:
            assert pl["data"][n].dtype == want[n].dtype
            assert pl["data"][n].shape == want[n].shape
            assert pl["data"][n].tobytes() == want[n].tobytes(), n
        assert pl["geometry"] == {
            "kv_planes": L, "num_layers": L, "ut_steps": 1,
            "kv_heads": nkv, "head_dim": hd,
            "block_size": bs, "kv_bits": 8, "dtype": "int8"}
        assert pl["schema"] == 1 and pl["crc"] == kv_payload_crc(want)

    def test_int8_round_trip_lands_the_same_blocks(self, model, params):
        """export_kv -> accept_migration(kv=) -> the receiver's pool holds
        the payload's blocks byte for byte (exported again from there,
        before any decode step touches them) and the continuation is the
        colocated int8 engine's."""
        reqs = _reqs(n=2)
        q = {"kv_cache_bits": 8}
        base = _serving(model, params, config=q).run(
            [(p.copy(), k) for p, k in reqs])
        src = _serving(model, params, config=q, role="prefill")
        dst = _serving(model, params, config=q, role="both")
        rids = _prefill_all(src, reqs)
        payloads = src.export_kv(rids)
        dst.accept_migration(src.release_requests(rids), source="src",
                             kv=payloads)
        for _ in range(50):                # admit + scatter + tail span
            live = {r.rid: r for r in dst.scheduler.running}
            if all(rid in live and live[rid].prefill_done for rid in rids):
                break
            dst.step()
        back = dst.export_kv(rids)
        for rid in rids:
            rows = payloads[rid]["rows"]
            assert back[rid]["rows"] >= rows
            for n, a in payloads[rid]["data"].items():
                b = back[rid]["data"][n][:, :a.shape[1]]
                # rows past the shipped ones belong to the receiver
                full, part = divmod(rows, 16)
                np.testing.assert_array_equal(a[:, :full], b[:, :full], n)
                if part:
                    np.testing.assert_array_equal(
                        a[:, full, :, :part], b[:, full, :, :part], n)
        outs = _run_to_done(dst, rids)
        for rid in base:
            np.testing.assert_array_equal(base[rid], outs[rid])
        assert dst.stats()["handoff_fallbacks"] == 0

    def test_handoff_mid_chunked_prefill(self, model, params):
        """A chunked-prefill request handed off MID-PROMPT ships only the
        rows it has cached; the receiver's tail span finishes the prompt
        and the continuation still matches the colocated engine."""
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, 128, size=(60,)).astype(np.int32)
        base = _serving(model, params).run([(prompt.copy(), 6)])
        src = _serving(model, params, role="prefill",
                       prefill_token_budget=16)
        dst = _serving(model, params, role="decode")
        rid = src.add_request(prompt, max_new_tokens=6)
        req = src._requests[rid]
        for _ in range(5):                # land the first 16-token chunk
            src.step()
            if req.cached_rows > 0:
                break
        assert not req.prefill_done and 0 < req.cached_rows < 60
        payloads = src.export_kv([rid])
        assert payloads[rid]["rows"] == req.cached_rows
        recs = src.release_requests([rid])
        dst.accept_migration(recs, source="src", kv=payloads)
        outs = _run_to_done(dst, [rid])
        np.testing.assert_array_equal(base[rid], outs[rid])

    def test_handoff_onto_live_prefix_cache(self, model, params):
        """A receiver with a warm prefix cache takes the KV import
        verbatim (the import skips prefix matching — its rows are
        already exact) and both the handed-off request and later
        cache-hitting admissions stay token-identical."""
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, 128, size=(33,)).astype(np.int32)
        base = _serving(model, params).run([(prompt.copy(), 6)])
        src = _serving(model, params, role="prefill")
        dst = _serving(model, params, enable_prefix_cache=True,
                       num_blocks=32)
        # warm the receiver's prefix cache with the same prompt (outputs
        # are prompt + generated, so the warm run is a strict prefix)
        warm = dst.run([(prompt.copy(), 4)])
        np.testing.assert_array_equal(base[0][:len(prompt) + 4], warm[0])
        rid = _prefill_all(src, [(prompt.copy(), 6)])[0]
        payloads = src.export_kv([rid])
        recs = src.release_requests([rid])
        dst.accept_migration(recs, source="src", kv=payloads)
        outs = _run_to_done(dst, [rid])
        np.testing.assert_array_equal(base[0], outs[rid])
        # and the cache still serves fresh admissions correctly
        again = dst.run([(prompt.copy(), 6)])
        np.testing.assert_array_equal(base[0], list(again.values())[0])


# ---------------------------------------------------------------------------
# role-aware routing: prefill tier -> decode tier, interop, decommission
# ---------------------------------------------------------------------------

class TestRoleRouting:
    def test_prefill_role_engine_never_decodes(self, model, params):
        """The role contract at the engine: a prefill-role engine samples
        the FIRST token (prefill output) and then parks — decode quanta
        never run, so the request never finishes there."""
        with pytest.raises(ValueError, match="role"):
            _serving(model, params, role="bogus")
        src = _serving(model, params, role="prefill")
        rid = src.add_request(np.arange(9, dtype=np.int32),
                              max_new_tokens=4)
        for _ in range(25):
            src.step()
        req = src._requests[rid]
        assert req.prefill_done and len(req.generated) == 1
        assert not src.scheduler.done     # parked, not lost

    def test_router_disagg_end_to_end_token_identical(self, tmp_path,
                                                      model, params):
        """prefill+decode fleet through the REAL router: new requests
        land on the prefill tier, the sweep hands every prefill-done
        request (KV bytes attached) to the decode tier, outputs match
        the single colocated engine exactly, and the role gauges /
        handoff counters tell the story."""
        reqs = _reqs(n=4, lens=(7, 21, 12, 30), news=(8, 6, 9, 5))
        base = _serving(model, params, max_seqs=4).run(
            [(p.copy(), k) for p, k in reqs])
        router = ServingRouter(RouterConfig(
            store_dir=str(tmp_path / "store"),
            drain_dir=str(tmp_path / "drains")))
        router.register("pre0", _serving(model, params, role="prefill"),
                        role="prefill")
        router.register("dec0", _serving(model, params, role="decode"),
                        role="decode")
        import collections
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
            rounds += 1
            assert rounds < 300, "disagg router did not converge"
        st = router.stats()
        assert st["handoffs"] == len(reqs)
        assert st["handoff_fallbacks"] == 0
        assert st["lost_requests"] == 0
        assert st["handoff_bytes"] > 0 and st["handoff_ms"] > 0
        fs = router.fleet_stats()
        assert fs["fleet_prefill_replicas"] == 1
        assert fs["fleet_decode_replicas"] == 1
        assert fs["fleet_both_replicas"] == 0
        hops = rb_events.history("request_handoff")
        assert len(hops) == len(reqs)
        assert all(e["src"] == "pre0" and e["dst"] == "dec0"
                   and e["kv"] for e in hops)
        assert set(outs) == set(base)
        for rid in base:
            np.testing.assert_array_equal(
                base[rid], outs[rid],
                err_msg=f"request {rid} diverged across the disagg hop")

    def test_old_no_role_heartbeat_interops_as_both(self, tmp_path):
        """A pre-ISSUE-19 replica publishes ``role: "replica"`` (or no
        meta at all): the router must treat it as "both" — admissible
        for new requests AND a valid decode target."""
        from deepspeed_tpu.analysis.serving_lint import _StubReplica
        router = ServingRouter(RouterConfig(
            store_dir=str(tmp_path / "store"),
            drain_dir=str(tmp_path / "drains")))
        c = router.config
        old = _StubReplica("old0", c.store_dir, c.drain_dir)
        assert old.meta()["role"] == "replica"      # the old string
        router.register_handle(old)
        assert router._role_of(old) == "both"
        rid = router.add_request(np.arange(4, dtype=np.int32), 4)
        assert router._placement[rid] == "old0"
        assert router.fleet_stats()["fleet_both_replicas"] == 1

    def test_new_requests_prefer_prefill_capable_replicas(self, tmp_path):
        """Admission order: decode-role replicas only see handoffs — a
        NEW request goes to the prefill tier even when the decode
        replica is less loaded; with ONLY decode replicas alive the
        router still admits (serving beats shedding)."""
        from deepspeed_tpu.analysis.serving_lint import _StubReplica
        router = ServingRouter(RouterConfig(
            store_dir=str(tmp_path / "store"),
            drain_dir=str(tmp_path / "drains")))
        c = router.config

        class _RoleStub(_StubReplica):
            def __init__(self, *a, role="both", **kw):
                super().__init__(*a, **kw)
                self.role = role

        pre = _RoleStub("pre0", c.store_dir, c.drain_dir, role="prefill")
        dec = _RoleStub("dec0", c.store_dir, c.drain_dir, role="decode")
        router.register_handle(pre)
        router.register_handle(dec)
        # load the prefill replica: it must STILL win new admissions
        for _ in range(3):
            rid = router.add_request(np.arange(4, dtype=np.int32), 4)
            assert router._placement[rid] == "pre0"
        pre.dead = True                   # confirmed death out-of-band
        rid = router.add_request(np.arange(4, dtype=np.int32), 4)
        assert router._placement[rid] == "dec0"     # fallback, not a shed

    def test_decommission_drains_and_retires_heartbeat(self, tmp_path):
        """Planned scale-down: in-flight work fails over to survivors
        (zero lost) and the heartbeat is retired so dead registry
        entries don't accumulate across scale cycles."""
        from deepspeed_tpu.analysis.serving_lint import _StubReplica

        class _KillableStub(_StubReplica):
            def kill(self):
                self.killed_t = self._clock()
                self.die()

        t = [0.0]
        router = ServingRouter(RouterConfig(
            store_dir=str(tmp_path / "store"),
            drain_dir=str(tmp_path / "drains"), clock=lambda: t[0]))
        c = router.config
        r0 = _KillableStub("r0", c.store_dir, c.drain_dir, clock=c.clock,
                           service_rate=0)
        r1 = _KillableStub("r1", c.store_dir, c.drain_dir, clock=c.clock)
        router.register_handle(r0)
        router.register_handle(r1)
        for _ in range(2):
            router.add_request(np.arange(4, dtype=np.int32), 8)
        r0.publish()
        r1.publish()
        assert "r0" in router._registry.live_hosts()
        router.decommission("r0")
        st = router.stats()
        assert st["lost_requests"] == 0.0
        assert st["migrated"] == 2.0
        assert "r0" not in router._registry.live_hosts()   # retired
        assert router.replica_inflight()["r1"] == 2


# ---------------------------------------------------------------------------
# the kv_handoff fault seam: fail + corrupt degrade to re-prefill
# ---------------------------------------------------------------------------

class TestHandoffFaultSeam:
    def test_schedule_validation(self):
        with pytest.raises(ValueError):
            FaultSchedule([{"kind": "kv_handoff"}])   # needs at/rate

    def test_fail_and_corrupt_degrade_to_reprefill(self, tmp_path,
                                                   model, params):
        """Handoff 0 is corrupted in flight (caught by the crc on the
        receiver — typed refusal, re-prefill), handoff 1 fails outright
        (the bytes never arrive, the record does). Both continuations
        still finish TOKEN-IDENTICAL to the fault-free engine: the seam
        degrades throughput, never correctness."""
        reqs = _reqs(n=2)
        base = _serving(model, params).run([(p.copy(), k) for p, k in reqs])
        inj = FaultInjector(FaultSchedule([
            {"kind": "kv_handoff", "at": 0, "mode": "corrupt"},
            {"kind": "kv_handoff", "at": 1},
        ], seed=0))
        rb_faults.install(inj)
        router = ServingRouter(RouterConfig(
            store_dir=str(tmp_path / "store"),
            drain_dir=str(tmp_path / "drains")))
        router.register("pre0", _serving(model, params, role="prefill"))
        router.register("dec0", _serving(model, params, role="decode"))
        import collections
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
            rounds += 1
            assert rounds < 300, "faulted disagg router did not converge"
        st = router.stats()
        assert st["handoffs"] == 2 and st["handoff_fallbacks"] == 2
        assert st["lost_requests"] == 0
        assert {r["kind"] for r in inj.fired} == {"kv_handoff"}
        assert len(inj.fired) == 2
        hops = rb_events.history("request_handoff")
        assert [e["kv"] for e in hops] == [False, False]
        for rid in base:
            np.testing.assert_array_equal(
                base[rid], outs[rid],
                err_msg=f"request {rid} decoded garbage under the seam")


# ---------------------------------------------------------------------------
# the FleetController: sustained pressure scales up, lull drains, zero lost
# ---------------------------------------------------------------------------

def _fleet_fixture(tmp_path, t, **cfg_kw):
    from deepspeed_tpu.analysis.serving_lint import _StubReplica

    class _KillableStub(_StubReplica):
        def kill(self):
            self.killed_t = self._clock()
            self.die()

    router = ServingRouter(RouterConfig(
        store_dir=str(tmp_path / "store"),
        drain_dir=str(tmp_path / "drains"), clock=lambda: t[0]))
    c = router.config
    made = []

    def spawn(name, role):
        rep = _KillableStub(name, c.store_dir, c.drain_dir, clock=c.clock,
                            capacity=2, service_rate=1)
        made.append(rep)
        return rep

    cfg = FleetConfig(**dict(dict(
        role="both", min_replicas=1, max_replicas=3, scale_up_load=1.0,
        scale_up_after=2, scale_down_load=0.05, scale_down_after=2,
        cooldown_ticks=1), **cfg_kw))
    ctl = FleetController(router, spawn, cfg)
    return router, ctl, spawn, made, _KillableStub


class TestFleetController:
    def test_config_validation(self):
        with pytest.raises(ValueError, match="role"):
            FleetConfig(role="frontend")
        with pytest.raises(ValueError, match="min_replicas"):
            FleetConfig(min_replicas=4, max_replicas=2)
        with pytest.raises(ValueError, match="band|flap"):
            FleetConfig(scale_up_load=0.5, scale_down_load=0.5)

    def test_bootstrap_below_min(self, tmp_path):
        """An empty tier is this controller's job too: it spawns up to
        min_replicas even with no load signal to average."""
        t = [0.0]
        router, ctl, _, made, _ = _fleet_fixture(
            tmp_path, t, min_replicas=2)
        name = ctl.tick()
        assert name == "auto-both-0" and len(router.replicas) == 1
        made[0].publish()
        t[0] += 1.0
        assert ctl.tick() is None            # cooldown tick
        t[0] += 1.0
        assert ctl.tick() == "auto-both-1"   # second bootstrap spawn
        assert ctl.stats()["scale_ups"] == 2.0

    def test_burst_scales_up_lull_drains_zero_lost(self, tmp_path):
        """The full loop: sustained pressure doubles the tier, the lull
        drains it back to min through decommission (integrity-chain
        drain + failover), and every admitted request completes."""
        t = [0.0]
        router, ctl, spawn, made, Stub = _fleet_fixture(tmp_path, t)
        c = router.config
        r0 = Stub("r0", c.store_dir, c.drain_dir, clock=c.clock,
                  capacity=2, service_rate=1)
        router.register_handle(r0)
        burst = [(np.arange(4, dtype=np.int32), 4) for _ in range(10)]
        import collections
        pending = collections.deque(burst)
        done = 0
        peak = 1
        for _ in range(60):
            while pending:
                try:
                    router.add_request(*pending[0])
                except AdmissionRejected:
                    break
                pending.popleft()
            done += len(router.step())
            ctl.tick()
            live = int(router.fleet_stats()["fleet_live"])
            peak = max(peak, live)
            t[0] += 1.0
            if done == len(burst) and not pending and live == 1:
                break
        assert done == len(burst)
        assert router.stats()["lost_requests"] == 0.0
        assert peak >= 2, "the burst never scaled the tier up"
        assert int(router.fleet_stats()["fleet_live"]) == 1
        st = ctl.stats()
        assert st["scale_ups"] >= 1 and st["scale_downs"] >= 1
        assert rb_events.history("fleet_scale_up")
        assert rb_events.history("fleet_scale_down")
        # scaled-down replicas' heartbeats are retired, not stale
        assert router._registry.live_hosts() == ["r0"] or \
            len(router._registry.live_hosts()) == 1

    def test_foreign_host_never_touched(self, tmp_path):
        """A heartbeat from a host this router doesn't drive (shared
        store) is tier load but never a decommission victim."""
        from deepspeed_tpu.elasticity.rendezvous import FileRendezvous
        t = [0.0]
        router, ctl, _, made, Stub = _fleet_fixture(
            tmp_path, t, scale_down_after=1, cooldown_ticks=0)
        c = router.config
        r0 = Stub("r0", c.store_dir, c.drain_dir, clock=c.clock)
        router.register_handle(r0)
        foreign = FileRendezvous(c.store_dir, "foreign0",
                                 clock=lambda: t[0])
        for _ in range(6):
            foreign.heartbeat(meta={"queue_depth": 0, "running": 0,
                                    "capacity": 4})
            r0.publish()
            router.step()
            ctl.tick()
            t[0] += 1.0
        # the controller observed the foreign host's load but never
        # tried to kill it — only router-driven replicas are victims
        assert "foreign0" in router._registry.live_hosts()

    def test_spawn_refusal_is_not_a_scale_event(self, tmp_path):
        t = [0.0]
        router, ctl, _, made, _ = _fleet_fixture(tmp_path, t)
        ctl.spawn = lambda name, role: None    # deployment out of quota
        assert ctl.tick() is None              # bootstrap refused
        assert ctl.stats()["scale_ups"] == 0.0
        assert len(router.replicas) == 0


# ---------------------------------------------------------------------------
# the handoff-recompute corpus twin (the defect this PR exists to prevent)
# ---------------------------------------------------------------------------

class TestHandoffRecomputeCorpus:
    def test_defect_fires_ttft_growth(self):
        from deepspeed_tpu.analysis.serving_lint import audit_handoff
        report = audit_handoff(kv=False)
        assert not report.ok
        assert [f.rule for f in report.findings] == ["ttft-growth"]
        sim = report.meta
        assert sim["handoffs"] > 0
        assert sim["handoff_fallbacks"] == sim["handoffs"]  # all re-paid
        ttfts = sim["decode_ttfts"]
        assert all(b >= a for a, b in zip(ttfts, ttfts[1:]))

    def test_kv_twin_passes(self):
        from deepspeed_tpu.analysis.serving_lint import audit_handoff
        report = audit_handoff(kv=True)
        assert report.ok, [f.rule for f in report.findings]
        assert report.meta["handoffs"] > 0
        assert report.meta["handoff_fallbacks"] == 0
        assert report.meta["lost"] == 0

    def test_cli_both_directions(self, capsys):
        from deepspeed_tpu.analysis.serving_lint import main as lint_main
        assert lint_main(["--handoff"]) == 1
        assert "ttft-growth" in capsys.readouterr().out
        assert lint_main(["--handoff", "--kv"]) == 0
        assert "OK" in capsys.readouterr().out

    def test_corpus_entry_registered(self):
        from deepspeed_tpu.analysis.corpus import run_corpus
        assert not run_corpus("handoff-recompute").ok


# ---------------------------------------------------------------------------
# slow: tp=2 -> tp=2 handoff, engine-backed autoscale soak
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestDisaggSlow:
    def test_tp2_to_tp2_handoff_token_identical(self, model, params):
        """Sharded pools hand off too: the export assembles the full
        head dim (logical bytes, mesh-independent), the tp=2 receiver
        re-shards on scatter, and the continuation matches the tp=2
        colocated engine exactly."""
        from deepspeed_tpu.parallel import MeshPlan, build_mesh

        def _mesh():
            return build_mesh(MeshPlan(tensor=2),
                              devices=jax.devices()[:2])

        reqs = _reqs(n=2)
        base = _serving(model, params, mesh=_mesh()).run(
            [(p.copy(), k) for p, k in reqs])
        src = _serving(model, params, mesh=_mesh(), role="prefill")
        dst = _serving(model, params, mesh=_mesh(), role="decode")
        rids = _prefill_all(src, reqs)
        payloads = src.export_kv(rids)
        # logical geometry: the payload carries the FULL head count
        assert payloads[rids[0]]["geometry"]["kv_heads"] == 2
        recs = src.release_requests(rids)
        dst.accept_migration(recs, source="src", geometry={"tp": 2},
                             kv=payloads)
        outs = _run_to_done(dst, rids)
        for rid in base:
            np.testing.assert_array_equal(
                base[rid], outs[rid],
                err_msg=f"request {rid} diverged across the tp2 handoff")

    def test_autoscale_soak_engine_backed(self, tmp_path, model, params):
        """Burst-then-lull over REAL engines: the controller doubles the
        tier under pressure, drains it on the lull, and every request's
        output matches the single-engine baseline — scale events never
        cost tokens."""
        reqs = _reqs(n=10, lens=(7, 21, 12, 30, 16),
                     news=(8, 6, 9, 5, 7))
        base = _serving(model, params, max_seqs=4).run(
            [(p.copy(), k) for p, k in reqs])
        router = ServingRouter(RouterConfig(
            store_dir=str(tmp_path / "store"),
            drain_dir=str(tmp_path / "drains")))
        router.register("r0", _serving(model, params, max_queue=4))
        ctl = FleetController(
            router, lambda name, role: _serving(model, params,
                                                max_queue=4),
            FleetConfig(role="both", min_replicas=1, max_replicas=3,
                        scale_up_load=1.0, scale_up_after=2,
                        scale_down_load=0.05, scale_down_after=3,
                        cooldown_ticks=1))
        import collections
        pending = collections.deque(reqs)
        outs, rounds, peak = {}, 0, 1
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
            ctl.tick()
            peak = max(peak, int(router.fleet_stats()["fleet_live"]))
            rounds += 1
            assert rounds < 600, "autoscale soak did not converge"
        for _ in range(12):                 # the lull drains the tier
            router.step()
            ctl.tick()
        assert router.stats()["lost_requests"] == 0.0
        assert peak >= 2, "the burst never scaled the tier"
        assert int(router.fleet_stats()["fleet_live"]) == 1
        assert set(outs) == set(base)
        for rid in base:
            np.testing.assert_array_equal(
                base[rid], outs[rid],
                err_msg=f"request {rid} diverged across scale events")
