"""Serving engine: continuous batching + paged KV cache + quantized decode.

Reference behavior being exceeded: SURVEY §6's InferenceEngine serves one
shape-bucketed batch per generate() call; the serving tier admits/evicts at
decode-step boundaries over a shared block pool. The load-bearing contracts
pinned here:

  - paged decode is BIT-FOR-BIT the contiguous ring-buffer decode (same
    einsums on a gathered view — greedy tokens AND logits identical over
    20+ steps, float and int8-KV caches);
  - the scheduler admits FIFO, evicts on finish, preempts newest-first
    under pool pressure, and queues gracefully on exhaustion (never OOM);
  - the Pallas paged kernel and the XLA gather agree (backend is a
    measured choice, logged as a telemetry event, never silently wrong);
  - a leaked block pool is a lint failure (`paged-cache-leak` corpus).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.kv_cache import (BlockAllocator,
                                              BlockPoolExhausted, blocks_for)
from deepspeed_tpu.inference.scheduler import RequestScheduler
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.models import TransformerConfig, make_model


def _cfg(**overrides):
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, max_seq_len=256, position_type="rotary",
                activation="silu_glu", norm_type="rmsnorm",
                tie_embeddings=False, dtype=jnp.float32,
                attention_impl="xla")
    base.update(overrides)
    return TransformerConfig(**base)


# ---------------------------------------------------------------------------
# Block allocator (pure host)
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_block0_reserved_and_lifo_reuse(self):
        a = BlockAllocator(8)
        assert a.free_blocks == 7           # block 0 never in the free list
        got = a.alloc(3)
        assert 0 not in got
        a.free(got)
        assert a.alloc(1) == [got[-1]]      # LIFO: warmest block first

    def test_exhaustion_raises_typed(self):
        a = BlockAllocator(4)
        a.alloc(3)
        assert not a.can_alloc(1)
        with pytest.raises(BlockPoolExhausted):
            a.alloc(1)

    def test_double_free_and_trash_free_raise(self):
        a = BlockAllocator(4)
        ids = a.alloc(2)
        a.free(ids)
        with pytest.raises(ValueError, match="double free"):
            a.free([ids[0]])
        with pytest.raises(ValueError, match="trash"):
            a.free([0])

    def test_blocks_for(self):
        assert blocks_for(0, 16) == 0
        assert blocks_for(1, 16) == 1
        assert blocks_for(16, 16) == 1
        assert blocks_for(17, 16) == 2


# ---------------------------------------------------------------------------
# Scheduler (pure host: admit / evict / preempt ordering)
# ---------------------------------------------------------------------------

def _sched(num_blocks=32, max_seqs=4, bs=16, quantum=4, mb=8):
    alloc = BlockAllocator(num_blocks)
    return alloc, RequestScheduler(
        alloc, max_seqs, bs, quantum,
        prompt_blocks=lambda n: blocks_for(max(n, bs), bs),
        max_blocks_per_seq=mb)


class TestScheduler:
    def test_fifo_admission_order(self):
        _, s = _sched()
        reqs = [s.submit(np.arange(10), 8) for _ in range(3)]
        out = s.schedule()
        assert out["admitted"] == reqs      # arrival order
        assert [r.state for r in reqs] == ["running"] * 3

    def test_slot_limit_queues(self):
        _, s = _sched(max_seqs=2)
        reqs = [s.submit(np.arange(10), 8) for _ in range(3)]
        out = s.schedule()
        assert len(out["admitted"]) == 2
        assert s.num_waiting == 1 and reqs[2].state == "waiting"

    def test_pool_exhaustion_queues_not_raises(self):
        # 9 usable blocks; each request needs ceil((32+4)/16)=3 -> 3 admit
        alloc, s = _sched(num_blocks=10, max_seqs=8)
        reqs = [s.submit(np.arange(32), 8) for _ in range(5)]
        out = s.schedule()
        assert len(out["admitted"]) == 3
        assert s.num_waiting == 2
        assert alloc.free_blocks == 0
        # finishing one frees its blocks and the queue head admits next
        s.finish(reqs[0])
        out = s.schedule()
        assert out["admitted"] == [reqs[3]]

    def test_growth_preempts_newest_first(self):
        # two running, pool exactly covers their prompts; growth pressure
        # must preempt the NEWEST and keep the oldest progressing
        alloc, s = _sched(num_blocks=7, max_seqs=4, bs=16, quantum=4)
        r1 = s.submit(np.arange(30), 64)    # 3 blocks (ctx+quantum=34)
        r2 = s.submit(np.arange(30), 64)
        assert len(s.schedule()["admitted"]) == 2
        assert alloc.free_blocks == 0
        # simulate r1 decoding to the edge of its coverage
        r1.cached_rows = 46                 # needs blocks_for(50)=4 next
        r1.generated = list(range(16))
        out = s.schedule()
        assert out["preempted"] == [r2]
        assert r2.state == "waiting" and r2.preemptions == 1
        assert len(r1.block_ids) == 4       # oldest got its growth
        # the preempted request resumes at the FRONT of the queue with its
        # generated tokens intact (re-prefill recomputes its rows)
        r3 = s.submit(np.arange(8), 8)
        assert s.waiting[0] is r2 and s.waiting[1] is r3
        assert r2.cached_rows == 0

    def test_a_finish_by_length_is_counted_ahead(self):
        """Rows in flight count: growth covers the quantum BEHIND them, and
        a request whose budget they exhaust gives slot and blocks to an
        admission of the same decision, staying ``ending`` (the scheduler
        is not done) until the engine finishes it — or a recovery takes it
        back to the queue with everything else."""
        alloc, s = _sched(max_seqs=1)
        a, b = s.submit(np.arange(10), 5), s.submit(np.arange(10), 12)
        assert s.schedule()["admitted"] == [a] and len(a.block_ids) == 1
        a.cached_rows, a.prefill_done = 10, True
        a.generated.append(7)                  # its first token, committed
        a.inflight_rows = 4                    # and a quantum on its way
        out = s.schedule()
        assert out["ended"] == [a] and out["admitted"] == [b]
        assert a.state == "ending" and a.slot is None and b.slot == 0
        assert s.running == [b] and s.ending == [a] and not s.done
        s.finish(a)
        assert a.state == "finished" and not s.ending
        # b: three of its twelve tokens left after the rows in flight
        b.cached_rows, b.prefill_done, b.inflight_rows = 14, True, 4
        b.generated.extend(range(5))
        out = s.schedule()
        assert out["ended"] == [] and len(b.block_ids) == 2   # 14 + 4 + 4
        b.generated.extend(range(4))
        b.cached_rows = 18
        assert s.schedule()["ended"] == [b]
        assert s.preempt_all() == 1 and s.waiting[0] is b
        assert b.state == "waiting" and b.inflight_rows == 0
        assert not s.ending and alloc.used_blocks == 0

    def test_growth_clamps_at_table_width(self):
        alloc, s = _sched(num_blocks=32, max_seqs=2, bs=16, quantum=8, mb=3)
        r = s.submit(np.arange(40), 16)
        s.schedule()
        r.cached_rows = 47                  # target 55 -> 4 blocks > mb=3
        s.schedule()
        assert len(r.block_ids) == 3        # clamped, no table overflow

    @pytest.mark.parametrize("leave", ["finish", "cancel", "preempt"])
    def test_lowest_free_slot_is_handed_out(self, leave):
        """An admission takes the LOWEST free slot, however the slots came
        back (ISSUE 33): the running requests stay in the first rows and a
        decode round can be sized by the highest of them. The stack this
        replaces handed back the slot freed last."""
        _, s = _sched(num_blocks=64, max_seqs=6)
        reqs = [s.submit(np.arange(10), 8) for _ in range(6)]
        s.schedule()
        assert [r.slot for r in reqs] == [0, 1, 2, 3, 4, 5]
        for r in (reqs[1], reqs[4], reqs[3]):       # 3 is freed LAST
            getattr(s, leave)(r)
        if leave == "preempt":
            # the victims head the queue again, newest preemption first
            assert list(s.waiting) == [reqs[3], reqs[4], reqs[1]]
            again = s.schedule()["admitted"]
            assert [(r.rid, r.slot) for r in again] == [
                (reqs[3].rid, 1), (reqs[4].rid, 3), (reqs[1].rid, 4)]
            return
        new = [s.submit(np.arange(10), 8) for _ in range(2)]
        s.schedule()
        assert [r.slot for r in new] == [1, 3]
        s.finish(reqs[0])
        last = s.submit(np.arange(10), 8)
        s.schedule()
        assert last.slot == 0 and sorted(s._free_slots) == [4]


# ---------------------------------------------------------------------------
# Paged vs contiguous decode: bit-for-bit
# ---------------------------------------------------------------------------

def _paged_vs_contiguous(kv_bits, dtype, steps=24):
    cfg = _cfg(dtype=dtype, kv_cache_bits=kv_bits)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    B, P, bs, MB = 2, 32, 16, 6            # gathered width == max_len == 96
    ids = rng.integers(0, cfg.vocab_size, (B, P)).astype(np.int32)

    cache = model.init_cache(B, MB * bs, dtype=dtype)
    lg_c, cache = model.prefill(params, jnp.asarray(ids), cache)

    pools = model.init_paged_cache(num_blocks=B * MB + 1, block_size=bs,
                                   dtype=dtype)
    tabs = np.zeros((B, MB), np.int32)
    nxt_blk = 1
    lg_rows = []
    for s in range(B):
        row = list(range(nxt_blk, nxt_blk + MB))
        nxt_blk += MB
        tabs[s] = row
        lgp, pools = model.prefill_paged(params, jnp.asarray(ids[s:s + 1]),
                                         pools,
                                         jnp.asarray(row[:P // bs],
                                                     jnp.int32), length=P)
        lg_rows.append(lgp)
    lg_p = jnp.concatenate(lg_rows, 0)
    np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))

    tok = jnp.argmax(lg_c, -1).astype(jnp.int32)
    tok_p = jnp.argmax(lg_p, -1).astype(jnp.int32)
    tabs_d = jnp.asarray(tabs)
    lens = jnp.asarray([P] * B, jnp.int32)
    dsc = jax.jit(lambda p, t, c: model.decode_step(p, t, c))
    dsp = jax.jit(lambda p, t, pl, tb, ln: model.decode_step_paged(
        p, t, pl, tb, ln, backend="xla"))
    for i in range(steps):
        lc, cache = dsc(params, tok, cache)
        lp, pools = dsp(params, tok_p, pools, tabs_d, lens)
        lens = lens + 1
        if kv_bits == 8:
            # bit-for-bit: the int8 read sums integers (exact in any
            # order) and its float operations are the ring path's, in the
            # ring path's order (junk masked to exact zeros)
            np.testing.assert_array_equal(np.asarray(lc), np.asarray(lp),
                                          err_msg=f"step {i}")
        else:
            # a float pool is contracted token-major, as gathered, where
            # the ring buffer is head-major: the same products summed in
            # another order, so a logit may round to the neighbouring
            # bf16 value (PR 27). Greedy tokens stay equal (below).
            lc32, lp32 = (np.asarray(a, np.float32) for a in (lc, lp))
            ulp = 2.0 ** (np.floor(np.log2(np.abs(lc32).max())) - 7)
            err = np.abs(lc32 - lp32).max() / ulp
            assert err <= 2, (i, err)
        tok = jnp.argmax(lc, -1).astype(jnp.int32)
        tok_p = jnp.argmax(lp, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_p))


def test_paged_matches_contiguous_bf16():
    """>= 20 greedy decode steps, bf16 cache: tokens exactly equal and
    logits within 2 bf16 ulps (of the step's largest logit) between the
    paged pool and the contiguous ring buffer."""
    _paged_vs_contiguous(0, jnp.bfloat16)


@pytest.mark.slow
def test_paged_matches_contiguous_int8_kv():
    """Same contract through the int8-quantized pool (scales gathered and
    fused into the score scaling — identical math to the int8 ring)."""
    _paged_vs_contiguous(8, jnp.bfloat16)


@pytest.mark.parametrize("span", [1, 3], ids=["token", "span"])
@pytest.mark.parametrize("bits", [0, 8], ids=["float", "int8"])
def test_layer_is_a_coordinate_of_the_gather(bits, span):
    """A layer scan hands the read the WHOLE pools and its index (ISSUE 27);
    what it returns is what a layer's slice returns, bit for bit — for one
    token a slot and for a span, a float and an int8 pool, trash entries in
    the tables and an empty slot."""
    from deepspeed_tpu.models.transformer import _paged_attention
    L, NB, bs, MB, nkv, nq, D, S = 3, 9, 16, 3, 2, 8, 16, 3
    rng = np.random.default_rng(bits + span)
    if bits == 8:
        pk = jnp.asarray(rng.integers(-127, 128, (L, NB, bs, nkv, D)), jnp.int8)
        pv = jnp.asarray(rng.integers(-127, 128, (L, NB, bs, nkv, D)), jnp.int8)
        sc = tuple(jnp.asarray(rng.random((L, NB, nkv * bs)) * 0.02 + 1e-3,
                               jnp.float32) for _ in range(2))
    else:
        pk = jnp.asarray(rng.normal(size=(L, NB, bs, nkv, D)), jnp.float32)
        pv = jnp.asarray(rng.normal(size=(L, NB, bs, nkv, D)), jnp.float32)
        sc = None
    q = jnp.asarray(rng.normal(size=(S, span, nq, D)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(S, nkv, span, D)), jnp.float32)
    vr = jnp.asarray(rng.normal(size=(S, nkv, span, D)), jnp.float32)
    tabs = rng.integers(1, NB, (S, MB)).astype(np.int32)
    tabs[0] = 0
    tabs[1, 2:] = 0
    tabs, lens = jnp.asarray(tabs), jnp.asarray([0, 21, MB * bs - span],
                                                jnp.int32)
    cfg = _cfg()

    @jax.jit
    def whole(pk, pv, sc):
        return jax.lax.scan(lambda c, i: (c, _paged_attention(
            q, pk, pv, tabs, lens, cfg, kv_row=(kr, vr), kv_scale=sc,
            layer=i)), 0, jnp.arange(L))[1]

    @jax.jit
    def sliced(pk, pv, sc):
        return jnp.stack([_paged_attention(
            q, pk[i], pv[i], tabs, lens, cfg, kv_row=(kr, vr),
            kv_scale=None if sc is None else (sc[0][i], sc[1][i]))
            for i in range(L)])
    np.testing.assert_array_equal(np.asarray(whole(pk, pv, sc)),
                                  np.asarray(sliced(pk, pv, sc)))


def test_paged_matches_contiguous_int8_kv_first_steps():
    """The quick tier's share of the int8 contract above: the block-diagonal
    int8 contractions of the paged read (ISSUE 27) give the int8 ring
    buffer's logits EXACTLY over the first steps."""
    _paged_vs_contiguous(8, jnp.bfloat16, steps=5)


def test_paged_kernel_agrees_with_xla_gather():
    """_paged_attention backend parity on mixed lengths (interpret-mode
    Pallas on CPU): the measured backend choice must never change
    results."""
    from deepspeed_tpu.models.transformer import _paged_attention
    cfg = _cfg()
    S, NB, MB, nkv, nq, bs, D = 3, 10, 3, 2, 4, 32, 16
    # D=16 < the kernel's TPU-lane sweet spot but interpret mode is exact
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (S, 1, nq, D), jnp.float32)
    pk = jax.random.normal(ks[1], (NB, bs, nkv, D), jnp.float32)
    pv = jax.random.normal(ks[2], (NB, bs, nkv, D), jnp.float32)
    kr = jax.random.normal(ks[3], (S, nkv, 1, D), jnp.float32)
    vr = jax.random.normal(ks[4], (S, nkv, 1, D), jnp.float32)
    tabs = jnp.asarray(
        np.random.default_rng(0).permutation(np.arange(1, 10))[:S * MB]
        .reshape(S, MB), jnp.int32)
    lens = jnp.asarray([0, 17, 96], jnp.int32)
    o_x = _paged_attention(q, pk, pv, tabs, lens, cfg, kv_row=(kr, vr),
                           backend="xla")
    o_p = _paged_attention(q, pk, pv, tabs, lens, cfg, kv_row=(kr, vr),
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------

def _serving(model=None, params=None, **serving):
    model = model or make_model(_cfg())
    defaults = dict(max_seqs=2, block_size=16, max_model_len=128,
                    decode_quantum=4, prompt_bucket=16)
    defaults.update(serving)
    return deepspeed_tpu.init_serving(model, config={}, serving=defaults,
                                      dtype=jnp.float32, params=params)


def test_serving_matches_oneshot_generate():
    """Two concurrent variable-length requests through the serving engine
    produce exactly the one-shot greedy generate() outputs."""
    model = make_model(_cfg())
    srv = _serving(model)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 128, size=(7,)).astype(np.int32), 9),
            (rng.integers(0, 128, size=(21,)).astype(np.int32), 6)]
    outs = srv.run(reqs)
    assert srv.scheduler.done
    eng = deepspeed_tpu.init_inference(
        model, config={"kv_cache_bits": 0}, dtype=jnp.float32,
        params=jax.device_get(srv.engine.params))
    for i, (p, n) in enumerate(reqs):
        one = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
        np.testing.assert_array_equal(outs[i], one)
    st = srv.stats()
    assert st["completed"] == 2 and st["generated_tokens"] == 15
    assert st["p50_ttft_ms"] > 0 and st["tok_per_sec"] > 0


@pytest.mark.slow
def test_serving_multitenant_queue_and_exhaustion():
    """More requests than slots + a pool sized BELOW full residency: the
    scheduler queues and (under growth pressure) preempts, every request
    still completes with the exact one-shot output, and the pool never
    OOMs. Also pins continuous batching actually interleaving: with 2
    slots and 5 requests the engine must run multiple rounds."""
    model = make_model(_cfg())
    # 9 usable blocks < 2 slots x 8 full-residency blocks
    srv = _serving(model, num_blocks=10)
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 128, size=(n,)).astype(np.int32), k)
            for n, k in ((30, 40), (25, 30), (5, 12), (40, 20), (17, 8))]
    outs = srv.run(reqs)
    assert len(outs) == 5 and srv.allocator.used_blocks == 0
    eng = deepspeed_tpu.init_inference(
        model, config={"kv_cache_bits": 0}, dtype=jnp.float32,
        params=jax.device_get(srv.engine.params))
    for i, (p, n) in enumerate(reqs):
        one = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
        np.testing.assert_array_equal(outs[i], one,
                                      err_msg=f"request {i} diverged")


@pytest.mark.slow
def test_serving_int8_kv_pool():
    """Quantized serving: int8 KV blocks end to end (the int8 pool rides
    the same scheduler/tables; dequant is fused into the read)."""
    model = make_model(_cfg())
    # kv_cache_bits=8 flows through the InferenceConfig surface
    srv = deepspeed_tpu.init_serving(
        model, config={"kv_cache_bits": 8}, serving=dict(
            max_seqs=2, block_size=16, max_model_len=128,
            decode_quantum=4, prompt_bucket=16), dtype=jnp.float32)
    assert srv.model.config.kv_cache_bits == 8
    assert srv.pools["k"].dtype == jnp.int8
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, 128, size=(12,)).astype(np.int32), 8),
            (rng.integers(0, 128, size=(33,)).astype(np.int32), 8)]
    outs = srv.run(reqs)
    # int8 parity bar: same as the contiguous int8 cache — compare against
    # the one-shot engine with the SAME int8 cache (bit-for-bit paged ==
    # contiguous is pinned in test_paged_matches_contiguous_int8_kv)
    eng = deepspeed_tpu.init_inference(
        model, config={"kv_cache_bits": 8}, dtype=jnp.float32,
        params=jax.device_get(srv.engine.params))
    for i, (p, n) in enumerate(reqs):
        one = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
        # windowed-read staging differs from the paged read here, so the
        # bar is greedy-token agreement on the first tokens + near-total
        got = outs[i]
        assert (got[:p.size + 4] == one[:p.size + 4]).all(), (got, one)
        assert (got == one).mean() > 0.9


# ---------------------------------------------------------------------------
# The decode step reads a flat list of the round's live blocks (ISSUE 38)
# ---------------------------------------------------------------------------

def _table_read(q, pk, pv, sc, tabs, lens, cfg, kr, vr, groups=1):
    """The table read of the parent (PR 27-37's ``_paged_token_attention``,
    kept here as the reference): the blocks of rectangular tables
    ``tabs[S, MB]`` gathered as ``[S, MB*bs, Nkv, D]`` and contracted per
    SLOT — every (query head, kv head) pair of P.V first and the diagonal
    afterwards."""
    from deepspeed_tpu.models.transformer import _quant_probs, _quant_query
    S, _, Nq, D = q.shape
    MB, (bs, Nkv) = tabs.shape[1], pk.shape[1:3]
    T, rep = MB * bs, Nq // Nkv
    vk, vv = (p[tabs].reshape(S, T, Nkv, D) for p in (pk, pv))
    qg = q.reshape(S, Nkv, rep, D)
    if sc is not None:
        ks, vs = (a[tabs].reshape(S, MB, Nkv, bs).transpose(0, 2, 1, 3)
                  .reshape(S, Nkv, T) for a in sc)
        X, G = groups, Nkv // groups
        eye = jnp.eye(G, dtype=jnp.int8)
        qi, qs = _quant_query(qg.astype(jnp.float32))
        qd = jnp.einsum("sxgrd,gh->sxgdhr", qi.reshape(S, X, G, rep, D), eye)
        scores = jnp.einsum("stxgd,sxgdhr->sxhrt", vk.reshape(S, T, X, G, D),
                            qd, preferred_element_type=jnp.int32
                            ).reshape(S, Nkv, rep, T).astype(jnp.float32)
        scores = scores * qs[..., None] * ks[:, :, None, :]
    else:
        scores = jnp.einsum("sgrd,stgd->sgrt", qg, vk).astype(jnp.float32)
    scores = scores * (1.0 / np.sqrt(D))
    keep = jnp.arange(T)[None, :] < lens[:, None]
    scores = jnp.where(keep[:, None, None, :], scores, -1e30)
    s_self = jnp.einsum("bgrd,bgtd->bgrt", qg, kr).astype(jnp.float32)
    s_self = s_self * (1.0 / np.sqrt(D))
    probs = jax.nn.softmax(jnp.concatenate([scores, s_self], axis=-1),
                           axis=-1)
    pp = probs[..., :T]
    if sc is not None:
        pvi, ps = _quant_probs(pp * vs[:, :, None, :])
        acc = jnp.einsum("sxhrt,stxgd->sxhrgd", pvi.reshape(S, X, G, rep, T),
                         vv.reshape(S, T, X, G, D),
                         preferred_element_type=jnp.int32)
        acc = jnp.sum(jnp.where((eye != 0)[None, None, :, None, :, None],
                                acc, 0), axis=4)
        out = (acc.reshape(S, Nkv, rep, D).astype(jnp.float32)
               * ps[..., None]).astype(q.dtype)
    else:
        out = jnp.einsum("sgrt,stgd->sgrd", pp.astype(q.dtype), vv)
    out = out + probs[..., T:].astype(q.dtype) * vr.astype(q.dtype)
    return out.reshape(S, 1, Nq, D)


def _block_list(tabs, held, run, pad_to):
    """``tabs[S, MB]`` with ``held[s]`` blocks a slot as a ``BlockList`` of
    ``pad_to`` blocks in runs of ``run``, the slots in REVERSE order (the
    list's order is nobody's business)."""
    from deepspeed_tpu.models.transformer import BlockList
    S, MB = tabs.shape
    wide, runs = -(-MB // run), pad_to // run
    ids = np.zeros((pad_to,), np.int32)
    where = np.full((runs,), S * wide, np.int32)
    inv = np.full((S, wide), runs, np.int32)
    n = 0
    for s in reversed(range(S)):
        k = -(-held[s] // run)
        ids[n * run:n * run + held[s]] = tabs[s, :held[s]]
        where[n:n + k] = s * wide + np.arange(k)
        inv[s, :k] = np.arange(n, n + k)
        n += k
    assert n <= runs
    return BlockList(*map(jnp.asarray, (ids, where, inv)))


@pytest.mark.parametrize("groups", [1, 2], ids=["one-chip", "tensor-2"])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("bits", [0, 8], ids=["float-pool", "int8-pool"])
def test_the_flat_read_is_the_table_read(bits, rep, groups, monkeypatch):
    """The read of a flat list of the live blocks (ISSUE 38) against the
    table read of the parent, at one query head per kv head and at four, on
    one chip and with the kv heads in two tensor groups: bit for bit on an
    int8 pool (integer sums are exact in any order, the float operations
    are the table read's on the same values), to 2e-6 on a float pool,
    whose P.V is summed per block and then over the blocks. Lists of one
    block an entry and of runs of two, padded and not, an empty slot, a
    slot at the table's end — and rectangular tables, which are the list of
    all their entries."""
    from deepspeed_tpu.models import transformer as T
    # an even table width: runs of two then cover the table's positions
    # and no more (an odd one rounds the view up by a block of exact zeros,
    # and the softmax's float sum is over another length)
    NB, bs, MB, nkv, D, S = 23, 16, 6, 4, 16, 4
    nq = nkv * rep
    rng = np.random.default_rng(bits + rep)
    if bits == 8:
        pk, pv = (jnp.asarray(rng.integers(-127, 128, (NB, bs, nkv, D)),
                              jnp.int8) for _ in range(2))
        sc = tuple(jnp.asarray(rng.random((NB, nkv * bs)) * 0.02 + 1e-3,
                               jnp.float32) for _ in range(2))
    else:
        pk, pv = (jnp.asarray(rng.normal(size=(NB, bs, nkv, D)), jnp.float32)
                  for _ in range(2))
        sc = None
    q = jnp.asarray(rng.normal(size=(S, 1, nq, D)), jnp.float32)
    kr, vr = (jnp.asarray(rng.normal(size=(S, nkv, 1, D)), jnp.float32)
              for _ in range(2))
    held = [0, 2, MB, 3]
    tabs = np.zeros((S, MB), np.int32)
    ids = iter(rng.permutation(np.arange(1, NB)))
    for s in range(S):
        tabs[s, :held[s]] = [next(ids) for _ in range(held[s])]
    lens = jnp.asarray([0, 21, MB * bs - 1, 3 * bs - 7], jnp.int32)
    cfg = _cfg(num_heads=nq, num_kv_heads=nkv, hidden_size=nq * D)
    want = np.asarray(jax.jit(lambda t: _table_read(
        q, pk, pv, sc, t, lens, cfg, kr, vr, groups))(jnp.asarray(tabs)))
    monkeypatch.setattr(T, "_head_groups", lambda: groups)
    for what in (jnp.asarray(tabs), _block_list(tabs, held, 1, 11),
                 _block_list(tabs, held, 1, 16), _block_list(tabs, held, 2, 14),
                 _block_list(tabs, held, 2, 24)):
        got = np.asarray(jax.jit(lambda t: T._paged_attention(
            q, pk, pv, t, lens, cfg, kv_row=(kr, vr), kv_scale=sc))(what))
        if bits == 8:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
        # where a step writes its fresh row: the block in column len // bs
        col = jnp.minimum(lens // bs, jnp.asarray(held) - 1)
        np.testing.assert_array_equal(
            np.asarray(T._block_at(what, col))[1:], tabs[np.arange(1, S),
                                                         np.asarray(col)[1:]])


def _drive_rounds(srv, script):
    """Run ``script`` ({round: [(prompt, max_new_tokens[, adapter]), ...]})
    to the end -> (outputs by submission order, [(the round's shape, blocks
    held, blocks listed — each request's padded to whole runs —, highest
    running slot)] as ``_tables_device`` built them)."""
    from deepspeed_tpu.inference.serving import _RUN
    rounds, seen, rids, outs = 0, [], [], {}
    build = srv._tables_device

    def spy(full=False):
        out = build(full)
        running = srv.scheduler.running
        assert out[2] == sum(len(r.block_ids) for r in running)
        seen.append((out[1], out[2],
                     sum(-(-len(r.block_ids) // _RUN) for r in running) * _RUN,
                     max(r.slot for r in running)))
        return out

    srv._tables_device = spy
    while rounds <= max(script) or not srv.scheduler.done:
        for prompt, n, *adapter in script.get(rounds, ()):
            rids.append(srv.add_request(prompt, n, adapter_id=sum(adapter)))
        for r in srv.step():
            outs[r.rid] = r.output
        rounds += 1
    return [outs[r] for r in rids], seen


def _first_shape_that_holds(srv, listed, highest):
    return next(sh for sh in srv._step_shapes()
                if sh[0] > highest and sh[0] * sh[1] >= listed)


@pytest.mark.parametrize("kv_bits", [0, 8], ids=["float-pool", "int8-pool"])
def test_list_length_follows_the_blocks_the_slots_hold(kv_bits, monkeypatch):
    """A decode round is handed a flat list of the blocks its requests
    hold, of the smallest length of the ladder that holds their SUM: a
    round whose sum overflows a rung takes the next, whoever the longest
    request is. What the list leaves out are exact zeros of the softmax, so
    the tokens are those of an engine whose ladder is the full list alone.
    ``stats()`` counts the rounds per ``"<slots>x<columns>"`` and the
    blocks gathered and held."""
    from deepspeed_tpu.inference import serving
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(29)

    def prompt(n):
        return rng.integers(0, 128, size=(n,)).astype(np.int32)

    # 3 slots x 16 columns of 16 tokens, lists of 12 / 24 / 48 blocks: a
    # request that grows from five blocks to ten, a short one beside it, and
    # a long one admitted mid-flight that takes the sum over both rungs and
    # finishes first
    script = {0: [(prompt(70), 90), (prompt(40), 20)],
              3: [(prompt(180), 60)]}

    def engine():
        return deepspeed_tpu.init_serving(
            model, config={"kv_cache_bits": kv_bits}, params=params,
            serving=dict(max_seqs=3, block_size=16, max_model_len=256,
                         decode_quantum=4, prompt_bucket=16),
            dtype=jnp.float32)

    srv = engine()
    assert serving._RUN == 2 and list(srv._step_shapes()) == [
        (3, 4), (3, 8), (3, 16)]
    outs, seen = _drive_rounds(srv, script)
    # every round: the first shape that holds the blocks listed
    for shape, held, listed, highest in seen:
        assert held <= listed <= held + len(srv.scheduler.running) + 3
        assert shape == _first_shape_that_holds(srv, listed, highest)
    shapes = [sh for sh, *_ in seen]
    assert set(shapes) == {(3, 4), (3, 8), (3, 16)}
    assert shapes[2] == (3, 4) and shapes[3] == (3, 8)   # the admission
    assert shapes[-1] == (3, 4) and (3, 16) in shapes    # ... and after it
    # the longest request alone says nothing: a round at the first rung
    # whose longest request holds more columns than the rung has a slot
    assert any(sh == (3, 4) and held > 4 for sh, held, *_ in seen)
    # the counters: one entry a shape, "<slots>x<columns>" as the
    # benchmark's reader parses it (slots x columns = blocks gathered),
    # summing to the decode rounds; the blocks gathered and held
    st = srv.stats()
    assert st["step_shape_rounds"] == {f"{S}x{W}": shapes.count((S, W))
                                       for S, W in srv._step_shapes()}
    assert st["kv_blocks_gathered"] == sum(
        int(k.split("x")[0]) * int(k.split("x")[1]) * n
        for k, n in st["step_shape_rounds"].items())
    assert st["kv_blocks_held"] == sum(held for _, held, *_ in seen)
    assert 1.0 <= st["kv_blocks_gathered"] / st["kv_blocks_held"] < 3.0
    srv.reset_stats()
    st = srv.stats()
    assert not any(st["step_shape_rounds"].values())
    assert st["kv_blocks_gathered"] == st["kv_blocks_held"] == 0
    # the same tokens as with the full list in every round
    monkeypatch.setattr(serving, "_list_ladder", lambda MB, shares: (MB,))
    full = engine()
    assert list(full._step_shapes()) == [(3, 16)]
    want, seen_full = _drive_rounds(full, script)
    assert {sh for sh, *_ in seen_full} == {(3, 16)}
    for got, ref in zip(outs, want):
        np.testing.assert_array_equal(got, ref)


def test_every_slot_at_max_model_len_takes_the_full_program():
    """The worst case keeps its program: with every slot's context at
    ``max_model_len`` the list is the whole of ``max_seqs x MB``."""
    srv = _serving(max_seqs=3, max_model_len=128, block_size=16,
                   decode_quantum=4)
    rng = np.random.default_rng(38)
    script = {0: [(rng.integers(0, 128, size=(100,)).astype(np.int32), 28)
                  for _ in range(3)]}
    _, seen = _drive_rounds(srv, script)
    top = max(srv._step_shapes(), key=lambda sh: sh[0] * sh[1])
    assert top == (3, 8) and seen[-1][0] == top and seen[-1][1] == 3 * 8
    srv.close()


@pytest.mark.parametrize("kind", ["float-pool", "int8-pool", "lora"])
def test_slot_count_follows_the_highest_running_slot(kind, monkeypatch):
    """A decode round is handed only the slots it can reach (ISSUE 33): the
    smallest count of the ladder above the highest running slot — with the
    short lists of the few slots' programs while the blocks held fit them,
    and ``max_seqs`` otherwise. A row's attention sees no other row and the
    rows left out hold no request, so the tokens are those of an engine
    whose ladder is ``max_seqs`` alone; the per-slot token vector stays
    whole, so a round that widens again finds the tokens prefills left
    beyond the narrow rounds' rows."""
    from deepspeed_tpu.inference import serving
    from deepspeed_tpu.inference.lora import make_random_adapter
    cfg = _cfg()
    model = make_model(cfg)
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(33)
    lora = kind == "lora"

    def req(n, new, i=0):
        return (rng.integers(0, 128, size=(n,)).astype(np.int32), new,
                *([i % 3] if lora else []))

    # 40 slots, ladder 16 / 40. Eighteen arrivals fill slots 0-17; sixteen
    # of them leave after two rounds, the straggler in slot 17 keeps the
    # rounds wide until it leaves, the one in slot 0 runs on; two arrivals
    # in round 4 take the lowest free slots, 1 and 2 (the stack would have
    # given them the slots freed last), and seventeen more in round 12 widen
    # the rounds again, their first tokens waiting in slots the narrow
    # rounds left out
    script = {0: [req(60, 60)] + [req(5 + i, 6, i) for i in range(16)]
              + [req(20, 22, 1)],
              4: [req(12, 30, 2), req(30, 26)],
              12: [req(7 + i, 5, i) for i in range(17)]}

    def engine():
        srv = deepspeed_tpu.init_serving(
            model, config={"kv_cache_bits": 8 if kind == "int8-pool" else 0},
            params=params, dtype=jnp.float32,
            serving=dict(max_seqs=40, block_size=16, max_model_len=128,
                         decode_quantum=4, prompt_bucket=16,
                         **(dict(adapter_slots=3, lora_rank=4) if lora
                            else {})))
        for a in (1, 2) if lora else ():
            srv.register_adapter(a, make_random_adapter(cfg, 4, seed=a,
                                                        scale=0.2))
        return srv

    srv = engine()
    assert srv._slot_counts == (16, 40)
    # 8 columns: an eighth and a quarter of 16 x 8 are both one run a slot
    assert list(srv._step_shapes()) == [(16, 2), (40, 4), (40, 8)]
    compiled = []

    def on(name, secs, **kw):
        compiled.append((name.rsplit("/", 1)[-1], str(kw.get("fun_name"))))

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        outs, seen = _drive_rounds(srv, script)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    # every program is lowered once, at the first decode round
    assert list(srv._get_quantum_step()) == list(srv._step_shapes())
    assert compiled.count(("jaxpr_to_mlir_module_duration", "jit(step)")) == 3
    # every round: the first shape above the highest running slot that
    # holds the blocks listed
    for shape, _, listed, highest in seen:
        assert shape == _first_shape_that_holds(srv, listed, highest)
    slots = [shape[0] for shape, *_ in seen]
    assert slots[:2] == [40, 40]
    # the straggler in slot 17 (22 tokens: six rounds) keeps the round wide
    # after the sixteen short ones have left, and the round narrows with it
    assert [h for *_, h in seen[2:6]] == [17] * 4 and slots[2:6] == [40] * 4
    assert slots[6:12] == [16] * 6           # with the two new arrivals in
    assert [h for *_, h in seen[6:8]] == [2, 2]
    assert slots[12] == 40 and slots[-1] == 16
    # the counter: one entry a shape, summing to the decode rounds
    st = srv.stats()
    shapes = [shape for shape, *_ in seen]
    assert st["step_shape_rounds"] == {
        f"{S}x{W}": shapes.count((S, W)) for S, W in srv._step_shapes()}
    assert sum(st["step_shape_rounds"].values()) == len(seen)
    srv.reset_stats()
    st = srv.stats()
    assert not any(st["step_shape_rounds"].values())
    assert set(st["step_shape_rounds"]) == {
        f"{S}x{W}" for S, W in srv._step_shapes()}
    assert srv._tokens.shape == (40,)
    # the same tokens as with every slot in every round
    monkeypatch.setattr(serving, "_slot_ladder", lambda S: (S,))
    full = engine()
    assert full._slot_counts == (40,) and len(full._step_shapes()) == 3
    want, seen_full = _drive_rounds(full, script)
    assert {shape[0] for shape, *_ in seen_full} == {40}
    assert [h for *_, h in seen_full] == [h for *_, h in seen]
    for got, ref in zip(outs, want):
        np.testing.assert_array_equal(got, ref)


def test_no_step_program_is_built_after_the_first_decode_round():
    """Every width's step program is built with the first, from abstract
    arguments: however the lengths move afterwards, no decode step is
    lowered or compiled again — also after the backend swap has thrown the
    programs away and the next decode round has rebuilt them."""
    events = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    names = []

    def on(name, secs, **kw):
        if name in events:
            names.append(str(kw.get("fun_name", "?")))

    # head_dim 64: the forced Pallas backend runs (interpret mode), so the
    # degradation to the gather backend is the real swap
    srv = _serving(make_model(_cfg(hidden_size=256)), max_seqs=3,
                   max_model_len=256, decode_backend="pallas")
    assert srv.decode_backend == "pallas"
    rng = np.random.default_rng(7)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:

        def load():
            # lengths whose sum visits every list: 12, 24 and 48 blocks
            return [(rng.integers(0, 128, size=(n,)).astype(np.int32), k)
                    for n, k in ((70, 8), (100, 70), (150, 70))]

        for swap in (False, True):
            if swap:
                srv._degrade_backend()
                assert srv.decode_backend == "xla"
            srv.reset_stats()
            del names[:]
            srv.add_request(np.arange(5, dtype=np.int32), 6)
            srv.step()                    # the first decode round
            # one lowering and one compile a shape, here and nowhere else
            assert names.count("jit(step)") == 2 * len(srv._step_shapes())
            del names[:]
            srv.run(load())
            assert "jit(step)" not in names, names
            # the Pallas kernel has one program a slot count, the gather
            # backend one a list length: the load visits every one
            rounds = srv.stats()["step_shape_rounds"]
            assert len(rounds) == (3 if swap else 1) and all(
                rounds.values()), rounds
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
        srv.close()


def test_backend_selection_event_and_reason():
    """The backend choice short-circuits with a recorded reason and lands
    in the telemetry event stream. Capability gates take precedence over
    everything (a FORCED pallas that the decode step would silently
    downgrade must be refused with the why), then the non-TPU check."""
    from deepspeed_tpu.robustness import events
    events.clear()
    srv = _serving()                      # head_dim 16: kernel-ineligible
    assert srv.decode_backend == "xla"
    assert srv.backend_bench["reason"] == "head_dim 16 < 64"
    evs = events.history("decode_backend_selected")
    assert evs and evs[-1]["backend"] == "xla"
    # forced pallas on an ineligible config: refused, reason says why
    srv2 = _serving(model=make_model(_cfg()), decode_backend="pallas")
    assert srv2.decode_backend == "xla"
    assert "pallas unavailable" in srv2.backend_bench["reason"]
    # kernel-eligible shape on CPU: the non-TPU short-circuit
    big = make_model(_cfg(hidden_size=256))   # head_dim 64
    srv3 = _serving(model=big)
    assert srv3.backend_bench["reason"] == "non-TPU backend"


@pytest.mark.parametrize("slots,max_model_len,n_kv,want,why", [
    (16, 8192, 8, "pallas", "the kernel is the cheaper read"),
    (16, 4096, 8, "xla", "the XLA read is cheaper"),  # the call's fixed cost
    (64, 3072, 8, "xla", "under one wave"),     # a quarter table: 12 blocks
    (16, 8192, 2, "xla", "head-major")])
def test_an_int8_pools_read_is_priced_not_timed(monkeypatch, slots,
                                                max_model_len, n_kv, want,
                                                why):
    """``decode_backend: "auto"`` on an int8 pool, asked as on the chip: the
    choice is ``paged_read_price`` at the engine's shapes — nothing is timed
    — and the event and ``stats()`` carry the two prices and the choice."""
    from deepspeed_tpu.inference import serving as serving_mod
    from deepspeed_tpu.robustness import events

    def timed(*a, **k):
        raise AssertionError("an int8 pool's read was timed")
    monkeypatch.setattr(serving_mod, "measure_paged_backends", timed)
    model = make_model(_cfg(hidden_size=1024, num_layers=1, num_heads=8,
                            num_kv_heads=n_kv, head_dim=128,
                            max_seq_len=max_model_len))
    events.clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        srv = deepspeed_tpu.init_serving(
            model, config={"kv_cache_bits": 8}, dtype=jnp.float32,
            serving=dict(max_seqs=slots, block_size=64, num_blocks=130,
                         max_model_len=max_model_len, prompt_bucket=64))
    finally:
        monkeypatch.undo()
    assert srv.decode_backend == want, srv.backend_bench
    ev = events.history("decode_backend_selected")[-1]
    assert ev["backend"] == want and why in ev["reason"], ev
    assert ev["xla_bytes"] > 0 and ev["kernel_bytes"] > 0
    assert ev["priced"] == want
    st = srv.stats()
    assert st["decode_backend"] == want
    assert st["decode_backend_choice"]["kernel_bytes"] == ev["kernel_bytes"]
    # the kernel's step has ONE program a slot count, at the table's width
    if want == "pallas":
        assert list(srv._step_shapes()) == [(16, srv.MB)]
    srv.close()


def test_kv_cache_bits_default_is_context_aware():
    """The r5 regression fix: short-context engines keep the compute-dtype
    cache (decode there is op-latency bound; blanket int8 cost the ctx-256
    rung 2.6%), long-context engines default to int8."""
    model = make_model(_cfg())
    short = deepspeed_tpu.init_inference(model, config={"max_tokens": 256},
                                         dtype=jnp.float32)
    assert short.model.config.kv_cache_bits == 0
    model2 = make_model(_cfg(max_seq_len=4096))
    long = deepspeed_tpu.init_inference(model2,
                                        config={"max_tokens": 2048},
                                        dtype=jnp.float32)
    assert long.model.config.kv_cache_bits == 8


def test_init_serving_respects_explicit_max_tokens():
    """The serving-cap default must not override an explicit user
    max_tokens (which drives the context-aware int8-KV default)."""
    model = make_model(_cfg(max_seq_len=4096))
    srv = deepspeed_tpu.init_serving(
        model, config={"max_tokens": 256},
        serving=dict(max_seqs=2, block_size=16, max_model_len=2048),
        dtype=jnp.float32)
    assert srv.engine.config.max_tokens == 256
    assert srv.model.config.kv_cache_bits == 0    # user's short-ctx intent
    srv2 = deepspeed_tpu.init_serving(
        model, serving=dict(max_seqs=2, block_size=16, max_model_len=2048),
        dtype=jnp.float32)
    assert srv2.engine.config.max_tokens == 2048  # default: serving cap
    assert srv2.model.config.kv_cache_bits == 8


def test_init_serving_clamps_max_tokens_to_model_cap():
    """Over-asking max_model_len on a short-context model must not flip
    the engine's int8-KV default: max_tokens clamps to the model cap the
    same way the serving cap does (the r5 regression class)."""
    model = make_model(_cfg())                     # max_seq_len 256
    srv = deepspeed_tpu.init_serving(model, serving=dict(
        max_seqs=2, block_size=16, max_model_len=2048), dtype=jnp.float32)
    assert srv.max_model_len == 256
    assert srv.engine.config.max_tokens == 256
    assert srv.model.config.kv_cache_bits == 0


def test_measure_paged_backends_returns_timings():
    """The shared micro-bench recipe (engine init + bench evidence) runs
    both backends and returns positive timings (interpret-mode Pallas on
    CPU — tiny shapes)."""
    from deepspeed_tpu.inference.serving import measure_paged_backends
    cfg = _cfg()
    nkv, hd = cfg.kv_heads, cfg.dim_per_head
    kp = jnp.zeros((5, 8, nkv, hd), jnp.float32)
    xla_ms, pallas_ms = measure_paged_backends(
        cfg, kp, kp, max_seqs=2, MB=2, block_size=8, num_blocks=5,
        dtype=jnp.float32, iters=1)
    assert xla_ms > 0 and pallas_ms > 0


def test_add_request_validates_context_cap():
    srv = _serving()
    with pytest.raises(ValueError, match="max_model_len"):
        srv.add_request(np.arange(120, dtype=np.int32), 64)


def test_pool_must_fit_one_sequence():
    with pytest.raises(ValueError, match="num_blocks"):
        _serving(num_blocks=4)   # max_model_len 128 / bs 16 needs 8 + trash


def test_paged_cache_leak_corpus_entry():
    """The seeded defect must fire `memory-peak`; the correctly-freed twin
    stays under the identical budget (regression floor for modeling the
    block pool in MemoryLint)."""
    from deepspeed_tpu.analysis.analyzers import AnalysisSettings
    from deepspeed_tpu.analysis.corpus import (PAGED_LEAK_BUDGET,
                                               _paged_decode_program,
                                               run_corpus)
    from deepspeed_tpu.analysis.lint import analyze_programs
    from deepspeed_tpu.analysis.corpus import _FakePlan, _stage0_config
    rep = run_corpus("paged-cache-leak")
    assert not rep.ok
    assert any(f.rule == "memory-peak" for f in rep.findings)
    art = _paged_decode_program(num_blocks=33)
    rep2 = analyze_programs(
        [art], _stage0_config(), _FakePlan(),
        settings=AnalysisSettings(max_hbm_bytes=PAGED_LEAK_BUDGET))
    assert rep2.ok, [f.rule for f in rep2.findings]


# ---------------------------------------------------------------------------
# Reliability tier (ISSUE 10): typed allocator errors, aging, watermarks,
# deadlines, fault recovery, drain/resume
# ---------------------------------------------------------------------------

from deepspeed_tpu.inference.kv_cache import InvalidBlock  # noqa: E402
from deepspeed_tpu.inference.scheduler import AdmissionRejected  # noqa: E402
from deepspeed_tpu.robustness import events as rb_events  # noqa: E402
from deepspeed_tpu.robustness import faults as rb_faults  # noqa: E402
from deepspeed_tpu.robustness.faults import (FaultInjector,  # noqa: E402
                                             FaultSchedule)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """Reliability tests install process-global injectors; never leak one
    into a neighboring test."""
    rb_faults.clear()
    yield
    rb_faults.clear()


class TestInvalidBlock:
    def test_out_of_range_free_raises_typed_with_owner(self):
        """Both directions of the satellite: an out-of-range id (high OR
        negative — the negative case previously WRAPPED into another
        block's held bit via Python list indexing) raises InvalidBlock
        naming the block and owning sequence; a valid free still works."""
        a = BlockAllocator(8)
        ids = a.alloc(3)
        with pytest.raises(InvalidBlock, match=r"block id 99.*sequence 7"):
            a.free([99], owner=7)
        with pytest.raises(InvalidBlock, match=r"block id -1"):
            a.free([-1])
        # the failed frees changed nothing: the held blocks free cleanly
        a.free(ids, owner=7)
        assert a.free_blocks == 7
        with pytest.raises(ValueError, match="double free"):
            a.free([ids[0]])

    def test_invalid_block_is_a_value_error(self):
        # callers catching the pre-typed ValueError keep working
        assert issubclass(InvalidBlock, ValueError)

    def test_reserve_squeezes_visible_pool_only(self):
        a = BlockAllocator(8)
        a.set_reserve(5)
        assert a.free_blocks == 2
        assert not a.can_alloc(3)
        got = a.alloc(2)
        with pytest.raises(BlockPoolExhausted, match="squeezed"):
            a.alloc(1)
        a.set_reserve(0)
        assert a.free_blocks == 5
        a.free(got)


class TestSchedulerAntiStarvation:
    def test_resumed_tenant_is_not_revictimized(self):
        """The satellite pin, 2-slot pool: when growth pressure returns
        and the only co-tenant is a request that was ALREADY preempted
        once, the victim ROTATES — the grower yields — instead of
        re-preempting the same resumed request. The pre-aging
        ``running.pop()`` picked the resumed request every time (it was
        always the newest list entry): the livelock this pins against."""
        alloc, s = _sched(num_blocks=7, max_seqs=2, bs=16, quantum=4, mb=8)
        r1 = s.submit(np.arange(30), 64)       # 3 blocks each
        r2 = s.submit(np.arange(30), 64)
        assert len(s.schedule()["admitted"]) == 2
        assert (r1.admission_seq, r2.admission_seq) == (0, 1)
        assert alloc.free_blocks == 0
        # r2 stands in for a request that was preempted once and resumed:
        # same slot, same blocks, but it carries the aging bonus
        r2.preemptions = 1
        r1.cached_rows = 46                    # r1 needs a 4th block
        r1.generated = list(range(16))
        out = s.schedule()
        # effective seq: r1 = 0, r2 = 1 - AGING_BONUS*1 = -1 -> the GROWER
        # rotates out; r2 keeps its slot and makes progress
        assert out["preempted"] == [r1]
        assert r2.state == "running" and r2.preemptions == 1
        assert r1.state == "waiting" and r1.preemptions == 1
        # r1's generated tokens survive for its re-prefill resume
        assert r1.generated == list(range(16))

    def test_two_slot_adversarial_no_repeat_victim(self):
        """End-to-end adversarial pattern: 2 slots, a 5-block pool, a new
        arrival every round, every tenant growing a quantum per round and
        finishing at 24 tokens. Sustained churn must never preempt the
        same request twice in a row while another tenant was running, and
        the queue keeps draining (no livelock: requests finish)."""
        alloc, s = _sched(num_blocks=5, max_seqs=2, bs=16, quantum=8,
                          mb=8)
        reqs = [s.submit(np.arange(16), 24) for _ in range(2)]
        victims = []          # (rid, tenants alive at preemption)
        done = 0
        for rnd in range(16):
            out = s.schedule()
            victims += [(r.rid, len(s.running) + len(out["preempted"]))
                        for r in out["preempted"]]
            for r in list(s.running):  # a quantum of growth per round
                r.generated.extend([1] * 8)
                r.cached_rows = len(r.prompt) + len(r.generated)
                if len(r.generated) >= r.max_new_tokens:
                    s.finish(r)
                    done += 1
            reqs.append(s.submit(np.arange(16), 24))   # adversarial stream
        assert len(victims) >= 3, victims
        repeats = [(a, b) for a, b in zip(victims, victims[1:])
                   if a[0] == b[0] and b[1] >= 2]
        assert not repeats, f"victim repeated with tenants alive: {victims}"
        assert done >= 5          # the pool kept serving through the churn
        # every preempted request either finished or is still en route —
        # none is starved with multiple preemptions
        for rid, _ in victims:
            req = next(r for r in reqs if r.rid == rid)
            assert req.preemptions <= 2, (rid, req.preemptions)


class TestAdmissionWatermarks:
    def test_queue_watermark_sheds_typed_and_counts(self):
        rb_events.clear()
        srv = _serving(max_queue=1)
        srv.add_request(np.arange(4, dtype=np.int32), 4)
        with pytest.raises(AdmissionRejected, match="queue_full"):
            srv.add_request(np.arange(4, dtype=np.int32), 4)
        assert srv.stats()["shed"] == 1.0
        evs = rb_events.history("request_shed")
        assert evs and evs[-1]["reason"] == "queue_full"
        # the accepted request still completes
        while not srv.scheduler.done:
            srv.step()
        assert srv.stats()["completed"] == 1.0

    def test_pool_watermark_sheds_under_pressure(self):
        srv = _serving(pool_watermark=0.05)
        srv.add_request(np.arange(8, dtype=np.int32), 32)
        srv.step()                       # admitted: pool now holds blocks
        assert srv.allocator.used_fraction > 0.05
        with pytest.raises(AdmissionRejected, match="pool_pressure"):
            srv.add_request(np.arange(8, dtype=np.int32), 4)

    def test_unbounded_queue_corpus_both_directions(self):
        """The seeded defect fires `queue-growth`; the watermarked twin
        sheds (typed) and passes — both runnable from the CLI too
        (analysis.lint --corpus / analysis.serving_lint --max-queue)."""
        from deepspeed_tpu.analysis.corpus import run_corpus
        from deepspeed_tpu.analysis.serving_lint import audit_admission
        rep = run_corpus("serving-unbounded-queue")
        assert not rep.ok
        assert any(f.rule == "queue-growth" for f in rep.findings)
        assert rep.meta["shed"] == 0
        twin = audit_admission(max_queue=8)
        assert twin.ok, [f.rule for f in twin.findings]
        assert twin.meta["shed"] > 0                 # typed, not silent
        assert max(twin.meta["queue_depths"]) <= 8   # bounded


class TestDeadlines:
    def test_total_deadline_cancels_mid_decode_and_frees_blocks(self):
        rb_events.clear()
        srv = _serving()
        rid = srv.add_request(np.arange(9, dtype=np.int32), 64)
        srv.step()                       # admits + generates a quantum
        held = srv.allocator.used_blocks
        assert held > 0
        # the budget expires while the request is mid-decode (set after
        # the first round so compile wall-time can't race the clock)
        srv._requests[rid].deadline_ms = 1e-3
        srv.step()                       # boundary sweep: past deadline
        req = srv._requests[rid]
        assert req.state == "cancelled"
        assert req.cancel_reason == "total_deadline"
        assert srv.allocator.used_blocks == 0    # blocks returned mid-decode
        assert srv.scheduler.done
        st = srv.stats()
        assert st["deadline_misses"] == 1.0 and st["cancelled"] == 1.0
        assert st["completed"] == 0.0
        # partial output stays readable; the miss is a structured event
        assert len(srv.cancelled) == 1 and len(req.output) >= 9
        ev = rb_events.history("deadline_miss")[-1]
        assert ev["rid"] == rid and ev["kind"] == "total"

    def test_ttft_deadline_sheds_queued_request(self):
        srv = _serving(max_seqs=1)
        # slot taken by a long request; the queued one can never make TTFT
        first = srv.add_request(np.arange(5, dtype=np.int32), 24)
        queued = srv.add_request(np.arange(5, dtype=np.int32), 8,
                                 ttft_deadline_ms=1e-3)
        srv.step()              # round 1: `first` admitted and decoding
        srv.step()              # boundary sweep sheds the queued request
        q = srv._requests[queued]
        assert q.state == "cancelled" and q.cancel_reason == "ttft_deadline"
        assert not q.generated
        # `first` got its first token in round 1: TTFT no longer applies
        f = srv._requests[first]
        assert f.first_token_t is not None
        assert f.state in ("running", "finished")
        while not srv.scheduler.done:
            srv.step()
        assert f.state == "finished"
        st = srv.stats()
        assert st["deadline_misses"] == 1.0 and st["completed"] == 1.0


class TestFaultRecovery:
    def test_dispatch_fault_recovers_bit_identical(self):
        """An injected failed dispatch mid-serve: the engine preempts all,
        rebuilds the pool, re-prefills from host cursors — outputs exactly
        equal the fault-free run, recovery evented."""
        model = make_model(_cfg())
        params = model.init(jax.random.PRNGKey(0))
        import jax as _jax
        rng = np.random.default_rng(2)
        reqs = [(rng.integers(0, 128, size=(n,)).astype(np.int32), k)
                for n, k in ((7, 16), (21, 12))]

        def fresh():
            return _serving(model=model,
                            params=_jax.device_get(params))

        base = fresh().run(list(reqs))
        rb_events.clear()
        inj = rb_faults.install(FaultInjector(FaultSchedule([
            {"kind": "decode_dispatch", "at": 1},
            {"kind": "pool_exhaust", "at": 3},
        ], seed=0)))
        srv = fresh()
        outs = srv.run(list(reqs))
        assert {f["kind"] for f in inj.fired} == {"decode_dispatch",
                                                  "pool_exhaust"}
        st = srv.stats()
        assert st["recoveries"] >= 1 and st["recovery_ms"] > 0
        assert rb_events.history("serving_recovered")
        for i in base:
            np.testing.assert_array_equal(base[i], outs[i],
                                          err_msg=f"request {i}")

    def test_round_failure_exhausts_retries_and_raises(self):
        """A deterministic fault (times > retries) must surface, not spin:
        the typed failure names the retry budget."""
        rb_faults.install(FaultInjector(FaultSchedule([
            {"kind": "decode_dispatch", "at": 0, "times": 99},
        ], seed=0)))
        srv = _serving(round_retries=1)
        srv.add_request(np.arange(5, dtype=np.int32), 4)
        with pytest.raises(RuntimeError, match="recovery retries"):
            srv.step()
        assert srv.stats()["recoveries"] == 2.0   # 1 try + 1 retry


class TestDrainResume:
    def test_drain_resume_bit_identical(self, tmp_path):
        """SIGTERM contract minus the signal: drain() checkpoints block
        tables + host cursors + generated tokens through the integrity
        chain; a FRESH engine resumes them and the merged outputs equal
        the uninterrupted run byte for byte."""
        import jax as _jax
        model = make_model(_cfg())
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(4)
        reqs = [(rng.integers(0, 128, size=(n,)).astype(np.int32), k)
                for n, k in ((7, 12), (21, 8), (12, 10))]

        def fresh():
            return _serving(model=model, params=_jax.device_get(params))

        base = fresh().run(list(reqs))

        rb_events.clear()
        srv = fresh()
        for p, k in reqs:
            srv.add_request(p, k)
        srv.step()                        # partial progress
        tag_dir = srv.drain(str(tmp_path))
        from deepspeed_tpu.robustness import integrity
        ok, reason = integrity.validate_tag(tag_dir)
        assert ok, reason                 # manifest + COMMITTED, verified
        with pytest.raises(AdmissionRejected, match="draining"):
            srv.add_request(np.arange(3, dtype=np.int32), 4)

        srv2 = fresh()
        rids = srv2.resume(str(tmp_path))
        assert rids                       # something was in flight
        outs = {}
        while not srv2.scheduler.done:
            for r in srv2.step():
                outs[r.rid] = r.output
        for r in srv._finished:           # finished before the drain
            outs.setdefault(r.rid, r.output)
        assert set(outs) == set(base)
        for i in base:
            np.testing.assert_array_equal(base[i], outs[i],
                                          err_msg=f"request {i}")
        assert rb_events.history("serving_drained")
        assert rb_events.history("serving_resumed")

    def test_resume_refuses_torn_drain(self, tmp_path):
        """A drain without its COMMITTED marker (crash mid-drain) must be
        skipped by tag resolution, not half-loaded."""
        srv = _serving()
        srv.add_request(np.arange(5, dtype=np.int32), 8)
        tag_dir = srv.drain(str(tmp_path))
        import os
        os.remove(os.path.join(tag_dir, "COMMITTED"))
        srv2 = _serving()
        with pytest.raises(FileNotFoundError, match="integrity-valid"):
            srv2.resume(str(tmp_path))

    def test_resume_refuses_smaller_engine(self, tmp_path):
        """Resuming into an engine with a smaller context cap must refuse
        loudly — past the block-table width the growth clamp would
        silently corrupt the continuation. Cross-replica (ISSUE 11): the
        refusal is TYPED (ResumeIncompatible) and fires on the drained
        engine's recorded geometry, so a whole-drain resume onto a
        smaller pool refuses even before any individual request is
        checked."""
        from deepspeed_tpu.inference.serving import ResumeIncompatible
        srv = _serving()                          # max_model_len 128
        srv.add_request(np.arange(60, dtype=np.int32), 60)
        srv.drain(str(tmp_path))
        small = _serving(max_model_len=64)
        with pytest.raises(ResumeIncompatible, match="max_model_len"):
            small.resume(str(tmp_path))
        # the typed error names the block-table geometry both sides
        with pytest.raises(ValueError, match="table width"):
            small.resume(str(tmp_path))

    def test_cross_replica_resume_larger_engine_ok(self, tmp_path):
        """The other direction: a foreign drain resumed onto a LARGER
        engine continues byte-identically (re-prefill determinism across
        engines — the router's failover bar)."""
        import jax as _jax
        model = make_model(_cfg())
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(9)
        reqs = [(rng.integers(0, 128, size=(n,)).astype(np.int32), k)
                for n, k in ((6, 10), (18, 8))]
        small_kw = dict(max_model_len=64, max_seqs=2)
        base = _serving(model=model, params=_jax.device_get(params),
                        **small_kw).run(list(reqs))

        srv = _serving(model=model, params=_jax.device_get(params),
                       **small_kw)
        for p, k in reqs:
            srv.add_request(p, k)
        srv.step()                        # partial progress
        srv.drain(str(tmp_path), source="r-small")
        big = _serving(model=model, params=_jax.device_get(params),
                       max_model_len=128, max_seqs=4)
        rids = big.resume(str(tmp_path))
        assert rids
        outs = {}
        while not big.scheduler.done:
            for r in big.step():
                outs[r.rid] = r.output
        for r in srv._finished:
            outs.setdefault(r.rid, r.output)
        assert set(outs) == set(base)
        for i in base:
            np.testing.assert_array_equal(base[i], outs[i],
                                          err_msg=f"request {i}")

    def test_cross_block_size_resume_compares_tokens_not_widths(
            self, tmp_path):
        """Geometry check is in TOKENS: a strictly larger engine with
        BIGGER blocks (hence a numerically smaller table width) must not
        be falsely refused."""
        srv = _serving()                    # 128 tokens / 16-token blocks
        srv.add_request(np.arange(20, dtype=np.int32), 8)
        srv.drain(str(tmp_path))
        # 256-token cap via 64-token blocks: table width 4 < 8, capacity 2x
        big = _serving(max_model_len=256, block_size=64, prompt_bucket=64)
        assert big.resume(str(tmp_path))    # restores, no refusal

    def test_accept_migration_per_request_check(self, tmp_path):
        """The router's per-request migration path: records that FIT a
        smaller survivor restore fine; the one that can't raises the
        typed ResumeIncompatible (the router then tries the next
        survivor), and the refusal is all-or-nothing for its batch."""
        from deepspeed_tpu.inference.serving import (ResumeIncompatible,
                                                     load_drain_state)
        srv = _serving()                          # max_model_len 128
        srv.add_request(np.arange(8, dtype=np.int32), 8)      # fits 64
        srv.add_request(np.arange(50, dtype=np.int32), 40)    # needs 90
        srv.drain(str(tmp_path), source="r-big")
        state = load_drain_state(str(tmp_path))
        assert state["source"] == "r-big"
        assert state["engine"]["max_model_len"] == 128
        small = _serving(max_model_len=64)
        fits = [r for r in state["requests"] if r["rid"] == 0]
        too_big = [r for r in state["requests"] if r["rid"] == 1]
        assert small.accept_migration(fits, source="r-big") == [0]
        with pytest.raises(ResumeIncompatible, match="max_model_len"):
            small.accept_migration(too_big, source="r-big")
        # all-or-nothing: the failed batch enqueued nothing
        assert small.scheduler.num_waiting == 1


# ---------------------------------------------------------------------------
# The loop looks ahead (ISSUE 36): a call of step() dispatches round k+1
# while the last steps of round k are still unfetched, then fetches those,
# its prefills' first tokens and the first steps of round k+1
# ---------------------------------------------------------------------------

_AHEAD_FAMILIES = {
    "mistral": dict(),
    "mixtral": dict(num_experts=4, top_k=2, drop_tokens=False),
    "olmoe": dict(num_experts=8, top_k=4, drop_tokens=False, qk_norm=True,
                  norm_topk_prob=False, num_kv_heads=4),
    "hybrid": dict(position_type="none", activation="relu2", num_experts=4,
                   top_k=2, drop_tokens=False, moe_scoring="sigmoid",
                   routed_scaling_factor=2.5, moe_shared_size=48,
                   block_pattern="ME*E", num_layers=4, head_dim=16,
                   mamba_num_heads=4, mamba_head_dim=16, mamba_n_groups=2,
                   ssm_state_size=16, mamba_chunk=16, intermediate_size=64),
    "looped": dict(ut_steps=3, sandwich_norm=True, exit_gate=True,
                   num_kv_heads=4),
}


def _ahead_load(n=10, seed=7, new=(2, 30)):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 128, size=(int(rng.integers(3, 40)),)
                          ).astype(np.int32), int(rng.integers(*new)))
            for _ in range(n)]


def _drive(srv, reqs, parent_order=False, each=None):
    """The requests through ``step()`` as ``serve_job.drive`` calls it ->
    (outputs in submission order, requests running after each call).
    ``parent_order``: fetch the steps left in flight right after the call
    that dispatched them, so every schedule sees what the parent's saw — a
    finish at the commit, its slot given out in the NEXT call."""
    rids = [srv.add_request(p, n) for p, n in reqs]
    occupancy = []
    while srv.scheduler.running or srv.scheduler.num_waiting:
        srv.step()
        if parent_order and srv._inflight is not None:
            rec, srv._inflight = srv._inflight, None
            srv._land(rec, None, [], {"fetch_ms": 0.0, "commit_ms": 0.0})
        occupancy.append(len(srv.scheduler.running))
        if each is not None:
            each(srv)
    done = {r.rid: r for r in srv._finished}
    return [done[i].output for i in rids], occupancy


@pytest.mark.parametrize("kv_bits", [0, 8], ids=["float-pool", "int8-pool"])
@pytest.mark.parametrize("family", list(_AHEAD_FAMILIES))
def test_looking_ahead_gives_the_parent_orders_tokens(family, kv_bits):
    """Ten requests over three slots: token for token what the same engine
    gives when every round is fetched whole in the call that dispatched
    it, with no more decode rounds and no lower occupancy, whatever the
    model keeps per slot (K/V planes, a recurrent state row, planes per
    pass)."""
    model = make_model(_cfg(**_AHEAD_FAMILIES[family]))
    srv = deepspeed_tpu.init_serving(
        model, config={"kv_cache_bits": kv_bits}, dtype=jnp.float32,
        serving=dict(max_seqs=3, block_size=16, max_model_len=128,
                     decode_quantum=4, prompt_bucket=16))
    reqs = _ahead_load()
    want, occ_parent = _drive(srv, reqs, parent_order=True)
    st = srv.stats()
    rounds_parent = sum(st["step_shape_rounds"].values())
    assert st["rounds_ahead"] == 0 and st["dropped_slot_rounds"] == 0
    srv.reset_stats()
    got, occ = _drive(srv, reqs)
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
        assert len(a) == len(reqs[i][0]) + reqs[i][1]
    st = srv.stats()
    rounds = sum(st["step_shape_rounds"].values())
    assert rounds == rounds_parent
    assert st["rounds_ahead"] == rounds - 1      # all but the first
    assert st["dropped_slot_rounds"] == 0
    # the same slot-rounds in one call more (the last fetch): occupancy as
    # the harness reads it, after each call, is not below the parent's
    assert np.mean(occ) >= np.mean(occ_parent)
    assert srv._inflight is None and srv.allocator.used_blocks == 0
    srv.close()


@pytest.mark.parametrize("quantum", [1, 2, 8])
def test_every_quantum_splits_into_fetched_steps_and_steps_behind(quantum):
    """A quarter of the quantum stays in flight, at least one step: a
    quantum of 1 is all behind (every round fetched a call later), of 2 one
    and one, of 8 six and two. Tokens and rounds are the parent order's."""
    from deepspeed_tpu.inference.serving import _ahead_steps
    assert _ahead_steps(quantum) == {1: 1, 2: 1, 8: 2}[quantum]
    srv = _serving(decode_quantum=quantum, max_seqs=3)
    reqs = _ahead_load(n=6, new=(1, 14))
    want, _ = _drive(srv, reqs, parent_order=True)
    rounds_parent = sum(srv.stats()["step_shape_rounds"].values())
    srv.reset_stats()
    got, _ = _drive(srv, reqs)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    st = srv.stats()
    assert sum(st["step_shape_rounds"].values()) == rounds_parent
    assert st["rounds_ahead"] > 0 and srv._inflight is None


def test_a_finish_by_length_gives_its_slot_away_in_the_same_call():
    """The scheduler counts a finish by length ahead: the call that
    dispatches round k+1 gives the slot of a request whose budget the
    unfetched steps of round k exhaust to an admission, and finishes the
    request when those tokens are committed a moment later."""
    srv = _serving(max_seqs=1)             # quantum 4: 3 steps + 1 behind
    first = srv.add_request(np.arange(5, dtype=np.int32), 5)   # 1 + 3 + 1
    second = srv.add_request(np.arange(9, dtype=np.int32), 6)
    seen = []
    schedule = srv.scheduler.schedule

    def spy(**kw):
        out = schedule(**kw)
        seen.append(([r.rid for r in out["ended"]],
                     [(r.rid, r.slot) for r in out["admitted"]],
                     srv._inflight is not None))
        return out

    srv.scheduler.schedule = spy
    assert srv.step() == [] and seen[-1] == ([], [(first, 0)], False)
    req = srv._requests[first]
    assert len(req.generated) == 4 and req.inflight_rows == 1
    assert req.cached_rows == 8                  # rows the host holds
    done = srv.step()
    # ended and admitted in ONE schedule, with round 1 still in flight
    assert seen[-1] == ([first], [(second, 0)], True)
    assert [r.rid for r in done] == [first] and len(req.generated) == 5
    assert req.state == "finished" and not srv.scheduler.ending
    assert [r.rid for r in srv.scheduler.running] == [second]
    while not srv.scheduler.done:
        srv.step()
    assert srv.stats()["dropped_slot_rounds"] == 0
    eng = deepspeed_tpu.init_inference(
        srv.model, config={"kv_cache_bits": 0}, dtype=jnp.float32,
        params=jax.device_get(srv.engine.params))
    for r, n in ((srv._finished[0], 5), (srv._finished[1], 6)):
        one = np.asarray(eng.generate(r.prompt[None], max_new_tokens=n))[0]
        np.testing.assert_array_equal(r.output, one)


@pytest.mark.parametrize("nth,dropped", [(3, 0), (5, 1)],
                         ids=["in-the-fetched-steps", "in-the-step-behind"])
def test_an_eos_ends_the_request_with_the_parent_orders_tokens(nth, dropped):
    """A finish the host cannot count. An eos among the steps a call
    fetches is seen before the next round is dispatched, like the
    parent's; one in the steps left in flight (quantum 4: the 5th token)
    is found a round late — the request is in the next round by then, that
    slot's quantum is dropped and counted. Either way the tokens are the
    parent order's, and end AT the eos."""
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    for seed in range(20):                 # an eos that is not seen sooner
        prompt = np.random.default_rng(seed).integers(0, 128, 9
                                                      ).astype(np.int32)
        free = _serving(model=model, params=params).run([(prompt, 24)])[0]
        new = list(free[len(prompt):])
        if new[nth - 1] not in new[:nth - 1]:
            break
    eos = int(new[nth - 1])
    outs = {}
    for order in ("parent", "ahead"):
        srv = _serving(model=model, params=params, eos_token_id=eos)
        outs[order], _ = _drive(srv, [(prompt, 24)],
                                parent_order=order == "parent")
        assert srv.stats()["dropped_slot_rounds"] == (
            dropped if order == "ahead" else 0)
        assert srv._inflight is None and srv.allocator.used_blocks == 0
    np.testing.assert_array_equal(outs["ahead"][0], outs["parent"][0])
    np.testing.assert_array_equal(outs["ahead"][0],
                                  free[:len(prompt) + nth])


def test_something_runs_or_waits_while_tokens_are_uncommitted(tmp_path):
    """The harness calls ``step()`` only while ``scheduler.running or
    num_waiting``: a request stays running until its last tokens are on
    the host, so that loop finishes every request at its full length, and
    ``run()`` and ``drain()`` leave no round in flight."""
    srv = _serving()
    reqs = _ahead_load(n=6, new=(1, 20))

    def holds(srv):
        rec = srv._inflight
        if rec is not None and rec.live():
            assert srv.scheduler.running
            assert all(req in srv.scheduler.running for req, _ in rec.live())
        assert not srv.scheduler.ending          # only inside a round

    outs, _ = _drive(srv, reqs, each=holds)
    assert [len(o) for o in outs] == [len(p) + n for p, n in reqs]
    assert srv._inflight is None and srv.scheduler.done
    srv.reset_stats()
    again = srv.run(list(reqs))
    assert srv._inflight is None
    for a, b in zip(outs, (again[i] for i in sorted(again))):
        np.testing.assert_array_equal(a, b)
    # a drain mid-load: the round in flight is dropped, not fetched — the
    # state holds what the host has and a fresh engine computes the rest
    srv.reset_stats()
    rids = [srv.add_request(p, n) for p, n in reqs]
    srv.step(), srv.step()
    assert srv._inflight is not None and srv._inflight.live()
    before = list(srv._finished)
    srv.drain(str(tmp_path))
    assert srv._inflight is None and srv._finished == before
    assert all(r.inflight_rows == 0 for r in
               list(srv.scheduler.waiting) + srv.scheduler.running)
    srv2 = _serving(params=jax.device_get(srv.engine.params))
    srv2.resume(str(tmp_path))
    resumed = {r.rid: r.output for r in before}
    while not srv2.scheduler.done:
        for r in srv2.step():
            resumed[r.rid] = r.output
    assert sorted(resumed) == rids
    for a, i in zip(outs, rids):
        np.testing.assert_array_equal(a, resumed[i])


def test_a_speculation_engine_never_runs_ahead():
    """A verify step's proposals are drafted from the tokens on the host:
    a speculation round is fetched whole by the call that dispatched it."""
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    reqs = _ahead_load(n=5, new=(4, 20))
    want, _ = _drive(_serving(model=model, params=params), reqs)

    def never(srv):
        assert srv._inflight is None

    srv = _serving(model=model, params=params, spec_tokens=2)
    got, _ = _drive(srv, reqs, each=never)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    st = srv.stats()
    assert st["rounds_ahead"] == 0 and st["spec_steps"] > 0
    assert sum(st["step_shape_rounds"].values()) == 0


def test_a_prompt_buckets_first_prefill_runs_in_one_chunk(monkeypatch):
    """A bucket's first prompt traces and lowers its prefill below a frame
    that opens a 1 MiB chunk of the interpreter's frame stack (ISSUE 36:
    across a 16 KiB chunk boundary the same lowering cost a chat cell 2.6
    or 4.7 s of set-up, whichever way a frame's size above it pushed);
    later prompts of the bucket, and a re-prefill after a preemption, call
    the program directly. Tokens are one-shot ``generate``'s."""
    from deepspeed_tpu.inference import serving
    # 64 Ki words of declared stack: the chunk CPython opens for the frame
    # is twice that, the rest holds what the frame calls
    assert serving._in_one_chunk.__code__.co_stacksize == 1 << 16
    assert serving._in_one_chunk(divmod, 7, 2) == (3, 1)
    firsts, traced = [], []
    in_one_chunk = serving._in_one_chunk

    def spy(fn, *args):
        if fn in srv._prefill_fns.values():            # not a step's lowering
            firsts.append(args[1].shape[1])            # the bucket
        return in_one_chunk(fn, *args)

    monkeypatch.setattr(serving, "_in_one_chunk", spy)
    model = make_model(_cfg())
    srv = _serving(model, max_seqs=2, num_blocks=9)
    prefill_paged = srv.model.prefill_paged

    def count(params, ids, *a, **kw):
        traced.append(ids.shape[1])
        return prefill_paged(params, ids, *a, **kw)

    srv.model.prefill_paged = count
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 128, size=(n,)).astype(np.int32), 40)
            for n in (9, 26, 12, 30)]                  # buckets 16, 32, 16, 32
    outs = srv.run(reqs)
    assert srv.stats()["preemptions"] >= 1             # re-prefills happened
    # the prompts' buckets and those of the re-prefilled contexts, each
    # traced once, under the roomy frame
    assert {16, 32} < set(firsts) and len(set(firsts)) == len(firsts)
    assert traced == firsts and sorted(firsts) == sorted(srv._prefill_fns)
    eng = deepspeed_tpu.init_inference(
        model, config={"kv_cache_bits": 0}, dtype=jnp.float32,
        params=jax.device_get(srv.engine.params))
    for i, (p, n) in enumerate(reqs):
        one = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
        np.testing.assert_array_equal(outs[sorted(outs)[i]], one)
