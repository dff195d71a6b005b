"""Speculative decoding + token-budget chunked prefill (ISSUE 12).

Load-bearing contracts pinned here:

  - greedy speculation is OUTPUT-PRESERVING: spec K>0 produces the exact
    greedy token stream of K=0, which is the exact stream of speculation
    off, which is the exact PR-9 one-shot ``generate()`` stream (the
    accept rule only ever emits the target model's own argmaxes);
  - the n-gram self-drafting proposer actually accepts on repetitive
    traffic (the win is real, not a no-op code path);
  - chunked prefill under a token budget slices a long prompt across
    rounds WITHOUT changing any output, and running requests keep
    decoding between the chunks (the ITL win's mechanism);
  - rejected speculation rolls the cursor back without disturbing
    refcounted/shared blocks (composed prefix-cache + spec run stays
    exact and leak-free);
  - config gates: speculation is greedy-only, and all three latency
    features refuse a model without the span protocol.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.spec_decode import (NgramProposer,
                                                 greedy_accept_len)
from deepspeed_tpu.models import TransformerConfig, make_model


def _cfg(**overrides):
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, max_seq_len=256, position_type="rotary",
                activation="silu_glu", norm_type="rmsnorm",
                tie_embeddings=False, dtype=jnp.float32,
                attention_impl="xla")
    base.update(overrides)
    return TransformerConfig(**base)


def _serving(model, params, **serving):
    defaults = dict(max_seqs=2, block_size=16, max_model_len=128,
                    decode_quantum=4, prompt_bucket=16)
    defaults.update(serving)
    return deepspeed_tpu.init_serving(model, config={}, serving=defaults,
                                      dtype=jnp.float32,
                                      params=jax.device_get(params))


# ---------------------------------------------------------------------------
# Proposer + accept rule (pure host / tiny jit)
# ---------------------------------------------------------------------------

class TestNgramProposer:
    def test_matches_most_recent_occurrence(self):
        p = NgramProposer(n=2)
        ctx = np.asarray([1, 2, 9, 9, 1, 2, 7, 8, 1, 2], np.int32)
        # trailing gram (1, 2): rightmost earlier occurrence at 4 -> 7, 8
        np.testing.assert_array_equal(p.propose(ctx, 2), [7, 8])

    def test_no_match_proposes_zeros(self):
        p = NgramProposer(n=3)
        ctx = np.asarray([1, 2, 3, 4, 5], np.int32)
        np.testing.assert_array_equal(p.propose(ctx, 3), [0, 0, 0])

    def test_short_context_and_truncated_continuation(self):
        p = NgramProposer(n=4)
        assert p.propose(np.asarray([5], np.int32), 2).tolist() == [0, 0]
        # match near the end: fewer than k continuation tokens exist
        ctx = np.asarray([3, 4, 6, 3, 4], np.int32)
        np.testing.assert_array_equal(NgramProposer(2).propose(ctx, 4),
                                      [6, 3, 4, 0])

    def test_rejects_bad_n(self):
        with pytest.raises(ValueError):
            NgramProposer(0)


def test_greedy_accept_len_math():
    nxt = jnp.asarray([[5, 6, 7, 8],      # all 3 proposals right
                       [5, 6, 7, 8],      # first wrong
                       [5, 6, 7, 8]])     # second wrong
    prop = jnp.asarray([[5, 6, 7],
                        [9, 6, 7],
                        [5, 9, 7]])
    np.testing.assert_array_equal(np.asarray(greedy_accept_len(nxt, prop)),
                                  [3, 0, 1])


# ---------------------------------------------------------------------------
# Config gates
# ---------------------------------------------------------------------------

class TestConfigGates:
    def test_spec_is_greedy_only(self):
        model = make_model(_cfg())
        with pytest.raises(ValueError, match="greedy-only"):
            _serving(model, model.init(jax.random.PRNGKey(0)),
                     spec_tokens=2, temperature=0.7)

    def test_latency_features_need_span_protocol(self):
        model = make_model(_cfg())
        spanless = dataclasses.replace(model, decode_span_paged=None)
        params = model.init(jax.random.PRNGKey(0))
        for kw in (dict(spec_tokens=2), dict(enable_prefix_cache=True),
                   dict(prefill_token_budget=64)):
            with pytest.raises(ValueError, match="span protocol"):
                _serving(spanless, params, **kw)

    def test_negative_knobs_refused(self):
        model = make_model(_cfg())
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="spec_tokens"):
            _serving(model, params, spec_tokens=-1)
        with pytest.raises(ValueError, match="prefill_token_budget"):
            _serving(model, params, prefill_token_budget=0)


# ---------------------------------------------------------------------------
# Bit-parity: spec K>0 == K=0 == off == one-shot generate()
# ---------------------------------------------------------------------------

def _repetitive_load(rng, n=3):
    """Prompts full of repeated trigrams — the self-drafting proposer's
    home turf, so acceptance is exercised for real."""
    reqs = []
    for _ in range(n):
        motif = rng.integers(0, 128, size=(4,)).astype(np.int32)
        prompt = np.concatenate([motif, motif, motif,
                                 rng.integers(0, 128, size=(3,))
                                 .astype(np.int32)])
        reqs.append((prompt, 10))
    return reqs


def test_spec_bit_parity_and_acceptance():
    """spec K=3 == spec K=0 == speculation off == PR-9 generate(), token
    for token, AND the proposer actually accepted something."""
    model = make_model(_cfg())
    # data-dependent pin, re-measured on jax 0.9.0 (PR 21): whether the
    # n-gram proposer ever hits depends on the random-init weights' greedy
    # stream. PRNGKey(0)'s model never re-enters a prompt trigram on this
    # stack (0 of 27 verify steps accepted anything); PRNGKey(1)'s does
    # (12 accepted over 21 steps).
    params = model.init(jax.random.PRNGKey(1))
    reqs = _repetitive_load(np.random.default_rng(2))
    off = _serving(model, params).run(list(reqs))          # spec_tokens=0
    spec_srv = _serving(model, params, spec_tokens=3)
    on = spec_srv.run(list(reqs))
    for rid in off:
        np.testing.assert_array_equal(off[rid], on[rid],
                                      err_msg=f"request {rid} diverged")
    st = spec_srv.stats()
    assert st["spec_steps"] > 0
    assert st["spec_accepted"] > 0 and st["spec_accept_rate"] > 0
    # and the unspeculated stream is the PR-9 one-shot stream (pinned in
    # test_serving too — re-pinned here so this module stands alone)
    eng = deepspeed_tpu.init_inference(
        model, config={"kv_cache_bits": 0}, dtype=jnp.float32,
        params=jax.device_get(params))
    for i, (p, n) in enumerate(reqs):
        one = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
        np.testing.assert_array_equal(off[i], one)


def test_spec_draft_hook_is_used():
    """A custom draft proposer (the draft-model hook) drives proposals;
    an oracle hook that always guesses the model's own next tokens gets
    everything accepted."""
    model = make_model(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    reqs = [(rng.integers(0, 128, size=(9,)).astype(np.int32), 8)]
    base = _serving(model, params).run(list(reqs))
    oracle = base[0]                       # the full greedy continuation

    def draft(ctx, k):
        # next tokens after the current context, straight from the oracle
        pos = ctx.size
        return oracle[pos:pos + k]

    srv = _serving(model, params, spec_tokens=2, spec_proposer=draft)
    on = srv.run(list(reqs))
    np.testing.assert_array_equal(base[0], on[0])
    st = srv.stats()
    # an oracle draft only "misses" at the very end of the budget, where
    # it proposes past the sequence and the pads verify as wrong guesses
    assert st["spec_accept_rate"] >= 0.6
    assert st["spec_accepted"] >= 4


# ---------------------------------------------------------------------------
# Chunked prefill under a token budget
# ---------------------------------------------------------------------------

def test_chunked_prefill_exact_and_actually_chunks():
    model = make_model(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    reqs = [(rng.integers(0, 128, size=(n,)).astype(np.int32), k)
            for n, k in ((70, 8), (9, 8), (33, 8))]
    base = _serving(model, params).run(list(reqs))
    srv = _serving(model, params, prefill_token_budget=32)
    outs = srv.run(list(reqs))
    for rid in base:
        np.testing.assert_array_equal(base[rid], outs[rid],
                                      err_msg=f"request {rid} diverged")
    st = srv.stats()
    assert st["prefill_chunks"] >= 3       # the 70-token prompt was sliced
    assert st["prefill_chunk_tokens"] >= 70


def test_decode_progresses_while_long_prompt_chunks():
    """The ITL mechanism: with a budget, a running request keeps emitting
    tokens across the rounds a 96-token admission spends prefilling —
    the long prompt no longer monopolizes whole rounds."""
    model = make_model(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(8)
    srv = _serving(model, params, prefill_token_budget=24,
                   decode_quantum=2, max_seqs=2)
    short = srv.add_request(rng.integers(0, 128, size=(8,))
                            .astype(np.int32), 24)
    srv.step()                             # short admits + starts decoding
    long_rid = srv.add_request(rng.integers(0, 128, size=(96,))
                               .astype(np.int32), 4)
    long_req = srv._requests[long_rid]
    interleaved = 0
    for _ in range(40):
        if srv.scheduler.done:
            break
        before = len(srv._requests[short].generated)
        srv.step()
        if not long_req.prefill_done \
                and len(srv._requests[short].generated) > before:
            interleaved += 1
    assert srv.scheduler.done
    # the long admission spent >1 round prefilling AND the short request
    # gained tokens during those rounds
    assert interleaved >= 1, "decode stalled for the whole prefill"
    st = srv.stats()
    assert st["prefill_chunks"] >= 2


# ---------------------------------------------------------------------------
# Composition: cache + budget + speculation, exact and leak-free
# ---------------------------------------------------------------------------

def test_spec_at_context_cap_stays_exact():
    """A request whose prompt+budget exactly fills max_model_len decodes
    its last tokens under speculation: the verify step's overflow rows
    (proposals past the cap) must land in the trash block, not wrap into
    the slot's last block and clobber valid history (regression: the
    clipped block index used to alias position cap+i onto row i of the
    final block)."""
    model = make_model(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(31)
    prompt = rng.integers(0, 128, size=(100,)).astype(np.int32)
    reqs = [(prompt, 28)]                     # 100 + 28 == max_model_len
    base = _serving(model, params).run(list(reqs))
    on = _serving(model, params, spec_tokens=3).run(list(reqs))
    np.testing.assert_array_equal(base[0], on[0])


def test_all_three_compose_exactly():
    model = make_model(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 128, size=(40,)).astype(np.int32)
    reqs = [(np.concatenate([shared, rng.integers(0, 128, size=(5,))
                             .astype(np.int32)]), 8) for _ in range(4)]
    base = _serving(model, params).run(list(reqs))
    srv = _serving(model, params, enable_prefix_cache=True,
                   prefill_token_budget=32, spec_tokens=2)
    outs = srv.run(list(reqs))
    for rid in base:
        np.testing.assert_array_equal(base[rid], outs[rid],
                                      err_msg=f"request {rid} diverged")
    st = srv.stats()
    assert st["prefix_hits"] >= 1 and st["spec_steps"] > 0
    # rejected speculation rolled cursors back WITHOUT freeing shared
    # blocks: at drain time every held block is the cache's, refcounts
    # balanced
    assert srv.allocator.used_blocks == srv._prefix_cache.held_blocks


@pytest.mark.slow
def test_spec_parity_bf16():
    model = make_model(_cfg(dtype=jnp.bfloat16))
    params = model.init(jax.random.PRNGKey(0))
    reqs = _repetitive_load(np.random.default_rng(21), n=4)
    base = deepspeed_tpu.init_serving(
        model, config={}, serving=dict(max_seqs=2, block_size=16,
                                       max_model_len=128, decode_quantum=4,
                                       prompt_bucket=16),
        params=jax.device_get(params)).run(list(reqs))
    srv = deepspeed_tpu.init_serving(
        model, config={}, serving=dict(max_seqs=2, block_size=16,
                                       max_model_len=128, decode_quantum=4,
                                       prompt_bucket=16, spec_tokens=3),
        params=jax.device_get(params))
    on = srv.run(list(reqs))
    for rid in base:
        np.testing.assert_array_equal(base[rid], on[rid],
                                      err_msg=f"request {rid} diverged")
    assert srv.stats()["spec_accepted"] > 0


@pytest.mark.slow
def test_spec_int8_kv_agreement():
    """int8 pools under speculation: the verify span reads its own fresh
    rows as floats where sequential steps re-read them quantized — same
    relaxation as the contiguous int8 cache (test_serving_int8_kv_pool):
    prompt+first tokens exact, near-total agreement."""
    model = make_model(_cfg())
    reqs = _repetitive_load(np.random.default_rng(23), n=3)
    serving = dict(max_seqs=2, block_size=16, max_model_len=128,
                   decode_quantum=4, prompt_bucket=16)
    base = deepspeed_tpu.init_serving(
        model, config={"kv_cache_bits": 8}, serving=serving,
        dtype=jnp.float32).run(list(reqs))
    srv = deepspeed_tpu.init_serving(
        model, config={"kv_cache_bits": 8},
        serving=dict(serving, spec_tokens=3), dtype=jnp.float32)
    on = srv.run(list(reqs))
    for i, (p, _) in enumerate(reqs):
        got, ref = on[i], base[i]
        assert (got[:p.size + 4] == ref[:p.size + 4]).all(), (got, ref)
        assert (got == ref).mean() > 0.9
