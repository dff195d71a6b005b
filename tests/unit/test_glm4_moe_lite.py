"""``glm4_moe_lite`` (GLM-4.7-Flash) on the normal serving path, at toy widths
on the CPU: latent attention served through ``init_serving`` like every other
model — a prompt EXPANDED, a decode step ABSORBED against the latent pool.

The logits of both paths against the plain reference, each seeded defect, the
packed prefill and the table of HF weight names are held in
``benchmark/tests/test_glm4_moe_lite_family.py`` (collected into tier-1 through
``test_benchmark_suite.py``). Here it is the ENGINE: what it builds for such a
model by default, that queuing, packing, preemption and the read's backend
never change a token, what the pool's read costs either way, what is refused,
and what the importer makes of the published dict.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402
from benchmark.families import glm4_moe_lite as fam  # noqa: E402
from deepspeed_tpu.inference.serving import ResumeIncompatible  # noqa: E402
from deepspeed_tpu.models import hybrid, latent_attention, make_model  # noqa: E402
from deepspeed_tpu.models.hf_import import hf_config_to_transformer  # noqa: E402
from deepspeed_tpu.ops import latent_decode  # noqa: E402
from deepspeed_tpu.robustness import events  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "glm-4.7-flash-serve.json")) as _f:
    PUBLISHED = {k: v for k, v in json.load(_f).items() if k not in (
        "source", "reduced", "assumed", "deployment", "run", "correct")}
HF = {**PUBLISHED, **fam.TOY, "max_position_embeddings": 512}
SERVING = dict(max_seqs=3, block_size=16, max_model_len=256, decode_quantum=4,
               prompt_bucket=16)


@pytest.fixture(scope="module")
def toy():
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32, norm_init_jitter=0.5)
    model = make_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(3))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], n)


def _greedy(model, params, prompt, n, width=96):
    """n greedy tokens by the whole forward over the sequence so far, padded
    at the end to one width (causal: no real position sees a pad)."""
    fwd = _FWD.setdefault(id(model), jax.jit(model.apply))
    seq = list(prompt)
    for _ in range(n):
        buf = np.zeros((1, width), np.int32)
        buf[0, :len(seq)] = seq
        lg = fwd(params, jnp.asarray(buf))[0, len(seq) - 1]
        seq.append(int(np.asarray(lg).argmax()))
    return seq[len(prompt):]


_FWD = {}


REQS = [(slice(0, 40), 12), (slice(5, 30), 9), (slice(0, 17), 20),
        (slice(3, 60), 7), (slice(0, 33), 15)]


@pytest.fixture(scope="module")
def want(toy):
    _, model, params = toy
    ids = _ids(70)
    return [(ids[s], n, _greedy(model, params, ids[s], n)) for s, n in REQS]


def _serve(model, params, want, **serving):
    srv = deepspeed_tpu.init_serving(model, config={}, params=params,
                                     dtype=jnp.float32,
                                     serving={**SERVING, **serving})
    outs = srv.run([(p, n) for p, n, _ in want])
    got = [list(outs[rid])[-n:] for (_, n, _), rid in zip(want, sorted(outs))]
    return srv, got


def test_the_pattern_the_cache_and_the_engines_defaults(toy):
    cfg, model, params = toy
    assert cfg.block_pattern == "LDLELE" and hybrid.period(cfg) == ("LDLELE", 1)
    assert (cfg.latent_planes, cfg.latent_row_width, cfg.kv_planes,
            cfg.attention_blocks, cfg.slot_state_blocks) == (3, 40, 0, 3, 0)
    assert latent_attention.stored_width(cfg) == 128
    assert model.slot_leaves == () and model.ring_rows == 0
    assert model.decode_span_paged is None
    pools = model.init_paged_cache(9, 16, dtype=jnp.bfloat16, max_seqs=2)
    assert {k: (v.shape, v.dtype) for k, v in pools.items()} == {
        "latent": ((3, 9, 16, 128), jnp.bfloat16)}
    # the engine a user gets by default, at a context where a per-head pool
    # would be int8: the latent rows stay float, and say so
    srv = deepspeed_tpu.init_serving(
        model, params=params, serving=dict(SERVING, max_model_len=2048))
    try:
        assert srv.model.config.kv_cache_bits == 0
        assert srv.kv_pool_dtype == "bfloat16" and srv.max_seqs == 3
        assert srv.pools["latent"].shape == (3, 3 * 32 + 1, 16, 128)
        assert srv.decode_backend == "xla"
        # the toy's rank of 32 is off the lane grid: the capability gate
        assert "cannot be built" in srv.backend_bench["reason"]
        st = srv.stats()
        assert (st["latent_planes"], st["latent_row_bytes"]) == (3.0, 80.0)
        assert st["kv_pool_bytes"] == 3 * 97 * 16 * 128 * 2
    finally:
        srv.close()
    with pytest.raises(ValueError, match="latent attention"):
        deepspeed_tpu.init_serving(model, params=params, serving=SERVING,
                                   config={"kv_cache_bits": 8})
    with pytest.raises(NotImplementedError, match="kv_cache_bits"):
        import dataclasses
        make_model(dataclasses.replace(cfg, kv_cache_bits=8)).init_paged_cache(
            9, 16, max_seqs=2)


def test_the_engine_serves_it_token_for_token(toy, want):
    """More requests than slots, prompts that share prefill rows: every
    output is the whole forward's greedy continuation."""
    _, model, params = toy
    srv, got = _serve(model, params, want)
    try:
        assert got == [w for _, _, w in want]
        assert srv.allocator.used_blocks == 0
        assert srv.stats()["moe_experts_touched_per_step"] > 0
    finally:
        srv.close()


def test_preemption_and_the_kernel_change_no_token(toy, want):
    """A pool too small for three full slots (growth collides: preemptions)
    and the read through the Pallas kernel (forced; interpret mode here,
    rectangular tables): the same tokens."""
    _, model, params = toy
    # uniform long answers on two slots collide in growth: 2 x 5 blocks > 8
    ids = _ids(4 * 26, seed=1).reshape(4, 26)
    long = [(p, 40, _greedy(model, params, p, 40)) for p in ids]
    srv, got = _serve(model, params, long, max_seqs=2, max_model_len=128,
                      num_blocks=9)
    try:
        assert got == [w for _, _, w in long]
        assert srv.stats()["preemptions"] >= 1
    finally:
        srv.close()
    # the kernel reads a 16-bit pool: bf16 near-ties may flip a toy's argmax,
    # so its engine is compared with the XLA read's on the SAME bf16 pool
    # ... and the kernel wants the latent's columns in whole lane tiles: a
    # toy of rank 128 (the published toy's 32 cannot build it, and says so)
    cfg = hf_config_to_transformer({**HF, "kv_lora_rank": 128},
                                   dtype=jnp.bfloat16, norm_init_jitter=0.5)
    model16 = make_model(cfg)
    params = model16.init(jax.random.PRNGKey(3))
    srv = deepspeed_tpu.init_serving(
        model, config={}, params=toy[2], dtype=jnp.bfloat16,
        serving={**SERVING, "decode_backend": "pallas"})
    assert srv.decode_backend == "xla" and "cannot be built" in \
        srv.backend_bench["reason"]
    srv.close()
    srv = deepspeed_tpu.init_serving(model16, params=params, serving=SERVING,
                                     dtype=jnp.bfloat16)
    bench = srv.backend_bench            # priced, and the CPU keeps XLA
    assert srv.decode_backend == "xla" and bench["reason"] == "non-TPU backend"
    assert bench["xla_bytes"] > 0 < bench["kernel_bytes"] and bench["priced"]
    srv.close()
    outs = {}
    for backend in ("xla", "pallas"):
        srv = deepspeed_tpu.init_serving(
            model16, config={}, params=params, dtype=jnp.bfloat16,
            serving={**SERVING, "decode_backend": backend})
        try:
            assert srv.decode_backend == backend
            assert srv.backend_bench["reason"] == "forced by config"
            res = srv.run([(p, n) for p, n, _ in want])
            outs[backend] = [list(res[r]) for r in sorted(res)]
        finally:
            srv.close()
    same = sum(a == b for x, y in zip(outs["xla"], outs["pallas"])
               for a, b in zip(x, y))
    total = sum(len(x) for x in outs["xla"])
    assert same / total > 0.9, (same, total)


def test_the_kernel_is_the_list_read(toy):
    """``latent_decode`` (interpret mode) against the XLA list read on one
    plane: slots of every kind of length — none, one row, a whole block, past
    one wave of 16 blocks — to bf16 rounding."""
    rng = np.random.default_rng(0)
    S, Nq, lanes, width, rank, bs, MB, L = 5, 20, 640, 576, 512, 16, 40, 2
    NB = S * MB + 1

    def stored(shape):
        x = rng.standard_normal(shape + (width,)).astype(np.float32) * 0.5
        return jnp.asarray(np.pad(x, [(0, 0)] * len(shape) + [(0, lanes - width)]),
                           jnp.bfloat16)
    pool, q, row = stored((L, NB, bs)), stored((S, Nq)), stored((S,))
    lens = np.array([0, 1, 16, 300, 640], np.int32)
    tables = np.zeros((S, MB), np.int32)
    perm, k = rng.permutation(np.arange(1, NB)), 0
    for s in range(S):
        n = -(-int(lens[s]) // bs)
        tables[s, :n] = perm[k:k + n]
        k += n
    for layer in (0, 1):
        a, b = (np.asarray(latent_attention.latent_read(
            q, pool, jnp.asarray(tables), jnp.asarray(lens), row,
            jnp.int32(layer), 1 / 16, rank, backend), np.float32)
            for backend in ("xla", "pallas"))
        assert a.shape == b.shape == (S, Nq, rank)
        assert np.abs(a).max() > 0.5 and np.abs(a - b).max() < 0.02


def test_the_price_of_the_read():
    """At the cell's shape (128 slots x 76 columns of 64 rows stored in 640
    lanes, 20 heads) the kernel is the cheaper read; a float32 pool or rows
    off the lane grid cannot build it; a table of a few blocks is a tie the
    XLA read keeps."""
    cell = dict(slots=128, MB=76, block_size=64, heads=20, lanes=640, rank=512)
    p = latent_decode.latent_read_price(**cell)
    assert p["choice"] == "pallas" and p["kernel_bytes"] < p["xla_bytes"]
    assert latent_decode.latent_read_price(**cell, itemsize=4)["choice"] == "xla"
    assert latent_decode.latent_read_price(
        **dict(cell, lanes=576))["why"] == "the kernel cannot be built"
    assert latent_decode.latent_read_price(**dict(cell, slots=4, MB=4))["choice"] == "xla"


def test_what_is_refused(toy):
    _, model, params = toy
    for armed in (dict(enable_prefix_cache=True), dict(spec_tokens=2),
                  dict(prefill_token_budget=32)):
        with pytest.raises(ValueError, match="span protocol"):
            deepspeed_tpu.init_serving(model, params=params, dtype=jnp.float32,
                                       serving={**SERVING, **armed})
    with pytest.raises(ValueError, match="latent attention"):
        deepspeed_tpu.init_serving(model, params=params, dtype=jnp.float32,
                                   serving=SERVING, tensor_parallel=2)
    srv = deepspeed_tpu.init_serving(model, config={}, params=params,
                                     dtype=jnp.float32, serving=SERVING)
    try:
        rid = srv.add_request(_ids(20), 8)
        srv.step()
        with pytest.raises(ResumeIncompatible, match="latent"):
            srv.export_kv([rid])
    finally:
        srv.close()

def test_the_importer_reads_the_published_dict_and_refuses_the_rest():
    cfg = hf_config_to_transformer(PUBLISHED)
    assert cfg.block_pattern == "LD" + "LE" * 5 and cfg.num_layers == 12
    assert (cfg.num_heads, cfg.dim_per_head, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        20, 256, 768, 512, 192, 64, 256)
    assert (cfg.num_experts, cfg.top_k, cfg.moe_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.moe_shared_size, cfg.ffn_dim,
            cfg.dense_ffn_size) == (64, 4, "sigmoid", True, 1.8, 1536, 1536, 10240)
    assert (cfg.position_type, cfg.rotary_interleaved, cfg.rope_theta,
            cfg.tie_embeddings, cfg.vocab_size) == ("rotary", True, 1e6, False, 154880)
    assert (cfg.latent_planes, cfg.latent_row_width,
            latent_attention.stored_width(cfg)) == (6, 576, 640)
    for key, bad in (("num_nextn_predict_layers", 1), ("n_group", 2),
                     ("topk_method", "greedy"), ("rope_scaling", {"type": "yarn"}),
                     ("attention_bias", True), ("num_experts", 32),
                     ("q_lora_rank", None), ("num_key_value_heads", 4)):
        with pytest.raises(ValueError, match=key):
            hf_config_to_transformer({**PUBLISHED, key: bad})


def test_the_scopes_and_the_backend_event(toy):
    """``attn/latent_q``, ``attn/latent_read``, ``attn/latent_up`` in the
    step's lowered text, and the event that says which read and why."""
    _, model, params = toy
    pools = model.init_paged_cache(13, 16, dtype=jnp.float32, max_seqs=2)
    text = jax.jit(model.decode_step_paged).lower(
        params, jnp.zeros((2,), jnp.int32), pools,
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2,), jnp.int32)
    ).as_text(debug_info=True)
    for scope in ("attn/latent_q", "attn/latent_read", "attn/latent_up",
                  "attn/latent_read/kv_gather", "attn/kv_write", "moe/"):
        assert scope in text, scope
    events.clear()
    srv = deepspeed_tpu.init_serving(model, params=params, dtype=jnp.float32,
                                     serving=SERVING)
    srv.close()
    ev = events.history("decode_backend_selected")
    assert ev and ev[-1]["backend"] == "xla" and "reason" in ev[-1]
