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


_KERNEL_DIMS = dict(Nq=20, lanes=640, width=576, rank=512, bs=16, MB=40, L=2)
# lengths a slot, at 16 rows a block so a wave is 256 rows: what walks the chain
# of first waves (``ops/latent_decode.py``: slot s + 1's wave 0 is started
# under slot s's last wave, into the buffer that wave leaves free)
_CHAINS = {
    "every-kind": [0, 1, 16, 300, 640],
    "idle-first-between-last-but-one": [0, 0, 300, 0, 17],
    "idle-after-the-first": [300, 0, 0, 0, 0],
    "all-idle": [0, 0, 0, 0, 0],
    "one-slot": [300],
    "odd-and-even-waves": [256, 257, 512, 513],
    "even-and-odd-waves": [513, 512, 257, 256],
}


def _kernel_case(lens):
    """(q, pool, tables, lens, row) of ``_KERNEL_DIMS`` for these lengths."""
    d = _KERNEL_DIMS
    rng = np.random.default_rng(0)
    S, NB = len(lens), len(lens) * d["MB"] + 1

    def stored(shape):
        x = rng.standard_normal(shape + (d["width"],)).astype(np.float32) * 0.5
        return jnp.asarray(np.pad(x, [(0, 0)] * len(shape)
                                  + [(0, d["lanes"] - d["width"])]), jnp.bfloat16)
    pool, q, row = stored((d["L"], NB, d["bs"])), stored((S, d["Nq"])), stored((S,))
    lens = np.asarray(lens, np.int32)
    tables = np.zeros((S, d["MB"]), np.int32)
    perm, k = rng.permutation(np.arange(1, NB)), 0
    for s in range(S):
        n = -(-int(lens[s]) // d["bs"])
        tables[s, :n] = perm[k:k + n]
        k += n
    return q, pool, jnp.asarray(tables), jnp.asarray(lens), row


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("chain", list(_CHAINS))
def test_the_kernel_is_the_list_read(chain, layer):
    """``latent_decode`` (interpret mode) against the XLA list read on one
    plane, to bf16 rounding, over lengths that walk the chain of first waves:
    slots of every kind of length — none, one row, a whole block, past one wave
    of 16 blocks —; idle slots first, last and between live ones (an idle slot
    hands the baton on); exactly one wave, one row past it, two, one row past
    (the buffer's turn carried through odd and even wave counts, both ways).

    Only slot 0 starts its own first wave, so a live slot after the first that
    agrees with the list read FOUND its wave 0 started before its grid step
    began and in the buffer it waited on: the chain engages for every live slot
    but the first by construction, which is why no counter reports it."""
    q, pool, tables, lens, row = _kernel_case(_CHAINS[chain])
    rank = _KERNEL_DIMS["rank"]
    a, b = (np.asarray(latent_attention.latent_read(
        q, pool, tables, lens, row, jnp.int32(layer), 1 / 16, rank, backend),
        np.float32) for backend in ("xla", "pallas"))
    assert a.shape == b.shape == (len(lens), _KERNEL_DIMS["Nq"], rank)
    # (300 rows of std 0.5 average to ~0.14 at the most; a short slot is ~2)
    assert np.abs(a).max() > 0.1 and np.abs(a - b).max() < 0.02


def test_the_kernel_is_the_same_from_run_to_run():
    """The chain leaves nothing to timing: two calls on the same pool are
    equal bit for bit, and so is a slot whichever slots stand before it (its
    rows enter its softmax in the same order whatever buffer its waves
    start in)."""
    lens = _CHAINS["idle-first-between-last-but-one"]
    q, pool, tables, ln, row = _kernel_case(lens)
    rank = _KERNEL_DIMS["rank"]
    read = lambda q, tables, ln, row: np.asarray(      # noqa: E731
        latent_decode.latent_decode(q, pool, tables, ln, jnp.int32(1), row,
                                    rank=rank, sm_scale=1 / 16), np.float32)
    first = read(q, tables, ln, row)
    np.testing.assert_array_equal(first, read(q, tables, ln, row))
    # slot 2 (300 rows: two waves) alone, its waves from buffer 0 on
    alone = read(q[2:3], tables[2:3], ln[2:3], row[2:3])
    np.testing.assert_array_equal(first[2], alone[0])


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
