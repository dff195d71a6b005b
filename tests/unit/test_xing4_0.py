"""``xing4_0`` (Xing4.0-29B-A4B) on the normal serving path, at toy widths on
the CPU: a prompt's prefill (EXPANDED, into the latent pool) and decode steps
(ABSORBED, against it) with a stream four rows wide, against the plain
reference's FULL forward on logits; latent attention whose V is narrower than
its keys, in every attention path; the importer on the catalog's config; what
the engine reports and refuses. The mappings by themselves, the whole forward
and every seeded defect are in ``test_hyper_connections.py``.

Tolerances: float32 at the "highest" matmul precision on both sides, sums in
another order (the absorbed read contracts the latent, the reference the
expanded heads): 2e-5 is held on logits of mean size ~0.2 (measured 6e-7). The
kernel and the XLA read on one bf16 pool differ by bf16 rounding of the
probabilities: 2e-2 on outputs of size ~1.
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
from benchmark.families import xing4_0 as fam  # noqa: E402
from deepspeed_tpu.models import latent_attention, make_model  # noqa: E402
from deepspeed_tpu.models.hf_import import (  # noqa: E402
    Xing40Unsupported, hf_config_to_transformer, xing4_0_weight_names)
from deepspeed_tpu.ops import flash_attention as fa  # noqa: E402

# the catalog row's `config` (model-configs guide, architectures.jsonl), verbatim
PUBLISHED = {
 "attention_bias": False,
 "ep_size": 1,
 "first_k_dense_replace": 2,
 "hidden_act": "silu",
 "hidden_size": 3584,
 "intermediate_size": 9216,
 "kv_lora_rank": 512,
 "max_position_embeddings": 262144,
 "model_type": "xing4_0",
 "moe_intermediate_size": 1024,
 "moe_layer_freq": 1,
 "n_group": 1,
 "n_routed_experts": 64,
 "n_shared_experts": 1,
 "norm_topk_prob": True,
 "num_attention_heads": 32,
 "num_experts_per_tok": 4,
 "num_hidden_layers": 40,
 "num_key_value_heads": 32,
 "num_nextn_predict_layers": 1,
 "hc_mult": 4,
 "hc_sinkhorn_iters": 20,
 "hc_eps": 1e-06,
 "mhc_h_res_clamp_min": -30,
 "mhc_h_res_clamp_max": 30,
 "q_lora_rank": 768,
 "qk_nope_head_dim": 128,
 "qk_rope_head_dim": 64,
 "rms_norm_eps": 1e-06,
 "rope_theta": 10000,
 "rope_scaling": {
  "beta_fast": 32,
  "beta_slow": 1,
  "factor": 64,
  "mscale": 1,
  "mscale_all_dim": 1,
  "original_max_position_embeddings": 4096,
  "type": "yarn"
 },
 "routed_scaling_factor": 2,
 "scoring_func": "sigmoid",
 "tie_word_embeddings": False,
 "topk_group": 1,
 "topk_method": "noaux_tc",
 "v_head_dim": 128,
 "vocab_size": 131072
}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "xing4.0-29b-a4b-serve.json")) as _f:
    AS_RUN = {k: v for k, v in json.load(_f).items() if k not in (
        "source", "reduced", "assumed", "deployment", "run", "correct")}
HF = {**AS_RUN, **fam.TOY, "max_position_embeddings": 256}
TOL = 2e-5
BS = 16


@pytest.fixture(scope="module")
def toy():
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32, norm_init_jitter=0.5,
                                   hc_init_std=1.0)
    model = make_model(cfg)
    return cfg, model, jax.jit(model.init)(jax.random.PRNGKey(5))


def test_prefill_then_decode_through_the_pool_is_the_references_forward(toy):
    """Two prompts of different lengths in ONE padded prefill row (the
    engine's packed form), then three decode steps of three slots — the two
    requests and an IDLE slot between them — then a third request prefilled
    into the first one's blocks and slot (a slot given again): every logit
    row against the reference's whole forward over the sequence so far."""
    cfg, model, params = toy
    rng = np.random.default_rng(0)
    a, b, c = (rng.integers(0, HF["vocab_size"], n) for n in (24, 31, 28))
    ref = fam.Reference(HF, params)
    want = {k: ref.logits(v, pad_to=64) for k, v in (("a", a), ("b", b), ("c", c))}
    pools = model.init_paged_cache(9, BS, dtype=jnp.float32)
    prefill = jax.jit(lambda p, ids, pools, blocks, starts, lens:
                      model.prefill_paged(p, ids, pools, blocks,
                                          segments=(starts, lens)))
    step = jax.jit(lambda p, toks, pools, tables, lens, active:
                   model.decode_step_paged(p, toks, pools, tables, lens,
                                           active=active))
    na, nb = 20, 27                     # prompt lengths; the rest is decoded
    row = np.zeros((1, 64), np.int32)
    row[0, :na], row[0, 32:32 + nb] = a[:na], b[:nb]
    starts, lens = np.array([0, 32, 0, 0]), np.array([na, nb, 0, 0])
    lg, pools = prefill(params, row, pools, jnp.array([1, 2, 3, 4]), starts, lens)
    assert np.abs(np.asarray(lg[0]) - want["a"][na - 1]).max() < TOL
    assert np.abs(np.asarray(lg[1]) - want["b"][nb - 1]).max() < TOL
    tables = jnp.array([[1, 2, 0], [0, 0, 0], [3, 4, 0]])
    active = jnp.array([True, False, True])
    for t in range(3):
        toks = jnp.array([a[na + t], 0, b[nb + t]])
        lg, pools = step(params, toks, pools, tables,
                         jnp.array([na + t, 0, nb + t]), active)
        assert np.abs(np.asarray(lg[0]) - want["a"][na + t]).max() < TOL, t
        assert np.abs(np.asarray(lg[2]) - want["b"][nb + t]).max() < TOL, t
    nc = 25                              # into request a's blocks and slot
    row = np.zeros((1, 64), np.int32)
    row[0, :nc] = c[:nc]
    lg, pools = prefill(params, row, pools, jnp.array([1, 2, 0, 0]),
                        np.array([0, 0, 0, 0]), np.array([nc, 0, 0, 0]))
    assert np.abs(np.asarray(lg[0]) - want["c"][nc - 1]).max() < TOL
    lg, pools = step(params, jnp.array([c[nc], 0, b[nb + 3]]), pools, tables,
                     jnp.array([nc, 0, nb + 3]), active)
    assert np.abs(np.asarray(lg[0]) - want["c"][nc]).max() < TOL
    assert np.abs(np.asarray(lg[2]) - want["b"][nb + 3]).max() < TOL


def _plain_attention(q, k, v, scale, segment_ids=None):
    """softmax(q k^T scale) v, causal, in float64 numpy: [B, S, N, D]."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    S = q.shape[1]
    s = np.einsum("bsnd,btnd->bnst", q, k) * scale
    ok = np.tril(np.ones((S, S), bool))[None, None]
    if segment_ids is not None:
        seg = np.asarray(segment_ids)
        ok = ok & (seg[:, :, None] == seg[:, None, :])[:, None]
    s = np.where(ok, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bnst,btnd->bsnd", p / p.sum(-1, keepdims=True), v)


def test_v_narrower_than_the_keys_in_every_attention_path(toy):
    """``mixer_forward`` (XLA path) of one ``L`` block against plain softmax
    attention written out — q, k 24 wide, V 16, the YaRN table, the scale with
    ``mscale^2`` —, and the two flash calls in interpret mode at 192 / 128 over
    a row of 128: the packed forward keeps V's width, the differentiable call
    pads V and slices."""
    cfg, _, params = toy
    p = {k: v[1] for k, v in params["layers"]["latent"].items()}
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.hidden_size))
    out, row = latent_attention.mixer_forward(p, h, cfg)
    nq, dn, dr, dv, _, rkv = latent_attention.dims(cfg)
    assert (dn + dr, dv) == (24, 16) and row.shape == (2, 32, rkv + dr)
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    q_nope, q_rope, _ = latent_attention._project(p, h, cfg, pos)
    kv = (row[..., :rkv] @ p["wkv_b"]).reshape(2, 32, nq, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        row[:, :, None, rkv:], (2, 32, nq, dr))], -1)
    assert abs(cfg.attn_scale - 24 ** -0.5 * (0.1 * np.log(8) + 1) ** 2) < 1e-9
    o = _plain_attention(jnp.concatenate([q_nope, q_rope], -1), k,
                         kv[..., dn:], cfg.attn_scale)
    want = o.reshape(2, 32, nq * dv) @ np.asarray(p["wo"], np.float64)
    assert np.abs(np.asarray(out) - want).max() < TOL
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k = (jax.random.normal(ks[i], (1, 128, 2, 192)) for i in (0, 1))
    v = jax.random.normal(ks[2], (1, 128, 2, 128))
    seg = jnp.asarray(np.repeat([0, 1, 2], [50, 46, 32])[None], jnp.int32)
    got = fa.flash_attention_packed(q, k, v, seg, sm_scale=0.1)
    assert got.shape == (1, 128, 2, 128)
    assert np.abs(np.asarray(got) - _plain_attention(q, k, v, 0.1, seg)).max() < 1e-4
    got = fa.flash_attention(q, k, v, sm_scale=0.1)
    assert got.shape == (1, 128, 2, 128)
    assert np.abs(np.asarray(got) - _plain_attention(q, k, v, 0.1)).max() < 1e-4


def test_the_kernel_reads_what_the_list_read_reads():
    """ABSORBED, one block alone: ``mixer_step`` through ``latent_decode`` in
    interpret mode (rectangular tables, a bf16 pool, rank 128 so that it can
    be built) against the XLA list read on the same pool, slots of different
    lengths and an empty one — with this family's scale and a V of 16 under
    keys of 24."""
    cfg = hf_config_to_transformer({**HF, "kv_lora_rank": 128},
                                   dtype=jnp.bfloat16)
    ks = jax.random.split(jax.random.PRNGKey(3), 12)
    p = {name: (jax.random.normal(k, shape) * (shape[0] ** -0.5 if len(shape) > 1
                                               else 1.0)).astype(jnp.bfloat16)
         for k, (name, shape) in zip(ks, latent_attention.leaf_shapes(cfg).items())}
    W = latent_attention.stored_width(cfg)
    assert W == 256
    pool = latent_attention.as_stored(
        jax.random.normal(ks[8], (2, 9, BS, cfg.latent_row_width)) * 0.5, cfg
    ).astype(jnp.bfloat16)
    h = jax.random.normal(ks[9], (3, 1, cfg.hidden_size)).astype(jnp.bfloat16)
    tables = jnp.array([[1, 2, 3], [0, 0, 0], [4, 5, 0]], jnp.int32)
    lens = jnp.array([40, 0, 17], jnp.int32)
    outs = [np.asarray(latent_attention.mixer_step(
        p, h, cfg, pool, tables, lens, jnp.int32(1), backend)[0], np.float32)
        for backend in ("xla", "pallas")]
    assert np.abs(outs[0]).max() > 0.3
    assert np.abs(outs[0] - outs[1]).max() < 2e-2
    # the engine PRICES the read of such a model: its stated softmax scale
    # (``attn_scale``, YaRN's mscale^2) is the latent kernel's argument, not
    # the "unsupported variant" it is to the per-head kernels (the first chip
    # run of this cell fell back to the list read over the whole pool: 59.8 ms
    # a step)
    model = make_model(cfg)
    srv = deepspeed_tpu.init_serving(
        model, config={}, params=jax.jit(model.init)(jax.random.PRNGKey(0)),
        dtype=jnp.bfloat16, serving=dict(max_seqs=3, block_size=BS,
                                         max_model_len=128, prompt_bucket=16))
    try:
        assert cfg.attn_scale is not None and srv.backend_bench["priced"]
        assert srv.backend_bench["reason"] == "non-TPU backend"
    finally:
        srv.close()


def test_the_importer_reads_the_catalogs_config_and_refuses_the_rest():
    # the file as run: every published key verbatim but the three cuts, and
    # the one name the benchmark's shared readers look the experts up under
    assert AS_RUN == {**PUBLISHED, "num_hidden_layers": 6,
                      "first_k_dense_replace": 1,
                      "num_nextn_predict_layers": 0, "num_experts": 64}
    cfg = hf_config_to_transformer({**PUBLISHED, "num_nextn_predict_layers": 0})
    assert cfg.block_pattern == "LDLD" + "LE" * 38 and cfg.num_layers == 80
    assert (cfg.num_heads, cfg.dim_per_head, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        32, 192, 768, 512, 128, 64, 128)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp,
            cfg.norm_eps) == (4, 20, 1e-6, (-30.0, 30.0), 1e-6)
    assert (cfg.num_experts, cfg.top_k, cfg.moe_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.moe_shared_size, cfg.ffn_dim,
            cfg.dense_ffn_size, cfg.vocab_size, cfg.tie_embeddings) == (
        64, 4, "sigmoid", True, 2.0, 1024, 1024, 9216, 131072, False)
    (kind, table), = cfg.rope_tables
    assert kind == "latent" and (table.theta, table.factor,
                                 table.original_max_position, table.beta_fast,
                                 table.beta_slow, table.attention_factor) == (
        1e4, 64.0, 4096, 32.0, 1.0, 1.0)
    assert table.band(64) == (10, 23)
    assert abs(cfg.attn_scale - 192 ** -0.5 * 2.004739701682487) < 1e-12
    assert (cfg.latent_planes, cfg.latent_row_width,
            latent_attention.stored_width(cfg)) == (40, 576, 640)
    # 29.5 B in all, 3.9 B a token: the published 29B-A4B
    assert abs(fam.param_count(PUBLISHED) / 1e9 - 29.5) < 0.05
    assert abs(fam.active_params(PUBLISHED) / 1e9 - 3.93) < 0.05
    for key, bad in (("num_nextn_predict_layers", 1), ("n_group", 2),
                     ("topk_group", 2), ("ep_size", 8), ("attention_bias", True),
                     ("scoring_func", "softmax"), ("num_experts", 32),
                     ("hc_mult", None), ("num_key_value_heads", 8)):
        with pytest.raises(Xing40Unsupported, match=key) as e:
            hf_config_to_transformer({**AS_RUN, key: bad})
        assert e.value.key == key and e.value.value == bad
    with pytest.raises(Xing40Unsupported, match="rope_scaling.type"):
        hf_config_to_transformer(
            {**AS_RUN, "rope_scaling": {**AS_RUN["rope_scaling"], "type": "linear"}})
    plain = hf_config_to_transformer({**AS_RUN, "rope_scaling": None})
    assert plain.attn_scale is None and plain.rope_tables[0][1].factor == 1.0
    # every leaf of the tree has ONE checkpoint tensor, the mappings included
    cfg = hf_config_to_transformer(HF)
    names = xing4_0_weight_names(cfg)
    tree = jax.eval_shape(make_model(cfg).init, jax.random.PRNGKey(0))
    leaves = {(k, leaf) for k, st in tree["layers"].items() for leaf in st}
    assert {(k, leaf) for k, _, leaf, _ in names.values() if k} == leaves
    assert {leaf for k, _, leaf, _ in names.values() if not k} == \
        set(tree) - {"layers"}
    assert names["model.layers.2.hc_ffn_fn"] == ("moe", 0, "hc_phi", None)


def test_what_the_engine_reports_and_refuses(toy):
    """Prefix cache, chunked prefill and speculation answer as for GLM's
    stack (no span protocol); ``stats()`` carries the stream's width."""
    cfg, model, params = toy
    serving = dict(max_seqs=3, block_size=BS, max_model_len=128,
                   decode_quantum=4, prompt_bucket=16)
    for armed in (dict(enable_prefix_cache=True), dict(spec_tokens=2),
                  dict(prefill_token_budget=32)):
        with pytest.raises(ValueError, match="span protocol"):
            deepspeed_tpu.init_serving(model, params=params, dtype=jnp.float32,
                                       serving={**serving, **armed})
    with pytest.raises(ValueError, match="latent attention"):
        deepspeed_tpu.init_serving(model, params=params, dtype=jnp.float32,
                                   serving=serving, tensor_parallel=2)
    with pytest.raises(NotImplementedError, match="hc_mult"):
        import dataclasses
        make_model(dataclasses.replace(cfg, block_pattern=None, num_layers=2,
                                       rope_tables=None))
    srv = deepspeed_tpu.init_serving(model, config={}, params=params,
                                     dtype=jnp.float32, serving=serving)
    try:
        st = srv.stats()
        assert (st["hc_mult"], st["stream_bytes_per_token"]) == (
            4.0, 4.0 * cfg.hidden_size * 4)
        assert st["latent_planes"] == 4.0 and srv.model.config.hc_mult == 4
        assert set(srv.pools) == {"latent"} and model.slot_leaves == ()
    finally:
        srv.close()
