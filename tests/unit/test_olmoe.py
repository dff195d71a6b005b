"""OLMoE-1B-7B on the normal path (ISSUE 26), at toy widths on the CPU.

The toy model keeps the published 64 experts x top-8 (hidden 64, expert width
32, 2 layers, 4 heads); an odd one (12 experts, top-5) catches tile
assumptions. Everything is float32 (``conftest`` sets "highest" matmuls), so
the program and the plain reference (``benchmark/families/olmoe.py``: a
Python loop over experts, no sort, no capacity, no cache — it imports
nothing from ``deepspeed_tpu``) differ by summation order only.

TOLERANCE. The toy's branches are scaled up until its logits have sigma ~1.6
(``build``). The program and the reference then agree to ~2e-5; ``ATOL`` =
2e-4 leaves room for another BLAS and is 500-30000 x under what each seeded
defect moves the worst logit by (renormalised weights 0.09, a bf16 router
0.25, dropped assignments 4.1, no q/k norm 6.7: printed by the test):
computing the router, the combine or the norm in any lower precision than
the configuration states fails it. The int8 pool rounds K and V to 8 bits
per (position, head): positions read 0.03-0.08 off (``ATOL_INT8`` = 0.15), and
a position where that rounding swaps a token's 8th and 9th expert in a later
layer moves by a few tenths, so the int8 cases ask for 75 % of positions
within 0.15 and all within 1.0 — which still fails a dropped assignment or
a missing norm; the finer defects are tested on the float pool.
"""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import make_model
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (hf_config_to_transformer,
                                            load_hf_params)
from deepspeed_tpu.moe import sharded_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL, ATOL_INT8 = 2e-4, 0.15

# the catalog's `config` of OLMoE-1B-7B-0125-Instruct, verbatim
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}
TOY = dict(CATALOG, hidden_size=64, intermediate_size=32, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=4, vocab_size=128,
           max_position_embeddings=128)
ODD = dict(TOY, num_experts=12, num_experts_per_tok=5)
SHAPES = {"64x8": TOY, "12x5": ODD}


@pytest.fixture(params=["sorted", "one-hot"])
def dispatch(request, monkeypatch):
    """Both dropless dispatches, whatever `_sorts` would pick (the one-hot
    masks under ~300 tokens, under a mesh and in training: the sorted
    dispatch is forced here so that its arithmetic, and its gradient, are
    held to the reference before a later PR opens those to it)."""
    monkeypatch.setattr(sharded_moe, "_sorts",
                        lambda *a: request.param == "sorted")
    return request.param


def family():
    spec = importlib.util.spec_from_file_location(
        "benchmark.families.olmoe",
        os.path.join(ROOT, "benchmark", "families", "olmoe.py"))
    import sys
    sys.path.insert(0, ROOT)                 # it imports benchmark.families.mistral
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAM = family()


def build(hf, seed=0, **overrides):
    """(cfg, params) as the importer builds the model; the norm scales are
    randomised so that leaving a norm out, or its scale, shows."""
    cfg = hf_config_to_transformer(hf, max_seq_len=128, dtype=jnp.float32,
                                   param_dtype=jnp.float32,
                                   attention_impl="xla", **overrides)
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    k = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    lay = dict(params["layers"])
    for i, name in enumerate(("q_norm", "k_norm", "ln1_scale", "ln2_scale")):
        if name in lay:
            lay[name] = 1.0 + 0.3 * jax.random.normal(k[i], lay[name].shape)
    # a router with an opinion (logits of sigma ~1, as a trained one has) and
    # branches that matter: at init_params' std 0.02 the attention and the
    # expert outputs are 1e-3 of the residual stream and no defect in them
    # would reach the logits
    for name, gain in (("wg", 40.0), ("wv", 6.0), ("wo", 25.0), ("moe_w_in", 8.0),
                       ("moe_w_gate", 8.0), ("moe_w_out", 40.0)):
        lay[name] = lay[name] * gain
    params = {**params, "tok_embed": params["tok_embed"] * 50.0,
              "lm_head": params["lm_head"] * 10.0}
    return cfg, {**params, "layers": lay}


def ids_of(n, seed=3, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def program_logits(cfg, params, ids):
    return np.asarray(T.forward(params, jnp.asarray(ids)[None], cfg)[0])


# ---- (a) the full forward --------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
def test_forward_logits_match_the_plain_reference(shape, dispatch):
    hf = SHAPES[shape]
    cfg, params = build(hf)
    ids = ids_of(48)
    want = FAM.Reference(hf, params).logits(ids, pad_to=16)
    np.testing.assert_allclose(program_logits(cfg, params, ids), want, atol=ATOL, rtol=0)


# ---- (b) prefill_paged then decode_step_paged ------------------------------

def serve_logits(cfg, params, ids, n_prompt, bs=16):
    """Logits of positions n_prompt-1 .. len-1 through the paged path:
    prefill the prompt (padded to its bucket), then teacher-forced decode
    steps in slot 1 of 3 (slot 0 inactive, slot 2 decoding another prompt,
    so lockstep slots and the trash block are exercised)."""
    MB = 128 // bs
    pools = T.init_paged_cache(cfg, 1 + 3 * MB, bs)
    P = -(-n_prompt // bs) * bs
    buf = np.zeros((1, P), np.int32)
    buf[0, :n_prompt] = ids[:n_prompt]
    blocks = {1: np.arange(1, 1 + MB), 2: np.arange(1 + MB, 1 + 2 * MB)}
    last, pools = T.prefill_paged(params, jnp.asarray(buf), cfg, pools,
                                  jnp.asarray(blocks[1][:P // bs]),
                                  length=jnp.int32(n_prompt))
    other = ids_of(bs, seed=9)
    _, pools = T.prefill_paged(params, jnp.asarray(other[None]), cfg, pools,
                               jnp.asarray(blocks[2][:1]), length=jnp.int32(bs))
    out = [np.asarray(last[0])]
    tables = np.zeros((3, MB), np.int32)
    tables[1], tables[2] = blocks[1], blocks[2]
    active = jnp.asarray([False, True, True])
    step = jax.jit(lambda pools, tok, lens: T.decode_step_paged(
        params, tok, cfg, pools, jnp.asarray(tables), lens, active=active))
    for t in range(n_prompt, len(ids)):
        tok = jnp.asarray([0, ids[t], other[0]], jnp.int32)
        lens = jnp.asarray([0, t, bs + t - n_prompt], jnp.int32)
        logits, pools = step(pools, tok, lens)
        out.append(np.asarray(logits[1]))
    return np.stack(out)


@pytest.mark.parametrize("bits,atol", [(0, ATOL), (8, ATOL_INT8)], ids=["float-pool", "int8-pool"])
@pytest.mark.parametrize("shape", SHAPES)
def test_prefill_then_decode_match_the_reference_full_forward(shape, bits, atol, dispatch):
    hf = SHAPES[shape]
    cfg, params = build(hf, kv_cache_bits=bits)
    ids, n_prompt = ids_of(29), 21
    want = FAM.Reference(hf, params).logits(ids, pad_to=16)[n_prompt - 1:]
    err = np.abs(serve_logits(cfg, params, ids, n_prompt) - want).max(axis=-1)
    print(f"pool bits {bits}: per-position max |logit error| {np.sort(err)}")
    if bits == 0:
        assert err.max() <= atol
    else:
        # a position whose 8th / 9th expert the int8 rounding of an earlier
        # layer's K/V swapped is off by a few tenths; the others are close
        assert np.mean(err <= atol) >= 0.75 and err.max() < 1.0


# ---- (c) gating -------------------------------------------------------------

def test_top5_without_renormalisation_is_top_k_of_the_softmax():
    logits = jax.random.normal(jax.random.PRNGKey(4), (40, 12)) * 2.0
    w, idx, gates = sharded_moe.route(logits, 5, renormalize=False)
    top, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 5)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(top))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(top_i))
    assert float(jnp.max(jnp.sum(w, axis=-1))) < 1.0
    wn, _, _ = sharded_moe.route(logits, 5, renormalize=True)
    np.testing.assert_allclose(np.asarray(jnp.sum(wn, axis=-1)), 1.0, atol=1e-6)


def _top_k_gating_pr25(logits, k, capacity):
    """The argmax / one-hot / cumsum chain as it stood before ISSUE 26
    (noise policies left out): the oracle that pins Mixtral's k = 2."""
    T_, E = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    combine = jnp.zeros((T_, E, capacity), jnp.float32)
    masked, gate_sum = gates, jnp.zeros((T_,), jnp.float32)
    claimed = jnp.zeros((E,), jnp.int32)
    for _ in range(k):
        onehot = jax.nn.one_hot(jnp.argmax(masked, axis=-1), E, dtype=jnp.float32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1.0) * onehot, axis=-1).astype(jnp.int32) \
            + jnp.sum(onehot * claimed[None, :], axis=-1).astype(jnp.int32)
        keep = pos < capacity
        val = jnp.where(keep, jnp.sum(gates * onehot, axis=-1), 0.0)
        pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity, dtype=jnp.float32)
        combine = combine + (val[:, None] * onehot * keep[:, None])[..., None] * pos_oh[:, None, :]
        gate_sum = gate_sum + val
        claimed = claimed + jnp.sum(onehot, axis=0).astype(jnp.int32)
        masked = masked * (1.0 - onehot)
    if k > 1:
        combine = combine / jnp.where(gate_sum > 0, gate_sum, 1.0)[:, None, None]
    return combine


@pytest.mark.parametrize("capacity", [4, 10, 64])
def test_mixtral_top2_gating_is_bit_for_bit_what_it_was(capacity):
    """One lax.top_k and one cumsum over the choice-major list give the same
    experts, the same slots and the same drops as the chain of k argmax
    passes, and the mask is built by the same elementwise passes in the
    same order: equal to the bit, with drops (capacity 4, 10) and without."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (64, 8)) * 1.5
    combine, dispatch, _, _ = sharded_moe.top_k_gating(logits, 2, capacity)
    want = _top_k_gating_pr25(logits, 2, capacity)
    np.testing.assert_array_equal(np.asarray(combine), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(dispatch), np.asarray(want > 0))


def test_mixtral_dropless_outputs_are_what_the_one_hot_dispatch_gave(dispatch):
    """Mixtral's dropless layer as PR 25 computed it ([T, E, C = T] masks
    from the argmax chain) against both of today's dispatches. The one-hot
    one is the same arithmetic; the sorted one picks the same experts and
    the same renormalised weights but sums a token's two experts in another
    order (weights applied in float32 where the einsum applied them in the
    activation dtype): equal to float32 rounding, not to the bit."""
    cfg = T.mixtral_config("tiny", dtype=jnp.float32, drop_tokens=False,
                           num_experts=8)
    k = jax.random.split(jax.random.PRNGKey(6), 5)
    H, F, E = cfg.hidden_size, cfg.ffn_dim, 8
    p = {"wg": jax.random.normal(k[0], (H, E)) * 0.3,
         "w_in": jax.random.normal(k[1], (E, H, F)) * 0.05,
         "w_gate": jax.random.normal(k[2], (E, H, F)) * 0.05,
         "w_out": jax.random.normal(k[3], (E, F, H)) * 0.05}
    x = jax.random.normal(k[4], (2, 24, H))
    got, _ = sharded_moe.moe_ffn(p, x, cfg, train=False)
    tokens = x.reshape(-1, H)
    combine = _top_k_gating_pr25(tokens @ p["wg"], 2, tokens.shape[0])
    ein = jnp.einsum("tec,th->ech", (combine > 0).astype(x.dtype), tokens)
    act = jax.nn.silu(jnp.einsum("ech,ehf->ecf", ein, p["w_gate"])) \
        * jnp.einsum("ech,ehf->ecf", ein, p["w_in"])
    want = jnp.einsum("tec,ech->th", combine,
                      jnp.einsum("ecf,efh->ech", act, p["w_out"]))
    np.testing.assert_allclose(np.asarray(got).reshape(-1, H), np.asarray(want),
                               atol=1e-6, rtol=1e-5)


# ---- (d) dropless under skew ------------------------------------------------

def one_layer_moe(params, wg=None):
    lay = params["layers"]
    return {"wg": (lay["wg"] if wg is None else wg)[0], "w_in": lay["moe_w_in"][0],
            "w_gate": lay["moe_w_gate"][0], "w_out": lay["moe_w_out"][0]}


@pytest.mark.parametrize("shape", SHAPES)
def test_a_router_that_sends_every_token_to_one_expert_drops_nothing(shape, dispatch):
    hf = SHAPES[shape]
    cfg, params = build(hf)
    k, n = hf["num_experts_per_tok"], 40
    # every input has a large positive mean, so expert 3's logit dwarfs the
    # rest for EVERY token; the other k - 1 choices still vary by token
    wg = params["layers"]["wg"].at[:, :, 3].set(5.0)
    x = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (1, n, hf["hidden_size"]))
    with sharded_moe.expert_load_tap() as tap:
        got, _ = sharded_moe.moe_ffn(one_layer_moe(params, wg), x, cfg, train=False)
    row = np.asarray(tap.stacked())[0]
    assert row[3] == n                          # every token reached expert 3
    assert row[:-1].sum() == row[-1] == n * k   # and nothing was dropped
    ref = FAM.Reference(hf, params)
    layers = {**params["layers"], "wg": wg}
    with jax.default_matmul_precision("highest"):
        w = ref._router(layers, 0, x[0])
        want = sum(w[:, e, None] * ref._one_expert(layers, 0, e, x[0])
                   for e in range(hf["num_experts"]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=ATOL, rtol=0)


# ---- (e) each seeded defect fails the comparison ---------------------------

def _bf16_router(monkeypatch):
    real = sharded_moe.route
    monkeypatch.setattr(sharded_moe, "route", lambda logits, *a, **kw: real(
        logits.astype(jnp.bfloat16).astype(jnp.float32), *a, **kw))


@pytest.mark.parametrize("defect", ["renormalise", "drop", "no_qk_norm", "bf16_router"])
def test_each_seeded_defect_fails_the_comparison(defect, monkeypatch):
    monkeypatch.setattr(sharded_moe, "_sorts", lambda *a: True)
    hf = TOY
    cfg, params = build(hf)
    ids = ids_of(48)
    want = FAM.Reference(hf, params).logits(ids, pad_to=16)
    np.testing.assert_allclose(program_logits(cfg, params, ids), want, atol=ATOL, rtol=0)
    import dataclasses
    bad_params = params
    if defect == "renormalise":                   # Mixtral's rule on OLMoE
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    elif defect == "drop":                        # the capacity path: 48 x 8
        cfg = dataclasses.replace(cfg, drop_tokens=True,      # over 64 x 4 slots
                                  eval_capacity_factor=1.0, min_capacity=4)
    elif defect == "no_qk_norm":
        lay = {n: v for n, v in params["layers"].items()
               if n not in ("q_norm", "k_norm")}
        bad_params = {**params, "layers": lay}
    else:
        _bf16_router(monkeypatch)
    got = program_logits(cfg, bad_params, ids)
    print(f"defect {defect}: max |logit error| {np.abs(got - want).max():.3g} "
          f"(logits sigma {want.std():.3g})")
    assert np.abs(got - want).max() > 10 * ATOL, defect
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


# ---- (f) loss and gradients of the train forward ---------------------------

@pytest.mark.parametrize("shape", SHAPES)
def test_train_loss_and_gradients_match_the_reference(shape, dispatch):
    hf = SHAPES[shape]
    cfg, params = build(hf, moe_aux_loss_weight=0.0)   # the reference has no aux term
    batch = np.stack([ids_of(24, seed=s) for s in (11, 12)])
    ref = FAM.Reference(hf, params)
    model = make_model(cfg)

    def program_loss(p):
        return model.loss_fn(p, {"input_ids": jnp.asarray(batch)}, None, False)

    loss, grads = jax.value_and_grad(program_loss)(params)
    want_loss, want_grads = jax.value_and_grad(ref.loss_of)(params, batch)
    assert float(loss) == pytest.approx(ref.loss(batch), abs=1e-5)
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(g) for g in (grads, want_grads))
    assert [p for p, _ in flat] == [p for p, _ in want_flat]
    for (path, g), (_, w) in zip(flat, want_flat):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4 * scale,
                                   rtol=0, err_msg=jax.tree_util.keystr(path))


def test_initialize_trains_the_imported_model_from_the_reference_loss():
    """The training entry point on a config dict: the first step's loss is
    the reference's on the initial parameters (+ the aux term, switched off
    here), and it falls."""
    cfg = hf_config_to_transformer(TOY, max_seq_len=32, dtype=jnp.float32,
                                   attention_impl="xla", moe_aux_loss_weight=0.0)
    engine, *_ = deepspeed_tpu.initialize(model=make_model(cfg), config={
        "train_batch_size": 8, "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
        "bf16": {"enabled": False}, "steps_per_print": 1000})
    batch = {"input_ids": np.stack([ids_of(32, seed=s) for s in range(8)])}
    params0 = jax.device_get(engine.state["params"])
    want = FAM.Reference(TOY, params0).loss(batch["input_ids"])
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(8)]
    assert losses[0] == pytest.approx(want, abs=1e-4)
    assert losses[-1] < losses[0]
    engine.close()


# ---- (g) the import ---------------------------------------------------------

def test_the_catalog_config_imports_as_what_the_model_is():
    cfg = hf_config_to_transformer(CATALOG)
    assert (cfg.num_experts, cfg.top_k, cfg.ffn_dim) == (64, 8, 1024)
    assert cfg.drop_tokens is False and cfg.norm_topk_prob is False
    assert cfg.qk_norm is True and cfg.tie_embeddings is False
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
            cfg.dim_per_head, cfg.vocab_size) == (16, 2048, 16, 16, 128, 50304)
    assert (cfg.norm_type, cfg.norm_eps, cfg.rope_theta, cfg.activation) == \
        ("rmsnorm", 1e-5, 10000.0, "silu_glu")
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert n == 16 * (419_561_472 + 4 * 2048) + 2 * 50304 * 2048 + 2048   # 6.92 B
    with pytest.raises(ValueError, match="clip_qkv"):
        hf_config_to_transformer(dict(CATALOG, clip_qkv=8.0))
    # Mixtral keeps its own rule: renormalised, no q/k norm
    mix = hf_config_to_transformer({"model_type": "mixtral", "vocab_size": 64,
                                    "hidden_size": 32, "num_hidden_layers": 1,
                                    "num_attention_heads": 2, "intermediate_size": 48})
    assert mix.norm_topk_prob is True and mix.qk_norm is False


def published_state_dict(params, hf):
    """The program's tree under the PUBLISHED names (torch Linear layout:
    [out, in])."""
    lay, sd = params["layers"], {}
    sd["model.embed_tokens.weight"] = np.asarray(params["tok_embed"])
    sd["model.norm.weight"] = np.asarray(params["final_norm_scale"])
    sd["lm_head.weight"] = np.asarray(params["lm_head"]).T
    names = {"input_layernorm": "ln1_scale", "post_attention_layernorm": "ln2_scale",
             "self_attn.q_norm": "q_norm", "self_attn.k_norm": "k_norm"}
    mats = {"self_attn.q_proj": "wq", "self_attn.k_proj": "wk",
            "self_attn.v_proj": "wv", "self_attn.o_proj": "wo", "mlp.gate": "wg"}
    experts = {"gate_proj": "moe_w_gate", "up_proj": "moe_w_in", "down_proj": "moe_w_out"}
    for i in range(hf["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        for theirs, ours in names.items():
            sd[pre + theirs + ".weight"] = np.asarray(lay[ours][i])
        for theirs, ours in mats.items():
            sd[pre + theirs + ".weight"] = np.asarray(lay[ours][i]).T
        for e in range(hf["num_experts"]):
            for theirs, ours in experts.items():
                sd[f"{pre}mlp.experts.{e}.{theirs}.weight"] = np.asarray(lay[ours][i, e]).T
    return sd


def test_the_weight_table_round_trips_the_published_names():
    cfg, params = build(ODD)
    sd = published_state_dict(params, ODD)
    back = load_hf_params(sd, cfg)
    flat = dict(jax.tree_util.tree_leaves_with_path(params))
    for path, leaf in jax.tree_util.tree_leaves_with_path(back):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(flat[path]),
                                      err_msg=jax.tree_util.keystr(path))
    assert len(flat) == len(jax.tree.leaves(back))
    with pytest.raises(ValueError, match="unmapped key"):
        load_hf_params({**sd, "model.layers.0.mlp.shared_expert.weight": np.zeros(1)}, cfg)


def test_logits_match_the_published_implementation():
    """transformers' OlmoeForCausalLM on random weights: the q/k norm sits
    where the published block has it, the top-8 weights are not
    renormalised, and the weight table reads the published names."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    hf_cfg = transformers.OlmoeConfig(**{k: v for k, v in ODD.items() if k != "model_type"})
    torch.manual_seed(0)
    model = transformers.OlmoeForCausalLM(hf_cfg).eval()
    with torch.no_grad():                      # norms off 1, so a missing scale shows
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.3 * torch.randn_like(p))
    cfg = hf_config_to_transformer(hf_cfg.to_dict(), max_seq_len=128, dtype=jnp.float32,
                                   attention_impl="xla")
    params = load_hf_params(model, cfg)
    ids = ids_of(24)
    with torch.no_grad():
        want = model(torch.from_numpy(ids[None].astype(np.int64))).logits[0].numpy()
    np.testing.assert_allclose(program_logits(cfg, params, ids), want, atol=5e-4, rtol=0)
    np.testing.assert_allclose(FAM.Reference(ODD, params).logits(ids, pad_to=8), want,
                               atol=5e-4, rtol=0)


# ---- (h) q/k norm under tensor parallelism ----------------------------------

def test_qk_norm_over_the_tensor_axis_agrees_with_one_device():
    """The norm runs over the whole projection; with the heads split over
    `tensor` its mean square is a reduction across the axis (GSPMD inserts
    it for the global array), never a per-shard norm: the loss of a
    tensor-parallel engine equals the one-device loss."""
    cfg, _ = build(TOY, moe_aux_loss_weight=0.0)
    batch = {"input_ids": np.stack([ids_of(32, seed=s) for s in range(8)])}
    losses = {}
    for tp in (1, 2):
        conf = {"train_batch_size": 8, "bf16": {"enabled": False},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "steps_per_print": 1000}
        if tp > 1:
            conf["tensor_parallel"] = {"size": tp}
        engine, *_ = deepspeed_tpu.initialize(model=make_model(cfg), config=conf)
        if tp > 1:
            spec = engine.state["params"]["layers"]["q_norm"].sharding.spec
            assert "tensor" in str(spec), spec
        losses[tp] = [float(engine.train_batch(batch)["loss"]) for _ in range(3)]
        engine.close()
    np.testing.assert_allclose(losses[2], losses[1], rtol=2e-5)


# ---- the routing counters ride the round's one fetch ------------------------

def test_counters_ride_the_rounds_one_fetch(monkeypatch):
    """The decode step's and the prefill's outputs grow by one [L, E + 1]
    int32 array each, beside the tokens they already returned; a round still makes exactly ONE ``jax.device_get``
    (under ``ds:serve.fetch``); ``stats()`` gains the three routing keys and
    a dropless model drops nothing. Inactive slots and pad tokens do not
    count: the assignments add up to the real tokens x top-k."""
    cfg, _ = build(TOY)
    srv = deepspeed_tpu.init_serving(
        make_model(cfg), serving=dict(max_seqs=4, max_model_len=128),
        rng=jax.random.PRNGKey(0), dtype=jnp.float32)
    L, E, k = 2, 64, 8
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)   # noqa: E731
    out = jax.eval_shape(srv._quantum_step_fn().__wrapped__, srv.engine.params,
                         srv.pools, i32(4), i32(4, srv.MB), i32(4),
                         jax.ShapeDtypeStruct((4,), jnp.bool_), key)
    load, exits = out[1][1]                  # the counters, beside the tokens
    assert (load.shape, load.dtype) == ((L, E + 1), jnp.int32) and exits is None
    out = jax.eval_shape(srv._get_prefill_fn(64).__wrapped__, srv.engine.params,
                         i32(1, 64), srv.pools, i32(1), i32(4), i32(4), key)
    load, exits = out[0][1]                  # beside the first tokens
    assert (load.shape, load.dtype) == ((L, E + 1), jnp.int32) and exits is None

    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: gets.append(1) or real_get(x))
    prompts = [ids_of(n, seed=n) for n in (5, 17, 40)]
    for p in prompts:
        srv.add_request(p, 9)                # 1 token from the prefill + 8 steps
    rounds = 0
    while srv.scheduler.running or srv.scheduler.num_waiting:
        before = len(gets)
        srv.step()
        rounds += 1
        assert len(gets) - before == 1
    st = srv.stats()
    assert st["moe_dropped_share"] == 0.0
    # 3 slots x 8 decode steps + the real prompt tokens, on 2 layers x top-8
    assert st["moe_assignments"] == (3 * 8 + 5 + 17 + 40) * L * k
    assert 1.0 <= st["moe_experts_touched_per_step"] <= 3 * k
    assert st["moe_load_max_over_mean"] >= 1.0
    srv.reset_stats()
    assert "moe_dropped_share" not in srv.stats()
    srv.close()


def test_a_model_without_experts_has_no_routing_counters():
    cfg = T.TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                              num_heads=4, max_seq_len=128, dtype=jnp.float32,
                              attention_impl="xla")
    srv = deepspeed_tpu.init_serving(
        make_model(cfg), serving=dict(max_seqs=2, max_model_len=128),
        rng=jax.random.PRNGKey(0), dtype=jnp.float32)
    srv.add_request(ids_of(7), 4)
    while srv.scheduler.running or srv.scheduler.num_waiting:
        srv.step()
    assert not [key for key in srv.stats() if key.startswith("moe_")]
    srv.close()


# ---- the grouped-matmul kernel (interpret mode here; compiled for the chip
# in test_pool_layout.py) -----------------------------------------------------

@pytest.mark.parametrize("M,E,K,N,L", [(256, 64, 256, 128, 2), (512, 8, 256, 256, 1),
                                        (200, 12, 128, 384, 3), (64, 8, 128, 128, 1)])
def test_grouped_matmul_kernel_matches_ragged_dot(M, E, K, N, L):
    """Uneven groups, an empty expert, rows that belong to nobody (7 of M),
    a row count off the tile (200), and the layer picked out of a stack."""
    from deepspeed_tpu.ops import grouped_matmul as G
    rng = np.random.default_rng(M + E)
    sizes = rng.multinomial(M - 7, np.ones(E) / E).astype(np.int32)
    sizes[1], sizes[2] = sizes[1] + sizes[2], 0
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((L, E, K, N)) * 0.1, jnp.float32)
    got = jax.jit(G.grouped_matmul)(x, w, jnp.int32(L - 1), jnp.asarray(sizes))
    want = jax.lax.ragged_dot(x, w[L - 1], jnp.asarray(sizes))
    n = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]), atol=1e-4, rtol=0)


def test_grouped_matmul_visits_walk_every_tile_expert_pair_once():
    from deepspeed_tpu.ops import grouped_matmul as G
    # experts 0, 2, 3, 5 hold rows 0-2, 3-132, 133-137, 138-255 of two tiles
    off, expert, tile, n = G.visits(jnp.asarray([3, 0, 130, 5, 0, 118], jnp.int32), 128, 2)
    assert int(n) == 5
    assert list(map(int, off)) == [0, 3, 3, 133, 138, 138, 256]
    assert list(map(int, expert[:5])) == [0, 2, 2, 3, 5]
    assert list(map(int, tile[:5])) == [0, 0, 1, 1, 1]
    # every row to one expert: as many visits as row tiles
    one = jnp.zeros((64,), jnp.int32).at[3].set(256)
    assert int(G.visits(one, 128, 2)[3]) == 2
    assert G.row_tile(256, 64) == 128 and G.row_tile(6144, 64) == 256
    assert G.row_tile(64, 8) == 64 and G.row_tile(512, 8) == 256


def test_the_layer_runs_on_the_kernel_as_on_ragged_dot(monkeypatch):
    """The sorted dispatch through the Pallas kernel (interpret mode, the
    expert stacks handed over whole) gives what it gives through XLA's
    ragged_dot."""
    cfg = T.mixtral_config("tiny", dtype=jnp.float32, drop_tokens=False,
                           num_experts=8, hidden_size=128, intermediate_size=256)
    k = jax.random.split(jax.random.PRNGKey(8), 5)
    H, F, E, L = 128, 256, 8, 3
    stacks = {"w_in": jax.random.normal(k[1], (L, E, H, F)) * 0.05,
              "w_gate": jax.random.normal(k[2], (L, E, H, F)) * 0.05,
              "w_out": jax.random.normal(k[3], (L, E, F, H)) * 0.05}
    wg = jax.random.normal(k[0], (H, E)) * 0.3
    x = jax.random.normal(k[4], (1, 40, H))
    plain = {"wg": wg, **{n: v[1] for n, v in stacks.items()}}
    want, _ = sharded_moe.moe_ffn(plain, x, cfg, train=False)
    monkeypatch.setattr(sharded_moe, "_use_gmm_kernel", lambda *a: True)
    held = {"wg": wg, **{n: sharded_moe.LayerOf(v, jnp.int32(1)) for n, v in stacks.items()}}
    got, _ = jax.jit(lambda x: sharded_moe.moe_ffn(held, x, cfg, train=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=0)
    got, _ = sharded_moe.moe_ffn(plain, x, cfg, train=False)     # un-stacked weights
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=0)


# (one token's row of H, one expert's matrices) in bytes at the published
# widths, bf16: the two extents the rule prices a call by
_BYTES = {"mixtral": (4096 * 2, 3 * 4096 * 14336 * 2),
          "olmoe": (2048 * 2, 3 * 2048 * 1024 * 2),
          "nemotron": (2688 * 2, 2 * 2688 * 1856 * 2),
          "qwen3-next": (2048 * 2, 3 * 2048 * 512 * 2),
          "trinity": (3072 * 2, 3 * 3072 * 3072 * 2)}
# (experts held, assignments a token is expected to have on them)
_HELD = {"mixtral": (8, 2), "olmoe": (64, 8), "nemotron": (128, 6),
         "qwen3-next": (128, 10 * 128 / 512), "trinity": (32, 4 * 32 / 256)}


def _published_widths(monkeypatch, family):
    """The toy layer's experts priced at ``family``'s published widths: the
    sorted dispatch's fixed work is a time, so against a toy expert of a few
    hundred KiB it is hundreds of visits and the rule keeps the masks at
    every length; priced at the widths the toy stands for it picks what the
    cell's program picks."""
    monkeypatch.setattr(sharded_moe, "_expert_shapes", lambda p: _BYTES[family])


@pytest.mark.parametrize("family,T_,one_hot", [
    # Mixtral's cell (prompts to 256, 32 slots) never sorts: 8 experts of
    # 352 MB hide 256 rows each, and their [E, T, H] rows are nothing beside
    # them (8.0 - 8.9 visits against 8.0 - 9.6)
    *[("mixtral", T_, True) for T_ in (32, 64, 128, 192, 256)],
    *[("mixtral", T_, False) for T_ in (384, 512, 2048)],
    # OLMoE's 32-slot step keeps the masks, inside the tie (67.0 against
    # 63.9 + 1.0, the tie 2.6 visits; measured 1.146 / 1.154 ms against
    # 1.082 / 1.117); every prompt bucket sorts since PR 46 (64 tokens, the
    # nearest: 70.8 against 67.0 + 1.0 + 2.6; in the cell a lone prompt
    # reaches half the experts)
    *[("olmoe", T_, True) for T_ in (32, 48)],
    *[("olmoe", T_, False) for T_ in (64, 128, 192, 256, 320, 384, 512, 768,
                                      4096)],
    # PR 45, the five cells' DECODE STEPS (T = max_seqs). Trinity's 64 slots
    # put 32 rows on 32 held experts and are expected to touch 20.4: sorted
    ("trinity", 64, False),
    ("olmoe", 32, True), ("mixtral", 32, True),
    # PR 46: Nemotron's 128 slots x top-6 reach every one of its 128 experts
    # (132.7 visits sorted), and the one-hot form's [E, T, H] passes and
    # einsums are 27 visits on top of its 128: measured alone 4.64 ms
    # against 3.59
    ("nemotron", 128, False),
    # Qwen3-Next, 128 slots x 10 of 512 on the 128 held: 119.6 + 2.1 of 128
    # visits, sorted. Measured both ways on the chip before the constant was
    # fixed (PR 45, the cell's `serve_tokens_per_s`, two seeds a side):
    # one-hot 3318.4 / 3307.2, sorted 3766.2 / 3742.5
    ("qwen3-next", 128, False),
    # each cell's smallest and largest prompt bucket
    ("mixtral", 64, True), ("mixtral", 256, True),
    ("olmoe", 64, False), ("olmoe", 768, False),
    ("nemotron", 1024, False), ("qwen3-next", 1024, False),
    ("trinity", 4096, False), ("trinity", 9216, False),
    ("nemotron", 64, False), ("qwen3-next", 64, False),
    # Nemotron's and Qwen3-Next's buckets between: all sorted since PR 46
    # (Qwen3-Next's 192 was the last on the masks: 128.0 + 2.1 of 128 under
    # the old price, 243 of 130 under this one; measured 2.00 ms against
    # 1.23)
    ("nemotron", 192, False), ("nemotron", 256, False),
    ("qwen3-next", 192, False), ("qwen3-next", 256, False),
])
def test_the_dispatch_is_chosen_by_the_calls_shapes(family, T_, one_hot):
    """One-hot masks while T rows per expert hide under the expert's weight
    bytes, the ``[E, T, H]`` rows around them are few beside those bytes AND
    the rows are expected to reach nearly every expert; sorting beyond, and
    where they reach few."""
    E, k = _HELD[family]
    assert sharded_moe._one_hot_is_cheaper(T_, E, k, *_BYTES[family]) == one_hot
    # ... at inference and in training alike (one rule since PR 48)
    assert sharded_moe._sorts(T_, E, k, *_BYTES[family]) != one_hot


# one expert layer ALONE on the chip, both forms (ms a layer; PERF.md section
# 6, PR 46: `scratch_chip/layer_forms.py`, a fresh function a form, the stacks
# handed whole, an even router): (family, T, one-hot, sorted)
_MEASURED = [
    ("nemotron", 32, 3.442, 2.771), ("nemotron", 64, 3.543, 3.263),
    ("nemotron", 128, 4.644, 3.591), ("nemotron", 192, 4.577, 3.635),
    ("nemotron", 256, 5.609, 3.741), ("nemotron", 384, 8.392, 3.921),
    ("nemotron", 512, 11.648, 4.300),
    ("olmoe", 32, 1.146, 1.082), ("olmoe", 48, 1.164, 1.149),
    ("olmoe", 64, 1.196, 1.149), ("olmoe", 128, 1.184, 1.231),
    ("olmoe", 192, 1.294, 1.342), ("olmoe", 256, 1.579, 1.395),
    ("olmoe", 320, 2.113, 1.446), ("olmoe", 384, 2.328, 1.503),
    ("qwen3-next", 64, 1.215, 0.871), ("qwen3-next", 128, 1.411, 1.125),
    ("qwen3-next", 192, 1.998, 1.225), ("qwen3-next", 256, 2.778, 1.405),
    ("qwen3-next", 384, 4.485, 1.477),
    ("mixtral", 32, 3.768, 3.778), ("mixtral", 64, 3.758, 3.800),
    ("mixtral", 128, 3.778, 4.174), ("mixtral", 192, 3.808, 4.728),
    ("mixtral", 256, 4.243, 4.734), ("mixtral", 384, 6.132, 5.294),
    ("trinity", 64, 2.443, 1.719), ("trinity", 128, 2.469, 2.346),
    ("trinity", 256, 2.950, 2.840),
]
# where the rule and the isolated layer part ways, and why it stands: at an
# EVEN router OLMoE's 128- and 192-token calls read 0.05 ms faster on the
# masks; the price says 0.14 / 0.30 ms slower (the one-hot side's largest
# residuals under 256 tokens). In the cell a lone prompt's tokens reach half
# the experts (`moe_experts_touched_per_prefill` 30-35 of 64) and the sorted
# program reads only those: 21.7 / 22.6 ms a program on the masks against
# 16.2 for the SORTED 320-token bucket (PERF.md section 5)
_PARTED = {("olmoe", 128), ("olmoe", 192)}


@pytest.mark.parametrize("family,T_,one_hot_ms,sorted_ms", _MEASURED)
def test_the_two_prices_reproduce_the_measured_layer(family, T_, one_hot_ms,
                                                     sorted_ms):
    """Each measured point a case: the two prices, turned back into time (a
    visit = an expert's bytes at the 764 GB/s both forms stream at, + the
    0.09 ms either form pays whatever its shapes), say which form is the
    faster one wherever the measurement can tell (more than the tie's 0.04 ms
    apart) and, under 256 tokens — where the choice is made —, land within
    0.55 ms of each reading (rms 0.19 one-hot, 0.08 sorted); beyond, within
    two fifths of it (the one-hot price runs high there: OLMoE's 384 tokens
    3.15 ms for 2.33 measured, against 1.50 sorted)."""
    from deepspeed_tpu.ops import grouped_matmul as G
    E, k = _HELD[family]
    row_bytes, expert_bytes = _BYTES[family]
    visit_ms = expert_bytes / 764e9 * 1e3
    rows = math.ceil(T_ * k)
    one_hot = G.one_hot_cost(T_, E, row_bytes, expert_bytes) * visit_ms + 0.09
    sorts = (G.visit_cost(rows, E, G.row_tile(rows, E)) * visit_ms + 0.09
             + G.SORTED_FIXED_BYTES / 819e9 * 1e3)
    for price, read in ((one_hot, one_hot_ms), (sorts, sorted_ms)):
        assert abs(price - read) < (0.55 if T_ < 256 else 0.4 * read)
    gap = one_hot_ms - sorted_ms
    takes_masks = sharded_moe._one_hot_is_cheaper(T_, E, k, *_BYTES[family])
    if (family, T_) in _PARTED:
        assert -0.06 < gap < 0 and not takes_masks
    elif abs(gap) > 0.07:              # clear of the tie and of its scatter
        assert takes_masks == (gap < 0)
    else:                              # a tie either way: the rule may keep
        assert abs((one_hot - sorts) - gap) < 0.06      # the masks or not


def _dropless_layer(tokens=512, E=8, H=128, F=256):
    cfg = T.mixtral_config("tiny", dtype=jnp.float32, drop_tokens=False,
                           num_experts=E, hidden_size=H, intermediate_size=F)
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    params = {"wg": jax.random.normal(k[0], (H, E)) * 0.3,
              "w_in": jax.random.normal(k[1], (E, H, F)) * 0.05,
              "w_gate": jax.random.normal(k[2], (E, H, F)) * 0.05,
              "w_out": jax.random.normal(k[3], (E, F, H)) * 0.05}
    return cfg, params, jax.random.normal(k[4], (1, tokens, H))


def test_a_dropless_call_under_a_mesh_keeps_the_one_hot_einsums(monkeypatch):
    """The sorted dispatch sets no sharding constraint and nobody has
    compiled it with the experts sharded: under a mesh, at inference and in
    training, a dropless call of many tokens lowers to what it lowered to
    before PR 26 (no ragged dot; the `[E, C, H]` arrays constrained over
    `expert`), and gives what the sorted dispatch gives on one device —
    where training sorts as inference does (PR 48: the grouped matmul has a
    backward)."""
    from jax.sharding import Mesh, NamedSharding
    _published_widths(monkeypatch, "mixtral")
    cfg, params, x = _dropless_layer()

    def layer(train):
        return jax.jit(lambda p, x: sharded_moe.moe_ffn(p, x, cfg, train=train)[0])

    def sorts(train, p):
        return "ragged_dot" in str(jax.make_jaxpr(layer(train))(p, x))

    assert sorts(False, params)                      # one device, inference
    assert sorts(True, params)                       # ... and training
    want = layer(False)(params, x)

    def constraints(text):
        return text.count("sharding_constraint") + text.count("@Sharding")

    assert constraints(layer(True).lower(params, x).as_text()) == 0   # no mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    with mesh:
        placed = {n: jax.device_put(v, NamedSharding(
            mesh, P() if n == "wg" else P("expert"))) for n, v in params.items()}
        assert not sorts(False, placed) and not sorts(True, placed)
        text = layer(False).lower(placed, x).as_text()
        # expert_in and the experts' output, [E, C, H] over `expert`
        assert constraints(text) >= 2
        got = layer(False)(placed, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)


def test_the_load_taps_of_two_threads_do_not_meet():
    """The serving engine's watchdog traces a step on a thread of its own
    and may abandon it; two engines may trace at once. A tap belongs to the
    thread that opened it."""
    import threading
    seen = {}

    def other():
        seen["wanted"] = sharded_moe.expert_load_wanted()
        with sharded_moe.expert_load_tap() as tap:
            sharded_moe.record_expert_load(jnp.ones((2, 9), jnp.int32))
            seen["rows"] = len(tap.rows)

    with sharded_moe.expert_load_tap() as mine:
        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert sharded_moe.expert_load_wanted()
        assert not mine.rows                         # the other thread's rows
    assert seen == {"wanted": False, "rows": 2}
    assert not sharded_moe.expert_load_wanted()
