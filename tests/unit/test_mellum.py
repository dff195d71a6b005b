"""``mellum`` (JetBrains Mellum2-12B-A2.5B) on the normal TRAINING path, at toy
widths on the CPU in float32, against the plain reference of
``benchmark/families/mellum.py``: logits, the loss AND the gradient of every
parameter leaf (both dropless dispatches; remat and the chunked loss on), each
seeded defect, the four quarter-shares of an expert layer against the uncut
layer, the grouped matmul's VJP against ``jax.grad`` of a dense per-expert
loop (kernel in interpret mode), the YaRN table against the closed form, a
step whose router sends 90 % of the rows to one expert, the engine's load
metrics, and what ``hf_import`` maps and refuses.

Tolerances, float32 on the CPU: logits of size ~1 agree to 2e-4 (sound
readings 3e-6); a gradient leaf to 2e-4 of its own largest entry + 1e-7
(sound readings 2e-5: sums over 96 positions in another order); the smallest
defect moves the logits by 2e-3."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402
from benchmark.families import mellum as fam  # noqa: E402
from deepspeed_tpu.models import hf_import, hybrid, make_model  # noqa: E402
from deepspeed_tpu.models.hf_import import hf_config_to_transformer  # noqa: E402
from deepspeed_tpu.models.transformer import RopeTable, rotary_embed  # noqa: E402
from deepspeed_tpu.moe import sharded_moe as sm  # noqa: E402
from deepspeed_tpu.ops import grouped_matmul as gmm  # noqa: E402
from deepspeed_tpu.ops import moe_rows as mr  # noqa: E402

TOL = 2e-4
WINDOW = 16
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
# the toy's own stretch: 32 positions "original", so that pairs of a 32-dim
# head fall on both sides of the ramp within a 96-position test
TOY_YARN = dict(YARN, rope_theta=10000, original_max_position_embeddings=32,
                factor=4, attention_factor=0.1 * math.log(4) + 1)
HF = {"model_type": "mellum", "hidden_act": "silu", "attention_bias": False,
      "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
      "tie_word_embeddings": False, "num_experts_per_tok": 8,
      "norm_topk_prob": True, "use_sliding_window": True,
      "max_window_layers": 0,
      "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
      "mlp_layer_types": ["sparse"] * 4,
      "rope_parameters": {
          "full_attention": TOY_YARN,
          "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
      **fam.TOY, "sliding_window": WINDOW}


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], shape)


@pytest.fixture(scope="module")
def toy():
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32, remat=True,
                                   remat_policy="save_nothing", loss_chunk=32)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    # norm scales away from 1, so that leaving a norm out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)
        if any(getattr(k, "key", "") in ("ln_scale", "q_norm", "k_norm",
                                         "final_norm_scale") for k in path)
        else a, params)
    return cfg, model, params, fam.Reference(HF, params)


# ---- what hf_import maps and refuses ---------------------------------------

def test_the_published_config_maps_to_the_pattern():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        cat = next(json.loads(ln) for ln in f if '"Mellum2-12B-A2.5B-Instruct"' in ln)
    cfg = hf_config_to_transformer(cat["config"])
    assert cfg.block_pattern == "WEWEWE*E" * 7 and cfg.num_layers == 56
    assert hybrid.window(cfg) == 1024 and cfg.window_blocks == 21
    assert (cfg.num_heads, cfg.kv_heads, cfg.dim_per_head) == (32, 4, 128)
    assert (cfg.num_experts, cfg.moe_router_width, cfg.top_k, cfg.ffn_dim) == (
        64, 64, 8, 896)
    assert cfg.norm_topk_prob and not cfg.drop_tokens and cfg.qk_norm_per_head
    assert not cfg.attn_out_gate and not cfg.sandwich_norm
    assert cfg.moe_shared_size == 0 and cfg.moe_aux_loss_weight == 0.0
    assert cfg.moe_scoring == "softmax" and not cfg.tie_embeddings
    tables = dict(cfg.rope_tables)
    assert tables["wattn"] == RopeTable(500000.0)
    assert tables["attn"] == RopeTable(500000.0, 16.0, 8192, 32.0, 1.0,
                                       1.2772588722239782)
    # the chip's share, as the benchmark's configuration states it
    part = hf_config_to_transformer(dict(
        cat["config"], num_hidden_layers=4, num_experts=16,
        num_experts_router=64, vocab_size=24576))
    assert (part.block_pattern, part.num_experts, part.moe_router_width,
            part.moe_held_first) == ("WEWEWE*E", 16, 64, 0)


@pytest.mark.parametrize("change, what", [
    ({"mlp_layer_types": ["sparse", "dense", "sparse", "sparse"]}, "sparse"),
    ({"rope_parameters": {**HF["rope_parameters"], "full_attention": {
        "rope_type": "linear", "rope_theta": 1e4, "factor": 4}}}, "rope_type"),
    ({"rope_parameters": {"sliding_attention": {"rope_theta": 1e4}}}, "no group"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"layer_types": ["linear_attention"] * 4}, "layer_types"),
    ({"sliding_window": None}, "sliding_window"),
    ({"expert_first": 30}, "held of"),
])
def test_what_the_importer_refuses(change, what):
    with pytest.raises(ValueError, match=what):
        hf_config_to_transformer(dict(HF, **change))


def test_a_table_per_kind_belongs_to_a_hybrid_stack():
    from deepspeed_tpu.models import TransformerConfig
    with pytest.raises(NotImplementedError, match="rope_tables"):
        make_model(TransformerConfig(rope_tables=(("attn", RopeTable()),)))


def test_the_table_of_hf_weight_names(toy):
    cfg, _, params, _ = toy
    names = hf_import.mellum_weight_names(cfg)
    # every leaf of the tree is named, every expert of every stack once
    seen = {}
    for name, (kind, j, leaf, part) in names.items():
        tree = params if kind is None else params["layers"][kind]
        assert leaf in tree, name
        seen.setdefault((kind, leaf), set()).add((j, part))
    leaves = {(None, k) for k in ("tok_embed", "final_norm_scale", "lm_head")} | {
        (kind, leaf) for kind, st in params["layers"].items() for leaf in st}
    assert set(seen) == leaves
    assert seen[("moe", "moe_w_in_t")] == {(j, e) for j in range(4)
                                           for e in range(8)}
    assert names["model.layers.3.self_attn.q_norm.weight"] == (
        "attn", 0, "q_norm", None)
    assert names["model.layers.2.mlp.experts.5.up_proj.weight"] == (
        "moe", 2, "moe_w_in_t", 5)
    assert names["model.layers.1.post_attention_layernorm.weight"] == (
        "moe", 1, "ln_scale", None)


def test_embed_init_scale_draws_the_embeddings_larger_and_nothing_else():
    """An INITIALISER: the same draw times the scale in ``tok_embed``, every
    other leaf as it was, on the hybrid walker and the homogeneous stack."""
    import dataclasses
    from deepspeed_tpu.models import TransformerConfig
    hybrid_cfg = hf_config_to_transformer(HF, dtype=jnp.float32)
    plain_cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=1,
                                  num_heads=2, max_seq_len=16)
    for cfg in (hybrid_cfg, plain_cfg):
        one = make_model(cfg).init(jax.random.PRNGKey(0))
        big = make_model(dataclasses.replace(cfg, embed_init_scale=50.0)).init(
            jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(big["tok_embed"]),
                                   50.0 * np.asarray(one["tok_embed"]), rtol=1e-6)
        assert float(jnp.std(big["tok_embed"])) == pytest.approx(1.0, rel=0.05)
        rest = lambda t: jax.tree.leaves({k: v for k, v in t.items()
                                          if k != "tok_embed"})
        for a, b in zip(rest(one), rest(big)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- the YaRN table ---------------------------------------------------------

@pytest.mark.parametrize("pair", [0, 17, 18, 19, 26, 34, 35, 36, 63])
def test_the_yarn_table_is_the_closed_form(pair):
    """ISSUE 48's equations at both ends, at ``lo`` = 18 and ``hi`` = 35 and
    on either side of each: pairs up to 18 keep the published frequency,
    pairs from 35 turn 16 times slower, a linear ramp between."""
    d, b = 128, 500000.0
    table = RopeTable(b, 16.0, 8192, 32.0, 1.0, 1.2772588722239782)

    def c(r):
        return d * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(b))
    assert (round(c(32), 2), round(c(1), 2)) == (18.08, 34.98)
    assert table.band(d) == (18, 35) == fam.yarn_band(YARN, d)
    g = 1.0 - min(max((pair - 18) / (35 - 18), 0.0), 1.0)
    want = b ** (-2 * pair / d) * ((1 - g) / 16 + g)
    got = float(table.frequencies(d)[pair])
    assert got == pytest.approx(want, rel=2e-6)
    assert fam.rope_table(YARN, d)[0][pair] == pytest.approx(want, rel=1e-12)
    if pair <= 18:
        assert want == b ** (-2 * pair / d)
    if pair >= 35:
        assert want == pytest.approx(b ** (-2 * pair / d) / 16, rel=1e-12)
    assert 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)


def test_the_attention_factor_multiplies_cos_and_sin():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 32))
    pos = jnp.arange(40)[None]
    group = TOY_YARN
    table = hf_import._rope_table("full_attention", group)
    got = rotary_embed(x, pos, table.theta, None, False, table)
    freqs, factor = fam.rope_table(group, 32)
    want = fam._rotate(x[0], freqs, factor)
    assert factor == table.attention_factor and factor > 1.1
    assert float(jnp.abs(got[0] - want).max()) < 1e-5
    plain = rotary_embed(x, pos, table.theta)
    assert float(jnp.abs(got - plain).max()) > 0.1
    # |rotated| = factor x |x|: both cos and sin carry it
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               factor * np.linalg.norm(x, axis=-1), rtol=1e-5)


# ---- against the reference --------------------------------------------------

def test_the_pattern_and_the_tables(toy):
    cfg = toy[0]
    assert cfg.block_pattern == "WEWEWE*E" and hybrid.window(cfg) == WINDOW
    assert [k for k, _ in hybrid.blocks(cfg)] == [k for k, _ in fam.blocks(HF)]
    assert hybrid.rope_table(cfg, "wattn").factor == 1.0
    assert hybrid.rope_table(cfg, "attn").factor == 4.0
    assert (cfg.num_experts, cfg.moe_router_width, cfg.top_k) == (8, 32, 8)


def test_forward_matches_the_reference(toy):
    _, model, params, ref = toy
    ids = _ids(96)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None])[0])
    want = ref.logits(ids)
    assert np.abs(got - want).max() < TOL and np.abs(want).max() > 0.5


def _forced(monkeypatch, dispatch):
    """Both dropless dispatches whatever ``_sorts`` would pick at toy widths
    (the masks: a toy expert is 96 KiB)."""
    monkeypatch.setattr(sm, "_sorts", lambda *a: dispatch == "sorted")


@pytest.mark.parametrize("dispatch", ["one-hot", "sorted"])
def test_loss_and_every_gradient_leaf_match_the_reference(toy, monkeypatch,
                                                          dispatch):
    """The engine's loss function (train=True, remat of every block, loss in
    chunks of 32) against ``jax.grad`` of the plain reference's loss."""
    _forced(monkeypatch, dispatch)
    _, model, params, ref = toy
    batch = _ids((2, 96), seed=1)
    with sm.expert_load_tap() as tap:
        jax.eval_shape(lambda p: model.loss_fn(
            p, {"input_ids": jnp.asarray(batch)}, None, False), params)
    assert tap.form == ("sorted/ragged_dot" if dispatch == "sorted"
                        else "one-hot")
    loss, grads = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(
        p, {"input_ids": jnp.asarray(batch)}, None, False)))(params)
    want, want_grads = jax.value_and_grad(ref.loss_fn)(params, batch)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(loss) == pytest.approx(ref.loss(batch), rel=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    wants = jax.tree.leaves(want_grads)
    assert len(flat) == len(wants) == 22
    for (path, g), w in zip(flat, wants):
        top = float(jnp.abs(w).max())
        assert top > 0, path                      # every leaf is reached
        assert float(jnp.abs(g - w).max()) < TOL * top + 1e-7, (path, top)


@pytest.fixture(scope="module")
def sound(toy):
    _, model, params, _ = toy
    ids = _ids(96, seed=2)
    return ids, np.asarray(model.apply(params, jnp.asarray(ids)[None])[0])


@pytest.mark.parametrize("defect", fam.DEFECTS)
def test_each_defect_fails(toy, sound, defect):
    """Every seeded defect moves the toy's logits (of size ~1) by more than
    ten times the tolerance the sound program meets. (The LOSS, a mean over
    positions of a model at its initial scale, moves far less — by 3e-6 for
    ``no_renorm`` — and is no judge of a defect here.)"""
    _, _, params, _ = toy
    ids, got = sound
    bad = fam.Reference(HF, params, defect=defect)
    assert np.abs(got - bad.logits(ids)).max() > 10 * TOL


def test_the_four_quarter_shares_add_up_to_the_uncut_layer(toy):
    """An expert layer of 32 experts, whole, against the sum of its 4 shares
    of 8 held experts each (router width 32, top-8, weights normalised over
    all eight chosen), and the uncut layer against the REFERENCE's."""
    hf = dict(HF, num_experts=32, num_experts_router=32)
    cfg = hf_config_to_transformer(hf, dtype=jnp.float32)
    params = make_model(cfg).init(jax.random.PRNGKey(5))
    st = {k: v[1] if not k.startswith("moe_w_") else v
          for k, v in params["layers"]["moe"].items()}
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 24, cfg.hidden_size))

    def layer(cfg, stacks, train=False):
        p = dict(st, **{k: sm.LayerOf(v, 0) for k, v in stacks.items()})
        return np.asarray(hybrid._moe_mixer(p, h, cfg, train=train)[0])

    full = {k: v[1:2] for k, v in params["layers"]["moe"].items()
            if k.startswith("moe_w_")}
    whole = layer(cfg, full)
    total = np.zeros_like(whole)
    for first in range(0, 32, 8):
        part = hf_config_to_transformer(
            dict(hf, num_experts=8, expert_first=first), dtype=jnp.float32)
        share = {k: v[:, first:first + 8] for k, v in full.items()}
        total += layer(part, share)
        assert np.abs(layer(part, share, train=True)
                      - layer(part, share)).max() < 1e-7
    assert np.abs(total - whole).max() < 1e-9 and np.abs(whole).max() > 1e-4
    ref = fam.Reference(hf, params)
    moe = params["layers"]["moe"]
    with jax.default_matmul_precision("highest"):
        w = ref._route(moe, 1, h[0])
        want = jnp.zeros_like(h[0])
        for e in range(32):
            want = ref._add_expert(moe, 1, e, h[0], w[:, e], want)
    assert np.abs(whole[0] - np.asarray(want)).max() < 1e-9


# ---- the grouped matmul's backward ------------------------------------------

def _dense(rows, stack, layer, sizes, transposed):
    """Row i times the matrix of the expert whose group it is in, one expert
    at a time; rows past the groups give zeros."""
    w = stack[layer]
    w = jnp.swapaxes(w, 1, 2) if transposed else w
    ends = np.cumsum(sizes)
    out = jnp.zeros((rows.shape[0], w.shape[-1]), jnp.float32)
    for e, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        out = out.at[lo:hi].set(rows[lo:hi] @ w[e])
    return out


@pytest.mark.parametrize("transposed", [False, True])
@pytest.mark.parametrize("sizes", [
    (50, 50, 50, 50),         # even, the rows all assigned
    (3, 0, 170, 27),          # uneven, an EMPTY expert
    (0, 0, 0, 130),           # one expert alone, rows past the groups
    (600, 1, 0, 99),          # past a row tile of the backward (512)
    (0, 0, 0, 0),             # nobody's rows
])
def test_grouped_matmul_vjp_is_the_dense_loops_gradient(sizes, transposed):
    M, K, N, L = max(200, sum(sizes) + 20), 128, 256, 2
    ks = jax.random.split(jax.random.PRNGKey(sum(sizes)), 3)
    rows = jax.random.normal(ks[0], (M, K))
    stack = jax.random.normal(ks[1], (L, 4, N, K) if transposed else (L, 4, K, N))
    ct = jax.random.normal(ks[2], (M, N))
    gs = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(M) < sum(sizes))[:, None]

    def kernel(r, s):       # rows past the groups come back undefined
        out = gmm.grouped_matmul(r, s, 1, gs, transposed)
        return jnp.sum(jnp.where(live, out, 0.0) * ct)

    def dense(r, s):
        return jnp.sum(_dense(r, s, 1, np.asarray(sizes), transposed) * ct)

    with jax.default_matmul_precision("highest"):
        got, want = (jax.jit(jax.grad(f, (0, 1)))(rows, stack)
                     for f in (kernel, dense))
        assert float(kernel(rows, stack)) == pytest.approx(
            float(dense(rows, stack)), rel=1e-5, abs=1e-4)
    # a row past the groups has no gradient and comes back undefined
    for g, w in zip((got[0][:sum(sizes)], got[1]),
                    (want[0][:sum(sizes)], want[1])):
        assert g.shape == w.shape
        assert float(jnp.abs(g - w).max(initial=0.0)) \
            < 1e-3 * max(1.0, float(jnp.abs(w).max(initial=0.0)))
    # the other layer's experts and an empty expert's matrix get exact zeros
    assert not np.asarray(got[1][0]).any()
    for e, n in enumerate(sizes):
        assert bool(np.asarray(got[1][1, e]).any()) == (n > 0)


def test_the_gradient_of_a_traced_layer_index():
    """A period scan hands the layer's index as a tracer."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    stack = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 128, 128))
    gs = jnp.asarray([40, 24], jnp.int32)

    def f(s, layer):
        return jnp.sum(gmm.grouped_matmul(rows, s, layer, gs) ** 2)
    want = jax.grad(f)(stack, 2)
    got = jax.jit(jax.grad(f))(stack, jnp.int32(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(got[:2]).any() and np.asarray(got[2]).any()


# ---- the row kernels of the sorted dispatch (interpret mode) -------------------

ROWS_T, ROWS_K, ROWS_H = 128, 4, 256          # 512 pairs: two tiles of 256 rows
# nobody's, one, either side of a tile's edge, every pair
ROWS_LIVE = [0, 1, mr.ROW_TILE - 1, mr.ROW_TILE, mr.ROW_TILE + 1,
             ROWS_T * ROWS_K]


def _pairs(seed, whole=False):
    """A permutation of the T k pairs as the sort leaves it (``order``, its
    inverse ``pos`` [T, k]) and rows of small whole numbers (``whole``: sums
    of k of them are exact in bf16) or normal ones."""
    rng = np.random.default_rng(seed)
    M = ROWS_T * ROWS_K
    order = rng.permutation(M).astype(np.int32)
    pos = np.argsort(order).astype(np.int32).reshape(ROWS_T, ROWS_K)

    def draw(n):
        a = (rng.integers(-4, 5, (n, ROWS_H)) if whole
             else rng.normal(size=(n, ROWS_H)))
        return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
    return rng, order, pos, draw(ROWS_T), draw(M)


def _bf16(a):
    return np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("n_live", ROWS_LIVE)
def test_moe_rows_gather_moves_the_live_rows_and_dots_them(n_live, weighted):
    """``out[i] = x[index[i]]`` for ``i < n_live``; ``weighted`` (the
    combine's gradient) ``scale[i] * x[index[i]]``, the product in float32,
    and ``<x[index[i]], other[i]>``, before the scale."""
    rng, order, _, x, other = _pairs(n_live + weighted)
    index = order // ROWS_K
    scale = rng.random(order.shape[0]).astype(np.float32)
    args = (jnp.asarray(x, jnp.bfloat16), jnp.asarray(index), jnp.int32(n_live))
    if not weighted:
        out = mr.moe_rows_gather(*args)
        np.testing.assert_array_equal(
            np.asarray(out.astype(jnp.float32))[:n_live], x[index][:n_live])
        return
    out, dots = mr.moe_rows_gather(
        *args, (jnp.asarray(scale), jnp.asarray(other, jnp.bfloat16)))
    np.testing.assert_array_equal(
        np.asarray(out.astype(jnp.float32))[:n_live],
        _bf16(x[index] * scale[:, None])[:n_live])
    np.testing.assert_allclose(np.asarray(dots)[:n_live],
                               (x[index] * other).sum(1)[:n_live],
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n_live", ROWS_LIVE)
def test_moe_rows_combine_is_the_dense_float32_loop(n_live):
    """``y[t] = sum_j w[t, j] * rows[pos[t, j]]`` over the live pairs against
    the loop in float32; the rows past ``n_live`` hold NaN and are never
    read; a token with no live pair gives zeros."""
    rng, _, pos, _, rows = _pairs(100 + n_live)
    w = rng.random(pos.shape).astype(np.float32)
    poisoned = rows.copy()
    poisoned[n_live:] = np.nan
    y = np.asarray(mr.moe_rows_combine(
        jnp.asarray(poisoned, jnp.bfloat16), jnp.asarray(pos), jnp.asarray(w),
        jnp.int32(n_live)).astype(jnp.float32))
    live = pos < n_live
    want = np.zeros((ROWS_T, ROWS_H), np.float32)
    for j in range(ROWS_K):
        want += np.where(live[:, j, None], rows[pos[:, j]] * w[:, j, None],
                         np.float32(0))
    assert np.isfinite(y).all()
    # bf16 of a float32 sum taken in the same order: at most one rounding apart
    np.testing.assert_allclose(y, _bf16(want), rtol=2 ** -7, atol=1e-6)
    none = ~live.any(axis=1)
    assert none.any() == (n_live < ROWS_T * ROWS_K) and not y[none].any()


@pytest.mark.parametrize("n_live", [1, mr.ROW_TILE - 1, mr.ROW_TILE + 1,
                                    ROWS_T * ROWS_K])
def test_the_two_row_kernels_are_each_others_transpose(n_live):
    """``<gather(x), y> = <x, combine(y)>`` over the live rows (unit weights;
    whole numbers, so every sum is exact in bf16)."""
    _, order, pos, x, y = _pairs(200 + n_live, whole=True)
    gathered = np.asarray(mr.moe_rows_gather(
        jnp.asarray(x, jnp.bfloat16), jnp.asarray(order // ROWS_K),
        jnp.int32(n_live)).astype(jnp.float32))
    combined = np.asarray(mr.moe_rows_combine(
        jnp.asarray(y, jnp.bfloat16), jnp.asarray(pos),
        jnp.ones(pos.shape, jnp.float32), jnp.int32(n_live)
    ).astype(jnp.float32))
    assert float((gathered[:n_live] * y[:n_live]).sum()) \
        == float((x * combined).sum()) != 0.0


def test_the_combine_adds_its_sources_over_the_live_rows():
    """Several row arrays: the combine of their sum as bf16 rows (XLA's
    ``a + b``), the rows past ``n_live`` of each holding NaN."""
    n_live = mr.ROW_TILE + 1
    rng, _, pos, _, a = _pairs(300)
    b = _pairs(301)[4]
    w = jnp.asarray(rng.random(pos.shape).astype(np.float32))
    a, b = (jnp.asarray(r, jnp.bfloat16) for r in (a, b))
    want = mr.moe_rows_combine(a + b, jnp.asarray(pos), w,
                                        jnp.int32(n_live))
    dead = jnp.arange(a.shape[0])[:, None] >= n_live
    got = mr.moe_rows_combine(
        (jnp.where(dead, jnp.nan, a), jnp.where(dead, jnp.nan, b)),
        jnp.asarray(pos), w, jnp.int32(n_live))
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    assert np.asarray(want.astype(jnp.float32)).any()


def _kernel_layer(monkeypatch, T=384, H=256):
    """One sorted expert layer in bf16 for the kernel path (the Pallas
    kernels in interpret mode): 4 of a 16-wide router's experts held, top-4,
    T tokens of width H — T x 4 pairs, about a quarter of them live."""
    hf = dict(HF, hidden_size=H, moe_intermediate_size=128, num_experts=4,
              num_experts_router=16, num_experts_per_tok=4)
    cfg = hf_config_to_transformer(hf, dtype=jnp.bfloat16)
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    p = {"wg": jax.random.normal(ks[0], (H, 16)) * 0.1,
         "w_in_t": jax.random.normal(ks[1], (4, 128, H)) * 0.06,
         "w_gate": jax.random.normal(ks[2], (4, H, 128)) * 0.06,
         "w_out": jax.random.normal(ks[3], (4, 128, H)) * 0.09}
    p = {n: a.astype(jnp.float32 if n == "wg" else jnp.bfloat16)
         for n, a in p.items()}
    x = jax.random.normal(ks[4], (1, T, H)).astype(jnp.bfloat16)
    monkeypatch.setattr(sm, "_sorts", lambda *a: True)
    return cfg, p, x


def _grads(cfg, p, x):
    """(the dispatch form, y, the gradient by every parameter and by x) of
    one training call of the layer, as one program."""
    with sm.expert_load_tap() as tap:
        (_, y), g = jax.jit(jax.value_and_grad(
            lambda p, x: (lambda y: (jnp.sum(y.astype(jnp.float32) ** 2), y))(
                sm.moe_ffn(p, x, cfg, train=True)[0]),
            argnums=(0, 1), has_aux=True))(p, x)
    return tap.form, y, g


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, a Pallas
    kernel's body left out (its `cond`s are `pl.when`s)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub)


def test_no_row_behind_the_live_ones_is_read_by_the_layer(monkeypatch):
    """Every row a kernel leaves undefined poisoned with NaN — what
    ``moe_rows_gather`` does not write (the dispatch's and, in the backward,
    the combine's gradient) and what ``moe_gmm`` leaves past the groups (each
    projection's output and, in the backward, its d rows): the layer's result
    and every gradient leaf stay finite and are those of the plain
    ``jnp.take`` form beside ``ragged_dot``."""
    cfg, p, x = _kernel_layer(monkeypatch)
    gather, matmul = mr.moe_rows_gather, gmm._gmm_call

    def dead(rows, n_live):
        return (jnp.arange(rows.shape[0]) >= n_live).reshape(
            (-1,) + (1,) * (rows.ndim - 1))

    def poisoned_gather(x, index, n_live, weighted=None):
        out = gather(x, index, n_live, weighted)
        return jax.tree.map(lambda a: jnp.where(dead(a, n_live), jnp.nan, a),
                            out)

    def poisoned_matmul(rows, stack, layer, group_sizes, transposed):
        out = matmul(rows, stack, layer, group_sizes, transposed)
        return jnp.where(dead(out, jnp.sum(group_sizes)), jnp.nan, out)

    monkeypatch.setattr(sm, "_use_gmm_kernel", lambda *a: False)
    form, want_y, want_g = _grads(cfg, p, x)
    assert form == "sorted/ragged_dot"
    monkeypatch.setattr(sm, "_use_gmm_kernel", lambda *a: True)
    monkeypatch.setattr(mr, "moe_rows_gather", poisoned_gather)
    monkeypatch.setattr(gmm, "_gmm_call", poisoned_matmul)
    form, y, g = _grads(cfg, p, x)
    assert form == "sorted/moe_gmm"
    leaves = lambda t: jax.tree.leaves(jax.tree.map(
        lambda a: np.asarray(a.astype(jnp.float32)), t))
    for got, want in zip(leaves((y, g)), leaves((want_y, want_g))):
        assert np.isfinite(got).all() and np.abs(want).max() > 0
        assert np.abs(got - want).max() < 0.02 * np.abs(want).max()


@pytest.mark.parametrize("T,H", [(200, 256), (256, 384)])
def test_training_off_the_row_kernels_tiles_takes_no_kernel_at_all(
        monkeypatch, T, H):
    """The grouped matmul's kernel pads any T and takes widths off the 256
    grid; the row kernels do not. On a TPU such a layer (``held`` set: three
    pairs in four are dead) TRAINS on ``ragged_dot`` beside ``jnp.take`` —
    no Pallas kernel in its gradient program, so no undefined d row for
    ``jnp.take``'s scatter-add to read — and still serves on ``moe_gmm``;
    every gradient leaf is finite."""
    cfg, p, x = _kernel_layer(monkeypatch, T, H)
    real = sm._use_gmm_kernel

    def as_on_a_tpu(*a):        # the rule itself, the kernels still interpreted
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            return real(*a)
    monkeypatch.setattr(sm, "_use_gmm_kernel", as_on_a_tpu)
    assert not mr.supported(T, H, cfg.top_k)
    form, y, g = _grads(cfg, p, x)
    assert form == "sorted/ragged_dot"
    for leaf in jax.tree.leaves((y, g)):
        a = np.asarray(leaf.astype(jnp.float32))
        assert np.isfinite(a).all() and a.any()
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(
        sm.moe_ffn(p, x, cfg, train=True)[0].astype(jnp.float32)),
        argnums=(0, 1)))(p, x)
    assert "pallas_call" not in {e.primitive.name for e in _eqns(jaxpr.jaxpr)}
    with sm.expert_load_tap() as tap:
        jax.make_jaxpr(lambda p, x: sm.moe_ffn(p, x, cfg, train=False)[0])(p, x)
    assert tap.form == "sorted/moe_gmm"
    # on the tiles the same rule takes the kernels in training too
    cfg, p, x = _kernel_layer(monkeypatch)
    with sm.expert_load_tap() as tap:
        jax.make_jaxpr(lambda p, x: sm.moe_ffn(p, x, cfg, train=True)[0])(p, x)
    assert tap.form == "sorted/moe_gmm"


def test_the_sorted_train_step_on_the_kernel_path_moves_rows_by_kernels_alone(
        monkeypatch):
    """The gradient program of one sorted expert layer on the forced kernel
    path: no ``cond`` (a ladder of caps was a ``lax.switch`` a mover), and
    no pad, scatter, gather, select or sum of cotangents with T k rows of H
    out — the rows move inside ``moe_rows_gather`` / ``moe_rows_combine``,
    which adds the up and the gate projection's cotangents itself — and no
    array with the tokens on two axes."""
    cfg, p, x = _kernel_layer(monkeypatch)
    monkeypatch.setattr(sm, "_use_gmm_kernel", lambda *a: True)
    T, k, H = 384, 4, 256
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(
        sm.moe_ffn(p, x, cfg, train=True)[0].astype(jnp.float32)),
        argnums=(0, 1)))(p, x)
    eqns = list(_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert {"moe_rows_gather", "moe_rows_combine", "moe_rows_pack", "moe_gmm",
            "moe_gmm_dw"} == set(kernels)
    assert kernels.count("moe_rows_gather") == kernels.count(
        "moe_rows_combine") == 2
    assert not {"cond", "while"} & set(names)
    shapes = [tuple(v.aval.shape) for e in eqns for v in e.outvars]
    for e in eqns:      # (the pads left place a layer's d experts in the stack)
        if e.primitive.name in ("pad", "gather", "scatter", "scatter-add",
                                "scatter_add", "select_n", "add_any"):
            assert all(v.aval.shape[-1:] != (H,) or v.aval.shape[0] != T * k
                       for v in e.outvars), e
    assert (T * k, H) in shapes
    assert not [s for s in shapes if s.count(T) >= 2]


# ---- imbalance ---------------------------------------------------------------

def _skewed_layer(share=0.9):
    """One expert layer (8 held of 32, top-8) whose router sends ``share`` of
    the tokens' FIRST choice to held expert 3: a large bias direction in the
    input that only that column of ``wg`` reads."""
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32)
    params = make_model(cfg).init(jax.random.PRNGKey(7))
    moe = {k: v[0] for k, v in params["layers"]["moe"].items()}
    T, H = 200, cfg.hidden_size
    x = jax.random.normal(jax.random.PRNGKey(8), (1, T, H))
    hot = (jnp.arange(T) < share * T).astype(jnp.float32)
    x = x.at[0, :, 0].set(hot * 50.0)
    moe["wg"] = moe["wg"].at[0].set(0.0).at[0, 3].set(1.0)
    p = {"wg": moe["wg"], "w_in_t": moe["moe_w_in_t"],
         "w_gate": moe["moe_w_gate"], "w_out": moe["moe_w_out"]}
    return cfg, p, x, int(share * T)


def test_a_step_with_ninety_percent_of_the_rows_on_one_expert_drops_none(
        monkeypatch):
    cfg, p, x, hot = _skewed_layer()

    def run(dispatch):
        _forced(monkeypatch, dispatch)
        with sm.expert_load_tap() as tap:
            (y, _), g = jax.value_and_grad(
                lambda p: (lambda y, aux: (jnp.sum(y ** 2), y))(
                    *sm.moe_ffn(p, x, cfg, train=True)), has_aux=True)(p)
        return y, g, np.asarray(tap.stacked()), tap.form

    y_s, g_s, load_s, form_s = run("sorted")
    y_o, g_o, load_o, form_o = run("one-hot")
    assert (form_s, form_o) == ("sorted/ragged_dot", "one-hot")
    # every assignment that landed on a held expert was kept, by both forms
    assert np.array_equal(load_s, load_o) and load_s.shape == (1, 9)
    assert load_s[0, 3] >= hot and load_s[0, -1] == 200 * 8
    assert load_s[0, 3] > 3 * np.delete(load_s[0, :8], 3).max()
    assert float(jnp.abs(y_s - y_o).max()) < 1e-6
    for k in g_s:
        assert float(jnp.abs(g_s[k] - g_o[k]).max()) < 1e-5 * max(
            1.0, float(jnp.abs(g_o[k]).max())), k
    # the hot expert's rows are in the result: without them it differs
    cut = dict(p, w_out=p["w_out"].at[3].set(0.0))
    assert float(jnp.abs(sm.moe_ffn(cut, x, cfg, train=True)[0] - y_s).max()) > 1e-4


def test_the_sorted_train_step_builds_no_mask_over_tokens_and_experts(
        monkeypatch):
    """No array of the sorted path's gradient program has the tokens on two
    axes ([T, E, T]: the one-hot form's masks, [E, T, T] transposed); the
    one-hot form's has."""
    cfg, p, x, _ = _skewed_layer()
    T = 200

    def shapes(dispatch):
        _forced(monkeypatch, dispatch)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(sm.moe_ffn(p, x, cfg, train=True)[0])))(p)
        return {tuple(getattr(v.aval, "shape", ()))
                for eqn in _eqns(jaxpr.jaxpr) for v in eqn.outvars}

    def masks(found):
        return {s for s in found if s.count(T) >= 2}

    assert masks(shapes("one-hot")) and not masks(shapes("sorted"))


# ---- the engine ---------------------------------------------------------------

def test_initialize_trains_it_and_reports_the_expert_load(toy):
    cfg = toy[0]
    engine, *_ = deepspeed_tpu.initialize(model=make_model(cfg), config={
        "train_batch_size": 8, "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
        "zero_optimization": {"stage": 1}, "steps_per_print": 1000},
        rng=jax.random.PRNGKey(0))
    batch = {"input_ids": _ids((8, 64), seed=3).astype(np.int32)}
    first = engine.train_batch(batch)
    ref = fam.Reference(HF, make_model(cfg).init(jax.random.PRNGKey(0)))
    assert float(first["loss"]) == pytest.approx(
        ref.loss(batch["input_ids"]), rel=1e-4)
    for _ in range(11):
        m = engine.train_batch(batch)
    assert float(m["loss"]) < 0.8 * float(first["loss"])
    # 512 tokens x 8 choices, a quarter of the router's experts held
    assert float(first["moe_held_rows"]) == pytest.approx(512 * 8 / 4, rel=0.35)
    assert float(first["moe_load_max_over_mean"]) >= 1.0
    assert float(m["moe_dropped_rows"]) == 0.0
    engine.close()
