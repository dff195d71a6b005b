"""Qwen3-Next (``model_type`` ``qwen3_next``) on the normal serving path, at
toy widths on the CPU in float32, held to the family's plain reference
(``benchmark/families/qwen3_next.py``) on LOGITS:

- the system's no-cache forward, and prefill-then-decode through the paged
  functions the serving engine calls (K/V blocks for the attention block, a
  per-slot matrix state and convolution tail for the Gated DeltaNet blocks);
- every seeded defect of the reference (``DEFECTS``) fails that comparison;
- padding: prompts of every length modulo the bucket and the chunk;
- a slot freed and reused, a preemption and re-admission, requests of
  different lengths in one batch, through ``init_serving``;
- THE CHIP'S SHARE: the four quarters of a layer's experts add up to the
  whole layer; both dispatch forms compute the same share; a model that
  holds every expert lowers to the program it had before the share existed;
- what is refused on a model with recurrent blocks, typed, at the earliest
  point; ``hf_config_to_transformer`` on the published dict and what it
  refuses.

TOL = 2e-4 on logits of size ~1: float32 on both sides, the differences are
the order of sums (the chunk form against a scan, the one-hot dispatch
against a loop over experts, flash-style attention against a softmax). A
recurrent state carried in bf16 moves these logits by 3e-3, and every other
defect by more; a router scored in bf16 by 6.5e-6, fifteen times what the
sound path reads (4e-7): ``test_each_defect_fails`` holds each to a limit of
its own size.
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
from benchmark.families import qwen3_next as fam  # noqa: E402
from deepspeed_tpu.inference import SlotStateUnsupported  # noqa: E402
from deepspeed_tpu.models import TransformerConfig, hybrid, make_model  # noqa: E402
from deepspeed_tpu.models.hf_import import hf_config_to_transformer  # noqa: E402
from deepspeed_tpu.moe import sharded_moe as sm  # noqa: E402

TOL = 2e-4
HF = {"model_type": "qwen3_next", "partial_rotary_factor": 0.25,
      "rope_theta": 10000000, "rms_norm_eps": 1e-6, "norm_topk_prob": True,
      "linear_conv_kernel_dim": 4, "decoder_sparse_step": 1,
      "mlp_only_layers": [], "hidden_act": "silu", "rope_scaling": None,
      "tie_word_embeddings": False, "max_position_embeddings": 512,
      **fam.TOY, "num_experts_per_tok": 4}
BS = BUCKET = 16                       # block size = prompt bucket
SLOTS, MB = 3, 8


@pytest.fixture(scope="module")
def toy():
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32, gdn_chunk=16)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    return cfg, model, params, fam.Reference(HF, params)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], n)


# ---- the paged path, driven as the engine drives it ------------------------

_JITS = {}


class Paged:
    """The model's paged functions over a pool of ``SLOTS`` slots, every
    slot with its own ``MB`` blocks: prefill into a slot, step all slots."""

    def __init__(self, model, params):
        self.model, self.params = model, params
        self.pools = model.init_paged_cache(SLOTS * MB + 1, BS,
                                            dtype=jnp.float32, max_seqs=SLOTS)
        self.tables = np.arange(1, SLOTS * MB + 1, dtype=np.int32
                                ).reshape(SLOTS, MB)
        self.lens = np.zeros(SLOTS, np.int32)
        # one pair of jitted functions a model: a test that builds another
        # ``Paged`` over the module's toy finds its programs compiled
        self._prefill, self._step = _JITS.setdefault(id(model), (
            jax.jit(model.prefill_paged), jax.jit(model.decode_step_paged)))

    def prefill(self, slot, prompt):
        n = len(prompt)
        P = -(-n // BUCKET) * BUCKET
        buf = np.zeros((1, P), np.int32)
        buf[0, :n] = prompt
        last, self.pools = self._prefill(
            self.params, jnp.asarray(buf), self.pools,
            jnp.asarray(self.tables[slot, :P // BS]), length=jnp.int32(n),
            slot=jnp.int32(slot))
        self.lens = self.lens.copy()
        self.lens[slot] = n
        return np.asarray(last[0])

    def step(self, tokens: dict):
        """tokens {slot: token} -> {slot: logits}; the other slots idle."""
        tok = np.zeros(SLOTS, np.int32)
        act = np.zeros(SLOTS, bool)
        for s, t in tokens.items():
            tok[s], act[s] = t, True
        lg, self.pools = self._step(
            self.params, jnp.asarray(tok), self.pools,
            jnp.asarray(self.tables), jnp.asarray(self.lens.copy()),
            active=jnp.asarray(act))
        self.lens = self.lens + act
        return {s: np.asarray(lg[s]) for s in tokens}

    def run(self, slot, prompt, generated):
        """Logits at the positions that predict ``generated`` and one more."""
        out = [self.prefill(slot, prompt)]
        for t in generated:
            out.append(self.step({slot: int(t)})[slot])
        return np.stack(out)


def _ref_tail(ref, prompt, generated):
    lg = ref.logits(np.concatenate([prompt, generated]), pad_to=64)
    return lg[len(prompt) - 1:]


# ---- against the reference -------------------------------------------------

def test_forward_matches_the_reference(toy):
    _, model, params, ref = toy
    ids = _ids(70)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None])[0])
    assert np.abs(got - ref.logits(ids, pad_to=64)).max() < TOL


def test_prefill_then_decode_matches_the_reference(toy):
    _, model, params, ref = toy
    prompt, gen = _ids(21, 1), _ids(12, 2)
    got = Paged(model, params).run(1, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


@pytest.mark.parametrize("defect", fam.DEFECTS)
def test_each_defect_fails(toy, defect):
    """The plain reference agrees with the paged path to a third of the
    limit; with any one defect seeded it does not (a defect on either side
    reads the same). The sound path reads 4e-7 here. A state rounded to bf16
    or K and V rounded to 4 bits in the one attention block move these toy
    logits by 3e-3 and 2e-2: held to 5e-5. A router scored in bf16 moves them
    by 6.5e-6 (top-4 of 32 with 8 held, the routed experts' down projection
    at a quarter scale): held to 3e-6, seven times the sound reading."""
    _, model, params, ref = toy
    prompt, gen = _ids(21, 1), _ids(40, 2)
    got = Paged(model, params).run(1, prompt, gen)
    limit = {"bf16_state": 5e-5, "kv_4bit": 5e-5, "bf16_router": 3e-6}.get(
        defect, TOL)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < limit / 3
    bad = fam.Reference(HF, params, defect=defect)
    assert np.abs(got - _ref_tail(bad, prompt, gen)).max() > limit


@pytest.mark.parametrize("n", [1, 3, 15, 16, 17, 31, 32, 33, 47])
def test_padding_does_not_move_the_state(toy, n):
    """Every prompt length modulo the bucket (= the chunk): the pad rows of
    the bucket leave the state and the convolution tail as the true rows
    left them, so the steps that follow agree with the unpadded reference."""
    _, model, params, ref = toy
    prompt, gen = _ids(n, 10 + n), _ids(5, 99)
    got = Paged(model, params).run(0, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


def test_a_reused_slot_carries_nothing_of_the_last_request(toy):
    _, model, params, ref = toy
    pg = Paged(model, params)
    pg.run(2, _ids(30, 5), _ids(6, 6))
    assert float(jnp.abs(pg.pools["gdn"][:, 2]).max()) > 0
    prompt, gen = _ids(9, 7), _ids(6, 8)
    got = pg.run(2, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


def test_requests_in_one_batch_equal_each_alone(toy):
    _, model, params, ref = toy
    prompts = {0: _ids(7, 20), 1: _ids(33, 21), 2: _ids(16, 22)}
    gens = {s: _ids(6, 30 + s) for s in prompts}
    pg = Paged(model, params)
    got = {s: [pg.prefill(s, p)] for s, p in prompts.items()}
    for i in range(6):
        live = {s: int(g[i]) for s, g in gens.items()}
        for s, lg in pg.step(live).items():
            got[s].append(lg)
    for s in prompts:
        assert np.abs(np.stack(got[s]) - _ref_tail(ref, prompts[s], gens[s])
                      ).max() < TOL, s


def test_an_inactive_slot_keeps_its_state(toy):
    _, model, params, ref = toy
    pg = Paged(model, params)
    prompt, gen = _ids(11, 40), _ids(4, 41)
    got = [pg.prefill(0, prompt)]
    pg.prefill(1, _ids(20, 42))
    for t in gen:
        pg.step({1: 5})                       # slot 0 idles through a step
        got.append(pg.step({0: int(t)})[0])
    assert np.abs(np.stack(got) - _ref_tail(ref, prompt, gen)).max() < TOL


def test_training_differentiates_through_the_jnp_forms(toy):
    """Training runs the ``jax.numpy`` forms on the CPU: the loss is finite
    through the whole model, and the Gated DeltaNet mixer — convolution, l2
    norms, the chunk form with its triangular solve, the gated norm — hands
    finite, non-zero gradients to every one of its leaves."""
    from deepspeed_tpu.models import gated_deltanet
    cfg, model, params, _ = toy
    batch = {"input_ids": jnp.asarray(_ids(24, 3).reshape(1, 24))}
    assert np.isfinite(float(jax.jit(model.loss_fn)(params, batch)))
    block = {k: a[1] for k, a in params["layers"]["gdn"].items()}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, cfg.hidden_size))
    grads = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
        gated_deltanet.mixer_forward(p, h, cfg)))))(block)
    grads.pop("ln_scale")                     # the walker's, not the mixer's
    assert all(bool(jnp.isfinite(g).all()) for g in grads.values())
    assert all(float(jnp.abs(g).max()) > 0 for g in grads.values())


# ---- a pattern that repeats is walked as a scan over its repeats --------------

@pytest.fixture(scope="module")
def two_periods():
    hf = dict(HF, num_hidden_layers=8)
    cfg = hf_config_to_transformer(hf, dtype=jnp.float32, gdn_chunk=16)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    return hf, cfg, model, params


def test_the_period_of_a_pattern():
    assert hybrid.period(TransformerConfig(block_pattern="GEGEGE*E" * 3)) \
        == ("GEGEGE*E", 3)
    assert hybrid.period(TransformerConfig(block_pattern="GEGEGE*E")) \
        == ("GEGEGE*E", 1)
    assert hybrid.period(TransformerConfig(block_pattern="*E*E")) == ("*E", 2)
    # Mamba-2's step kernel takes its block's index as a Python int
    assert hybrid.period(TransformerConfig(block_pattern="MEME")) == ("MEME", 1)
    assert hybrid.period(TransformerConfig(block_pattern="MEMEM*EME")) \
        == ("MEMEM*EME", 1)


def test_two_periods_scanned_match_the_reference(two_periods):
    """Eight layers = two periods: ONE ``while`` over the repeats in the
    lowered step, block indices traced (the state pool's layer a prefetched
    scalar of the step kernel, the K/V rows stacked by the scan) — and the
    same logits as the reference's plain loop over 16 blocks."""
    hf, cfg, model, params = two_periods
    assert hybrid.period(cfg) == ("GEGEGE*E", 2)
    assert (cfg.recurrent_blocks, cfg.attention_blocks) == (6, 2)
    ref = fam.Reference(hf, params)
    ids = _ids(40, 3)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None])[0])
    assert np.abs(got - ref.logits(ids, pad_to=64)).max() < TOL
    pg = Paged(model, params)
    prompt, gen = _ids(21, 1), _ids(10, 2)
    got = pg.run(1, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL
    assert pg.pools["gdn"].shape[0] == 6 and pg.pools["k"].shape[0] == 2
    text = pg._step.lower(
        params, jnp.zeros(SLOTS, jnp.int32), pg.pools, jnp.asarray(pg.tables),
        jnp.asarray(pg.lens), active=jnp.ones(SLOTS, bool)).as_text()
    assert text.count("stablehlo.while") == 1


def test_two_periods_hand_their_load_rows_to_the_tap(two_periods):
    """The scan's stacked load rows reach the open tap: ALL 8 expert blocks
    of a step, top-4 each over the counted slots, the held experts' share
    in the first 8 columns."""
    _, cfg, model, params = two_periods
    pools = model.init_paged_cache(SLOTS * MB + 1, BS, dtype=jnp.float32,
                                   max_seqs=SLOTS)
    tables = jnp.arange(1, SLOTS * MB + 1, dtype=jnp.int32).reshape(SLOTS, MB)

    def step(params, pools):
        with sm.expert_load_tap() as tap:
            model.decode_step_paged(
                params, jnp.arange(SLOTS), pools, tables,
                jnp.zeros(SLOTS, jnp.int32),
                active=jnp.asarray([True, False, True]))
        return tap.stacked()

    rows = np.asarray(jax.jit(step)(params, pools))
    assert rows.shape == (8, 8 + 1) and (rows[:, -1] == 2 * 4).all()
    assert (rows[:, :8].sum(axis=1) <= 2 * 4).all() and rows[:, :8].sum() > 0


# ---- through init_serving ---------------------------------------------------

def _serve(model, params, **serving):
    return deepspeed_tpu.init_serving(
        model, config={"kv_cache_bits": 0}, params=params, dtype=jnp.float32,
        serving=dict(dict(max_seqs=2, block_size=BS, max_model_len=128,
                          decode_quantum=4, prompt_bucket=BUCKET), **serving))


def _greedy(ref, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(ref.logits(np.asarray(ids), pad_to=64)[-1].argmax()))
    return ids[len(prompt):]


def test_serving_slot_reuse_and_stats(toy):
    cfg, model, params, ref = toy
    srv = _serve(model, params)
    reqs = [(_ids(n, 50 + n), m) for n, m in ((5, 7), (17, 9), (33, 5), (3, 6))]
    outs = srv.run(reqs)
    for (p, m), rid in zip(reqs, sorted(outs)):
        assert list(np.asarray(outs[rid])[-m:]) == _greedy(ref, p, m)
    st = srv.stats()
    assert st["state_slots_live"] == 0
    assert st["state_pool_bytes"] + st["kv_pool_bytes"] == st["pool_bytes"]
    # every state leaf summed: 3 blocks x 2 slots x (the float32 state of 4
    # value heads of 32 x 32 + a tail of 3 rows of 2 x 64 + 128 channels)
    assert st["state_pool_bytes"] == 3 * 2 * (4 * 32 * 32 * 4 + 3 * 256 * 4)
    assert st["moe_dispatch"]["step"] == "one-hot"
    assert (st["moe_held"], st["moe_router_width"]) == (8, 32)
    assert "moe_dropped_share" not in st
    assert 0 < st["moe_assignments_held"] < st["moe_assignments_asked"]
    assert st["moe_experts_touched_per_step"] <= 8
    assert srv.state_pool_dtype == "float32"
    assert srv.pools["k"].shape[0] == 1
    assert srv.pools["gdn"].shape == (3, 2, 4, 32, 32)
    assert srv.pools["gdn_conv"].shape == (3, 2, 3, 256)
    assert (cfg.recurrent_blocks, cfg.attention_blocks) == (3, 1)
    srv.close()


def test_serving_preemption_rebuilds_the_state(toy):
    _, model, params, ref = toy
    # 2 slots x 40 new tokens over 8 usable blocks: growth collides
    srv = _serve(model, params, num_blocks=9)
    reqs = [(_ids(26, 60 + i), 40) for i in range(4)]
    outs = srv.run(reqs)
    assert srv.stats()["preemptions"] >= 1
    for (p, m), rid in zip(reqs, sorted(outs)):
        assert list(np.asarray(outs[rid])[-m:]) == _greedy(ref, p, m)
    srv.close()


@pytest.mark.parametrize("serving,what", [
    ({"enable_prefix_cache": True}, "prefix cache"),
    ({"prefill_token_budget": 32}, "chunked prefill"),
    ({"spec_tokens": 2}, "speculative"),
    ({"adapter_slots": 2, "lora_rank": 4}, "LoRA"),
])
def test_refused_at_init_serving(toy, serving, what):
    _, model, params, _ = toy
    with pytest.raises(SlotStateUnsupported, match=what):
        _serve(model, params, **serving)


def test_refused_at_the_call(toy):
    _, model, params, _ = toy
    srv = _serve(model, params)
    rid = srv.add_request(_ids(5), 40)
    srv.step()
    with pytest.raises(SlotStateUnsupported, match="export"):
        srv.export_kv([rid])
    with pytest.raises(SlotStateUnsupported, match="import"):
        srv.import_kv(rid, {})
    with pytest.raises(SlotStateUnsupported, match="fork"):
        srv._dispatch_fork(srv.scheduler.running[0])
    assert model.decode_span_paged is None
    srv.close()


def test_a_tensor_parallel_pool_is_refused(toy):
    _, model, params, _ = toy
    with pytest.raises(SlotStateUnsupported, match="tensor-parallel"):
        deepspeed_tpu.init_serving(
            model, config={"kv_cache_bits": 0, "tensor_parallel": 2},
            dtype=jnp.float32,
            serving=dict(max_seqs=2, block_size=BS, max_model_len=128))


# ---- the chip's share of a deployment's experts ------------------------------

def _moe_params(st, j, lo=0, hi=None):
    hi = st["moe_w_out"].shape[1] if hi is None else hi
    mp = {"wg": st["wg"][j], "w_in_t": st["moe_w_in_t"][j, lo:hi],
          "w_gate": st["moe_w_gate"][j, lo:hi], "w_out": st["moe_w_out"][j, lo:hi]}
    for name in ("shared_w_in", "shared_w_out", "shared_w_gate", "shared_gate"):
        mp[name] = st[name][j]
    return mp


@pytest.fixture(scope="module")
def whole():
    """The toy with ALL 32 experts held: the uncut model."""
    hf = dict(HF, num_experts=32)
    cfg = hf_config_to_transformer(hf, dtype=jnp.float32)
    assert cfg.moe_router_experts is None and cfg.moe_router_width == 32
    params = make_model(cfg).init(jax.random.PRNGKey(5))
    return hf, cfg, params


@pytest.mark.parametrize("sorts", [False, True], ids=["one-hot", "sorted"])
def test_the_four_shares_add_up_to_the_whole_layer(whole, sorts, monkeypatch):
    """Guide section 4: held 0-7, 8-15, 16-23, 24-31 of 32 experts, each
    share's output (the shared expert counted ONCE) summed, against the
    uncut REFERENCE's whole expert layer — and against the system's own."""
    hf, cfg, params = whole
    monkeypatch.setattr(sm, "_sorts", lambda *a: sorts)
    st = params["layers"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, cfg.hidden_size))
    ref = fam.Reference(hf, params)
    with jax.default_matmul_precision("highest"):
        w = ref._route(st, 1, x[0])
        want = ref._shared(st, 1, x[0])
        for e in range(32):
            want = ref._add_expert(st, 1, e, x[0], w[:, e], want)
        shared = np.asarray(ref._shared(st, 1, x[0]))
    total = -3 * shared                       # four shares, one shared expert
    for first in (0, 8, 16, 24):
        share = TransformerConfig(**{**cfg.__dict__, "num_experts": 8,
                                     "moe_router_experts": 32,
                                     "moe_held_first": first})
        y, _ = jax.jit(lambda mp, x: sm.moe_ffn(mp, x, share, train=False))(
            _moe_params(st, 1, first, first + 8), x)
        total = total + np.asarray(y[0])
        # ... and each share is what the reference gives for it
        part = fam.Reference(dict(hf, num_experts=8, num_experts_router=32,
                                  expert_first=first), params)
        with jax.default_matmul_precision("highest"):
            wp = part._route(st, 1, x[0])
            assert np.allclose(np.asarray(wp), np.asarray(w)[:, first:first + 8])
    assert np.abs(total - np.asarray(want)).max() < 2e-5
    y_all, _ = jax.jit(lambda mp, x: sm.moe_ffn(mp, x, cfg, train=False))(
        _moe_params(st, 1), x)
    assert np.abs(total - np.asarray(y_all[0])).max() < 2e-5


def test_the_share_counts_assignments_on_held_experts(toy):
    """The load row of a share: the held experts' assignments, then ALL the
    assignments the router made for the counted tokens."""
    cfg, _, params, _ = toy
    st = params["layers"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 24, cfg.hidden_size))
    counted = jnp.arange(24) < 20

    def run(mp, x):
        with sm.expert_load_tap() as tap, sm.counted_tokens(counted):
            sm.moe_ffn(mp, x, cfg, train=False)
        return tap.stacked()

    rows = {}
    for sorts in (False, True):
        sm_sorts, sm._sorts = sm._sorts, (lambda *a, s=sorts: s)
        try:
            rows[sorts] = np.asarray(jax.jit(run)(_moe_params(st, 0), x))
        finally:
            sm._sorts = sm_sorts
    assert np.array_equal(rows[False], rows[True])
    row = rows[False][0]
    assert row.shape == (8 + 1,) and row[-1] == 20 * 4
    logits = np.asarray(x[0, :20]) @ np.asarray(st["wg"][0])
    chosen = np.argsort(-logits, -1)[:, :4]
    assert np.array_equal(row[:8], [(chosen == e).sum() for e in range(8)])


def test_a_held_range_outside_the_router_is_refused(toy):
    cfg, _, params, _ = toy
    bad = TransformerConfig(**{**cfg.__dict__, "moe_held_first": 30})
    with pytest.raises(ValueError, match="held of the router's 32"):
        sm.moe_ffn(_moe_params(params["layers"]["moe"], 0),
                   jnp.zeros((1, 4, cfg.hidden_size)), bad, train=False)


def test_all_experts_held_is_the_program_it_was(whole):
    """Router width and experts held are two numbers only where they differ:
    a config that names the width explicitly and holds every expert lowers
    to the text of one that does not name it (``tests/unit/test_program_
    text.py`` holds the older families' toy step, prefill and forward to the
    parent commit's text)."""
    _, cfg, params = whole
    named = TransformerConfig(**{**cfg.__dict__, "moe_router_experts": 32})
    shapes = jax.eval_shape(lambda: params)
    text = [jax.jit(lambda p, ids: make_model(c).apply(p, ids)).lower(
        shapes, jax.ShapeDtypeStruct((1, 32), jnp.int32)).as_text()
        for c in (cfg, named)]
    assert text[0] == text[1]
    assert "one_hot" not in sm.moe_ffn.__doc__ and cfg.moe_held_first == 0


# ---- the import -------------------------------------------------------------

def _published():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "qwen3-next-80b-a3b-serve.json")) as f:
            cfg = json.load(f)
        return dict(cfg, num_hidden_layers=48, num_experts=512,
                    vocab_size=151936)
    with open(path) as f:
        return next(json.loads(ln) for ln in f
                    if '"Qwen3-Next-80B-A3B-Instruct"' in ln)["config"]


def test_import_of_the_published_config():
    cfg = hf_config_to_transformer(_published())
    kinds = [k for k, _ in hybrid.blocks(cfg)]
    assert len(kinds) == 96
    assert (kinds.count("gdn"), kinds.count("moe"), kinds.count("attn")) \
        == (36, 48, 12)
    assert kinds[:8] == ["gdn", "moe", "gdn", "moe", "gdn", "moe", "attn", "moe"]
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.dim_per_head,
            cfg.rotary_dim, cfg.rope_theta, cfg.position_type) \
        == (2048, 16, 2, 256, 64, 1e7, "rotary")
    assert (cfg.gdn_num_k_heads, cfg.gdn_num_v_heads, cfg.gdn_head_k_dim,
            cfg.gdn_head_v_dim, cfg.conv_kernel, cfg.gdn_chunk) \
        == (16, 32, 128, 128, 4, 64)
    assert (cfg.num_experts, cfg.moe_router_width, cfg.moe_held_first,
            cfg.top_k, cfg.ffn_dim, cfg.moe_shared_size, cfg.moe_shared_gate,
            cfg.moe_scoring, cfg.norm_topk_prob, cfg.activation,
            cfg.drop_tokens) \
        == (512, 512, 0, 10, 512, 512, True, "softmax", True, "silu_glu", False)
    assert cfg.qk_norm_per_head and cfg.attn_out_gate
    assert cfg.norm_eps == 1e-6 and not cfg.tie_embeddings
    assert (cfg.recurrent_blocks, cfg.attention_blocks) == (36, 12)


def test_import_of_the_cell_configuration():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-serve.json")) as f:
        cell = json.load(f)
    cfg = hf_config_to_transformer(
        {k: v for k, v in cell.items() if k not in ("run", "correct")})
    assert cfg.block_pattern == "GEGEGE*E" * 3
    assert (cfg.num_experts, cfg.moe_router_width, cfg.moe_held_first,
            cfg.vocab_size) == (128, 512, 0, 37984)
    assert (cfg.recurrent_blocks, cfg.attention_blocks) == (9, 3)


@pytest.mark.parametrize("change,match", [
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"layer_types": ["full_attention"] * 4}, "layer_types"),
    ({"expert_first": 30}, "held of num_experts_router"),
])
def test_import_refuses_what_is_not_built(change, match):
    with pytest.raises(ValueError, match=match):
        hf_config_to_transformer(dict(HF, **change))


def test_a_hybrid_stack_refuses_other_positions_and_letters():
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                norm_type="rmsnorm", block_pattern="*E")
    with pytest.raises(NotImplementedError, match="rotary one"):
        hybrid.blocks(TransformerConfig(**base, position_type="alibi"))
    with pytest.raises(ValueError, match="G Gated DeltaNet"):
        hybrid.blocks(TransformerConfig(**{**base, "block_pattern": "*X"},
                                        position_type="none"))
