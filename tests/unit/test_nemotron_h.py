"""Nemotron-H (``model_type`` ``nemotron_h``) on the normal serving path, at
toy widths on the CPU in float32, held to the family's plain reference
(``benchmark/families/nemotron_h.py``) on LOGITS to 2e-4:

- the system's no-cache forward, and prefill-then-decode through the paged
  functions the serving engine calls (K/V blocks for the attention blocks, a
  per-slot recurrent state for the Mamba blocks);
- every seeded defect of the reference (``DEFECTS``) fails that comparison;
- padding: prompts of every length modulo the bucket and the chunk;
- a slot freed and reused, a preemption and re-admission, requests of
  different lengths in one batch, through ``init_serving``;
- the chunked scan and the one-step update (``jax.numpy`` form and the Pallas
  kernels in interpret mode) against a sequential scan;
- the sigmoid router and the shared expert of ``moe_ffn`` against a loop;
- what is refused on a model with recurrent blocks, typed, at the earliest
  point; ``hf_config_to_transformer`` on the published dict.
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
from benchmark.families import nemotron_h as fam  # noqa: E402
from deepspeed_tpu.inference import SlotStateUnsupported  # noqa: E402
from deepspeed_tpu.models import hybrid, make_model  # noqa: E402
from deepspeed_tpu.models.hf_import import hf_config_to_transformer  # noqa: E402
from deepspeed_tpu.moe import sharded_moe as sm  # noqa: E402
from deepspeed_tpu.ops import ssm  # noqa: E402

TOL = 2e-4
HF = {"model_type": "nemotron_h", "n_shared_experts": 1, "norm_topk_prob": True,
      "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2",
      "layer_norm_epsilon": 1e-5, "max_position_embeddings": 512,
      "tie_word_embeddings": False, "conv_kernel": 4, "rope_theta": 10000,
      **fam.TOY, "num_experts_per_tok": 2}
BS = BUCKET = 16                       # block size = prompt bucket = chunk
SLOTS, MB = 3, 8


@pytest.fixture(scope="module")
def toy():
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    return cfg, model, params, fam.Reference(HF, params)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], n)


# ---- the paged path, driven as the engine drives it ------------------------

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
        self._prefill = jax.jit(model.prefill_paged)
        self._step = jax.jit(model.decode_step_paged)

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
        # a NEW array: on the CPU `jnp.asarray` may alias the numpy buffer,
        # and the step that reads it is still in flight
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
    """The plain reference agrees with the paged path to 2e-4; with any one
    defect seeded it does not (a defect on either side reads the same). A
    state rounded to bf16, or K and V rounded to 4 bits in the one attention
    block, moves these toy logits (|logit| < 1) by 1-2e-4: those two are
    held to 5e-5, ten times what the sound path reads here."""
    _, model, params, ref = toy
    prompt, gen = _ids(21, 1), _ids(40, 2)
    got = Paged(model, params).run(1, prompt, gen)
    limit = 5e-5 if defect in ("bf16_state", "kv_4bit") else TOL
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < limit / 10
    bad = fam.Reference(HF, params, defect=defect)
    bad.prompt_len, bad.prompt_bucket = len(prompt), BUCKET
    assert np.abs(got - _ref_tail(bad, prompt, gen)).max() > limit


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 15, 16, 17, 18, 19, 31, 32, 33,
                               35, 47, 48])
def test_padding_does_not_move_the_state(toy, n):
    """Every prompt length modulo the bucket (= the chunk): the pad rows of
    the bucket leave the state and the convolution tail as the true rows
    left them, so the steps that follow agree with the unpadded reference."""
    _, model, params, ref = toy
    prompt, gen = _ids(n, 10 + n), _ids(5, 99)
    got = Paged(model, params).run(0, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


def test_a_reused_slot_carries_nothing_of_the_last_request(toy):
    """A prefill is a whole prompt from a zero state and overwrites the
    slot's rows: the slot's last state (here a longer request's, prompt
    rows and conv tail included) does not reach the next request."""
    _, model, params, ref = toy
    pg = Paged(model, params)
    pg.run(2, _ids(30, 5), _ids(6, 6))
    assert float(jnp.abs(pg.pools["ssm"][:, 2]).max()) > 0
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


# ---- the decode step that sorts (PR 46) -------------------------------------

# (one token's row, one expert's two matrices) in bytes at the published
# widths, bf16: what the cell's layer is priced at
PUBLISHED = (2688 * 2, 2 * 2688 * 1856 * 2)


def _sorting(monkeypatch, cfg):
    """A model of the same config whose programs are traced with the toy's
    experts priced at the published widths (as ``test_afmoe.py``'s does for
    Trinity): against a toy expert of 64 KiB the sort's fixed work is
    hundreds of visits and the rule keeps the masks; priced as the cell's it
    sorts the toy step from its shapes (3 slots x 2 of 8: 6 rows reach 4.4
    of the 8 experts) as it sorts the cell's own (128 slots x 6 of 128: the
    rows reach every expert, 132.7 visits, and the one-hot form's [E, T, H]
    passes and einsums put 27 on top of its 128). The grouped matmuls run
    through the Pallas kernel in interpret mode, ``w_in_t`` on its
    ``transposed`` path, the stacks handed whole."""
    T, E, k = SLOTS, cfg.num_experts, cfg.top_k
    assert sm._one_hot_is_cheaper(T, E, k, 128 * 4, 2 * 128 * 64 * 4)
    assert not sm._one_hot_is_cheaper(T, E, k, *PUBLISHED)
    assert not sm._one_hot_is_cheaper(128, 128, 6, *PUBLISHED)
    monkeypatch.setattr(sm, "_expert_shapes", lambda p: PUBLISHED)
    monkeypatch.setattr(sm, "_use_gmm_kernel", lambda *a: True)
    return make_model(cfg)


def _step_form(model, params, pg):
    """The dispatch ``pg``'s decode step takes, read at trace time."""
    def step(p, pools):
        with sm.expert_load_tap() as tap:
            model.decode_step_paged(
                p, jnp.zeros(SLOTS, jnp.int32), pools, jnp.asarray(pg.tables),
                jnp.asarray(pg.lens), active=jnp.ones(SLOTS, bool))
        return tap.form
    form = []
    jax.eval_shape(lambda p, pools: form.append(step(p, pools)), params,
                   pg.pools)
    return form[0]


@pytest.mark.parametrize("case", ["every_slot", "an_inactive_slot"])
def test_a_step_that_sorts_gives_the_one_hot_steps_logits(toy, monkeypatch,
                                                          case):
    """The toy's decode step in the sorted form, its up projection read from
    ``w_in_t`` by the kernel, against the one-hot step slot by slot and step
    by step, and both against the plain reference, within TOL."""
    cfg, model, params, ref = toy
    prompts = {s: _ids(9 + 5 * s, 60 + s) for s in range(SLOTS)}
    gens = {s: _ids(5, 70 + s) for s in range(SLOTS)}
    live = (0, 2) if case == "an_inactive_slot" else tuple(range(SLOTS))

    def run(model, want):
        pg, out = Paged(model, params), {s: [] for s in live}
        for s in range(SLOTS):
            first = pg.prefill(s, prompts[s])
            if s in live:
                out[s].append(first)
        assert _step_form(model, params, pg) == want
        for i in range(5):
            for s, lg in pg.step({s: int(gens[s][i]) for s in live}).items():
                out[s].append(lg)
        return {s: np.stack(v) for s, v in out.items()}

    masks = run(model, "one-hot")
    sorts = run(_sorting(monkeypatch, cfg), "sorted/moe_gmm")
    for s in live:
        assert np.abs(sorts[s] - masks[s]).max() < TOL, s
        assert np.abs(sorts[s] - _ref_tail(ref, prompts[s], gens[s])
                      ).max() < TOL, s


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
    _, model, params, ref = toy
    srv = _serve(model, params)
    reqs = [(_ids(n, 50 + n), m) for n, m in ((5, 7), (17, 9), (33, 5), (3, 6))]
    outs = srv.run(reqs)
    for (p, m), rid in zip(reqs, sorted(outs)):
        assert list(np.asarray(outs[rid])[-m:]) == _greedy(ref, p, m)
    st = srv.stats()
    assert st["state_slots_live"] == 0
    assert st["state_pool_bytes"] + st["kv_pool_bytes"] == st["pool_bytes"]
    assert st["state_pool_bytes"] == 4 * 2 * (8 * 16 * 32 * 4 + 3 * 256 * 4)
    assert st["moe_dispatch"]["step"] == "one-hot"
    assert srv.state_pool_dtype == "float32"
    assert srv.pools["k"].shape[0] == 1 and srv.pools["ssm"].shape[:2] == (4, 2)
    srv.close()


def test_a_per_slot_state_pool_keeps_every_slot_in_the_step(toy):
    """An engine that holds a per-slot state pool dispatches every decode
    round at ``max_seqs`` (ISSUE 33): the step kernel updates ``[blocks,
    slots, ...]`` whole and in place, so a step over the first rows would
    copy them out. Engines without one narrow (tests/unit/test_serving.py)."""
    from deepspeed_tpu.inference import serving
    _, model, params, ref = toy
    srv = _serve(model, params, max_seqs=40)
    assert serving._slot_ladder(40) == (16, 40)
    assert serving._slot_ladder(128) == (32, 128)
    assert srv._slot_counts == (40,)
    assert set(srv._step_shapes()) == {
        (40, W) for W in serving._list_ladder(srv.MB, (4, 2, 1))}
    reqs = [(_ids(9, 70), 6), (_ids(21, 71), 5)]
    outs = srv.run(reqs)
    for (p, m), rid in zip(reqs, sorted(outs)):
        assert list(np.asarray(outs[rid])[-m:]) == _greedy(ref, p, m)
    st = srv.stats()
    assert all(k.startswith("40x") for k in st["step_shape_rounds"])
    assert sum(st["step_shape_rounds"].values()) > 0
    assert srv.pools["ssm"].shape[1] == 40
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


# ---- the import -------------------------------------------------------------

def _published():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "nemotron-3-nano-30b-serve.json")) as f:
            cfg = json.load(f)
        return dict(cfg, num_hidden_layers=52, hybrid_override_pattern=(
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"))
    with open(path) as f:
        return next(json.loads(ln) for ln in f
                    if "Nemotron-3-Nano-30B" in ln)["config"]


def test_import_of_the_published_config():
    cfg = hf_config_to_transformer(_published())
    kinds = [k for k, _ in hybrid.blocks(cfg)]
    assert len(kinds) == 52
    assert (kinds.count("mamba"), kinds.count("moe"), kinds.count("attn")) \
        == (23, 23, 6)
    assert kinds[:9] == ["mamba", "moe", "mamba", "moe", "mamba", "attn",
                         "moe", "mamba", "moe"]
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.dim_per_head) \
        == (2688, 32, 2, 128)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.ssm_state_size, cfg.conv_kernel, cfg.mamba_chunk) \
        == (64, 64, 8, 128, 4, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.ffn_dim, cfg.moe_shared_size,
            cfg.moe_scoring, cfg.routed_scaling_factor, cfg.norm_topk_prob,
            cfg.activation, cfg.drop_tokens) \
        == (128, 6, 1856, 3712, "sigmoid", 2.5, True, "relu2", False)
    assert cfg.position_type == "none" and not cfg.tie_embeddings
    assert (cfg.recurrent_blocks, cfg.attention_blocks) == (23, 6)


@pytest.mark.parametrize("pattern", ["ME-M*", "MEX"])
def test_import_refuses_an_unknown_block(pattern):
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        hf_config_to_transformer(dict(HF, hybrid_override_pattern=pattern,
                                      num_hidden_layers=len(pattern)))


# ---- the recurrence ---------------------------------------------------------

def _sequential(x, dt, A, B, C, S0):
    H, G = x.shape[1], B.shape[1]

    def step(S, xs):
        x_t, dt_t, B_t, C_t = xs
        Bh, Ch = jnp.repeat(B_t, H // G, 0), jnp.repeat(C_t, H // G, 0)
        S = S * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[..., None] * Bh[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, Ch)
    S, y = jax.lax.scan(step, S0, (x, dt, B, C))
    return y, S


def _ssm_inputs(T, seed=0):
    H, P, G, N = 8, 16, 2, 32
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2)
    return (jax.random.normal(k[0], (T, H, P)), dt,
            -jnp.exp(jax.random.uniform(k[2], (H,), minval=0, maxval=2.7)),
            jax.random.normal(k[3], (T, G, N)), jax.random.normal(k[4], (T, G, N)),
            jax.random.normal(k[5], (H, P, N)))


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("T", [1, 16, 40])
def test_chunked_scan_equals_the_sequential_one(T, kernel):
    x, dt, A, B, C, S0 = _ssm_inputs(T)
    dt = dt.at[T - T // 5:].set(0.0)           # pad positions: dt = 0
    y0, s0 = _sequential(x, dt, A, B, C, S0)
    y, s = ssm.ssm_scan(x, dt, A, B, C, S0, chunk=16, kernel=kernel)
    assert float(jnp.abs(y - y0).max()) < 5e-5
    assert float(jnp.abs(s - s0).max()) < 5e-5
    keep = T - T // 5                           # ... and they moved nothing
    if keep:
        s_true = _sequential(x[:keep], dt[:keep], A, B[:keep], C[:keep], S0)[1]
        assert float(jnp.abs(s - s_true).max()) < 5e-5


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_step_updates_the_pool_in_place(kernel):
    S = 5
    x, dt, A, B, C, _ = _ssm_inputs(S, seed=1)
    dt = dt.at[2].set(0.0)                                # an inactive slot
    pool = jax.random.normal(jax.random.PRNGKey(9), (3, S, 8, 16, 32))
    y, new = ssm.ssm_step(pool, 1, x, dt, A, B, C, kernel=kernel)
    for s in range(S):
        y1, s1 = _sequential(x[s:s + 1], dt[s:s + 1], A, B[s:s + 1],
                             C[s:s + 1], pool[1, s])
        assert float(jnp.abs(y[s] - y1[0]).max()) < 5e-5
        assert float(jnp.abs(new[1, s] - s1).max()) < 5e-5
    assert bool((new[0] == pool[0]).all()) and bool((new[2] == pool[2]).all())
    assert bool((new[1, 2] == pool[1, 2]).all())


# ---- the expert layer -------------------------------------------------------

def test_sigmoid_route_chooses_by_bias_and_weighs_by_score():
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    logits = jax.random.normal(k[0], (9, 8))
    bias = jax.random.normal(k[1], (8,))
    w, idx, gates = sm.route(logits, 3, renormalize=True, scoring="sigmoid",
                             bias=bias, scale=2.5)
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    want_idx = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want_idx, -1))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w),
                               2.5 * picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates), s, rtol=1e-5)


@pytest.mark.parametrize("sorts", [False, True], ids=["one-hot", "sorted"])
def test_moe_ffn_with_a_shared_expert(toy, sorts, monkeypatch):
    cfg, _, params, _ = toy
    monkeypatch.setattr(sm, "_sorts", lambda *a: sorts)
    st = params["layers"]["moe"]
    mp = {"wg": st["wg"][1], "w_in_t": st["moe_w_in_t"][1],
          "w_out": st["moe_w_out"][1], "e_bias": st["e_bias"][1],
          "shared_w_in": st["shared_w_in"][1],
          "shared_w_out": st["shared_w_out"][1]}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, cfg.hidden_size))
    y, _ = sm.moe_ffn(mp, x, cfg, train=False)
    h = np.asarray(x[0], np.float64)
    s = 1 / (1 + np.exp(-(h @ np.asarray(mp["wg"], np.float64))))
    idx = np.argsort(-(s + np.asarray(mp["e_bias"], np.float64)), -1)[:, :2]
    want = np.square(np.maximum(h @ np.asarray(mp["shared_w_in"], np.float64), 0)
                     ) @ np.asarray(mp["shared_w_out"], np.float64)
    for t in range(h.shape[0]):
        w = s[t, idx[t]] / s[t, idx[t]].sum() * 2.5
        for e, we in zip(idx[t], w):
            up = h[t] @ np.asarray(mp["w_in_t"][e], np.float64).T
            want[t] += we * (np.square(np.maximum(up, 0))
                             @ np.asarray(mp["w_out"][e], np.float64))
    assert np.abs(np.asarray(y[0]) - want).max() < 1e-5
