"""``afmoe`` (Arcee Trinity) on the normal path, at toy widths on the CPU,
against the plain reference of ``benchmark/families/afmoe.py`` on LOGITS in
float32:

- the whole forward; prefill and then decode through the cache — the full
  plane's blocks AND the window rings — past TWO wraps of a ring (window 16,
  60+ positions) against the reference's full forward;
- every seeded defect of the reference (``DEFECTS``) fails that comparison;
- prompts of every length around the window and the bucket; a slot reused by
  a shorter request (its ring holds the last request's rows behind the new
  ones); an inactive slot keeps its ring; requests of different lengths in
  one batch; int8 rings; through ``init_serving`` with a preemption;
- THE CHIP'S SHARE: the eight eighths of a layer's experts add up to the
  uncut layer (the shared expert, which every chip computes alike, counted
  once);
- what is refused on a model with window blocks, typed, at the earliest
  point; ``hf_config_to_transformer`` on the published dict and what it
  refuses; the table of HF weight names.

TOL = 2e-4 on logits of size ~1: float32 on both sides, the differences are
the order of sums (the one-hot dispatch against a loop over experts, a softmax
over ring rows + the fresh row against one over a score row). The sound path
reads 6e-7 here; bf16 where float32 is stated reads ~1e-2 and fails it. The
seeded defects move these logits by 5e-2 (``no_route_scale``) to 1.2
(``no_post_norm``).
"""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402
from benchmark.families import afmoe as fam  # noqa: E402
from deepspeed_tpu.inference import SlotStateUnsupported  # noqa: E402
from deepspeed_tpu.models import hybrid, make_model  # noqa: E402
from deepspeed_tpu.models import hf_import  # noqa: E402
from deepspeed_tpu.models.hf_import import hf_config_to_transformer  # noqa: E402
from deepspeed_tpu.moe import sharded_moe as sm  # noqa: E402

TOL = 2e-4
WINDOW = 16
HF = {"model_type": "afmoe", "hidden_act": "silu", "rms_norm_eps": 1e-5,
      "rope_theta": 10000, "rope_scaling": None, "max_position_embeddings": 512,
      "tie_word_embeddings": False, "num_experts_per_tok": 4,
      "num_shared_experts": 1, "route_norm": True, "route_scale": 2.448,
      "score_func": "sigmoid", "mup_enabled": True,
      **fam.TOY, "sliding_window": WINDOW}
DRAW = {"norm_init_jitter": 0.5, "post_norm_init": 0.5}   # norms away from 1
BS = BUCKET = 8                        # block size = prompt bucket
SLOTS, MB = 3, 12


@pytest.fixture(scope="module")
def toy():
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32, **DRAW)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    return cfg, model, params, fam.Reference(HF, params)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], n)


_JITS = {}


class Paged:
    """The model's paged functions over a pool of ``SLOTS`` slots, every
    slot with its own ``MB`` blocks: prefill into a slot, step all slots."""

    def __init__(self, model, params, dtype=jnp.float32):
        self.model, self.params = model, params
        self.pools = model.init_paged_cache(SLOTS * MB + 1, BS, dtype=dtype,
                                            max_seqs=SLOTS)
        self.tables = np.arange(1, SLOTS * MB + 1, dtype=np.int32
                                ).reshape(SLOTS, MB)
        self.lens = np.zeros(SLOTS, np.int32)
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
    lg = ref.logits(np.concatenate([prompt, generated]), pad_to=32)
    return lg[len(prompt) - 1:]


# ---- against the reference -------------------------------------------------

def test_the_pattern_and_the_cache(toy):
    cfg, model, _, _ = toy
    assert cfg.block_pattern == "WDWEWE*EWE"
    assert cfg.attn_windows == (16, 0, 16, 0, 16, 0, 0, 0, 16, 0)
    assert hybrid.window(cfg) == WINDOW
    assert (cfg.attention_blocks, cfg.window_blocks, cfg.kv_planes,
            cfg.slot_state_blocks, cfg.recurrent_blocks) == (5, 4, 1, 4, 0)
    assert hybrid.period(cfg) == ("WDWEWE*EWE", 1)
    pools = model.init_paged_cache(9, BS, dtype=jnp.float32, max_seqs=2)
    assert pools["k"].shape == (1, 9, BS, 1, 32)        # the full plane alone
    assert len(pools["wk"]) == 4                       # a ring a block ...
    assert pools["wk"][0].shape == (2, WINDOW, 1, 32)   # ... and slot
    cfg8 = hf_config_to_transformer(HF, kv_cache_bits=8)
    pools = make_model(cfg8).init_paged_cache(9, BS, max_seqs=2)
    assert pools["wk"][0].dtype == jnp.int8
    assert pools["wv_scale"][3].shape == (2, 1 * WINDOW)


def test_forward_matches_the_reference(toy):
    _, model, params, ref = toy
    ids = _ids(64)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None])[0])
    assert np.abs(got - ref.logits(ids, pad_to=32)).max() < TOL


def test_bf16_where_float32_is_stated_fails_the_tolerance(toy):
    cfg, model, params, ref = toy
    import dataclasses
    low = make_model(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    ids = _ids(64)
    got = np.asarray(low.apply(params, jnp.asarray(ids)[None])[0])
    assert np.abs(got - ref.logits(ids, pad_to=32)).max() > 10 * TOL


def test_prefill_then_decode_past_two_wraps_matches_the_reference(toy):
    """21 prompt positions, then 45 steps: the rings (16 rows) wrap at 32,
    48 and 64, and every step's logits are the full forward's."""
    _, model, params, ref = toy
    prompt, gen = _ids(21, 1), _ids(45, 2)
    got = Paged(model, params).run(1, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


@pytest.mark.parametrize("defect", fam.DEFECTS)
def test_each_defect_fails(toy, defect):
    """The plain reference agrees with the paged path to a third of the
    limit; with any one defect seeded it does not."""
    _, model, params, ref = toy
    prompt, gen = _ids(21, 1), _ids(30, 2)
    got = Paged(model, params).run(1, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL / 3
    bad = fam.Reference(HF, params, defect=defect)
    assert np.abs(got - _ref_tail(bad, prompt, gen)).max() > TOL


@pytest.mark.parametrize("n", [1, 7, 8, 9, 15, 16, 17, 23, 24, 25, 33, 40])
def test_prompts_around_the_window_and_the_bucket(toy, n):
    """Every relation of the prompt to the window (16) and the bucket (8):
    a bucket shorter than the ring lands as it is, a longer one keeps the
    window that ends at the last TRUE position, whatever the padding."""
    _, model, params, ref = toy
    prompt, gen = _ids(n, 10 + n), _ids(20, 99)
    got = Paged(model, params).run(0, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


def test_a_slot_reused_by_a_shorter_request(toy):
    """The ring still holds the last request's rows behind the new prompt's
    five: they are masked by position until the steps overwrite them."""
    _, model, params, ref = toy
    pg = Paged(model, params)
    pg.run(2, _ids(40, 5), _ids(6, 6))
    assert float(jnp.abs(pg.pools["wk"][0][2]).min()) > 0    # a full ring
    prompt, gen = _ids(5, 7), _ids(30, 8)
    got = pg.run(2, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


def test_requests_in_one_batch_equal_each_alone(toy):
    _, model, params, ref = toy
    prompts = {0: _ids(7, 20), 1: _ids(33, 21), 2: _ids(16, 22)}
    gens = {s: _ids(20, 30 + s) for s in prompts}
    pg = Paged(model, params)
    got = {s: [pg.prefill(s, p)] for s, p in prompts.items()}
    for i in range(20):
        live = {s: int(g[i]) for s, g in gens.items()}
        for s, lg in pg.step(live).items():
            got[s].append(lg)
    for s in prompts:
        assert np.abs(np.stack(got[s]) - _ref_tail(ref, prompts[s], gens[s])
                      ).max() < TOL, s


def test_an_inactive_slot_keeps_its_ring(toy):
    _, model, params, ref = toy
    pg = Paged(model, params)
    prompt, gen = _ids(19, 40), _ids(6, 41)
    got = [pg.prefill(0, prompt)]
    pg.prefill(1, _ids(20, 42))
    for t in gen:
        before = np.asarray(pg.pools["wk"][1][0])
        pg.step({1: 5})                       # slot 0 idles through a step
        assert (np.asarray(pg.pools["wk"][1][0]) == before).all()
        got.append(pg.step({0: int(t)})[0])
    assert np.abs(np.stack(got) - _ref_tail(ref, prompt, gen)).max() < TOL


def test_int8_rings_are_quantised_as_pool_rows(toy):
    """int8 K/V (the engine's default), rings and pool alike: logits within
    the int8 cache's own error of the float32 reference (3e-2 here; the
    float cache reads 6e-7), past two wraps."""
    cfg, _, params, ref = toy
    import dataclasses
    model = make_model(dataclasses.replace(cfg, kv_cache_bits=8))
    prompt, gen = _ids(21, 1), _ids(45, 2)
    got = Paged(model, params).run(1, prompt, gen)
    err = np.abs(got - _ref_tail(ref, prompt, gen)).max()
    assert TOL < err < 0.1


# ---- the chip's share -------------------------------------------------------

def test_the_eight_shares_add_up_to_the_whole_layer(toy):
    """An expert layer of 16 experts, whole, against the sum of its 8 shares
    of 2 held experts each (router width 16, top-4, weights normalised over
    all four chosen): the routed parts add up and the shared expert, which
    every chip computes alike, is counted once."""
    hf = dict(HF, num_experts=16, num_experts_router=16)
    cfg = hf_config_to_transformer(hf, dtype=jnp.float32)
    params = make_model(cfg).init(jax.random.PRNGKey(5))
    st = {k: v[1] if not k.startswith("moe_w_") else v
          for k, v in params["layers"]["moe"].items()}
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 24, cfg.hidden_size))

    def layer(cfg, stacks):
        p = dict(st, **{k: sm.LayerOf(v, 0) for k, v in stacks.items()})
        return np.asarray(hybrid._moe_mixer(p, h, cfg)[0])

    full = {k: v[1:2] for k, v in params["layers"]["moe"].items()
            if k.startswith("moe_w_")}
    whole = layer(cfg, full)
    shared = layer(hf_config_to_transformer(
        dict(hf, num_experts=2, expert_first=0), dtype=jnp.float32),
        {k: jnp.zeros_like(v[:, :2]) for k, v in full.items()})
    total = shared.copy()
    for first in range(0, 16, 2):
        part = hf_config_to_transformer(
            dict(hf, num_experts=2, expert_first=first), dtype=jnp.float32)
        total += layer(part, {k: v[:, first:first + 2]
                              for k, v in full.items()}) - shared
    # outputs of size 4e-3 (a unit-normal input through std-0.02 matrices)
    assert np.abs(total - whole).max() < 1e-7 and np.abs(whole).max() > 1e-3
    # ... and the uncut layer is the REFERENCE's uncut layer
    ref = fam.Reference(hf, params)
    moe = params["layers"]["moe"]
    with jax.default_matmul_precision("highest"):
        w = ref._route(moe, 1, h[0])
        want = ref._shared(moe, 1, h[0])
        for e in range(16):
            want = ref._add_expert(moe, 1, e, h[0], w[:, e], want)
    assert np.abs(whole[0] - np.asarray(want)).max() < 1e-7


# ---- the decode step that sorts ---------------------------------------------

def _sorting(monkeypatch, cfg):
    """A model of the same config whose programs are traced with the toy's
    experts priced at Trinity's published widths (3 x 3072 x 3072 in bf16):
    the sorted dispatch's fixed work is a time, so against a toy expert of
    96 KiB it is a hundred visits and the rule keeps the masks; priced as
    the cell's it sorts the toy step from its shapes as it sorts the cell's
    (3 slots x 4 of 16, 8 held: 6 rows are expected to touch 4.4 of 8
    experts, 4.4 + 0.2 visits against 8)."""
    T, E, k = SLOTS, cfg.num_experts, cfg.top_k * cfg.num_experts / 16
    published = (3072 * 2, 3 * 3072 * 3072 * 2)
    assert sm._one_hot_is_cheaper(T, E, k, 128 * 4, 3 * 128 * 64 * 4)
    assert not sm._one_hot_is_cheaper(T, E, k, *published)
    monkeypatch.setattr(sm, "_expert_shapes", lambda p: published)
    return make_model(cfg)


def _load_reader(model):
    """read(params, pg, tokens) -> (form, [expert layers, E + 1] load rows)
    of the step ``pg.step(tokens)`` would take, on ``pg``'s pools as they
    are."""
    form = []

    def step(p, tok, pools, tab, lens, act):
        with sm.expert_load_tap() as tap:
            model.decode_step_paged(p, tok, pools, tab, lens, active=act)
        form.append(tap.form)                                   # trace time
        return tap.stacked()

    step = jax.jit(step)

    def read(params, pg, tokens: dict):
        tok = np.zeros(SLOTS, np.int32)
        act = np.zeros(SLOTS, bool)
        for s_, t in tokens.items():
            tok[s_], act[s_] = t, True
        rows = step(params, jnp.asarray(tok), pg.pools, jnp.asarray(pg.tables),
                    jnp.asarray(pg.lens), jnp.asarray(act))
        return form[0], np.asarray(rows)

    return read


@pytest.mark.parametrize("case", ["some_experts_empty", "no_held_expert_gets_a_row",
                                  "an_inactive_slot"])
def test_a_step_that_sorts_gives_the_one_hot_steps_logits(toy, monkeypatch, case):
    """The held share's decode step in the sorted form (``_sorts`` from its
    shapes) against the one-hot form, slot by slot and step by step, within
    TOL: (a) steps in which some held experts get no row, (b) steps in which
    NO held expert gets a row — the bias that makes the choice sends every
    token to the eight experts held elsewhere, the grouped matmuls have no
    group and every routed row is masked —, (c) a slot that idles. The load
    rows (experts touched, assignments held) are the same in both forms."""
    cfg, model, params, _ = toy
    if case == "no_held_expert_gets_a_row":
        moe = dict(params["layers"]["moe"])
        moe["e_bias"] = moe["e_bias"].at[:, :cfg.num_experts].set(-1e3)
        params = dict(params, layers=dict(params["layers"], moe=moe))
    prompts = {s_: _ids(9 + 4 * s_, 80 + s_) for s_ in range(SLOTS)}
    gens = {s_: _ids(5, 90 + s_) for s_ in range(SLOTS)}
    live = (0, 2) if case == "an_inactive_slot" else tuple(range(SLOTS))

    def run(model, want):
        """[(load rows, {slot: logits})] of five steps, traced here."""
        pg, read, out = Paged(model, params), _load_reader(model), []
        for s_ in range(SLOTS):
            pg.prefill(s_, prompts[s_])
        for i in range(5):
            tokens = {s_: int(gens[s_][i]) for s_ in live}
            form, loads = read(params, pg, tokens)
            assert form == want
            out.append((loads, pg.step(tokens)))
        return out, pg

    masks, pg_m = run(model, "one-hot")
    sorts, pg_s = run(_sorting(monkeypatch, cfg), "sorted/ragged_dot")
    for (loads, got), (loads_m, got_m) in zip(sorts, masks):
        assert (loads == loads_m).all()
        held = loads[:, :cfg.num_experts]
        if case == "no_held_expert_gets_a_row":
            assert held.sum() == 0 and loads[0, -1] == 4 * len(live)
        else:
            assert 0 < (held > 0).sum(1).min() and (held > 0).sum(1).max() < 8
            assert held.sum(1).max() <= 4 * len(live)
        for s_ in live:
            assert np.abs(got[s_] - got_m[s_]).max() < TOL
    if case == "an_inactive_slot":           # slot 1 idled: its ring is whole
        for leaf in ("wk", "wv"):
            assert (np.asarray(pg_s.pools[leaf][1][1])
                    == np.asarray(pg_m.pools[leaf][1][1])).all()


def test_a_served_step_that_sorts_counts_what_the_one_hot_step_counts(
        toy, monkeypatch):
    """Through ``init_serving``: the same requests under both forms give the
    same tokens, and ``stats()`` names the form and reads the same
    ``moe_experts_touched_per_step`` and ``moe_assignments_held``."""
    cfg, model, params, ref = toy
    reqs = [(_ids(n, 50 + n), m) for n, m in [(30, 9), (5, 12), (17, 10)]]

    def served(model):
        srv = _serve(model, params, max_seqs=SLOTS)
        outs = srv.run(reqs)
        st = srv.stats()
        srv.close()
        return [list(outs[r]) for r in range(len(reqs))], st

    outs, st = served(model)
    sorting = _sorting(monkeypatch, cfg)
    # the engine of this suite spans the 8 virtual devices, and under a mesh
    # `_sorts` keeps the masks: the rule alone here, as on one chip
    monkeypatch.setattr(sm, "_sorts", lambda T, E, k, *nbytes: (
        not sm._one_hot_is_cheaper(T, E, k, *nbytes)))
    outs_s, st_s = served(sorting)
    assert outs_s == outs
    assert st["moe_dispatch"]["step"] == "one-hot"
    assert st_s["moe_dispatch"]["step"] == "sorted/ragged_dot"
    for key in ("moe_experts_touched_per_step", "moe_assignments_held",
                "moe_assignments_asked", "moe_load_max_over_mean"):
        assert st_s[key] == st[key], key
    assert 0 < st["moe_experts_touched_per_step"] < cfg.num_experts


# ---- through init_serving ---------------------------------------------------

def _serve(model, params, **serving):
    return deepspeed_tpu.init_serving(
        model, config={"kv_cache_bits": 0}, params=params, dtype=jnp.float32,
        serving=dict(dict(max_seqs=2, block_size=BS, max_model_len=96,
                          decode_quantum=4, prompt_bucket=BUCKET), **serving))


def _greedy(ref, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(ref.logits(np.asarray(ids), pad_to=32)[-1].argmax()))
    return ids[len(prompt):]


def test_served_tokens_are_the_reference_greedy_tokens(toy):
    """Five requests over two slots (every slot is given again), prompts on
    both sides of the window, every request past a wrap."""
    _, model, params, ref = toy
    srv = _serve(model, params)
    reqs = [(_ids(n, 50 + n), m) for n, m in
            [(30, 20), (5, 30), (17, 25), (40, 12), (9, 28)]]
    outs = srv.run(reqs)
    for rid, (prompt, m) in enumerate(reqs):
        assert list(outs[rid][len(prompt):]) == _greedy(ref, prompt, m), rid
    st = srv.stats()
    assert st["window_blocks"] == 4 and st["window_rows"] == WINDOW
    assert st["ring_bytes_per_slot"] == 4 * WINDOW * 2 * 1 * 32 * 4
    assert st["kv_bytes_per_token"] == 1 * 2 * 1 * 32 * 4
    assert st["state_pool_bytes"] == 2 * st["ring_bytes_per_slot"]
    assert st["state_pool_bytes"] + st["kv_pool_bytes"] == st["pool_bytes"]
    assert 1.0 <= st["window_rows_read"] / st["window_rows_in_window"] < 1.5
    srv.close()


def test_a_preempted_request_is_recomputed(toy):
    """A pool too small for both requests at full length: one is preempted,
    prefilled again into a slot (its ring overwritten) and still matches."""
    _, model, params, ref = toy
    srv = _serve(model, params, num_blocks=14)
    reqs = [(_ids(30, 70), 40), (_ids(28, 71), 40)]
    outs = srv.run(reqs)
    for rid, (prompt, m) in enumerate(reqs):
        assert list(outs[rid][len(prompt):]) == _greedy(ref, prompt, m), rid
    assert srv.stats()["preemptions"] >= 1
    srv.close()


@pytest.mark.parametrize("serving, what", [
    ({"enable_prefix_cache": True}, "prefix cache"),
    ({"prefill_token_budget": 16}, "chunked prefill"),
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


# ---- the importer -----------------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-large-serve.json")) as f:
        cfg = json.load(f)
    return {k: v for k, v in cfg.items()
            if k not in ("source", "reduced", "assumed", "deployment", "run",
                         "correct")}


def test_the_published_config_maps_to_the_pattern():
    cfg = hf_config_to_transformer(_published())
    assert cfg.block_pattern == "WDWEWE*EWE"
    assert set(cfg.attn_windows) == {0, 4096} and hybrid.window(cfg) == 4096
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.dim_per_head,
            cfg.ffn_dim, cfg.dense_ffn_size, cfg.moe_shared_size) == (
        3072, 48, 8, 128, 3072, 12288, 3072)
    assert (cfg.num_experts, cfg.moe_router_width, cfg.top_k,
            cfg.moe_held_first) == (32, 256, 4, 0)
    assert cfg.moe_scoring == "sigmoid" and cfg.norm_topk_prob
    assert cfg.routed_scaling_factor == 2.448
    assert cfg.embed_scale == pytest.approx(3072 ** 0.5)
    assert cfg.position_type == "none" and cfg.sandwich_norm
    assert cfg.qk_norm_per_head and cfg.attn_out_gate
    whole = dict(_published(), num_hidden_layers=60, num_dense_layers=6,
                 num_experts=256, vocab_size=200192)
    whole.pop("num_experts_router")
    cfg = hf_config_to_transformer(whole)
    assert cfg.block_pattern == "".join(
        ("*" if (i + 1) % 4 == 0 else "W") + ("D" if i < 6 else "E")
        for i in range(60))
    assert cfg.block_pattern.count("W") == 45
    assert cfg.block_pattern.count("D") == 6
    assert cfg.moe_router_experts is None


@pytest.mark.parametrize("change", [
    {"n_group": 2}, {"score_func": "softmax"}, {"rope_scaling": {"type": "yarn"}},
    {"num_shared_experts": 2}, {"hidden_act": "gelu"},
    {"layer_types": ["sliding_attention", "chunked_attention"] * 3},
    {"expert_first": 12}, {"sliding_window": None},
])
def test_what_the_importer_refuses(change):
    with pytest.raises(ValueError, match="afmoe"):
        hf_config_to_transformer(dict(HF, **change))


def test_the_table_of_hf_weight_names(toy):
    """Every tensor of an HF ``afmoe`` checkpoint of this shape has ONE
    place in the program's tree, and every leaf is filled: names as
    ``modeling_afmoe`` registers them."""
    cfg, _, params, _ = toy
    table = hf_import.afmoe_weight_names(cfg)
    names = set(table)
    assert "model.embed_tokens.weight" in names and "lm_head.weight" in names
    for want in ("model.layers.0.self_attn.gate_proj.weight",
                 "model.layers.0.self_attn.q_norm.weight",
                 "model.layers.0.pre_mlp_layernorm.weight",
                 "model.layers.0.post_mlp_layernorm.weight",
                 "model.layers.0.mlp.up_proj.weight",
                 "model.layers.1.mlp.router.gate.weight",
                 "model.layers.1.mlp.expert_bias",
                 "model.layers.1.mlp.shared_experts.down_proj.weight",
                 "model.layers.4.mlp.experts.7.gate_proj.weight"):
        assert want in names, want
    assert not any(re.search(r"layers\.0\.mlp\.(router|experts)", n)
                   for n in names)                      # layer 0 is dense
    assert "model.layers.1.mlp.experts.8.up_proj.weight" not in names  # held: 8
    filled = {}
    for name, (kind, j, leaf, part) in table.items():
        filled.setdefault((kind, leaf), set()).add((j, part))
    for kind, stacks in params["layers"].items():
        for leaf, a in stacks.items():
            parts = filled.pop((kind, leaf))
            assert {j for j, _ in parts} == set(range(a.shape[0])), (kind, leaf)
    assert set(filled) == {(None, "tok_embed"), (None, "lm_head"),
                           (None, "final_norm_scale")}
    # layer 3 is the full-attention layer: the one "attn" block
    assert table["model.layers.3.self_attn.k_proj.weight"][:3] == \
        ("attn", 0, "wk")
    assert table["model.layers.4.self_attn.k_proj.weight"][:3] == \
        ("wattn", 3, "wk")
