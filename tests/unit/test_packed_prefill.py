"""Prompts admitted in one round share ONE prefill program (ISSUE 43).

The model half: ``prefill_paged(..., segments=(starts, lengths))`` puts
several prompts into the one row of a bucket's program, each from a block's
edge. Every prompt's logits, its blocks of the pool (and their scale planes)
and its part of the program's counters are what the same prompt gives alone —
for the four homogeneous-stack families at the benchmark's toy widths (a GQA
dense stack, a dropless expert stack, one with q/k norms that crosses
``_sorts``' threshold when packed, a looped stack with its exit tap) and for
learned positions. ``attention`` keeps two segments of a row apart.

The engine half: ``ServingEngine._pack_prefills`` shares a row only where the
programs are already built; what comes out is what the engine gives a prompt
at a time.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import serving
from deepspeed_tpu.models import TransformerConfig, make_model
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.looped import exit_tap
from deepspeed_tpu.moe.sharded_moe import expert_load_tap
from deepspeed_tpu.ops.flash_attention import packed_walk

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BS = 16
ATOL = 2e-4


# --------------------------------------------------------------------------
# the models
# --------------------------------------------------------------------------

def _family(name, **overrides):
    """A serve configuration of the benchmark at its toy widths, float32."""
    sys.path.insert(0, ROOT)
    from benchmark.harness import common
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    cfgf = common.load_config(name)
    return hf_config_to_transformer(
        common.hf_of(cfgf, rehearsal=True), max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32,
        **{"attention_impl": "xla", **cfgf["run"].get("overrides", {}),
           **overrides})


def _learned(**overrides):
    return TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                             num_heads=4, max_seq_len=512,
                             position_type="learned", dtype=jnp.float32,
                             **overrides)


# name -> (config, the lengths of the prompts of one row, the row's bucket);
# every prompt alone runs at the bucket ALONE
CASES = {
    "gqa-dense": (lambda **kw: _family("mistral-7b-serve", **kw),
                  (20, 37, 5), 128),
    # the flash kernel (interpret mode here) takes the row and the prompts
    # alone: a packed row runs it once a live segment
    "gqa-dense-kernel": (lambda **kw: _family(
        "mistral-7b-serve", attention_impl="pallas", **kw), (20, 37, 5), 128),
    "experts-one-hot": (lambda **kw: _family("mixtral-8x7b-serve", **kw),
                        (20, 37, 5), 128),
    # 64 experts, 8 a token, q/k norms, priced at widths whose 192-token
    # call keeps the one-hot masks and whose 384-token call sorts
    # (sharded_moe._sorts) — each prompt alone on one side, the row on the
    # other
    "experts-across-the-sort": (lambda **kw: _family("olmoe-1b-7b-serve", **kw),
                                (150, 100, 70), 384),
    "looped": (lambda **kw: _family("ouro-2.6b-serve", **kw), (20, 37, 5), 128),
    "learned-positions": (_learned, (20, 37, 5), 128),
}
ALONE = 192      # the bucket every prompt runs at alone


def _loud(params):
    """``init_params`` at std 0.02 makes every score ~0 and every branch 1e-3
    of the residual stream: scaled up so that a row read across segments, or
    a position counted from the row's start, reaches the logits."""
    lay = dict(params["layers"])
    for name, gain in (("wq", 8.0), ("wk", 8.0), ("wv", 5.0), ("wo", 5.0),
                       ("wg", 40.0), ("moe_w_in", 8.0), ("moe_w_gate", 8.0),
                       ("moe_w_out", 20.0)):
        if name in lay:
            lay[name] = lay[name] * gain
    out = {**params, "layers": lay}
    for name in ("lm_head", "pos_embed"):
        if name in out:
            out[name] = out[name] * 10.0
    return out


def _prompts(lengths, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lengths]


def _alone(model, params, pools, prompt, blocks):
    """One prompt through the one-prompt call: (logits [V], pools, expert
    load | None, exit distribution | None, the experts' dispatch form)."""
    ids = np.zeros((1, ALONE), np.int32)
    ids[0, :prompt.size] = prompt

    def run(params, ids, pools, blocks, n):
        with expert_load_tap() as tap, exit_tap() as gate:
            last, pools = model.prefill_paged(params, ids, pools, blocks,
                                              length=n)
        forms.append(tap.form)
        return last[0], pools, tap.stacked(), gate.summed()

    forms = []
    out = jax.jit(run)(params, ids, pools, blocks[:ALONE // BS],
                       np.int32(prompt.size))
    return out + (forms[0],)


def _packed(model, params, pools, prompts, blocks, P, K=4):
    """The prompts in one row of bucket ``P``, as the engine lays them out:
    (logits [K, V], pools, expert load | None, exit distribution | None,
    the experts' dispatch form)."""
    ids = np.zeros((1, P), np.int32)
    starts, lengths = np.zeros(K, np.int32), np.zeros(K, np.int32)
    row = []
    for k, (p, blk) in enumerate(zip(prompts, blocks)):
        starts[k], lengths[k] = len(row) * BS, p.size
        ids[0, starts[k]:starts[k] + p.size] = p
        row += list(blk[:-(-p.size // BS)])
    row += [0] * (P // BS - len(row))

    def run(params, ids, pools, row, starts, lengths):
        with expert_load_tap() as tap, exit_tap() as gate:
            last, pools = model.prefill_paged(params, ids, pools, row,
                                              segments=(starts, lengths))
        forms.append(tap.form)
        return last, pools, tap.stacked(), gate.summed()

    forms = []
    out = jax.jit(run)(params, ids, pools, np.asarray(row, np.int32), starts,
                       lengths)
    return out + (forms[0],)


@pytest.mark.parametrize("pool", ["float-pool", "int8-pool"])
@pytest.mark.parametrize("case", CASES)
def test_a_packed_row_gives_every_prompt_what_it_gets_alone(case, pool,
                                                            monkeypatch):
    build, lengths, P = CASES[case]
    if case == "experts-across-the-sort":
        # the toy's experts priced at published widths, as the rule prices
        # a cell's: against a toy expert the sort's fixed work is hundreds of
        # visits and nothing sorts. Mixtral's, not OLMoE's: since PR 46
        # every OLMoE bucket sorts, and this case wants a row ACROSS the
        # two forms (tests/unit/test_olmoe.py has both tables)
        from deepspeed_tpu.moe import sharded_moe
        monkeypatch.setattr(sharded_moe, "_expert_shapes",
                            lambda p: (4096 * 2, 3 * 4096 * 14336 * 2))
    cfg = build(kv_cache_bits=8 if pool == "int8-pool" else 0)
    model = make_model(cfg)
    params = _loud(model.init(jax.random.PRNGKey(0)))
    prompts = _prompts(lengths)
    nb = ALONE // BS
    blocks = [np.arange(1 + k * nb, 1 + (k + 1) * nb, dtype=np.int32)
              for k in range(len(prompts))]
    empty = model.init_paged_cache(1 + len(prompts) * nb, BS,
                                   dtype=jnp.float32)

    pools, alone = empty, []
    for p, blk in zip(prompts, blocks):
        last, pools, load, exits, form = _alone(model, params, pools, p, blk)
        alone.append((np.asarray(last), load, exits, form))
    last, packed_pools, load, exits, form = _packed(model, params, empty,
                                                    prompts, blocks, P)

    # logits at every prompt's last position
    for k, (want, _, _, _) in enumerate(alone):
        np.testing.assert_allclose(np.asarray(last[k]), want, atol=ATOL, rtol=0)
    # the pool: every prompt's blocks and their scale planes, bit for bit —
    # but behind an expert layer, whose sums over the row's tokens are
    # blocked by the row's length: there to the rounding of those sums (an
    # int8 row within one step of the other's, on a scale that agrees)
    assert set(packed_pools) == set(pools)
    assert ("k_scale" in pools) == (pool == "int8-pool")
    for p, blk in zip(prompts, blocks):
        held = blk[:-(-p.size // BS)]
        for leaf in pools:
            got = np.asarray(packed_pools[leaf][:, held])
            want = np.asarray(pools[leaf][:, held])
            if cfg.num_experts <= 1:
                np.testing.assert_array_equal(got, want, err_msg=leaf)
            elif got.dtype == np.int8:
                off = np.abs(got.astype(np.int32) - want)
                assert off.max() <= 1 and (off > 0).mean() < 1e-3, leaf
            else:
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5,
                                           err_msg=leaf)
    # the counters: a row's are the sum of its prompts'
    if cfg.num_experts > 1:
        np.testing.assert_array_equal(
            np.asarray(load), sum(np.asarray(a[1]) for a in alone))
        assert int(np.asarray(load)[0, -1]) == sum(lengths) * cfg.top_k
    else:
        assert load is None
    if cfg.ut_steps > 1:
        np.testing.assert_allclose(
            np.asarray(exits), sum(np.asarray(a[2]) for a in alone), atol=1e-5)
        assert float(exits[-1]) == len(prompts)     # a position a prompt
    else:
        assert exits is None
    if case == "experts-across-the-sort":
        assert {a[3] for a in alone} == {"one-hot"}
        assert form.startswith("sorted/")
    elif case == "experts-one-hot":
        assert form == "one-hot" and alone[0][3] == "one-hot"


def test_one_live_segment_is_the_one_prompt_call():
    """A row with ONE prompt in the engine's form: logits and blocks of the
    call with ``length``, bit for bit (ids that are all one segment
    mask nothing)."""
    cfg = _family("mistral-7b-serve")
    model = make_model(cfg)
    params = _loud(model.init(jax.random.PRNGKey(0)))
    prompt, = _prompts((37,))
    blocks = np.arange(1, 1 + ALONE // BS, dtype=np.int32)
    empty = model.init_paged_cache(1 + ALONE // BS, BS, dtype=jnp.float32)
    want, pools, *_ = _alone(model, params, empty, prompt, blocks)
    last, packed_pools, *_ = _packed(model, params, empty, [prompt], [blocks],
                                     ALONE)
    np.testing.assert_array_equal(np.asarray(last[0]), np.asarray(want))
    held = blocks[:-(-prompt.size // BS)]
    for leaf in pools:
        np.testing.assert_array_equal(np.asarray(packed_pools[leaf][:, held]),
                                      np.asarray(pools[leaf][:, held]))


# --------------------------------------------------------------------------
# attention() and its segment ids
# --------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["mha", "gqa", "window", "key-mask",
                                  "kernel", "kernel-key-mask"])
def test_attention_does_not_cross_segments(form):
    """``attention``'s XLA branch used the ids only to leave the flash path:
    two segments in a row attended across. Against each segment alone.
    ``kernel``: where the flash kernel takes a row (here in interpret mode)
    it is ONE call that walks the tiles the segments reach (ISSUE 53;
    tests/unit/test_flash_packed.py), a key mask on top."""
    cfg = TransformerConfig(vocab_size=32, hidden_size=64, num_layers=1,
                            num_heads=4, num_kv_heads=2 if form == "gqa" else 4,
                            dtype=jnp.float32,
                            attention_impl="pallas" if "kernel" in form
                            else "xla")
    S, cut = 48, 20
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (1, S, 4, 16)) * 2.0
    k = jax.random.normal(kk, (1, S, cfg.kv_heads, 16)) * 2.0
    v = jax.random.normal(kv, (1, S, cfg.kv_heads, 16))
    ids = jnp.asarray((np.arange(S) >= cut).astype(np.int32))[None]
    kw = {"window": jnp.int32(7)} if form == "window" else {}
    mask = None
    if "key-mask" in form:
        mask = jnp.asarray(np.arange(S) % 5 != 3)[None]
    got = T.attention(q, k, v, mask, cfg=cfg, segment_ids=ids, **kw)
    for lo, hi in ((0, cut), (cut, S)):
        want = T.attention(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                           None if mask is None else mask[:, lo:hi],
                           cfg=cfg, **kw)
        np.testing.assert_allclose(np.asarray(got[:, lo:hi]),
                                   np.asarray(want), atol=1e-5, rtol=0)
    # ... and it did cross: the second segment without ids reads the first
    across = T.attention(q, k, v, mask, cfg=cfg, **kw)
    assert float(jnp.max(jnp.abs(across[:, cut:] - got[:, cut:]))) > 1e-2
    # a row that is all one segment is the call without ids
    one = T.attention(q, k, v, mask, cfg=cfg,
                      segment_ids=jnp.zeros((1, S), jnp.int32), **kw)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(across))


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

def _serve(cfg, params=None, **over):
    d = dict(max_seqs=4, block_size=BS, max_model_len=256, decode_quantum=4,
             prompt_bucket=32, decode_backend="xla")
    d.update(over)
    model = make_model(cfg)
    if params is None:
        params = _loud(model.init(jax.random.PRNGKey(0)))
    return deepspeed_tpu.init_serving(
        model, config={"kv_cache_bits": 0}, serving=d, dtype=jnp.float32,
        params=jax.device_get(params))


def _finish(srv) -> dict:
    """Run what the engine holds to its end: {rid: output}."""
    while not srv.scheduler.done:
        srv.step()
    return {r.rid: r.output for r in srv._finished}


def _prefill_counts(srv):
    st = srv.stats()
    return tuple(int(st[k]) for k in ("prefill_prompts", "prefill_programs",
                                      "prefill_packed_prompts"))


@pytest.fixture(scope="module")
def compiles():
    """The benchmark's own counter of programs lowered or compiled (what a
    run's ``compiles_in_window`` reads): ``names`` grows with each."""
    sys.path.insert(0, ROOT)
    from benchmark.harness.common import CompileCounter
    return CompileCounter()


ENGINES = {
    "gqa-dense": lambda: _family("mistral-7b-serve"),
    # the flash kernel (interpret mode here) takes every row: the only one
    # of these engines whose attention tiles are counted
    "gqa-dense-kernel": lambda: _family("mistral-7b-serve",
                                        attention_impl="pallas"),
    "experts": lambda: _family("olmoe-1b-7b-serve"),
    "looped": lambda: _family("ouro-2.6b-serve"),
}


@pytest.mark.parametrize("name", ENGINES)
def test_three_admissions_of_one_round_run_as_one_program(name, compiles,
                                                          monkeypatch):
    """Warm the buckets the way the benchmark's warm-up does — a prompt a
    bucket, all admitted in one round: nothing is built, so every prompt runs
    alone and builds its program. Then three prompts admitted in one round
    are ONE program, nothing compiles, and every request's tokens — and the
    window's counters — are those of an engine that takes a prompt a program
    (``_SEGMENTS`` 1)."""
    cfg = ENGINES[name]()
    warm = [(p, 5) for p in _prompts((20, 40, 70, 100), seed=1)]
    reqs = [(p, 9) for p in _prompts((37, 20, 5))]

    def drive(srv):
        srv.run(warm)
        built = sorted(srv._prefill_fns)
        after_warm = _prefill_counts(srv)
        srv.reset_stats()
        rids = [srv.add_request(p, n) for p, n in reqs]
        before = len(compiles.names)
        srv.step()
        compiled = compiles.names[before:]
        record = dict(srv._phases[-1])
        outs = _finish(srv)
        return (built, after_warm, compiled, record,
                [outs[r] for r in rids], srv.stats())

    built, after_warm, compiled, record, packed, st = drive(_serve(cfg))
    assert built == [32, 64, 96, 128] and after_warm == (4, 4, 0)
    assert compiled == []
    assert (record["prefills"], record["prefill_programs"],
            record["prefill_tokens"]) == (3, 1, 96)     # 48 + 32 + 16
    assert tuple(int(st[k]) for k in (
        "prefill_prompts", "prefill_programs",
        "prefill_packed_prompts")) == (3, 1, 3)
    # the row's attention, in key tiles of one kv head's pass (ISSUE 53),
    # counted where the packed flash forward took the row and nowhere else:
    # the ONE call walks what the three segments reach, where a causal pass
    # a prompt walked three times the whole row
    kernel = cfg.attention_impl == "pallas"
    rep = cfg.num_heads // cfg.kv_heads
    walked, causal = packed_walk([0, 48, 80, 0], [37, 20, 5, 0], 96, rep)
    assert walked < causal
    assert (int(st["prefill_attn_tiles_walked"]),
            int(st["prefill_attn_tiles_looped"])) == (
                (walked, 3 * causal) if kernel else (0, 0))

    monkeypatch.setattr(serving, "_SEGMENTS", 1)
    *_, record, alone, st1 = drive(_serve(cfg))
    assert (record["prefills"], record["prefill_programs"],
            record["prefill_tokens"]) == (3, 3, 64 + 32 + 32)
    assert int(st1["prefill_packed_prompts"]) == 0
    # a prompt alone walks its bucket's causal pass, as it did
    alone_tiles = sum(packed_walk([0], [n], P, rep)[1]
                      for n, P in ((37, 64), (20, 32), (5, 32)))
    assert (int(st1["prefill_attn_tiles_walked"]),
            int(st1["prefill_attn_tiles_looped"])) == (
                alone_tiles if kernel else 0,) * 2
    for a, b in zip(packed, alone):
        np.testing.assert_array_equal(a, b)
    # every sum over requests is still a sum over programs
    for key in ("moe_assignments", "moe_dropped_share", "exit_step_expected"):
        assert (key in st) == (key in st1)
        if key in st:
            assert st[key] == pytest.approx(st1[key], rel=1e-6), key
    if name == "experts":
        assert st["moe_assignments"] > 0
    if name == "looped":
        assert 1.0 <= st["exit_step_expected"] <= cfg.ut_steps


def test_tokens_of_packed_rounds_are_generates():
    """More requests than slots, every later round admitting into freed
    slots with all buckets built: the tokens are one-shot ``generate``'s."""
    cfg = _family("mistral-7b-serve")
    srv = _serve(cfg, max_seqs=3)
    lengths = (20, 40, 70, 100, 37, 5, 90, 64, 12, 33)
    reqs = [(p, 6 + i % 5) for i, p in enumerate(_prompts(lengths, seed=2))]
    outs = srv.run(reqs)
    assert _prefill_counts(srv)[2] > 0          # some shared a row
    eng = deepspeed_tpu.init_inference(
        make_model(cfg), config={"kv_cache_bits": 0}, dtype=jnp.float32,
        params=jax.device_get(srv.engine.params))
    for rid, (p, n) in zip(sorted(outs), reqs):
        one = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
        np.testing.assert_array_equal(outs[rid], one)


def _admit(srv, lengths, seed=0, new=4):
    """One round that admits prompts of these lengths; its record."""
    for p in _prompts(lengths, seed=seed):
        srv.add_request(p, new)
    srv.step()
    rec = dict(srv._phases[-1])
    _finish(srv)
    return rec


def test_an_unbuilt_bucket_runs_alone_and_builds_its_program():
    srv = _serve(_family("mistral-7b-serve"))
    _admit(srv, (20, 40))                     # builds 32 and 64
    assert sorted(srv._prefill_fns) == [32, 64]
    rec = _admit(srv, (20, 5, 70), seed=1)    # 96 is not built
    assert (rec["prefills"], rec["prefill_programs"]) == (3, 2)
    assert sorted(srv._prefill_fns) == [32, 64, 96]
    assert _prefill_counts(srv) == (5, 4, 2)
    # a row is no longer than the longest bucket built: 64 + 48 > 96
    rec = _admit(srv, (60, 37), seed=2)
    assert (rec["prefills"], rec["prefill_programs"]) == (2, 2)
    # ... and at most _SEGMENTS prompts long
    srv = _serve(_family("mistral-7b-serve"), max_seqs=6)
    _admit(srv, (90, 5))
    rec = _admit(srv, (5, 6, 7, 8, 9), seed=3)
    assert (rec["prefills"], rec["prefill_programs"]) == (5, 2)
    assert sorted(srv._prefill_fns) == [32, 96]   # four blocks ran at 96
    assert serving._SEGMENTS == 4


@pytest.mark.parametrize("kind", ["recurrent", "lora", "chunked"])
def test_what_never_shares_a_row(kind):
    """A model with a recurrent state per slot (its scans run the whole row),
    a LoRA engine (every prefill is a span with the adapter's delta) and a
    chunked prefill (a span behind rows already written)."""
    if kind == "recurrent":
        srv = _serve(_family("nemotron-3-nano-30b-serve"))
    elif kind == "lora":
        srv = _serve(_family("mistral-7b-serve"), adapter_slots=2,
                     lora_rank=4)
    else:       # every prompt longer than a round's budget
        srv = _serve(_family("mistral-7b-serve"), prefill_token_budget=48)
    _admit(srv, (60, 70, 90))
    rec = _admit(srv, (66, 75, 80), seed=1)
    assert rec["prefills"] == rec["prefill_programs"] >= 1
    prompts, programs, packed = _prefill_counts(srv)
    assert packed == 0 and prompts == programs
    if kind == "recurrent":
        assert prompts == 6
    else:
        assert prompts == 0 and srv.stats()["prefill_chunks"] >= 6
