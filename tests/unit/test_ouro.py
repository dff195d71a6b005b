"""Ouro-2.6B, a looped model, on the normal path (ISSUE 35), at toy widths on
the CPU.

The toy model keeps the published four passes over 2 layers (hidden 64, 4
heads of 16, as many K/V heads): 8 planes of cache where the published model
has 192. Everything is float32 (``conftest`` sets "highest" matmuls), so the
program and the plain reference (``benchmark/families/ouro.py``: Python loops
over passes and layers, no scan, no cache — it imports nothing from
``deepspeed_tpu``) differ by summation order only.

TOLERANCE. ``build`` scales the toy's q / k projections and head up until
attention has an opinion and the logits have sigma ~1.5. Program and
reference then agree to 4e-6 on LOGITS on every path; ``ATOL`` = 1e-4 leaves
room for another BLAS and is four orders of magnitude under what the least
of the seeded defects moves the worst logit by (1.1, printed by the defects
test): a walk of 3 passes, a norm left out between passes or after a
sublayer, or a pass that reads another pass's K/V planes fails it. The int8
pool rounds K and V to 8 bits per (position, head) in all 8 planes:
positions read 0.05-0.13 off, ``ATOL_INT8`` = 0.2, and 4 bits read over 0.4.
"""
import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.kv_cache import pool_bytes
from deepspeed_tpu.inference.serving import ResumeIncompatible
from deepspeed_tpu.models import looped, make_model
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (EarlyExitUnsupported,
                                            hf_config_to_transformer,
                                            load_hf_params)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL, ATOL_INT8 = 1e-4, 0.2

# the catalog's `config` of Ouro-2.6B, verbatim
CATALOG = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
           "max_position_embeddings": 65536, "max_window_layers": 48,
           "model_type": "ouro", "num_attention_heads": 16,
           "num_hidden_layers": 48, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
           "sliding_window": None, "tie_word_embeddings": False,
           "total_ut_steps": 4, "early_exit_threshold": 1,
           "use_sliding_window": False, "vocab_size": 49152}
TOY = dict(CATALOG, hidden_size=64, intermediate_size=96, head_dim=16,
           num_hidden_layers=2, layer_types=["full_attention"] * 2,
           max_window_layers=2, num_attention_heads=4, num_key_value_heads=4,
           vocab_size=128, max_position_embeddings=128)
PLANES = 8                                   # 4 passes x 2 layers


def _load(*path):
    sys.path.insert(0, ROOT)       # the files import benchmark.families.mistral
    spec = importlib.util.spec_from_file_location(
        "benchmark." + ".".join(path), os.path.join(ROOT, "benchmark", *path) + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAM = _load("families", "ouro")
DEFECTS = _load("tools", "ouro_defects")


# where the benchmark's configuration starts the norm scales (its
# ``run.overrides``): away from 1, so that a norm left out shows
DRAW = dict(norm_init_jitter=0.5, post_norm_init=0.1)


def build(hf=TOY, seed=0, **overrides):
    """(cfg, params) as the importer builds the model and ``init_params``
    draws it (norm scales by ``DRAW`` and the gate away from 0 / 1), with
    q / k and the head scaled up: at std 0.02 and hidden 64 every score is
    ~0 and every attention a plain mean."""
    cfg = hf_config_to_transformer(hf, max_seq_len=128, dtype=jnp.float32,
                                   param_dtype=jnp.float32,
                                   attention_impl="xla", **{**DRAW, **overrides})
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    lay = dict(params["layers"])
    for name, gain in (("wq", 8.0), ("wk", 8.0)):
        lay[name] = lay[name] * gain
    return cfg, {**params, "layers": lay, "lm_head": params["lm_head"] * 10.0}


def ids_of(n, seed=3, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def serve(cfg, params, **serving):
    """The engine takes the pool's precision from ITS config, as a user
    gives it (the model config's is what the direct calls above read)."""
    d = dict(max_seqs=3, block_size=16, max_model_len=128, decode_quantum=4,
             prompt_bucket=16, decode_backend="xla")
    d.update(serving)
    return deepspeed_tpu.init_serving(
        make_model(cfg), config={"kv_cache_bits": cfg.kv_cache_bits},
        serving=d, dtype=jnp.float32, params=jax.device_get(params))


# ---- (a) the walks against the plain reference, on LOGITS -------------------

def paged_logits(cfg, params, ids, n_prompt, span=0, bs=16):
    """Logits of positions n_prompt-1 .. len-1 through the pool: prefill the
    prompt (padded to its bucket), then teacher-forced decode steps in slot
    1 of 3 (slot 0 inactive, slot 2 decoding another prompt, so lockstep
    slots and the trash block are exercised) — or, with ``span``, the rest
    in spans of that many tokens through ``decode_span_paged``."""
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
    if span:
        step = jax.jit(lambda pools, tok, lens: T.decode_span_paged(
            params, tok, cfg, pools, jnp.asarray(tables), lens, active=active))
        for t in range(n_prompt, len(ids), span):
            tok = np.zeros((3, span), np.int32)
            tok[1], tok[2] = ids[t:t + span], other[:span]
            lens = jnp.asarray([0, t, bs + t - n_prompt], jnp.int32)
            logits, pools = step(pools, jnp.asarray(tok), lens)
            out.extend(np.asarray(logits[1]))
        return np.stack(out), pools
    step = jax.jit(lambda pools, tok, lens: T.decode_step_paged(
        params, tok, cfg, pools, jnp.asarray(tables), lens, active=active))
    for t in range(n_prompt, len(ids)):
        tok = jnp.asarray([0, ids[t], other[0]], jnp.int32)
        lens = jnp.asarray([0, t, bs + t - n_prompt], jnp.int32)
        logits, pools = step(pools, tok, lens)
        out.append(np.asarray(logits[1]))
    return np.stack(out), pools


def contiguous_logits(cfg, params, ids, n_prompt):
    """The same positions through ``prefill`` / ``decode_step`` over the
    contiguous cache ``generate`` uses."""
    cache = T.init_cache(cfg, 1, 64)
    assert cache["k"].shape[0] == PLANES
    buf = np.zeros((1, 32), np.int32)
    buf[0, :n_prompt] = ids[:n_prompt]
    last, cache = T.prefill(params, jnp.asarray(buf), cfg, cache, length=n_prompt)
    out = [np.asarray(last[0])]
    step = jax.jit(lambda tok, cache: T.decode_step(params, tok, cfg, cache))
    for t in range(n_prompt, len(ids)):
        logits, cache = step(jnp.asarray(ids[t:t + 1]), cache)
        out.append(np.asarray(logits[0]))
    return np.stack(out)


PATHS = {
    "forward": lambda cfg, p, ids, n: np.asarray(
        T.forward(p, jnp.asarray(ids)[None], cfg)[0])[n - 1:],
    "prefill+decode_step": contiguous_logits,
    "prefill_paged+decode_step_paged": lambda *a: paged_logits(*a)[0],
    "prefill_paged+decode_span_paged": lambda *a: paged_logits(*a, span=4)[0],
}


@pytest.mark.parametrize("path", PATHS)
def test_logits_match_the_plain_reference(path):
    cfg, params = build()
    ids, n_prompt = ids_of(29), 21
    want = FAM.Reference(TOY, params).logits(ids, pad_to=16)
    assert 1.0 < want.std() < 3.0                     # logits with an opinion
    got = PATHS[path](cfg, params, ids, n_prompt)
    err = np.abs(got - want[n_prompt - 1:]).max()
    print(f"{path}: max |logit error| {err:.2e}")
    assert err <= ATOL


@pytest.mark.parametrize("span", [0, 4], ids=["decode_step", "decode_span"])
def test_int8_pool_logits_stay_close_and_the_leaves_have_a_plane_per_pass(span):
    cfg, params = build(kv_cache_bits=8)
    ids, n_prompt = ids_of(29), 21
    want = FAM.Reference(TOY, params).logits(ids, pad_to=16)[n_prompt - 1:]
    got, pools = paged_logits(cfg, params, ids, n_prompt, span=span)
    err = np.abs(got - want).max(axis=-1)
    print(f"int8 pool: per-position max |logit error| {np.sort(err)}")
    assert err.max() <= ATOL_INT8
    assert {k: v.shape[0] for k, v in pools.items()} == dict.fromkeys(
        ("k", "v", "k_scale", "v_scale"), PLANES)


@pytest.mark.parametrize("defect", ["three_passes", "no_between_pass_norm",
                                    "no_sandwich_norm", "shared_kv_planes"])
@pytest.mark.parametrize("path", ["forward", "prefill_paged+decode_step_paged"])
def test_each_seeded_defect_fails_the_comparison(defect, path):
    """The program against a reference that carries one defect (the
    comparison is symmetric): the worst logit moves by far more than the
    tolerance, on the full forward and through the pool."""
    cfg, params = build()
    ids, n_prompt = ids_of(29), 21
    wrong = DEFECTS.variants(FAM, TOY, params)[defect].logits(ids, pad_to=16)
    err = np.abs(PATHS[path](cfg, params, ids, n_prompt)
                 - wrong[n_prompt - 1:]).max()
    print(f"{defect} via {path}: max |logit error| {err:.3f} "
          f"({err / ATOL:.0f} x the tolerance)")
    assert err > 1000 * ATOL


def test_kv_4bit_moves_the_logits_past_the_int8_tolerance():
    cfg, params = build()
    ids = ids_of(29)
    refs = DEFECTS.variants(FAM, TOY, params)
    err = np.abs(refs["kv_4bit"].logits(ids, pad_to=16)
                 - refs["plain"].logits(ids, pad_to=16)).max()
    assert err > 2 * ATOL_INT8, err


# ---- (b) one property sizes every cache --------------------------------------

@pytest.mark.parametrize("bits", [0, 8])
def test_pool_bytes_are_the_leaves_bytes(bits):
    cfg, _ = build(kv_cache_bits=bits)
    assert (cfg.ut_steps, cfg.attention_blocks, cfg.kv_planes) == (4, 2, PLANES)
    pools = T.init_paged_cache(cfg, 7, 16)
    assert all(a.shape[0] == PLANES for a in pools.values())
    leaves = sum(a.size * a.dtype.itemsize for a in pools.values())
    assert pool_bytes(cfg, 7, 16, dtype=jnp.float32) == leaves
    srv = serve(cfg, build(kv_cache_bits=bits)[1])
    st = srv.stats()
    assert srv.pool_bytes_logical == sum(
        a.size * a.dtype.itemsize for a in srv.pools.values())
    assert (st["ut_steps"], st["kv_planes"]) == (4.0, float(PLANES))
    # one token over all planes: K and V, 4 heads of 16 (+ a scale a head)
    assert st["kv_bytes_per_token"] == PLANES * 2 * 4 * (16 + 4 if bits else 16 * 4)
    srv.close()


def test_the_published_config_keeps_192_planes_and_811008_bytes_a_token():
    cfg = hf_config_to_transformer(CATALOG, max_seq_len=1280, kv_cache_bits=8)
    assert (cfg.num_layers, cfg.ut_steps, cfg.kv_planes) == (48, 4, 192)
    assert cfg.sandwich_norm and cfg.exit_gate and not cfg.tie_embeddings
    assert (cfg.num_heads, cfg.kv_heads, cfg.dim_per_head, cfg.ffn_dim,
            cfg.rope_theta, cfg.norm_eps) == (16, 16, 128, 5632, 1e6, 1e-6)
    assert pool_bytes(cfg, 1, 1) == 811_008
    assert pool_bytes(cfg, 161, 64) == 161 * 64 * 811_008
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 2_667_974_657 == FAM.stored_params(CATALOG)


def test_the_initialiser_starts_norm_scales_at_one_unless_the_config_says():
    """``init_params`` is the program's, not the benchmark's: a looped model
    starts its norms at 1 like every other family, and the two initialiser
    fields (which the benchmark's configuration sets) move the norm scales
    and nothing else."""
    plain_cfg = hf_config_to_transformer(TOY, max_seq_len=128)
    plain = T.init_params(jax.random.PRNGKey(0), plain_cfg)
    drawn = T.init_params(jax.random.PRNGKey(0),
                          hf_config_to_transformer(TOY, max_seq_len=128, **DRAW))
    norms = {"ln1_scale", "ln2_scale", "ln1_post_scale", "ln2_post_scale"}
    for name in norms:
        assert np.all(np.asarray(plain["layers"][name], np.float32) == 1.0), name
        got = np.asarray(drawn["layers"][name], np.float32)
        lo, hi = (0.05, 0.15) if "post" in name else (0.5, 1.5)
        assert lo <= got.min() < got.max() <= hi + 1e-3 and got.std() > 0.2 * lo, name
    assert np.all(np.asarray(plain["final_norm_scale"], np.float32) == 1.0)
    assert 0.5 <= float(drawn["final_norm_scale"].min()) < float(drawn["final_norm_scale"].max())
    same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)), plain, drawn)
    assert all(ok for path, ok in jax.tree_util.tree_leaves_with_path(same)
               if not any(n in jax.tree_util.keystr(path) for n in norms | {"final_norm_scale"}))
    # and a family without the fields' use draws what it drew before them
    base = T.TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2)
    for a in jax.tree.leaves(T.init_params(jax.random.PRNGKey(0), base)["layers"]["ln1_scale"]):
        assert np.all(np.asarray(a) == 1.0)


# ---- (c) everything that shares, resumes or ships a request's blocks ---------

def _requests(n=6, prefix=50, tail=5):
    """A shared prefix that ends inside a block (50 = 3 blocks of 16 + 2
    rows): a later tenant shares the donor's fourth block and forks it."""
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 128, prefix).astype(np.int32)
    return [(np.concatenate([shared, rng.integers(0, 128, tail).astype(np.int32)]),
             8 + i % 3) for i in range(n)]


ASSISTS = {
    "prefix-cache+fork": (dict(enable_prefix_cache=True),
                          lambda st: st["prefix_hits"] >= 2 and st["cow_forks"] >= 1),
    "chunked-prefill": (dict(prefill_token_budget=16),
                        lambda st: st["prefill_chunks"] >= 4),
    "speculation": (dict(spec_tokens=3), lambda st: st["spec_steps"] >= 1),
}


@pytest.mark.parametrize("bits", [0, 8], ids=["float-pool", "int8-pool"])
@pytest.mark.parametrize("assist", ASSISTS)
def test_assisted_serving_gives_the_unassisted_tokens(assist, bits):
    """A block carries all 8 planes of its 16 tokens, so a shared prefix, a
    prompt chunk appended behind rows in the pool, and a verify span that is
    partly rolled back work on the looped model as on any other."""
    cfg, params = build(kv_cache_bits=bits)
    reqs = _requests()
    plain = serve(cfg, params).run(list(reqs))
    options, happened = ASSISTS[assist]
    srv = serve(cfg, params, **options)
    got = srv.run(list(reqs))
    assert happened(srv.stats()), srv.stats()
    if bits:
        # a span reads its own fresh rows as floats where steps re-read
        # them from the int8 pool, and a chunk reads the earlier chunks'
        # rows from the int8 pool where a whole prompt reads them as floats
        # (``_paged_span_attention``): near-ties may flip; the float pool
        # is held to equality
        same = np.mean([np.mean(plain[r] == got[r]) for r in plain])
        assert same >= 0.9, same
    else:
        for rid in plain:
            np.testing.assert_array_equal(plain[rid], got[rid])
    srv.close()


def _prefill_all(srv, reqs):
    rids = [srv.add_request(p, max_new_tokens=k) for p, k in reqs]
    for _ in range(100):
        srv.step()
        live = {r.rid: r for r in srv.scheduler.running}
        if all(rid in live and live[rid].prefill_done and live[rid].generated
               for rid in rids):
            return rids
    raise AssertionError("prefill never completed")


def _run_to_done(srv, rids):
    outs = {}
    for _ in range(200):
        for r in srv.step():
            outs[r.rid] = r.output
        if set(outs) >= set(rids):
            return outs
    raise AssertionError("requests never finished")


def test_export_then_import_continues_with_the_colocated_tokens():
    cfg, params = build()
    reqs = _requests(2)
    plain = serve(cfg, params).run(list(reqs))
    src = serve(cfg, params, role="prefill")
    dst = serve(cfg, params, role="decode")
    rids = _prefill_all(src, reqs)
    payloads = src.export_kv(rids)
    for pl in payloads.values():
        assert pl["data"]["k"].shape[0] == PLANES
        assert (pl["geometry"]["kv_planes"], pl["geometry"]["num_layers"],
                pl["geometry"]["ut_steps"]) == (PLANES, 2, 4)
    dst.accept_migration(src.release_requests(rids), source="src", kv=payloads)
    outs = _run_to_done(dst, rids)
    assert dst.stats()["handoffs"] == len(rids)          # shipped, not re-prefilled
    assert dst.stats()["handoff_fallbacks"] == 0
    for rid, want in zip(rids, plain.values()):
        np.testing.assert_array_equal(outs[rid], want)
    src.close(), dst.close()


def test_a_handoff_between_a_looped_and_an_unlooped_engine_is_refused():
    """8 planes on both sides — 4 passes x 2 layers here, 1 pass x 8 layers
    there — and every width equal: the payload's arrays fit, and only the
    geometry says that plane 5 is not layer 5."""
    cfg, params = build()
    flat = dataclasses.replace(cfg, num_layers=PLANES, ut_steps=1)
    assert flat.kv_planes == cfg.kv_planes
    src = serve(cfg, params, role="prefill")
    dst = deepspeed_tpu.init_serving(
        make_model(flat), config={}, dtype=jnp.float32,
        rng=jax.random.PRNGKey(0),
        serving=dict(max_seqs=3, block_size=16, max_model_len=128,
                     decode_quantum=4, prompt_bucket=16, decode_backend="xla",
                     role="decode"))
    assert {k: v.shape for k, v in src.pools.items()} \
        == {k: v.shape for k, v in dst.pools.items()}
    rids = _prefill_all(src, _requests(1))
    payloads = src.export_kv(rids)
    with pytest.raises(ResumeIncompatible, match="num_layers|ut_steps"):
        dst.accept_migration(src.release_requests(rids), source="src",
                             kv=payloads)
    src.close(), dst.close()


# ---- (d) what is refused ------------------------------------------------------

def test_early_exit_below_one_is_refused_with_its_own_error():
    with pytest.raises(EarlyExitUnsupported, match="K/V") as e:
        hf_config_to_transformer(dict(TOY, early_exit_threshold=0.9))
    assert e.value.threshold == 0.9 and isinstance(e.value, NotImplementedError)
    assert hf_config_to_transformer(dict(TOY, early_exit_threshold=1.0)).ut_steps == 4


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["full_attention", "sliding_attention"]),
    ("use_sliding_window", True),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("hidden_act", "gelu")])
def test_unsupported_config_keys_raise(key, value):
    with pytest.raises(ValueError, match=key):
        hf_config_to_transformer(dict(TOY, **{key: value}))


def test_the_suffix_decode_refuses_a_looped_model_and_generate_goes_without():
    """``decode_step_suffix`` unrolls its layers and its buffers have a plane
    a layer: called on a looped model it raises; ``make_model`` offers no
    suffix protocol, so ``generate`` decodes through ``decode_step`` over a
    cache with a plane per pass — and gives the reference's greedy tokens."""
    cfg, params = build()
    with pytest.raises(looped.LoopedModelUnsupported, match="suffix"):
        T.init_suffix(cfg, 1, 8)
    with pytest.raises(looped.LoopedModelUnsupported, match="suffix"):
        T.decode_step_suffix(params, jnp.zeros((1,), jnp.int32), cfg,
                             T.init_cache(cfg, 1, 32), None)
    model = make_model(cfg)
    assert (model.init_suffix, model.decode_step_suffix, model.merge_suffix) \
        == (None, None, None)
    eng = deepspeed_tpu.init_inference(model, config={}, dtype=jnp.float32,
                                       params=jax.device_get(params))
    prompt = ids_of(11)
    out = np.asarray(eng.generate(prompt[None], max_new_tokens=6))[0]
    ref = FAM.Reference(TOY, params)
    ids = list(prompt)
    for _ in range(6):
        ids.append(int(ref.logits(np.asarray(ids), pad_to=16)[-1].argmax()))
    np.testing.assert_array_equal(out[-6:], ids[-6:])


@pytest.mark.parametrize("overrides,error", [
    (dict(scan_layers=False), looped.LoopedModelUnsupported),
    (dict(block_pattern="M*"), looped.LoopedModelUnsupported),
    (dict(final_norm=False), ValueError),
    (dict(norm_style="post"), ValueError)])
def test_make_model_refuses_what_no_walk_computes(overrides, error):
    cfg, _ = build()
    with pytest.raises(error):
        make_model(dataclasses.replace(cfg, **overrides))


# ---- (e) the exit gate leaves the program as a counter -----------------------

def test_exit_counters_ride_the_rounds_one_fetch_and_equal_the_reference(monkeypatch):
    """The decode step's and the prefill's outputs grow by one float32
    [passes + 1] array beside the tokens; a round still makes exactly ONE
    ``jax.device_get``; ``stats()`` gives the mean exit distribution over
    every sampled position, which is the reference's over the same
    positions."""
    cfg, params = build()
    srv = serve(cfg, params, max_seqs=4)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)   # noqa: E731
    out = jax.eval_shape(srv._quantum_step_fn().__wrapped__, srv.engine.params,
                         srv.pools, i32(4), i32(4, srv.MB), i32(4),
                         jax.ShapeDtypeStruct((4,), jnp.bool_), key)
    load, exits = out[1][1]
    assert load is None and (exits.shape, exits.dtype) == ((5,), jnp.float32)
    out = jax.eval_shape(srv._get_prefill_fn(16).__wrapped__, srv.engine.params,
                         i32(1, 16), srv.pools, i32(1), i32(4), i32(4), key)
    assert out[0][1][0] is None and out[0][1][1].shape == (5,)
    assert [t.shape for t in out[0][0]] == [()] * 4    # a token a segment

    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: gets.append(1) or real_get(x))
    # 1 token from the prefill + 2 quanta of 4 steps: no step past the end
    prompts = [ids_of(n, seed=n) for n in (5, 17, 40)]
    rids = [srv.add_request(p, 9) for p in prompts]
    done = {}
    while srv.scheduler.running or srv.scheduler.num_waiting:
        before = len(gets)
        done.update({r.rid: r.output for r in srv.step()})
        assert len(gets) - before == 1
    st = srv.stats()
    ref = FAM.Reference(TOY, params)
    p = np.concatenate([
        ref.exit_distribution(np.asarray(done[rid])[:-1], pad_to=16)[len(prompt) - 1:]
        for rid, prompt in zip(rids, prompts)])           # [27 positions, 4]
    assert p.shape == (3 * 9, 4)
    np.testing.assert_allclose(st["exit_cdf"], np.cumsum(p.mean(axis=0)), atol=1e-5)
    np.testing.assert_allclose(st["exit_step_expected"],
                               (p * np.arange(1, 5)).sum(axis=1).mean(), atol=1e-5)
    assert 1.0 < st["exit_step_expected"] < 4.0 and st["exit_cdf"][-1] == pytest.approx(1.0)
    srv.reset_stats()
    assert "exit_step_expected" not in srv.stats() and srv.stats()["ut_steps"] == 4.0
    srv.close()


def test_no_tap_no_gate_and_two_threads_taps_do_not_meet():
    cfg, params = build()
    text = jax.jit(lambda p, ids: T.forward(p, ids, cfg)).lower(
        params, jnp.zeros((1, 16), jnp.int32)).as_text(debug_info=True)
    assert "exit_gate" not in text and "passes" in text
    import threading
    seen = []
    with looped.exit_tap() as mine:
        t = threading.Thread(target=lambda: seen.append(looped.gate(
            jnp.zeros((2, 64)), params)))
        t.start(), t.join()
        assert seen == [None]                    # the other thread has no tap
        assert looped.gate(jnp.zeros((2, 64)), params).shape == (2,)
    assert mine.summed() is None                 # nothing was walked inside


# ---- (f) the published names, and every other family as it was ----------------

def test_the_weight_table_round_trips_the_published_names():
    cfg, params = build()
    host = jax.device_get(params)
    sd = {"model.embed_tokens.weight": host["tok_embed"],
          "model.norm.weight": host["final_norm_scale"],
          "lm_head.weight": host["lm_head"].T,
          "model.early_exit_gate.weight": host["exit_gate_w"].T,
          "model.early_exit_gate.bias": host["exit_gate_b"]}
    names = {"input_layernorm": "ln1_scale", "input_layernorm_2": "ln1_post_scale",
             "post_attention_layernorm": "ln2_scale",
             "post_attention_layernorm_2": "ln2_post_scale"}
    mats = {"self_attn.q_proj": "wq", "self_attn.k_proj": "wk",
            "self_attn.v_proj": "wv", "self_attn.o_proj": "wo",
            "mlp.gate_proj": "w_gate", "mlp.up_proj": "w_in",
            "mlp.down_proj": "w_out"}
    for i in range(2):
        for hf_name, ours in names.items():
            sd[f"model.layers.{i}.{hf_name}.weight"] = host["layers"][ours][i]
        for hf_name, ours in mats.items():
            sd[f"model.layers.{i}.{hf_name}.weight"] = host["layers"][ours][i].T
    back = load_hf_params(sd, cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(host)
    got = dict(jax.tree_util.tree_flatten_with_path(back)[0])
    assert set(got) == {k for k, _ in flat}
    for k, v in flat:
        np.testing.assert_array_equal(np.asarray(got[k]), v, err_msg=str(k))


def _plain_cfg(**kw):
    return T.TransformerConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=128, position_type="rotary",
        activation="silu_glu", norm_type="rmsnorm", tie_embeddings=False,
        dtype=jnp.float32, attention_impl="xla"), **kw})


@pytest.mark.parametrize("family", ["mistral", "olmoe"])
def test_an_unlooped_family_walks_its_stack_once_and_serves_its_reference_tokens(family):
    """ut_steps = 1 is the plain layer scan: ONE loop in the step's lowered
    text, no pass scope, no gate, the final norm where it was (under
    ``lm_head``) — and the tokens of the family's own plain reference."""
    moe = dict(num_experts=8, top_k=2, drop_tokens=False) if family == "olmoe" else {}
    cfg = _plain_cfg(**moe, **({"qk_norm": True, "norm_topk_prob": False,
                                "num_kv_heads": 4} if moe else {}))
    assert cfg.ut_steps == 1 and cfg.kv_planes == cfg.num_layers
    srv = deepspeed_tpu.init_serving(
        make_model(cfg), config={}, dtype=jnp.float32, rng=jax.random.PRNGKey(1),
        serving=dict(max_seqs=2, block_size=16, max_model_len=128,
                     decode_quantum=4, prompt_bucket=16, decode_backend="xla"))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)   # noqa: E731
    text = jax.jit(srv._quantum_step_fn().__wrapped__).lower(
        srv.engine.params, srv.pools, i32(2), i32(2, srv.MB), i32(2),
        jax.ShapeDtypeStruct((2,), jnp.bool_),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    assert text.count("stablehlo.while") == 1
    assert "passes" not in text and "exit_gate" not in text
    assert "jit(step)/layers/" in text and "jit(step)/lm_head/" in text
    assert "ut_steps" not in srv.stats()
    prompt = ids_of(13)
    out = list(srv.run([(prompt, 7)]).values())[0]
    hf = {"hidden_size": 64, "num_attention_heads": 4, "rms_norm_eps": 1e-5,
          "num_key_value_heads": cfg.kv_heads, "rope_theta": 10000.0,
          "num_experts_per_tok": 2, "norm_topk_prob": False}
    ref = _load("families", family).Reference(hf, srv.engine.params)
    ids = list(prompt)
    for _ in range(7):
        ids.append(int(ref.logits(np.asarray(ids), pad_to=16)[-1].argmax()))
    np.testing.assert_array_equal(out[-7:], ids[-7:])
    srv.close()
