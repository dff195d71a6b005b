"""Falcon-H1 (``model_type`` ``falcon_h1``) on the normal serving path, at toy
widths on the CPU in float32, held to the family's plain reference
(``benchmark/families/falcon_h1.py``) on LOGITS:

- the system's no-cache forward, and prefill-then-decode through the paged
  functions the serving engine calls — a ``P`` block owns a K/V plane of the
  block pool AND a layer of the per-slot state pool, in every layer — with a
  padded bucket, an idle slot, a slot given again and two requests of
  different lengths in one batch;
- leaving out any of the fourteen muP multipliers, either branch of the
  block, rotary, the convolution's bias or ``D`` fails that comparison;
- both ``ops/ssm.py`` kernels in interpret mode at the published head shape
  (32 heads of 128, state 256, 2 groups) against a sequential scan;
- ``hf_config_to_transformer`` on the catalog's config verbatim, its typed
  refusals, and what a model with a recurrent state still refuses.

TOL 2e-4: toy logits have std 1 and reach 4.4; the sound paths read 4e-6
(forward) to 3e-5 (sixty positions through the pools) against the reference
here, the gentlest single omission (``ssm_multipliers[3]``, the one on C)
0.46, two thousand times the tolerance.
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
from benchmark.families import falcon_h1 as fam  # noqa: E402
from deepspeed_tpu.inference import SlotStateUnsupported  # noqa: E402
from deepspeed_tpu.models import hybrid, make_model  # noqa: E402
from deepspeed_tpu.models.hf_import import (  # noqa: E402
    FalconH1Unsupported, hf_config_to_transformer)
from deepspeed_tpu.ops import ssm  # noqa: E402

# the Mamba-2 cell's driver of the paged functions (three slots of eight
# 16-token blocks, bucket 16) and its sequential scan serve this family as
# they are
from tests.unit.test_nemotron_h import (BS, BUCKET, MB, SLOTS, Paged,  # noqa: E402
                                        _sequential)

TOL = 2e-4
# what the benchmark's configuration draws too (its `run.overrides`): every
# norm scale away from 1
DRAW = {"norm_init_jitter": 0.5}


def _published():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            return next(json.loads(ln) for ln in f
                        if "Falcon-H1-34B-Instruct" in ln)["config"]
    from benchmark.harness import common
    cfg = common.load_config("falcon-h1-34b-serve")
    return dict(common.hf_of(cfg), num_hidden_layers=72)


HF = dict(_published(), **fam.TOY, max_position_embeddings=512)


@pytest.fixture(scope="module")
def toy():
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32, **DRAW)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    return cfg, model, params, fam.Reference(HF, params)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], n)


def _ref_tail(ref, prompt, generated):
    lg = ref.logits(np.concatenate([prompt, generated]), pad_to=64)
    return lg[len(prompt) - 1:]


# ---- against the reference -------------------------------------------------

def test_forward_matches_the_reference(toy):
    _, model, params, ref = toy
    ids = _ids(70)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None])[0])
    assert np.abs(got - ref.logits(ids, pad_to=64)).max() < TOL


def test_a_block_owns_a_plane_and_a_state_layer(toy):
    cfg, model, _, _ = toy
    L = HF["num_hidden_layers"]
    assert cfg.block_pattern == "PD" * L
    assert (cfg.recurrent_blocks, cfg.attention_blocks, cfg.kv_planes) \
        == (L, L, L)
    assert model.slot_leaves == ("ssm", "conv")
    pools = jax.eval_shape(lambda: model.init_paged_cache(
        9, BS, dtype=jnp.float32, max_seqs=SLOTS))
    assert pools["k"].shape == (L, 9, BS, 4, 32)
    assert pools["ssm"].shape == (L, SLOTS, 8, 16, 32)
    assert pools["conv"].shape == (L, SLOTS, 3, 128 + 2 * 2 * 32)
    assert [hybrid.plane_of(cfg, "par", j) for j in range(L)] == list(range(L))
    assert [hybrid.state_layer(cfg, "par", j) for j in range(L)] \
        == list(range(L))
    assert hybrid.period(cfg) == (cfg.block_pattern, 1)     # unrolled


def test_prefill_then_decode_matches_the_reference(toy):
    """A prompt of 19 in a bucket of 32 (thirteen pad rows), then a dozen
    steps through both pools (forty: the ``served`` fixture below)."""
    _, model, params, ref = toy
    prompt, gen = _ids(19, 1), _ids(12, 2)
    got = Paged(model, params).run(1, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


@pytest.mark.parametrize("n", [1, 3, 15, 16, 17, 31, 33, 47])
def test_a_padded_bucket_moves_neither_state_nor_tail(toy, n):
    """Prompt lengths around the bucket (= the chunk): the pad rows leave the
    state and the convolution tail as the true rows left them, and their K/V
    rows are never seen."""
    _, model, params, ref = toy
    prompt, gen = _ids(n, 10 + n), _ids(5, 99)
    got = Paged(model, params).run(0, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


def test_a_slot_given_again_carries_nothing_of_the_last_request(toy):
    _, model, params, ref = toy
    pg = Paged(model, params)
    pg.run(2, _ids(30, 5), _ids(6, 6))
    assert float(jnp.abs(pg.pools["ssm"][:, 2]).max()) > 0
    prompt, gen = _ids(9, 7), _ids(6, 8)
    got = pg.run(2, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL


def test_requests_of_different_lengths_in_one_batch(toy):
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


def test_an_idle_slot_keeps_its_state_and_its_rows(toy):
    _, model, params, ref = toy
    pg = Paged(model, params)
    prompt, gen = _ids(11, 40), _ids(4, 41)
    got = [pg.prefill(0, prompt)]
    pg.prefill(1, _ids(20, 42))
    for t in gen:
        before = {k: np.asarray(pg.pools[k][:, 0]) for k in ("ssm", "conv")}
        pg.step({1: 5})                       # slot 0 idles through a step
        for k, a in before.items():
            assert np.array_equal(a, np.asarray(pg.pools[k][:, 0])), k
        got.append(pg.step({0: int(t)})[0])
    assert np.abs(np.stack(got) - _ref_tail(ref, prompt, gen)).max() < TOL


# ---- an int8 pool of four K/V heads is stored head-major -------------------

def test_an_int8_pool_of_four_heads_is_stored_head_major(toy, monkeypatch):
    """``hybrid.blocks_head_major``: ``k`` / ``v`` [planes, NB, heads, block,
    hd], read and written through their token-major view. Where the bytes lie
    changes no value: prefill, forty steps and a slot given again read the
    SAME logits, bit for bit, as the pool declared token-major — and the
    int8 rows, queries and probabilities move them by 0.10 against the
    float32 reference (logits of std 1; held to 0.3: a wrong plane, head or
    row reads 1 and more, as leaving out rotary does)."""
    import dataclasses
    cfg, _, params, ref = toy
    cfg8 = dataclasses.replace(cfg, kv_cache_bits=8)
    assert hybrid.blocks_head_major(cfg8) and not hybrid.blocks_head_major(cfg)
    # the rule reads the pool's dtype and heads and whether a block's bytes
    # ever leave the pool (a state a slot beside them: never), no letter
    for heads in (1, 2, 8, 16):
        assert not hybrid.blocks_head_major(
            dataclasses.replace(cfg8, num_kv_heads=heads, num_heads=heads * 5))
    L = HF["num_hidden_layers"]
    assert hybrid.blocks_head_major(dataclasses.replace(
        cfg8, block_pattern="M*" * L))
    assert not hybrid.blocks_head_major(dataclasses.replace(
        cfg8, block_pattern="*D" * L))

    def run():
        model = make_model(cfg8)
        pg = Paged(model, params)
        first = pg.run(1, _ids(30, 5), _ids(6, 6))
        return pg, first, pg.run(1, _ids(21, 1), _ids(40, 2))

    pg, _, got = run()
    assert pg.pools["k"].shape == (L, SLOTS * MB + 1, 4, BS, 32)
    assert pg.pools["k"].dtype == jnp.int8
    assert pg.pools["k_scale"].shape == (L, SLOTS * MB + 1, 4 * BS)
    monkeypatch.setattr(hybrid, "blocks_head_major", lambda cfg: False)
    flat, _, want = run()
    assert flat.pools["k"].shape == (L, SLOTS * MB + 1, BS, 4, 32)
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(pg.pools["k"]).swapaxes(2, 3),
                          np.asarray(flat.pools["k"]))
    err = np.abs(got - _ref_tail(ref, _ids(21, 1), _ids(40, 2))).max()
    assert TOL < err < 0.3, err


# ---- every part of the layer matters ---------------------------------------

OMISSIONS = [("multiplier", key, i) for key, i in fam.MULTIPLIERS] + [
    ("defect", d, None) for d in ("no_ssm_branch", "no_attn_branch",
                                  "no_rotary", "no_conv_bias", "no_D",
                                  "gate_after_norm", "state_not_zeroed",
                                  "kv_4bit", "precision_below")]


@pytest.fixture(scope="module")
def served(toy):
    """A prompt of 21 in a bucket of 32 and forty steps through both pools,
    ONCE for every case below: (prompt, generated, the paged path's logits),
    held to the plain reference to a quarter of TOL."""
    _, model, params, ref = toy
    prompt, gen = _ids(21, 1), _ids(40, 2)
    got = Paged(model, params).run(1, prompt, gen)
    assert np.abs(got - _ref_tail(ref, prompt, gen)).max() < TOL / 4
    return prompt, gen, got


@pytest.mark.parametrize(
    "how,what,index", OMISSIONS,
    ids=[f"{w}{'' if i is None else i}" for _, w, i in OMISSIONS])
def test_leaving_it_out_moves_the_logits(toy, served, how, what, index):
    """The paged path agrees with the plain reference to a quarter of TOL; a
    reference with one multiplier at 1, one branch, rotary, the convolution's
    bias or ``D`` left out, the gate after the norm, the state not zeroed, or
    the whole forward one precision down, differs by more than TOL (the
    least: 0.14 for a state not zeroed, seven hundred tolerances)."""
    params = toy[2]
    prompt, gen, got = served
    bad = (fam.Reference(fam.without_multiplier(HF, what, index), params)
           if how == "multiplier" else fam.Reference(HF, params, defect=what))
    assert np.abs(got - _ref_tail(bad, prompt, gen)).max() > TOL


def test_a_bf16_state_is_seen_on_logits(toy, served):
    """Rounding the float32 state to bf16 moves these toy logits by 1.6e-3,
    thirty times what the sound path reads here (under 5e-5)."""
    prompt, gen, got = served
    bad = fam.Reference(HF, toy[2], defect="bf16_state")
    assert np.abs(got - _ref_tail(bad, prompt, gen)).max() > 5e-4


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


def test_serving_reserves_both_kinds_of_state_for_a_request(toy):
    """More requests than slots through ``init_serving``: every output is the
    reference's greedy continuation, and the engine reports what it holds a
    token and a slot — which the family's cost model reproduces."""
    _, model, params, ref = toy
    srv = _serve(model, params)
    reqs = [(_ids(n, 50 + n), m) for n, m in ((5, 7), (17, 9), (33, 5), (3, 6))]
    outs = srv.run(reqs)
    for (p, m), rid in zip(reqs, sorted(outs)):
        assert list(np.asarray(outs[rid])[-m:]) == _greedy(ref, p, m)
    st = srv.stats()
    L = HF["num_hidden_layers"]
    assert st["state_slots_live"] == 0
    assert st["state_pool_bytes"] + st["kv_pool_bytes"] == st["pool_bytes"]
    assert st["state_bytes_per_slot"] == L * (8 * 16 * 32 * 4 + 3 * 256 * 4) \
        == fam.state_bytes_per_slot(HF, itemsize=4)
    assert st["kv_bytes_per_token"] == L * 2 * 4 * 32 * 4 \
        == 2 * fam.kv_bytes_per_token(HF, 0)      # (a float32 pool)
    assert srv.state_pool_dtype == "float32"
    assert srv.pools["k"].shape[0] == L and srv.pools["ssm"].shape[:2] == (L, 2)
    assert (srv.model.config.recurrent_blocks,
            srv.model.config.attention_blocks) == (L, L)
    srv.close()


def test_serving_preemption_rebuilds_both(toy):
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
])
def test_still_refused_at_init_serving(toy, serving, what):
    _, model, params, _ = toy
    with pytest.raises(SlotStateUnsupported, match=what):
        _serve(model, params, **serving)


def test_still_refused_at_the_call(toy):
    _, model, params, _ = toy
    srv = _serve(model, params)
    rid = srv.add_request(_ids(5), 40)
    srv.step()
    with pytest.raises(SlotStateUnsupported, match="export"):
        srv.export_kv([rid])
    with pytest.raises(SlotStateUnsupported, match="fork"):
        srv._dispatch_fork(srv.scheduler.running[0])
    assert model.decode_span_paged is None
    srv.close()


def test_the_two_branches_scopes_reach_the_lowered_text(toy):
    """``mix/ssm`` and ``mix/attn`` around a ``P`` block's branches, the
    mixers' own scopes inside them."""
    import re
    _, model, params, _ = toy
    pools = jax.eval_shape(lambda: model.init_paged_cache(
        9, BS, dtype=jnp.float32, max_seqs=2))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)       # noqa: E731
    step = jax.jit(model.decode_step_paged).lower(
        params, i32(2), pools, i32(2, 4), i32(2)).as_text(debug_info=True)
    prefill = jax.jit(model.prefill_paged).lower(
        params, i32(1, 32), pools, i32(2), length=i32(), slot=i32()
    ).as_text(debug_info=True)
    for text, scopes in ((step, ("mix/ssm/ssm/step", "mix/ssm/ssm/conv",
                                 "mix/attn/attn")),
                         (prefill, ("mix/ssm/ssm/scan",
                                    "mix/ssm/ssm/state_write",
                                    "mix/attn/attn"))):
        for scope in scopes:
            assert re.search(rf'/layer\d/{scope}[/"]', text), scope


# ---- the import -------------------------------------------------------------

def test_import_of_the_published_config():
    hf = _published()
    cfg = hf_config_to_transformer(hf)
    kinds = [k for k, _ in hybrid.blocks(cfg)]
    assert kinds == ["par", "dense"] * 72
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.dim_per_head,
            cfg.ffn_dim, cfg.vocab_size) == (5120, 20, 4, 128, 21504, 261120)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.ssm_state_size, cfg.conv_kernel, cfg.mamba_chunk) \
        == (32, 128, 2, 256, 4, 128)
    assert (cfg.position_type, cfg.rope_theta, cfg.activation,
            cfg.tie_embeddings, cfg.norm_eps) \
        == ("rotary", 1e11, "silu_glu", False, 1e-5)
    assert (cfg.embed_scale, cfg.lm_head_multiplier,
            cfg.attention_in_multiplier, cfg.attention_out_multiplier,
            cfg.key_multiplier, cfg.ssm_in_multiplier, cfg.ssm_out_multiplier) \
        == tuple(hf[k] for k in (
            "embedding_multiplier", "lm_head_multiplier",
            "attention_in_multiplier", "attention_out_multiplier",
            "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier"))
    assert cfg.ssm_multipliers == tuple(hf["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(hf["mlp_multipliers"])
    assert (cfg.recurrent_blocks, cfg.attention_blocks, cfg.kv_planes) \
        == (72, 72, 72)
    # the stored tree at the published widths (shapes only): 33.64 B, which
    # the family's count reproduces to the parameter
    params = jax.eval_shape(make_model(cfg).init, jax.random.PRNGKey(0))
    stored = sum(x.size for x in jax.tree.leaves(params))
    assert stored == fam.param_count(hf)
    assert round(stored / 1e9, 2) == 33.64
    assert (fam.block_params(hf, "attn"), fam.block_params(hf, "dense")) \
        == (31_457_280, 330_301_440)


@pytest.mark.parametrize("key,value", [
    ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
    ("mamba_use_mlp", False), ("rope_scaling", {"type": "linear", "factor": 2}),
    ("attn_layer_indices", [0, 2]), ("attention_bias", True),
    ("mlp_bias", True), ("mamba_proj_bias", True), ("projectors_bias", True),
    ("mamba_conv_bias", False), ("mamba_d_ssm", 2048)])
def test_import_refuses_what_nothing_here_computes(key, value):
    with pytest.raises(FalconH1Unsupported) as e:
        hf_config_to_transformer(dict(_published(), **{key: value}))
    assert e.value.key == key and e.value.value == value


# ---- the recurrence at the published head shape -----------------------------

def _ssm_inputs(T, seed=0):
    H, P, G, N = 32, 128, 2, 256
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2)
    return (jax.random.normal(k[0], (T, H, P)), dt,
            -jnp.exp(jax.random.uniform(k[2], (H,), minval=0, maxval=2.7)),
            jax.random.normal(k[3], (T, G, N)) / 4,
            jax.random.normal(k[4], (T, G, N)) / 4,
            jax.random.normal(k[5], (H, P, N)))


def test_the_scan_kernel_at_the_published_head_shape():
    """32 heads of 128, state 256, 2 groups: a group's 16 heads are two grid
    rows of 8 (``block_heads``), both reading the group's B and C; 40
    positions in chunks of 16, the last eight pad rows (dt = 0)."""
    assert ssm.block_heads(32, 16, 128, 256, ssm.SCAN_STATE_BYTES) == 8
    x, dt, A, B, C, S0 = _ssm_inputs(40)
    dt = dt.at[32:].set(0.0)
    y0, s0 = _sequential(x, dt, A, B, C, S0)
    y, s = ssm.ssm_scan(x, dt, A, B, C, S0, chunk=16, kernel=True)
    scale = float(jnp.abs(y0).max())
    assert float(jnp.abs(y - y0).max()) < 2e-5 * scale
    assert float(jnp.abs(s - s0).max()) < 2e-5
    s_true = _sequential(x[:32], dt[:32], A, B[:32], C[:32], S0)[1]
    assert float(jnp.abs(s - s_true).max()) < 2e-5


def test_the_step_kernel_at_the_published_head_shape():
    """A grid step holds 4 heads (512 KiB of state), a quarter of a group:
    every block reads the ONE group it lies in."""
    assert ssm.block_heads(32, 16, 128, 256, ssm.STEP_STATE_BYTES) == 4
    S = 3
    x, dt, A, B, C, _ = _ssm_inputs(S, seed=1)
    dt = dt.at[1].set(0.0)                                # an inactive slot
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, S, 32, 128, 256))
    y, new = ssm.ssm_step(pool, 1, x, dt, A, B, C, kernel=True)
    for s in range(S):
        y1, s1 = _sequential(x[s:s + 1], dt[s:s + 1], A, B[s:s + 1],
                             C[s:s + 1], pool[1, s])
        assert float(jnp.abs(y[s] - y1[0]).max()) < 1e-4
        assert float(jnp.abs(new[1, s] - s1).max()) < 2e-5
    assert bool((new[0] == pool[0]).all())
    assert bool((new[1, 1] == pool[1, 1]).all())


# ---- the cell's rehearsal ----------------------------------------------------

def test_the_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --rehearsal`` of the cell: the family file, the
    configuration, the traffic and the engine at toy widths, end to end. Here
    and not in ``benchmark/tests``: that suite is ONE serial process and
    tier-1's longest case (``test_benchmark_suite.py``), every rehearsal in it
    lengthens it, and this toy (two layers of width 128) finishes requests by
    the dozen beside tier-1's other workers."""
    import subprocess
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "falcon-h1-34b-serve.batch-reasoning", "--seconds", "10", "--seed",
         "5700000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = [ln for ln in p.stdout.splitlines() if ln.startswith("REHEARSAL")][-1]
    assert '"correct": true' in last and "serve_tokens_per_s" in last
    assert "sat_state_share_of_live_cache" in last
    assert "benchmark.families.falcon_h1" in p.stdout
