"""Pod-scale serving: TP-sharded paged decode + expert-parallel MoE
(ISSUE 15).

The serving engine is mesh-native: the paged KV block pools
``[L, NB, nkv, block_size, hd]`` shard on the kv-head dim over the
`tensor` mesh axis through the same Megatron col/row rules the weights
use, and the MoE FFN expert stacks shard over `expert`. The load-bearing
contracts pinned here:

  - a tp=2 serving engine's greedy outputs are TOKEN-IDENTICAL to the
    single-chip engine over the full workload (f32), including
    preemption/re-prefill resume, prefix-cache warm hits, chunked
    prefill and speculative decoding under sharding;
  - the per-round collective census of the tp=2 quantum step is pinned
    EXACTLY — the per-layer out-projection reductions (+ the vocab-
    sharded embed gather) are the only cross-chip collectives, the pool
    scatter contributes ZERO (`tp-serving-replicated-pool` corpus pins
    the replicated-pool drift defect both directions);
  - pool bytes price the PER-DEVICE shard (memory law:
    per_device * tp == logical), and every serving program's pool output
    is pinned to the head-sharded layout;
  - drains record the mesh topology (tp/ep); resume/accept_migration
    refuse a mesh-incompatible placement with the typed
    ``ResumeIncompatible`` (tp=2 -> tp=2 continues byte-identically,
    tp=2 -> tp=1 refuses loudly); replica heartbeats carry the topology.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.serving import ResumeIncompatible
from deepspeed_tpu.models import TransformerConfig, make_model
from deepspeed_tpu.parallel import MeshPlan, build_mesh


def _cfg(**overrides):
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, max_seq_len=256, position_type="rotary",
                activation="silu_glu", norm_type="rmsnorm",
                tie_embeddings=False, dtype=jnp.float32,
                attention_impl="xla")
    base.update(overrides)
    return TransformerConfig(**base)


def _mesh(n, **axes):
    return build_mesh(MeshPlan(**axes), devices=jax.devices()[:n])


def _serving(model, params, mesh=None, config=None, **serving):
    defaults = dict(max_seqs=2, block_size=16, max_model_len=128,
                    decode_quantum=4, prompt_bucket=16)
    defaults.update(serving)
    return deepspeed_tpu.init_serving(model, config=config or {},
                                      serving=defaults, dtype=jnp.float32,
                                      params=params, mesh=mesh)


def _reqs(seed=0, vocab=128, lens=(7, 21), news=(9, 6)):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, size=(n,)).astype(np.int32), k)
            for n, k in zip(lens, news)]


# ---------------------------------------------------------------------------
# tp=2 parity + pool sharding + the pool-bytes memory law
# ---------------------------------------------------------------------------

def test_tp2_token_identical_and_pool_bytes_law():
    """The headline ISSUE-15 contract: a tp=2 engine (pools head-sharded
    over `tensor`) produces exactly the single-chip greedy tokens, its
    pool output sharding survives serving rounds, and pool_bytes prices
    the PER-DEVICE shard — per_device * tp == logical, exactly (the
    memory-law style assert of the serve_pool_bytes fix)."""
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    reqs = _reqs()

    srv1 = _serving(model, params)
    outs1 = srv1.run(list(reqs))
    st1 = srv1.stats()
    assert (srv1.tp, srv1.ep) == (1, 1)
    assert st1["pool_bytes"] == st1["pool_bytes_logical"]

    srv2 = _serving(model, params, mesh=_mesh(2, tensor=2))
    assert (srv2.tp, srv2.ep) == (2, 1)
    assert srv2.mesh_desc == "tensor=2"
    # the token-major pool [L, NB, bs, nkv, hd] shards on the kv-head dim
    # (axis 3) over `tensor`
    spec = srv2.pools["k"].sharding.spec
    assert spec[3] == "tensor", spec
    shard = srv2.pools["k"].sharding.shard_shape(srv2.pools["k"].shape)
    assert shard[3] * 2 == srv2.pools["k"].shape[3]
    outs2 = srv2.run(list(reqs))
    for rid in outs1:
        np.testing.assert_array_equal(outs1[rid], outs2[rid],
                                      err_msg=f"request {rid}")
    st2 = srv2.stats()
    # memory law: the per-device shard is exactly logical / tp, and the
    # logical pool is mesh-independent
    assert st2["pool_bytes"] * 2 == st2["pool_bytes_logical"]
    assert st2["pool_bytes_logical"] == st1["pool_bytes_logical"]
    assert (st2["tp"], st2["ep"]) == (2.0, 1.0)
    # the out_shardings pin: after full serving rounds (prefill + quantum
    # steps + donations) the pool is still head-sharded, not replicated
    assert srv2.pools["k"].sharding.spec[3] == "tensor"


def test_ep4_moe_matches_unsharded():
    """Expert-parallel MoE serving: the Mixtral-family expert stacks
    shard over `expert` (dispatch/combine all-to-alls from the moe/
    constraints) and greedy outputs match the unsharded MoE engine
    token for token."""
    model = make_model(_cfg(num_experts=4, top_k=2))
    params = jax.device_get(model.init(jax.random.PRNGKey(1)))
    reqs = _reqs(seed=3)
    outs1 = _serving(model, params).run(list(reqs))
    srv4 = _serving(model, params, mesh=_mesh(4, expert=4))
    assert (srv4.tp, srv4.ep) == (1, 4)
    w = srv4.engine.params["layers"]["moe_w_in"]
    assert w.sharding.shard_shape(w.shape)[1] * 4 == w.shape[1]
    outs4 = srv4.run(list(reqs))
    for rid in outs1:
        np.testing.assert_array_equal(outs1[rid], outs4[rid],
                                      err_msg=f"request {rid}")


# ---------------------------------------------------------------------------
# mesh config validation
# ---------------------------------------------------------------------------

def test_kv_heads_must_divide_tp():
    model = make_model(_cfg(num_heads=6, num_kv_heads=3))
    with pytest.raises(ValueError, match="kv_heads"):
        _serving(model, None, mesh=_mesh(2, tensor=2))


def test_expert_parallel_needs_divisible_moe():
    dense = make_model(_cfg())
    with pytest.raises(ValueError, match="MoE"):
        deepspeed_tpu.init_inference(dense, config={"expert_parallel": 4},
                                     dtype=jnp.float32)
    moe = make_model(_cfg(num_experts=4, top_k=2))
    with pytest.raises(ValueError, match="num_experts"):
        deepspeed_tpu.init_inference(moe, config={"expert_parallel": 3},
                                     dtype=jnp.float32,
                                     mesh=_mesh(3, expert=3))


def test_mesh_contradicting_config_degree_refused():
    """An explicit mesh is authoritative; a config degree that contradicts
    it is a caller bug, not a silent replication."""
    model = make_model(_cfg())
    with pytest.raises(ValueError, match="tensor"):
        deepspeed_tpu.init_inference(model, config={"tensor_parallel": 4},
                                     dtype=jnp.float32,
                                     mesh=_mesh(2, tensor=2))


def test_dense_model_on_expert_mesh_degrades_not_crashes():
    """A SHARED mesh with an expert axis reused for a dense model must
    keep working (a dense model has no "expert" logical axis — nothing
    shards over it): ep degrades to 1 instead of the MoE validation
    firing, and the SERVING tier advertises the resolved degree (drains/
    heartbeats/migration must not claim expert sharding that does not
    exist — a dense survivor would be spuriously refused). Only an
    EXPLICIT expert_parallel request on a dense model is the caller bug
    that raises."""
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    eng = deepspeed_tpu.init_inference(model, config={},
                                       dtype=jnp.float32,
                                       mesh=_mesh(4, expert=4))
    assert eng.ep == 1
    srv = _serving(model, params, mesh=_mesh(4, expert=4))
    assert srv.ep == 1 and srv.tp == 1
    # migration between this engine and a plain dense engine is
    # geometry-compatible both ways
    srv.accept_migration([], geometry={"tp": 1, "ep": 1})
    with pytest.raises(ValueError, match="MoE"):
        deepspeed_tpu.init_inference(model, config={"expert_parallel": 4},
                                     dtype=jnp.float32,
                                     mesh=_mesh(4, expert=4))


def test_failover_prefers_geometry_matched_survivors(tmp_path):
    """The heartbeat tp/ep fields are load-bearing: _survivor_order ranks
    a geometry-matched survivor ahead of a less-loaded mismatched one (a
    mismatched survivor refuses drain-origin records typed anyway — the
    ordering skips the wasted round-trips); survivors without topology
    meta rank as matched (the typed refusal stays the arbiter)."""
    from deepspeed_tpu.analysis.serving_lint import _StubReplica
    from deepspeed_tpu.inference.router import RouterConfig, ServingRouter
    cfg = RouterConfig(store_dir=str(tmp_path / "store"),
                       drain_dir=str(tmp_path / "drains"))
    router = ServingRouter(cfg)
    for name in ("dead", "tp1", "tp2"):
        router.register_handle(_StubReplica(name, cfg.store_dir,
                                            cfg.drain_dir))
    # tp1 is the least loaded but mesh-mismatched; tp2 matches the drain
    router._info["tp1"] = {"ts": 0.0, "meta": {"tp": 1, "ep": 1,
                                               "queue_depth": 0,
                                               "running": 0,
                                               "capacity": 4}}
    router._info["tp2"] = {"ts": 0.0, "meta": {"tp": 2, "ep": 1,
                                               "queue_depth": 3,
                                               "running": 4,
                                               "capacity": 4}}
    order = [r.name for r in router._survivor_order(
        "dead", geometry={"tp": 2, "ep": 1})]
    assert order[0] == "tp2", order
    # without a drained geometry, plain load order wins — the order
    # _failover uses for resubmit-origin records, which regenerate from
    # scratch and must not skip a healthy idle survivor over a mesh
    # they don't care about
    order = [r.name for r in router._survivor_order("dead")]
    assert order[0] == "tp1", order


# ---------------------------------------------------------------------------
# collective census pin + the replicated-pool corpus twins
# ---------------------------------------------------------------------------

def test_tp2_census_pinned_exactly():
    """The tp=2 quantum step's per-round collective census, exact: 3
    all-reduces (the scanned layer body's attn/MLP out-projections + the
    vocab-sharded embed gather) and 2 tiny all-gathers (the greedy
    argmax's cross-shard (value, index) exchange). Nothing else — in
    particular ZERO collectives in the pool scatter: each chip writes its
    own head slice in place."""
    from deepspeed_tpu.analysis.corpus import (TP_SERVE_CENSUS,
                                               tp_serving_pool_report)
    rep = tp_serving_pool_report(shard_pool=True)
    assert rep.ok, [f.key for f in rep.findings]
    census = rep.census["serve_decode_step_tp2"]
    assert {k: v["count"] for k, v in census.items()} == TP_SERVE_CENSUS
    # the argmax exchange is control-plane tiny; every data-bearing
    # collective is an out-projection-shaped reduction
    assert census["all-gather"]["bytes"] <= 256


def test_tp_replicated_pool_corpus_both_directions():
    """The planted defect — KV pool replicated across `tensor` — must
    trip the replication budget AND the per-device memory peak AND drift
    the census (the fresh rows all-gather before the scatter); the
    head-sharded twin passes identical settings. Registered in the lint
    corpus (CLI: lint --corpus tp-serving-replicated-pool)."""
    from deepspeed_tpu.analysis.corpus import CORPUS, run_corpus
    assert "tp-serving-replicated-pool" in CORPUS
    bad = run_corpus("tp-serving-replicated-pool")
    assert not bad.ok
    rules = {f.rule for f in bad.findings}
    assert "replication-over-budget" in rules, rules
    assert "memory-peak" in rules, rules
    assert "collective-census-drift" in rules, rules


# ---------------------------------------------------------------------------
# mesh geometry: drains, migration, heartbeats
# ---------------------------------------------------------------------------

def test_drain_records_mesh_and_tp1_refuses(tmp_path):
    """Drain-state v2 records the mesh topology; a tp=1 engine resuming a
    tp=2 drain refuses with the typed ResumeIncompatible (continuation
    determinism is per-geometry), and a fresh tp=2 engine picks the work
    up. The replica heartbeat meta carries the same topology."""
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    srv2 = _serving(model, params, mesh=_mesh(2, tensor=2))
    srv2.add_request(np.arange(5, dtype=np.int32), 6)
    srv2.step()
    tag_dir = srv2.drain(str(tmp_path))

    import json
    import os
    with open(os.path.join(tag_dir, "state.json")) as f:
        state = json.load(f)
    assert state["engine"]["tp"] == 2 and state["engine"]["ep"] == 1

    srv1 = _serving(model, params)
    with pytest.raises(ResumeIncompatible, match="tp=2"):
        srv1.resume(str(tmp_path))
    # per-request migration applies the same check
    with pytest.raises(ResumeIncompatible, match="tp=2"):
        srv1.accept_migration(state["requests"],
                              geometry=state["engine"])
    # records that PREDATE the geometry fields interop (no refusal)
    legacy = {k: v for k, v in state["engine"].items()
              if k not in ("tp", "ep")}
    assert srv1.accept_migration(state["requests"], geometry=legacy)

    srv2b = _serving(model, params, mesh=_mesh(2, tensor=2))
    rids = srv2b.resume(str(tmp_path))
    assert rids == [state["requests"][0]["rid"]]

    # heartbeat meta: the router's registry sees the topology
    from deepspeed_tpu.inference.router import ReplicaHandle
    h = ReplicaHandle("r0", srv2b, str(tmp_path / "store"),
                      str(tmp_path / "drains"))
    meta = h.meta()
    assert meta["tp"] == 2 and meta["ep"] == 1


# ---------------------------------------------------------------------------
# slow: parity under preemption + prefix cache + latency tier, and the
# tp2 -> tp2 drained continuation
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tp2_parity_under_preemption_and_prefix_cache():
    """Sharded serving composes with the PR-9/12 host machinery: a pool
    sized BELOW full residency (preemptions + re-prefill resume) and the
    CoW prefix cache (warm hits on shared prefixes) — block ids are
    replicated host metadata, so both engines make identical decisions
    and the tp=2 outputs stay token-identical through it all."""
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 128, size=(17,)).astype(np.int32)
    reqs = []
    for i in range(4):
        tail = rng.integers(0, 128, size=(8 + i,)).astype(np.int32)
        # 40 new tokens against an 8-usable-block pool: two tenants'
        # growth crosses the 4-block mark together and the newest
        # preempts (re-prefill resume, then a warm re-admission)
        reqs.append((np.concatenate([shared, tail]), 40))
    serving = dict(max_seqs=2, num_blocks=9, enable_prefix_cache=True)

    def run(mesh):
        srv = _serving(model, params, mesh=mesh, **serving)
        outs = srv.run(list(reqs))
        return outs, srv.stats()

    outs1, st1 = run(None)
    outs2, st2 = run(_mesh(2, tensor=2))
    # the adversarial machinery actually engaged, identically on both
    for st in (st1, st2):
        assert st["preemptions"] >= 1
        assert st["prefix_hits"] >= 1
    assert st1["preemptions"] == st2["preemptions"]
    assert st1["prefix_hits"] == st2["prefix_hits"]
    for rid in outs1:
        np.testing.assert_array_equal(outs1[rid], outs2[rid],
                                      err_msg=f"request {rid}")


@pytest.mark.slow
def test_tp2_latency_tier_composes_token_identical():
    """Speculative decoding (span verify) + chunked prefill under tp=2:
    the decode_span_paged program runs head-sharded like the quantum
    step, and outputs still match the PLAIN single-chip engine exactly
    (the ISSUE-12 K=0 parity contract, now across meshes)."""
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    reqs = _reqs(seed=5, lens=(7, 33), news=(12, 10))
    plain = _serving(model, params).run(list(reqs))
    srv = _serving(model, params, mesh=_mesh(2, tensor=2),
                   spec_tokens=3, prefill_token_budget=48)
    outs = srv.run(list(reqs))
    st = srv.stats()
    assert st["spec_steps"] >= 1 and st["prefill_chunks"] >= 1
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], outs[rid],
                                      err_msg=f"request {rid}")


@pytest.mark.slow
def test_tp2_drain_resume_continues_byte_identical(tmp_path):
    """tp=2 -> tp=2 drained continuation: outputs merge byte-identically
    with the uninterrupted tp=2 run (the PR-10 drain/resume contract on
    a sharded mesh — the 'continues byte-identically' half of the
    geometry satellite)."""
    model = make_model(_cfg())
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    reqs = _reqs(seed=7, lens=(9, 25), news=(12, 10))

    base = _serving(model, params, mesh=_mesh(2, tensor=2)).run(list(reqs))

    srv = _serving(model, params, mesh=_mesh(2, tensor=2))
    for p, n in reqs:
        srv.add_request(p, n)
    srv.step()
    srv.drain(str(tmp_path))

    srv2 = _serving(model, params, mesh=_mesh(2, tensor=2))
    srv2.resume(str(tmp_path))
    outs = {}
    while not srv2.scheduler.done:
        for r in srv2.step():
            outs[r.rid] = r.output
    for rid, expect in base.items():
        np.testing.assert_array_equal(expect, outs[rid],
                                      err_msg=f"request {rid}")
