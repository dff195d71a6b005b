"""The glm4_moe_lite family (families/glm4_moe_lite.py) and its cell: the
configuration against the catalog row, the cost model's arithmetic against
hand counts (29.94 B at the published 47 layers, 3.896 B at the cut, 1 152 B a
cached row), ``decode_step_bytes`` on hand-made counters, the two readers of
latent attention on hand-built trace events, the program against the plain
reference on LOGITS at toy widths — the whole forward, and a prompt prefilled
EXPANDED and then decoded ABSORBED through the latent pool —, each seeded
defect, the table of HF weight names, the cell's rehearsal, and the cell's
entries in ``BENCHMARK.json`` — tested with ``in``, never by position: a
later PR appends after them.

TOL = 2e-4 on logits of size ~1: float32 on both sides, the differences are
the order of sums (the one-hot dispatch against a loop over experts, the
absorbed order against the expanded one, a softmax over gathered blocks + the
fresh row against one over a score row). The sound path reads ~1e-6; the
seeded defects move these logits by 4e-3 (``no_rope_on_k``) to 1 and more."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen, peaks, trace_reduce  # noqa: E402

CONFIG = "glm-4.7-flash-serve"
CELL = CONFIG + ".batch-docqa"
TOL = 2e-4
H, F, FD, V, E = 2048, 1536, 10240, 154880, 64
ATTN = H * 768 + 768 * 5120 + H * 576 + 512 * 8960 + 5120 * H
EXPERT = 3 * H * F
DENSE = 3 * H * FD
E_SIDE = EXPERT + H * E                               # shared expert, router
ROW = 2 * (512 + 64)                                  # one plane's cached row
STORED = 2 * 640                                      # ... in whole lane tiles
POOL = {"latent": {"shape": (6, 9729, 64, 640), "dtype": "bfloat16"}}

GATHER = ("%fusion.31 = bf16[4864,64,640]{2,1,0:T(8,128)(2,1)} fusion(bf16[58374,64,640]"
          "{2,1,0:T(8,128)(2,1)} %bitcast.9, s32[4864]{0} %ids), kind=kLoop")
SCORES = ("%fusion.40 = f32[2432,20,128]{2,1,0} fusion(bf16[2432,128,640]{2,1,0} %g, "
          "bf16[2432,20,640]{2,1,0} %q), kind=kOutput")
ROW_WRITE = ("%fusion.77 = bf16[6,9729,64,640]{3,2,1,0:T(8,128)(2,1)} fusion(bf16[6,9729,64,640]"
             "{3,2,1,0} %param.3, bf16[6,128,640]{2,1,0} %rows), kind=kLoop, calls=%fused_scatter")
BLOCK_WRITE = ("%scatter.5 = bf16[6,9729,64,640]{3,2,1,0} scatter(bf16[6,9729,64,640]{3,2,1,0} "
               "%param.3, s32[32]{0} %ids, bf16[6,32,64,640]{3,2,1,0} %blocks)")
KERNEL = ("%latent_decode.3 = f32[128,32,640]{2,1,0} custom-call(s32[1]{0} %l, "
          'bf16[6,9729,64,640]{3,2,1,0} %pool), custom_call_target="tpu_custom_call"')
FLASH = ("%flash_fwd.2 = (bf16[1,20,1,2048,256]{4,3,2,1,0}, f32[1,20,1,2048,1]{4,3,2,1,0}) "
         'custom-call(bf16[1,20,1,2048,256]{4,3,2,1,0} %a), custom_call_target="tpu_custom_call"')
Q_LAT = "%fusion.9 = bf16[128,20,640]{2,1,0} fusion(bf16[128,20,192]{2,1,0} %q), kind=kOutput"
ROWS = "%fusion.12 = bf16[6,128,640]{2,1,0} fusion(bf16[128,640]{1,0} %a, bf16[128,640]{1,0} %b), kind=kLoop"
OTHER = "%fusion.5 = bf16[128,2048]{1,0} fusion(bf16[128,2048]{1,0} %x), kind=kLoop"


def hf():
    return common.hf_of(common.load_config(CONFIG))


def fam():
    return loadgen.load_family(hf())


def test_the_configuration_is_the_catalog_entry_cut_in_depth_alone():
    h, cfg = hf(), common.load_config(CONFIG)
    assert h["model_type"] == "glm4_moe_lite"
    assert sorted(cfg["reduced"]) == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert (h["num_hidden_layers"], h["num_nextn_predict_layers"],
            h["first_k_dense_replace"]) == (6, 0, 1)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):        # every other published number as it is
        with open(path) as f:
            cat = next(json.loads(ln) for ln in f if '"GLM-4.7-Flash"' in ln)
        assert cfg["source"] == cat["source_url"]
        for k, v in cat["config"].items():
            if k not in cfg["reduced"]:
                assert h[k] == v, k
        assert (cat["config"]["num_hidden_layers"],
                cat["config"]["num_nextn_predict_layers"]) == (47, 1)
    # the one key that is not published repeats a published one
    assert h["num_experts"] == h["n_routed_experts"] == E
    for key in ("weights", "dtype", "rotary", "attention", "router", "mtp",
                "num_experts", "unread"):
        assert key in cfg["assumed"], key
    assert "8 pipeline stages" in cfg["deployment"] and "NOT here" in cfg["deployment"]
    assert cfg["run"]["overrides"] == {"norm_init_jitter": 0.5}
    assert cfg["run"]["init_serving"] == {}
    assert cfg["run"]["serving"] == {"max_seqs": 128, "max_model_len": 4864,
                                     "prompt_bucket": 512}
    expect = cfg["run"]["expect"]
    assert expect == {"kv_cache_bits": 0, "kv_pool_dtype": "bfloat16",
                      "latent_planes": 6, "latent_row_width": 576, "kv_planes": 0,
                      "num_experts": 64, "top_k": 4, "max_seqs": 128}
    assert "decode_backend" not in expect            # no pin of an implementation
    # floors of the model-configs guide: four layers after the dense one, the
    # experts and the vocabulary whole
    assert h["num_hidden_layers"] - h["first_k_dense_replace"] >= 4


def test_the_parameter_count_is_the_published_one_and_the_cuts():
    f, h = fam(), hf()
    assert (ATTN, EXPERT, DENSE, E_SIDE) == (21_757_952, 9_437_184, 62_914_560,
                                             9_568_256)
    assert f.attn_params(h) == f.block_params(h, "latent") == ATTN
    assert f.block_params(h, "dense") == DENSE
    assert f.block_params(h, "moe") == E * EXPERT + E_SIDE
    assert f.block_params(h, "moe", 2.5) == 2.5 * EXPERT + E_SIDE
    assert [k for k, _ in f.blocks(h)] == ["latent", "dense"] + ["latent", "moe"] * 5
    cut = 6 * ATTN + DENSE + 5 * (E * EXPERT + E_SIDE) + 2 * V * H
    assert f.param_count(h) == cut == 3_895_590_912          # 3.896 B
    assert round(2 * cut / 2 ** 30, 2) == 7.26               # GiB in bf16
    whole = 47 * ATTN + DENSE + 46 * (E * EXPERT + E_SIDE) + 2 * V * H
    assert f.param_count(dict(h, num_hidden_layers=47)) == whole == 29_943_136_256
    # expanded attention: 20 heads x 256 in Q K^T and in P V, the causal half
    assert f.flash_flops(h, 1, 2048)["fwd"] == 2 * 2 * 20 * 2048 * 2048 * 256 / 2
    assert f.flash_flops(h, 1, 2048)["total"] == 3.5 * f.flash_flops(h, 1, 2048)["fwd"]


def test_the_toy_keeps_every_mechanism():
    f = fam()
    toy = common.hf_of(common.load_config(CONFIG), rehearsal=True)
    assert [k for k, _ in f.blocks(toy)] == ["latent", "dense", "latent", "moe",
                                             "latent", "moe"]
    assert (toy["n_routed_experts"], toy["num_experts"], toy["num_experts_per_tok"],
            toy["n_shared_experts"], toy["routed_scaling_factor"]) == (8, 8, 4, 1, 1.8)
    nq, dn, dr, dv, rq, rkv = f.latent_dims(toy)
    assert dv == dn + dr and dn == 3 * dr and (rq, rkv) == (48, 32)
    assert len(f.DEFECTS) == 11


def test_the_row_and_decode_step_bytes():
    f, h = fam(), hf()
    assert f.latent_row_bytes(h) == ROW == 1152 and f.latent_planes(h) == 6
    assert f.latent_bytes_per_token(h) == 6 * ROW
    # the pool as the configuration sizes it: 128 x 4864 positions + the trash
    # block; as the chip stores it, a row in five whole lane tiles
    assert round((128 * 76 + 1) * 64 * 6 * ROW / 2 ** 30, 2) == 4.01
    assert round((128 * 76 + 1) * 64 * 6 * STORED / 2 ** 30, 2) == 4.45
    other = 6 * ATTN + DENSE + 5 * E_SIDE + V * H
    c = {"kv_cache_bits": 0, "mean_occupancy": 128.0, "mean_live_tokens": 300_000.0,
         "stats": {"moe_experts_touched_per_step": 62.5, "latent_planes": 6.0,
                   "latent_row_bytes": 1152.0}}
    # the live rows ONCE: a plane is both K and V
    assert f.decode_step_bytes(h, c) == (
        2 * (other + 5 * 62.5 * EXPERT) + 6 * ROW * 300_000)
    # the row's bytes are the RUN's (a pool in another dtype), not a constant
    c["stats"]["latent_row_bytes"] = 576.0
    assert f.decode_step_bytes(h, c) == (
        2 * (other + 5 * 62.5 * EXPERT) + 6 * 576 * 300_000)
    # no counter: every expert, the published row; no live row: the weights alone
    assert f.decode_step_bytes(h, {"kv_cache_bits": 0, "mean_live_tokens": 0.0}) \
        == 2 * (other + 5 * E * EXPERT)


def test_the_family_finds_latent_attention_in_a_trace():
    f = fam()
    c = {"pool": POOL}
    for name in (GATHER, SCORES, KERNEL):
        assert f.latent_read_op(name, c) and f.latent_op(name, c), name
    for name in (ROW_WRITE, BLOCK_WRITE, FLASH):
        assert f.latent_op(name, c) and not f.latent_read_op(name, c), name
    for name in (Q_LAT, ROWS, OTHER):
        assert not f.latent_op(name, c), name
    # a run without a latent pool (the parent's, another family's): nothing
    for name in (GATHER, KERNEL, FLASH, ROW_WRITE):
        assert not f.latent_op(name, {"pool": {"k": POOL["latent"]}})
        assert not f.latent_read_op(name, {})


def fake_run(events, modules, counters):
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OPS_LINE, "events": [list(e) for e in events]},
        {"name": trace_reduce.MODULES_LINE, "events": [list(m) for m in modules]}]}
    raw = {"planes": [plane, {"name": trace_reduce.HOST_PLANE, "lines": [
        {"name": "t", "events": [[trace_reduce.WINDOW_SPAN, 0.0, 1e9]]}]}]}
    return {"trace": trace_reduce.reduce(raw), "family": fam(), "hf": hf(),
            "peaks": peaks.peaks_for("TPU v5 lite"), "cell": {"name": CELL},
            "counters": counters}


def test_the_two_readers_of_latent_attention():
    share, roof = (loadgen.load_module("layer_metrics", n) for n in (
        "sat_mla_share_of_device", "sat_mla_read_roofline"))
    counters = {"pool": POOL, "mean_live_tokens": 300_000.0, "kv_cache_bits": 0,
                "stats": {"latent_planes": 6.0, "latent_row_bytes": 1152.0}}
    # two steps; the read's ops take four times their bytes' time, the writes
    # and a flash forward as long again, other ops as long as all of that
    floor_ns = 2 * 300_000 * 6 * ROW / 819e9 * 1e9
    events, t = [], 1e6
    for name, ns in ((GATHER, 2 * floor_ns), (SCORES, 2 * floor_ns),
                     (ROW_WRITE, floor_ns), (BLOCK_WRITE, floor_ns),
                     (FLASH, 2 * floor_ns), (OTHER, 8 * floor_ns)):
        events.append((name, t, ns))
        t += ns + 10.0
    run = fake_run(events, [("jit_step(1)", 0.0, 1e5), ("jit_step(1)", 2e5, 1e5),
                            ("jit_prefill(2)", 4e5, 1e5)], counters)
    assert share.read(run) == pytest.approx(50.0)
    assert roof.read(run) == pytest.approx(25.0)
    # a program without a latent pool (another family's, the parent's): nothing
    run = fake_run(events, [("jit_step(1)", 0.0, 1e5)],
                   {"pool": {}, "mean_live_tokens": 1.0, "stats": {}})
    assert share.read(run) is None and roof.read(run) is None
    run["family"] = loadgen.load_family({"model_type": "afmoe"})
    assert share.read(run) is None and roof.read(run) is None
    run["trace"] = None
    assert share.read(run) is None and roof.read(run) is None


def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "batch-docqa", 1)
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert conf["source"] == common.load_config(CONFIG)["source"]
    where = {m["name"]: m.get("workloads") for m in b["end_to_end"] + b["per_layer"]}
    for name in ("serve_tokens_per_s", "sat_batch_occupancy", "sat_host_share_of_round",
                 "sat_decode_step_device_ms", "sat_decode_step_roofline",
                 "sat_prefill_share_of_device", "serve_hbm_in_use_gib",
                 "sat_host_bound_idle_share", "sat_ahead_covered_share",
                 "sat_round_max_over_median", "sat_moe_share_of_device",
                 "sat_moe_sorted_share_of_device", "sat_moe_load_max_over_mean",
                 "sat_moe_experts_touched", "sat_moe_ffn_roofline",
                 "sat_moe_sorted_ffn_roofline", "sat_mla_share_of_device",
                 "sat_mla_read_roofline"):
        assert CELL in where[name], name
    for name in ("sat_mla_share_of_device", "sat_mla_read_roofline"):
        assert where[name] == [CELL], name
    # every expert is held; other families' mechanisms: not this cell's
    for name in ("sat_moe_held_assignment_share", "sat_attn_window_share_of_device",
                 "sat_ssm_share_of_device", "sat_gdn_share_of_device",
                 "sat_paged_read_roofline", "sat_kv_gathered_over_live"):
        assert CELL not in where[name], name
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    t = loadgen.load_traffic("batch-docqa")
    assert t["kind"] == "saturating" and t["requests"] == 1200
    assert t["prompt"] == {"median": 2048, "sigma": 0.3, "min": 1024, "max": 4096}
    assert t["output"] == {"median": 384, "sigma": 0.3, "min": 192, "max": 768}
    assert t["prompt"]["max"] + t["output"]["max"] == 4864


def test_precision_below_rounds_every_operand_and_the_row():
    import jax.numpy as jnp
    import numpy as np
    f, h = fam(), hf()
    below, plain = f.Reference(h, None, defect="precision_below"), f.Reference(h, None)
    a = jnp.asarray([0.013, 1.3, -0.7, 100.0], jnp.float32)
    assert np.array_equal(np.asarray(below._lo(a)), [0.013671875, 1.25, -0.75, 96.0])
    assert np.array_equal(np.asarray(plain._lo(a)), np.asarray(a))
    assert below._latent_fp8 and not plain._latent_fp8
    one = f.Reference(h, None, defect="latent_fp8")
    assert one._operand is None and one._latent_fp8
    with pytest.raises(ValueError, match="one of"):
        f.Reference(h, None, defect="no_such_defect")


# ---- the program against the reference, on logits at toy widths ------------

BS, SLOTS, MB = 8, 3, 12


@pytest.fixture(scope="module")
def toy():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    t = dict(common.hf_of(common.load_config(CONFIG), rehearsal=True),
             max_position_embeddings=256)
    cfg = hf_config_to_transformer(t, dtype=jnp.float32, norm_init_jitter=0.5)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    # a seeded model's attention is near uniform (scores of normed latents
    # through std-0.02 matrices) and its correction bias a twentieth of a
    # score: drawn further from that, so that the scale, the rotary key and
    # the bias each move a logit
    lat, moe = params["layers"]["latent"], params["layers"]["moe"]
    lat["wq_b"], lat["wkv_b"] = lat["wq_b"] * 8.0, lat["wkv_b"] * 8.0
    lat["wkv_a"] = lat["wkv_a"] * 4.0
    moe["e_bias"] = moe["e_bias"] * 30.0
    return t, cfg, model, params


@pytest.fixture(scope="module")
def toy_logits(toy):
    """The program's logits over 56 positions — the whole forward, and a
    prompt of 37 prefilled EXPANDED into slot 1's blocks and 19 tokens decoded
    ABSORBED against the latent pool — and the reference's, by defect."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    t, cfg, model, params = toy
    ids = np.random.default_rng(0).integers(0, t["vocab_size"], 56)
    whole = np.asarray(model.apply(params, jnp.asarray(ids)[None])[0])
    pools = model.init_paged_cache(SLOTS * MB + 1, BS, dtype=jnp.float32,
                                   max_seqs=SLOTS)
    assert set(pools) == {"latent"} and pools["latent"].shape == (3, 37, BS, 128)
    tables = np.arange(1, SLOTS * MB + 1, dtype=np.int32).reshape(SLOTS, MB)
    n = 37
    buf = np.zeros((1, 40), np.int32)
    buf[0, :n] = ids[:n]
    last, pools = jax.jit(model.prefill_paged)(
        params, jnp.asarray(buf), pools, jnp.asarray(tables[1, :5]),
        length=jnp.int32(n))
    paged = [np.asarray(last[0])]
    step = jax.jit(model.decode_step_paged)
    lens = np.zeros(SLOTS, np.int32)
    lens[1] = n
    act = np.asarray([False, True, False])
    for tok in ids[n:-1]:
        lg, pools = step(params, jnp.asarray([0, tok, 0], jnp.int32), pools,
                         jnp.asarray(tables), jnp.asarray(lens),
                         active=jnp.asarray(act))
        lens = lens + act
        paged.append(np.asarray(lg[1]))
    f = fam()
    return (whole, np.stack(paged), n,
            lambda defect: f.Reference(t, params, defect=defect).logits(ids, pad_to=64))


def test_the_program_is_the_plain_reference_on_logits(toy_logits):
    import numpy as np
    whole, paged, n, ref = toy_logits
    want = ref(None)
    assert np.abs(want).max() > 0.3                  # logits of size ~1
    assert np.abs(whole - want).max() < TOL
    # prefill (expanded) then decode (absorbed) through the latent pool
    assert np.abs(paged - want[n - 1:-1]).max() < TOL


def test_absorbed_is_expanded(toy_logits):
    """The decode steps' logits ARE the whole forward's at the same positions:
    two orders of one arithmetic, to float32 rounding."""
    import numpy as np
    whole, paged, n, _ = toy_logits
    assert np.abs(paged - whole[n - 1:-1]).max() < 2e-5


@pytest.mark.parametrize("defect", [
    "precision_below", "fp8_operands", "latent_fp8", "no_kv_norm", "no_q_norm",
    "no_rope_on_k", "scale_nope_only", "bias_in_weights", "no_routed_scale",
    "no_shared_expert", "v_wrong_columns"])
def test_each_seeded_defect_fails_on_logits_at_toy_widths(toy_logits, defect):
    """Every defect the configuration's ``correct.why`` names moves the toy's
    logits, in BOTH paths, by more than ten times the tolerance the sound
    program is held to."""
    import numpy as np
    whole, paged, n, ref = toy_logits
    bad = ref(defect)
    assert np.abs(whole - bad).max() > 10 * TOL
    assert np.abs(paged - bad[n - 1:-1]).max() > 10 * TOL
    assert defect in fam().DEFECTS


def test_packed_prompts_share_a_row(toy):
    """Two prompts in ONE prefill row, each from a block's edge: logits and
    latent rows as each prompt alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    t, cfg, model, params = toy
    rng = np.random.default_rng(5)
    a, b = rng.integers(0, t["vocab_size"], 13), rng.integers(0, t["vocab_size"], 21)
    prefill = jax.jit(model.prefill_paged)

    def fresh():
        return model.init_paged_cache(SLOTS * MB + 1, BS, dtype=jnp.float32,
                                      max_seqs=SLOTS)

    def alone(p, blocks):
        buf = np.zeros((1, 40), np.int32)
        buf[0, :len(p)] = p
        ids = np.zeros(5, np.int32)
        ids[:len(blocks)] = blocks
        last, pools = prefill(params, jnp.asarray(buf), fresh(), jnp.asarray(ids),
                              length=jnp.int32(len(p)))
        return np.asarray(last[0]), np.asarray(pools["latent"])

    la, pa = alone(a, [1, 2])
    lb, pb = alone(b, [3, 4, 5])
    buf = np.zeros((1, 40), np.int32)
    buf[0, :13], buf[0, 16:37] = a, b
    last, pools = prefill(params, jnp.asarray(buf), fresh(),
                          jnp.asarray([1, 2, 3, 4, 5], jnp.int32),
                          segments=(jnp.asarray([0, 16, 0, 0], jnp.int32),
                                    jnp.asarray([13, 21, 0, 0], jnp.int32)))
    last, pool = np.asarray(last), np.asarray(pools["latent"])
    assert np.abs(last[0] - la).max() < 2e-5 and np.abs(last[1] - lb).max() < 2e-5
    rows = pool.reshape(3, -1, 128)
    assert np.abs(rows[:, 8:8 + 13] - pa.reshape(3, -1, 128)[:, 8:8 + 13]).max() < 2e-5
    assert np.abs(rows[:, 24:24 + 21] - pb.reshape(3, -1, 128)[:, 24:24 + 21]).max() < 2e-5


def test_hf_weight_names_round_trip(toy):
    """Every leaf of the parameter tree is named by the table once an expert
    or once whole, with the shape HF stores (transposed but the experts' up
    projection), and no name lands twice in one place."""
    import numpy as np
    from deepspeed_tpu.models import hf_import
    t, cfg, model, params = toy
    names = hf_import.glm4_moe_lite_weight_names(cfg)
    pre = "model.layers.1."
    assert names[pre + "self_attn.kv_a_proj_with_mqa.weight"] == ("latent", 1, "wkv_a", None)
    assert names[pre + "self_attn.kv_b_proj.weight"] == ("latent", 1, "wkv_b", None)
    assert names[pre + "self_attn.q_a_layernorm.weight"] == ("latent", 1, "q_a_norm", None)
    assert names[pre + "mlp.gate.e_score_correction_bias"] == ("moe", 0, "e_bias", None)
    assert names[pre + "mlp.experts.7.up_proj.weight"] == ("moe", 0, "moe_w_in_t", 7)
    assert names["model.layers.0.mlp.down_proj.weight"] == ("dense", 0, "w_out", None)
    assert names["model.layers.0.post_attention_layernorm.weight"] == (
        "dense", 0, "ln_scale", None)
    assert not any(n.startswith("model.layers.3.") for n in names)   # no next-token layer
    # a checkpoint written from the tree under those names and read back by
    # them gives the tree: every leaf covered, none twice
    ckpt = {}
    for name, (kind, j, leaf, part) in names.items():
        a = np.asarray(params[leaf] if kind is None else params["layers"][kind][leaf][j])
        a = a if part is None else a[part]
        ckpt[name] = a if a.ndim < 2 or leaf in ("tok_embed", "moe_w_in_t") else a.T
    assert ckpt[pre + "self_attn.kv_b_proj.weight"].shape == (4 * (24 + 32), 32)
    assert ckpt["lm_head.weight"].shape == (512, 128)
    import jax
    back = jax.tree.map(lambda a: np.full(a.shape, np.nan, np.float32), params)
    for name, (kind, j, leaf, part) in names.items():
        a = ckpt[name]
        a = a if a.ndim < 2 or leaf in ("tok_embed", "moe_w_in_t") else a.T
        dst = back[leaf] if kind is None else back["layers"][kind][leaf][j]
        if part is None:
            assert np.isnan(dst).all(), name
            dst[...] = a
        else:
            assert np.isnan(dst[part]).all(), name
            dst[part] = a
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert np.array_equal(got, np.asarray(want, np.float32))


def test_the_defect_tool_judges_through_the_harness_check():
    src = open(os.path.join(ROOT, "benchmark", "tools", "glm4_moe_lite_defects.py")).read()
    assert "correct.check_tokens_vs_reference(" in src and "def judge" not in src
    assert 'CELL = "' + CELL + '"' in src


def test_the_cell_rehearses_on_the_cpu():
    """40 s: the first round is 128 prefills of 128-512 tokens, ~8 s alone; an
    answer is 24-96 tokens, three to twelve rounds, and a request has to
    FINISH."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seconds", "40", "--seed", "5200000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = [ln for ln in p.stdout.splitlines() if ln.startswith("REHEARSAL")][-1]
    assert '"correct": true' in last and "serve_tokens_per_s" in last
    assert "sat_moe_experts_touched" in last
    assert "benchmark.families.glm4_moe_lite" in p.stdout
    assert "latent (3, 1153, 64, 128) bfloat16" in p.stdout
    assert "kv_cache_bits=0" in p.stdout
