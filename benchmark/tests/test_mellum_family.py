"""The mellum family (families/mellum.py) and its cell: the configuration
against the catalog entry it was cut from, the cost model's arithmetic against
hand counts (12.15 B at the published 28 layers / 64 experts / whole
vocabulary, 595.1 M at the cut; 1.15 GFLOP of matmul a token; the visible
pairs of a band), the YaRN table's closed form, the family's kernels by
instruction name, the four readers on hand-built trace events, each seeded
defect on LOGITS at toy widths, program = reference on the loss and on every
gradient leaf, the cell's rehearsal (a train step at toy widths), and the
cell's entries in ``BENCHMARK.json`` — tested with ``in``, never by position:
a later PR appends after them (PERF.md section 7)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen, peaks, trace_reduce  # noqa: E402

CONFIG = "mellum2-12b-train"
CELL = CONFIG + ".seq8192"
H, F, V, V_ALL, W, S = 2304, 896, 24576, 98304, 1024, 8192
EXPERT = 3 * H * F                                    # gate, up and down
ATTN = H * 4096 + 2 * H * 512 + 4096 * H              # q, k, v, o
ROUTER = H * 64

GMM = ('%jvp_moe_gmm_.3 = bf16[131072,896]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %l, '
       'bf16[131072,2304]{1,0} %rows, bf16[4,16,2304,896]{3,2,1,0} %w), '
       'custom_call_target="tpu_custom_call"')
GMM_DX = GMM.replace("%jvp_moe_gmm_.3", "%transpose_jvp_moe_gmm__.5")
GMM_DW = ('%transpose_jvp_moe_gmm_dw__.4 = bf16[16,2304,896]{2,1,0:T(8,128)(2,1)} '
          'custom-call(s32[17]{0} %o, bf16[131072,2304]{1,0} %rows, bf16[131072,896]{1,0} %dy), '
          'custom_call_target="tpu_custom_call"')
BAND = ('%flash_fwd_band.3 = (bf16[2,4,8,8192,128]{4,3,2,1,0}, f32[2,4,8,8192,1]{4,3,2,1,0}) '
        'custom-call(bf16[2,4,8,8192,128]{4,3,2,1,0} %q), custom_call_target="tpu_custom_call"')
BAND_DQ = BAND.replace("%flash_fwd_band.3", "%transpose_jvp_flash_bwd_band_dq_.2")
BAND_DKV = BAND.replace("%flash_fwd_band.3", "%flash_bwd_band_dkv.7")
FLASH = BAND.replace("%flash_fwd_band.3", "%flash_fwd.2")
LOOKALIKE = ("%moe_gmm_like.5 = bf16[131072,896]{1,0} fusion(bf16[131072,896]{1,0} %x), "
             "kind=kLoop")
OTHER = "%fusion.5 = bf16[16384,2304]{1,0} fusion(bf16[16384,2304]{1,0} %x), kind=kLoop"


def hf():
    return common.hf_of(common.load_config(CONFIG))


def fam():
    return loadgen.load_family(hf())


def test_the_configuration_is_the_catalog_entry_cut_to_the_chips_share():
    h, cfg = hf(), common.load_config(CONFIG)
    assert h["model_type"] == "mellum"
    assert (h["num_hidden_layers"], h["num_experts"], h["vocab_size"]) == (4, 16, V)
    assert (h["num_experts_router"], h["expert_first"],
            h["num_experts_per_tok"]) == (64, 0, 8)
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    # the published lists are kept whole; the cut reads their first four entries
    assert len(h["layer_types"]) == 28 and h["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert set(h["mlp_layer_types"]) == {"sparse"}
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):        # every other published number as it is
        with open(path) as f:
            cat = next(json.loads(ln) for ln in f
                       if '"Mellum2-12B-A2.5B-Instruct"' in ln)
        assert cfg["source"] == cat["source_url"]
        for k, v in cat["config"].items():
            if k not in cfg["reduced"]:
                assert h[k] == v, k
        assert (cat["config"]["num_hidden_layers"], cat["config"]["num_experts"],
                cat["config"]["vocab_size"]) == (28, 64, V_ALL)
    for key in ("qk_norm", "mtp", "unread", "aux_loss", "rope", "weights"):
        assert key in cfg["assumed"], key
    assert "28-chip" in cfg["deployment"] and "NOT here" in cfg["deployment"]
    run = cfg["run"]
    assert run["job"] == "train" and run["chips"] == 1
    assert run["overrides"]["remat"] and run["overrides"]["loss_chunk"] > 0
    assert run["engine"]["zero_optimization"] == {"stage": 1}
    assert run["engine"]["bf16"] == {"enabled": True}
    assert 0 < cfg["correct"]["loss_rel_tol"] <= 0.02
    # floors of the model-configs guide: a whole period and four layers,
    # >= 8 experts, >= 1/8 of the vocabulary
    assert h["num_hidden_layers"] >= 4 and h["num_experts"] >= 8 and 8 * V >= V_ALL
    t = loadgen.load_traffic("seq8192")
    assert (t["kind"], t["seq_len"], t["tokens_per_step"], t["pool_batches"]) == (
        "fixed-token-batch", S, 16384, 8)


def test_the_parameter_count_is_the_published_one_and_the_cuts():
    f, h = fam(), hf()
    assert (EXPERT, ATTN, ROUTER) == (6_193_152, 21_233_664, 147_456)
    assert f.block_params(h, "attn") == f.block_params(h, "wattn") == ATTN
    assert f.block_params(h, "moe") == 16 * EXPERT + ROUTER
    assert f.block_params(h, "moe", 2.0) == 2 * EXPERT + ROUTER
    assert [k for k, _ in f.blocks(h)] == ["wattn", "moe"] * 3 + ["attn", "moe"]
    cut = 4 * (ATTN + ROUTER + 16 * EXPERT) + 2 * V * H
    assert f.param_count(h) == cut == 595_132_416               # 595.1 M
    assert round(16 * cut / 2 ** 30, 2) == 8.87                 # GiB of ZeRO-1 state
    full = dict(h, num_hidden_layers=28, num_experts=64, vocab_size=V_ALL)
    whole = 28 * (ATTN + ROUTER + 64 * EXPERT) + 2 * V_ALL * H
    assert f.param_count(full) == whole == 12_149_784_576       # the published 12B
    active = 28 * (ATTN + ROUTER + 8 * EXPERT) + 2 * V_ALL * H
    assert round(active / 1e9, 2) == 2.44                       # A2.5B
    assert f.count(full, "wattn") == 21 and f.count(full, "attn") == 7
    assert f.router_width(h) == 64 and f.held_share(h) == 0.25
    assert f.held_share(full) == 1.0


def test_the_toy_keeps_one_period_and_every_mechanism():
    f = fam()
    toy = common.hf_of(common.load_config(CONFIG), rehearsal=True)
    assert [k for k, _ in f.blocks(toy)] == ["wattn", "moe"] * 3 + ["attn", "moe"]
    assert (toy["num_experts"], toy["num_experts_router"],
            toy["num_experts_per_tok"]) == (8, 32, 8)
    assert toy["num_attention_heads"] == 8 * toy["num_key_value_heads"]
    assert toy["sliding_window"] == W and "sliding_window" not in f.TOY
    assert toy["rope_parameters"]["full_attention"]["rope_type"] == "yarn"
    assert len(f.DEFECTS) == 8


def test_the_work_of_a_token_and_of_a_step():
    f, h = fam(), hf()
    # 6 x (4 x 33.8 M + 56.6 M) = 1.15 GFLOP of matmul a token
    used = 4 * (ATTN + ROUTER + 2 * EXPERT) + V * H
    assert round(6 * used / 1e9, 2) == 1.15
    # query i sees keys max(0, i - W + 1) .. i
    assert f.band_pairs(S, W) == S * W - W * (W - 1) / 2 == 7_864_832
    assert f.band_pairs(S, S) == S * (S + 1) / 2
    pairs = f.attention_pairs(h, S)
    assert pairs == {"attn": S * (S + 1) / 2, "wattn": 3 * 7_864_832}
    assert f.pair_flops(h) == 2 * 32 * 128
    attn = 3 * 2 * f.pair_flops(h) * (pairs["attn"] + pairs["wattn"]) / S
    assert f.train_flops_per_token(h, S) == 6.0 * used + attn
    assert round(attn / 1e9, 2) == 0.34 and round(
        f.train_flops_per_token(h, S) / 1e9, 2) == 1.49
    # the banded layers at the causal kernel's cost would be 4.3 x theirs
    band, full = f.flash_band_flops(h, 2, S), f.flash_flops(h, 2, S)
    assert band["total"] == 7 * 2 * f.pair_flops(h) * 7_864_832
    assert band["bwd"] == 2.5 * band["fwd"] and full["bwd"] == 2.5 * full["fwd"]
    assert full["total"] / band["total"] == pytest.approx(4.27, abs=0.01)
    # 16384 tokens x 8 x 16 / 64 rows on the held experts: 2048 an expert
    assert f.expected_held_rows(h, 16384) == 32768 == 16 * 2048
    assert f.moe_gmm_train_flops(h, 16384) == 3 * 2 * 32768 * EXPERT
    assert f.moe_gmm_train_bytes(h, 16384) == 2 * (3 * 16 * EXPERT
                                                   + 6 * 32768 * H)
    # compute-bound by a wide margin at 2048 rows an expert
    pk = peaks.peaks_for("TPU v5 lite")
    assert (f.moe_gmm_train_flops(h, 16384) / pk["bf16_flops_per_s"]
            > 2 * f.moe_gmm_train_bytes(h, 16384) / pk["hbm_bytes_per_s"])


def test_the_yarn_table_of_the_published_group():
    import numpy as np
    f, h = fam(), hf()
    group = h["rope_parameters"]["full_attention"]
    assert f.yarn_band(group, 128) == (18, 35)
    freqs, factor = f.rope_table(group, 128)
    plain, one = f.rope_table(h["rope_parameters"]["sliding_attention"], 128)
    assert factor == 1.2772588722239782 and one == 1.0
    assert np.array_equal(plain, 500000.0 ** (-2.0 * np.arange(64) / 128))
    assert np.array_equal(freqs[:19], plain[:19])            # pairs 0..18
    assert np.allclose(freqs[35:], plain[35:] / 16, rtol=1e-15)   # 35..63
    ramp = freqs[19:35] / plain[19:35]
    assert np.all(np.diff(ramp) < 0) and 1 / 16 < ramp[-1] < ramp[0] < 1


def test_the_family_finds_its_kernels_by_name():
    f = fam()
    assert f.kernel(GMM) == f.kernel(GMM_DX) == "moe_gmm"
    assert f.kernel(GMM_DW) == "moe_gmm_dw"
    assert f.kernel(BAND) == "flash_fwd_band"
    assert f.kernel(BAND_DQ) == "flash_bwd_band_dq"
    assert f.kernel(BAND_DKV) == "flash_bwd_band_dkv"
    for name in (FLASH, LOOKALIKE, OTHER):
        assert f.kernel(name) is None, name


def fake_run(events, steps, family=None):
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OPS_LINE, "events": [list(e) for e in events]},
        {"name": trace_reduce.MODULES_LINE, "events": [
            [f"jit_train_step({i})", 0.0, 1e6] for i in range(steps)]}]}
    raw = {"planes": [plane, {"name": trace_reduce.HOST_PLANE, "lines": [
        {"name": "t", "events": [[trace_reduce.WINDOW_SPAN, 0.0, 1e12]]}]}]}
    return {"trace": trace_reduce.reduce(raw), "family": family or fam(),
            "hf": hf(), "peaks": peaks.peaks_for("TPU v5 lite"), "chips": 1,
            "cell": {"name": CELL},
            "counters": {"tokens_per_step": 16384, "sequences_per_step": 2,
                         "seq_len": S, "num_layers": 8}}


def test_the_four_readers_on_a_hand_built_trace():
    f, h = fam(), hf()
    share, roof, bshare, broof = (loadgen.load_module("layer_metrics", n) for n in (
        "moe_share_of_step", "moe_gmm_train_roofline",
        "flash_band_share_of_step", "flash_band_bwd_roofline"))
    peak = 197e12
    # TWO steps: the expert kernels at twice their floor (a forward replay
    # among them: time, no work), the banded backward at four times its floor,
    # the banded forward and other ops besides
    gmm_floor = 2 * 4 * f.moe_gmm_train_flops(h, 16384) / peak
    bwd_floor = 2 * 3 * f.flash_band_flops(h, 2, S)["bwd"] / peak
    events, t = [], 0.0
    for name, dur in ((GMM, 0.8 * gmm_floor), (GMM_DX, 0.6 * gmm_floor),
                      (GMM_DW, 0.6 * gmm_floor), (BAND_DQ, 2 * bwd_floor),
                      (BAND_DKV, 2 * bwd_floor), (BAND, bwd_floor),
                      (FLASH, bwd_floor), (LOOKALIKE, gmm_floor)):
        events.append((name, t, dur * 1e9))
        t += dur * 1e9 + 10.0
    busy = 3 * gmm_floor + 6 * bwd_floor
    run = fake_run(events, steps=2)
    assert run["trace"]["busy_s"] == pytest.approx(busy)
    assert share.read(run) == pytest.approx(100 * 2 * gmm_floor / busy)
    assert roof.read(run) == pytest.approx(50.0)
    assert bshare.read(run) == pytest.approx(100 * 5 * bwd_floor / busy)
    assert broof.read(run) == pytest.approx(25.0)
    # a program without these kernels (the parent's, the dense model's): nothing
    bare = fake_run([(OTHER, 0.0, 1e6), (FLASH, 2e6, 1e6)], steps=2)
    assert all(r.read(bare) is None for r in (share, roof, bshare, broof))
    other = fake_run(events, steps=2,
                     family=loadgen.load_family({"model_type": "mistral"}))
    assert all(r.read(other) is None for r in (share, roof, bshare, broof))
    assert all(r.read(dict(run, trace=None)) is None
               for r in (share, roof, bshare, broof))


def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "seq8192", 1)
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert conf["source"] == common.load_config(CONFIG)["source"]
    where = {m["name"]: m.get("workloads") for m in b["end_to_end"] + b["per_layer"]}
    mine = ("moe_share_of_step", "moe_gmm_train_roofline",
            "flash_band_share_of_step", "flash_band_bwd_roofline")
    for name in ("train_tokens_per_s_per_chip", "train_step_ms",
                 "hbm_in_use_gib") + mine:
        assert CELL in where[name], name
    for name in mine:
        assert where[name] == [CELL], name
    # both read EVERY Mosaic call as attention: false once `moe_gmm` is one
    for name in ("attn_share_of_step", "flash_attention_roofline",
                 "collective_exposed_share", "serve_tokens_per_s"):
        assert CELL not in where[name], name
    moves = {m["name"]: m["moves"] for m in b["per_layer"]}
    assert all(moves[n] == "train_tokens_per_s_per_chip" for n in mine)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert len(b["workloads"]) >= 10


@pytest.fixture(scope="module")
def toy():
    """A toy model with a window of 16 in float32 (remat and the chunked
    loss on, as the cell runs), its plain reference, and 80 positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    f = fam()
    hf_toy = dict(common.hf_of(common.load_config(CONFIG), rehearsal=True),
                  sliding_window=16, max_position_embeddings=256)
    hf_toy["rope_parameters"] = dict(hf_toy["rope_parameters"], full_attention=dict(
        hf_toy["rope_parameters"]["full_attention"], rope_theta=10000, factor=4,
        original_max_position_embeddings=32, attention_factor=None))
    hf_toy["rope_parameters"]["sliding_attention"] = {"rope_type": "default",
                                                      "rope_theta": 10000}
    cfg = hf_config_to_transformer(hf_toy, dtype=jnp.float32, remat=True,
                                   remat_policy="save_nothing", loss_chunk=16)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    ids = np.random.default_rng(0).integers(0, hf_toy["vocab_size"], (2, 80))
    got = np.asarray(model.apply(params, jnp.asarray(ids[0])[None])[0])
    return f, hf_toy, model, params, ids, got


def test_the_program_is_the_plain_reference_on_logits(toy):
    import numpy as np
    f, hf_toy, _, params, ids, got = toy
    assert np.abs(got - f.Reference(hf_toy, params).logits(ids[0])).max() < 1e-5


def test_the_program_is_the_plain_reference_on_the_loss_and_every_gradient(toy):
    import jax
    import jax.numpy as jnp
    f, hf_toy, model, params, ids, _ = toy
    ref = f.Reference(hf_toy, params)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(
        p, {"input_ids": jnp.asarray(ids)}, None, False)))(params)
    want, want_grads = jax.value_and_grad(ref.loss_fn)(params, ids)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(loss) == pytest.approx(ref.loss(ids), rel=2e-6)
    got, wants = jax.tree.leaves(grads), jax.tree.leaves(want_grads)
    assert len(got) == len(wants) == 22
    for g, w in zip(got, wants):
        top = float(jnp.abs(w).max())
        assert top > 0 and float(jnp.abs(g - w).max()) < 2e-4 * top + 1e-7


@pytest.mark.parametrize("defect", [
    "precision_below", "no_qk_norm", "yarn_on_sliding", "plain_on_full",
    "no_attention_factor", "no_renorm", "renorm_over_held", "band_off_by_one"])
def test_each_seeded_defect_fails_on_logits_at_toy_widths(toy, defect):
    """Every defect the configuration's ``correct.why`` names moves the toy's
    logits (of size ~1) by more than a hundred times what the sound program
    differs from the plain reference by (3e-6)."""
    import numpy as np
    f, hf_toy, _, params, ids, got = toy
    bad = f.Reference(hf_toy, params, defect=defect).logits(ids[0])
    assert np.abs(got - bad).max() > 1e-3
    assert defect in f.DEFECTS


def test_precision_below_rounds_every_operand():
    import jax.numpy as jnp
    import numpy as np
    f, h = fam(), hf()
    below, plain = f.Reference(h, None, defect="precision_below"), f.Reference(h, None)
    a = jnp.asarray([0.013, 1.3, -0.7, 100.0], jnp.float32)
    assert np.array_equal(np.asarray(below._lo(a)), [0.013671875, 1.25, -0.75, 96.0])
    assert np.array_equal(np.asarray(plain._lo(a)), np.asarray(a))
    with pytest.raises(ValueError, match="one of"):
        f.Reference(h, None, defect="no_such_defect")


def test_the_defect_tool_judges_through_the_harness_check():
    src = open(os.path.join(ROOT, "benchmark", "tools", "mellum_defects.py")).read()
    assert "correct.check_loss_vs_reference(" in src and "def judge" not in src
    assert 'CELL = "' + CELL + '"' in src


def test_the_cell_rehearses_on_the_cpu():
    """A train step at toy widths: 2 x 1024 tokens, ~35 s in all. The whole
    path runs and the engine's loss is the reference's; whether the loss
    FALLS is the chip's to judge: at the cell's learning rate (1e-6, the
    configuration says why) a toy's half dozen steps move it by less than the
    pool's batches differ, so the harness's ``loss_finite_and_falling`` — the
    first steps against the last — reads noise here, and ``correct`` (and
    the rehearsal's exit code) with it."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seconds", "4", "--seed", "4800000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode in (0, 1), p.stdout[-2000:] + p.stderr[-2000:]
    last = [ln for ln in p.stdout.splitlines() if ln.startswith("REHEARSAL")][-1]
    assert "train_tokens_per_s_per_chip" in last and "train_step_ms" in last
    checks = {c["name"]: c for c in (json.loads(ln.split("check ", 1)[1])
              for ln in p.stdout.splitlines() if ln.startswith("[bench] check "))}
    assert checks["loss_vs_reference"]["ok"], checks
    assert checks["loss_vs_reference"]["family"] == "benchmark.families.mellum"
    assert checks["no_compile_in_window"]["ok"]
    assert checks["loss_finite_and_falling"]["non_finite_steps"] == 0
    assert "2 x 1024 tokens per step" in p.stdout
