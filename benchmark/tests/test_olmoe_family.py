"""The OLMoE family (families/olmoe.py) and its cell: the cost model against
pinned numbers and hand counts, the two trace readers on hand-built events,
and the cell's rehearsal on the CPU."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen, peaks, program_spans, trace_reduce  # noqa: E402

CELL = "olmoe-1b-7b-serve.batch-longprompt"
H, F, E, K, V, L = 2048, 1024, 64, 8, 50304, 14
ATTN = 4 * H * H                       # 16 heads of 128, as many kv heads
EXPERT = 3 * H * F                     # up, gate, down of ONE expert
LAYER = ATTN + H * E + E * EXPERT      # + router


def hf():
    return common.hf_of(common.load_config("olmoe-1b-7b-serve"))


def fam():
    return loadgen.load_family(hf())


def test_the_configuration_is_the_catalog_entry_with_the_depth_cut():
    h = hf()
    assert h["model_type"] == "olmoe" and h["num_hidden_layers"] == L
    assert (h["hidden_size"], h["intermediate_size"], h["num_experts"],
            h["num_experts_per_tok"], h["vocab_size"]) == (H, F, E, K, V)
    assert h["norm_topk_prob"] is False and h["tie_word_embeddings"] is False
    cfg = common.load_config("olmoe-1b-7b-serve")
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["run"]["overrides"] == {}            # the import says dropless
    assert cfg["run"]["serving"] == {"max_seqs": 32, "max_model_len": 1024}


def test_parameters_per_layer_and_what_a_token_uses():
    f, h = fam(), hf()
    assert LAYER == 419_561_472
    assert f.layer_params(h) == LAYER
    assert f.layer_params(h, K) == ATTN + H * E + K * EXPERT == 67_239_936
    # all 16 published layers + embedding + head + norms: the published 6.9 B
    assert 16 * (LAYER + 4 * H) + 2 * V * H + H == 6_919_161_856


def test_pinned_costs_at_published_widths():
    f, h = fam(), hf()
    used = L * (ATTN + H * E + K * EXPERT) + V * H
    attn = 3 * L * (2 * 2 * 1024 * 16 * 128)       # causal half, 2 matmuls
    assert f.train_flops_per_token(h, 2048) == 6 * used + attn == 6_618_611_712.0
    assert f.flash_flops(h, batch=1, seq_len=1024) == {
        "fwd": 2 * (2 * 16 * 1024 * 1024 * 128 / 2),
        "bwd": 5 * (2 * 16 * 1024 * 1024 * 128 / 2),
        "total": 7 * (2 * 16 * 1024 * 1024 * 128 / 2)}
    assert f.moe_ffn_flops(h, 256) == 2 * 256 * EXPERT == 3_221_225_472
    # 60.5 touched experts' matrices + 256 rows in and out, bf16
    assert f.moe_ffn_bytes(h, 256, 60.5) == 2 * (60.5 * EXPERT + 2 * 256 * H) == 763_363_328


@pytest.mark.parametrize("bits,per_token", [(8, 2 * L * 16 * 132), (0, 2 * L * 16 * 256)])
def test_decode_step_bytes_charge_the_touched_experts_only(bits, per_token):
    f, h = fam(), hf()
    counters = {"kv_cache_bits": bits, "mean_live_tokens": 12345.5, "max_seqs": 32,
                "stats": {"moe_experts_touched_per_step": 60.5}}
    want = 2 * (L * (ATTN + H * E + 60.5 * EXPERT) + V * H) + per_token * 12345.5
    assert f.decode_step_bytes(h, counters) == want
    # a step that touched fewer experts needs fewer bytes; without the
    # counter every expert is charged
    fewer = dict(counters, stats={"moe_experts_touched_per_step": 40.0})
    assert f.decode_step_bytes(h, fewer) == want - 2 * L * 20.5 * EXPERT
    assert f.decode_step_bytes(h, dict(counters, stats={})) == want + 2 * L * 3.5 * EXPERT
    assert want == {8: 12_067_267_200.0, 0: 12_753_084_416.0}[bits]      # pinned


# ---- the two trace readers, on events shaped like the chip's -------------------

GMM_STEP = ("%moe_gmm.12 = bf16[256,1024]{1,0:T(8,128)(2,1)S(1)} custom-call(s32[1]{0:T(128)} "
            "%bitcast.3, s32[65]{0:T(128)S(1)} %fusion.9, bf16[256,2048]{1,0} %x, "
            "bf16[14,64,2048,1024]{3,2,1,0:T(8,128)(2,1)} %param.7), "
            'custom_call_target="tpu_custom_call"')
GMM_PREFILL = GMM_STEP.replace("%moe_gmm.12", "%moe_gmm.3").replace("[256,", "[4096,")
ONE_HOT_STEP = ("%fusion.316 = bf16[64,32,1024]{2,1,0:T(8,128)(2,1)} fusion(bf16[64,32,2048]{2,1,0} "
                "%fusion.314, bf16[14,64,2048,1024]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.9, "
                "s32[] %p), kind=kOutput, calls=%fused_computation.12")
OTHER = "%fusion.299 = s32[32,16,64,16,128]{4,3,2,1,0} fusion(s8[32,16,64,16,128]{4,3,2,1,0} %p)"


def test_the_family_finds_the_expert_matmuls_in_both_forms():
    f, h = fam(), hf()
    assert f.expert_matmul(GMM_STEP, h) == (32, 1)             # 256 rows / top-8
    assert f.expert_matmul(GMM_PREFILL, h) == (512, 1)
    assert f.expert_matmul(GMM_STEP.replace("%moe_gmm.12", "%gmm.2"), h) == (32, 1)
    # XLA's own ragged_dot, as the chip names it; its metadata call is not one
    assert f.expert_matmul(GMM_STEP.replace("%moe_gmm.12", "%ragged-dot-none.2"), h) == (32, 1)
    assert f.expert_matmul(GMM_STEP.replace("%moe_gmm.12", "%ragged-dot-none"), h) == (32, 1)
    assert f.expert_matmul(
        "%ragged-dot-metadata = (s32[65]{0:T(128)S(1)}, s32[64]{0:T(128)S(1)}) custom-call("
        "s32[64]{0:T(128)} %g.1), custom_call_target=\"tpu_custom_call\"", h) is None
    assert f.is_grouped_matmul(GMM_STEP) and f.is_grouped_matmul(GMM_PREFILL)
    assert not f.is_grouped_matmul(ONE_HOT_STEP) and not f.is_grouped_matmul(OTHER)
    assert f.expert_matmul(ONE_HOT_STEP, h) == (32, 1)         # every expert x 32 rows
    # XLA fuses the down projection with the combine: the result is the
    # tokens', the operands say what it is; and it may fuse two matmuls
    down = ("%bitcast_add_fusion.2 = bf16[1,192,2048]{2,1,0} fusion(bf16[1,192,2048]{2,1,0} %x, "
            "bf16[64,192,1024]{2,1,0} %act, bf16[14,64,1024,2048]{3,2,1,0} %w), kind=kOutput")
    assert f.expert_matmul(down, h) == (192, 1)
    two = ONE_HOT_STEP.replace("s32[] %p", "bf16[14,64,2048,1024]{3,2,1,0} %gate, s32[] %p")
    assert f.expert_matmul(two, h) == (32, 2)
    assert f.expert_matmul(OTHER, h) is None
    assert f.expert_matmul("%gmm_like.1 = bf16[4,4]{1,0} fusion(bf16[4,4] %a)", h) is None
    # a copy of a layer's slice of the stack, or a fusion that reads the
    # attention's stack, is not one
    assert f.expert_matmul("%f.1 = bf16[64,2048,1024]{2,1,0} fusion(bf16[14,64,2048,1024]{3,2,1,0} %w, s32[] %i)", h) is None
    assert f.expert_matmul(ONE_HOT_STEP.replace("[14,64,2048,1024]", "[14,2048,2048]"), h) is None


def fake_run(events, stats):
    """A traced run as run.py hands it to a reader, with one device plane
    holding ``events`` [(name, start_ns, dur_ns)] inside a 1 s window."""
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OPS_LINE, "events": [list(e) for e in events]},
        {"name": trace_reduce.MODULES_LINE, "events": []}]}
    raw = {"planes": [plane, {"name": trace_reduce.HOST_PLANE, "lines": [
        {"name": "t", "events": [[trace_reduce.WINDOW_SPAN, 0.0, 1e9]]}]}]}
    h = hf()
    return raw, {"trace": trace_reduce.reduce(raw), "family": fam(), "hf": h,
                 "peaks": peaks.peaks_for("TPU v5 lite"), "cell": {"name": CELL},
                 "counters": {"max_seqs": 32, "mean_occupancy": 30.0, "stats": stats}}


def test_share_and_roofline_of_the_grouped_matmuls(monkeypatch):
    share = loadgen.load_module("layer_metrics", "sat_moe_share_of_device")
    roof = loadgen.load_module("layer_metrics", "sat_moe_ffn_roofline")
    f, h = fam(), hf()
    # two decode-step matmuls (one of each form) that take exactly twice
    # their memory floor, one prefill matmul at its floor, and as much again
    # of something else
    step_floor = f.moe_ffn_bytes(h, 30.0 * K, 60.0) / 3 / 819e9
    pre_floor = max(f.moe_ffn_bytes(h, 4096, 64.0) / 3 / 819e9,
                    f.moe_ffn_flops(h, 4096) / 3 / 197e12)
    took = 4 * step_floor + pre_floor
    events = [(GMM_STEP, 0.0, 2 * step_floor * 1e9), (ONE_HOT_STEP, 0.05e9, 2 * step_floor * 1e9),
              (GMM_PREFILL, 0.1e9, pre_floor * 1e9), (OTHER, 0.5e9, took * 1e9)]
    stats = {"moe_experts_touched_per_step": 60.0, "moe_experts_touched_per_prefill": 64.0}
    raw, run = fake_run(events, stats)
    monkeypatch.setattr(program_spans, "find_xplane", lambda cell: "a-trace")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) == pytest.approx(50.0)
    assert roof.read(run) == pytest.approx(100.0 * (2 * step_floor + pre_floor) / took)
    assert roof.read(run) < 100.0
    # the sorted form alone: the two kernel calls, not the one-hot fusion
    sorted_share = loadgen.load_module("layer_metrics", "sat_moe_sorted_share_of_device")
    sorted_roof = loadgen.load_module("layer_metrics", "sat_moe_sorted_ffn_roofline")
    assert sorted_share.read(run) == pytest.approx(50.0 * (2 * step_floor + pre_floor) / took)
    assert sorted_roof.read(run) == pytest.approx(
        100.0 * (step_floor + pre_floor) / (2 * step_floor + pre_floor))
    # the parent's program: no such op, no such counter -> nothing, not zero
    raw, run = fake_run([(OTHER, 0.0, 1e6)], {})
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) is None and roof.read(run) is None
    assert sorted_share.read(run) is None and sorted_roof.read(run) is None
    for name in ("sat_moe_load_max_over_mean", "sat_moe_experts_touched"):
        assert loadgen.load_module("layer_metrics", name).read(run) is None
    run["counters"]["stats"] = {"moe_load_max_over_mean": 1.5, "moe_experts_touched_per_step": 61.0}
    assert loadgen.load_module("layer_metrics", "sat_moe_load_max_over_mean").read(run) == 1.5
    assert loadgen.load_module("layer_metrics", "sat_moe_experts_touched").read(run) == 61.0


def test_the_cell_rehearses_on_the_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seconds", "3", "--seed", "2600000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = [l for l in p.stdout.splitlines() if l.startswith("REHEARSAL")][-1]
    assert '"correct": true' in last and "serve_tokens_per_s" in last
    assert "benchmark.families.olmoe" in p.stdout
