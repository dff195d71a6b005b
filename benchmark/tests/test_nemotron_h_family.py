"""The Nemotron-H family (families/nemotron_h.py) and its cell: the cost
model's arithmetic against hand counts (31.58 B at the published 52 blocks,
6.073 B at the cut), ``decode_step_bytes`` with and without live slots, the
readers of the recurrence's kernels on hand-built trace events, and the
cell's entries in ``BENCHMARK.json``."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen, peaks, trace_reduce  # noqa: E402

CONFIG = "nemotron-3-nano-30b-serve"
CELL = CONFIG + ".batch-reasoning"
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
H, F, FS, E, K, V = 2688, 1856, 3712, 128, 6, 131072
EXPERT = 2 * H * F                                   # up and down, no gate
E_BLOCK = E * EXPERT + 2 * H * FS + H * E
M_BLOCK = H * (4096 + 6144 + 64) + 4096 * H
A_BLOCK = 2 * H * 32 * 128 + 2 * H * 2 * 128
STATE = 64 * 64 * 128 * 4 + 3 * 6144 * 2             # one slot, one M block


def hf():
    return common.hf_of(common.load_config(CONFIG))


def fam():
    return loadgen.load_family(hf())


def test_the_configuration_is_the_catalog_entry_with_the_pattern_cut():
    h, cfg = hf(), common.load_config(CONFIG)
    assert h["model_type"] == "nemotron_h"
    assert (h["num_hidden_layers"], h["hybrid_override_pattern"]) == (9, "MEMEM*EME")
    assert PUBLISHED.startswith(h["hybrid_override_pattern"]) and len(PUBLISHED) == 52
    assert (h["hidden_size"], h["moe_intermediate_size"],
            h["moe_shared_expert_intermediate_size"], h["n_routed_experts"],
            h["num_experts_per_tok"], h["vocab_size"]) == (H, F, FS, E, K, V)
    assert (h["mamba_num_heads"], h["mamba_head_dim"], h["n_groups"],
            h["ssm_state_size"], h["conv_kernel"], h["chunk_size"]) \
        == (64, 64, 8, 128, 4, 128)
    assert (h["num_attention_heads"], h["num_key_value_heads"], h["head_dim"]) \
        == (32, 2, 128)
    assert h["routed_scaling_factor"] == 2.5 and h["norm_topk_prob"] is True
    assert sorted(cfg["reduced"]) == ["hybrid_override_pattern", "num_hidden_layers"]
    assert cfg["run"]["overrides"] == {} and cfg["run"]["init_serving"] == {}
    assert cfg["run"]["serving"] == {"max_seqs": 128, "max_model_len": 2560}
    assert "decode_backend" not in cfg["run"]["expect"]
    for key in ("weights", "rotary", "scoring", "d_inner", "experts"):
        assert key in cfg["assumed"], key


def test_the_parameter_count_is_the_published_one():
    f, h = fam(), hf()
    assert (EXPERT, E_BLOCK, M_BLOCK, A_BLOCK) == (
        9_977_856, 1_297_465_344, 38_707_200, 23_396_352)
    assert f.block_params(h, "moe") == E_BLOCK
    assert f.block_params(h, "mamba") == M_BLOCK
    assert f.block_params(h, "attn") == A_BLOCK
    assert f.block_params(h, "moe", K) == K * EXPERT + 2 * H * FS + H * E
    full = dict(h, num_hidden_layers=52, hybrid_override_pattern=PUBLISHED)
    kinds = [k for k, _ in f.blocks(full)]
    assert (kinds.count("mamba"), kinds.count("moe"), kinds.count("attn")) == (23, 23, 6)
    assert f.param_count(full) == 23 * E_BLOCK + 23 * M_BLOCK + 6 * A_BLOCK + 2 * V * H \
        == 31_576_989_696                                   # the published 31.6 B
    assert f.param_count(h) == 4 * E_BLOCK + 4 * M_BLOCK + A_BLOCK + 2 * V * H \
        == 6_072_729_600                                    # 11.31 GiB in bf16
    assert round(2 * f.param_count(h) / 2 ** 30, 2) == 11.31


def test_the_toy_keeps_the_pattern_and_every_mechanism():
    f = fam()
    toy = common.hf_of(common.load_config(CONFIG), rehearsal=True)
    assert toy["hybrid_override_pattern"] == "MEMEM*EME"
    assert toy["n_routed_experts"] == 8 and toy["num_experts_per_tok"] == K
    assert toy["mamba_num_heads"] % toy["n_groups"] == 0
    assert len(f.DEFECTS) == 12


@pytest.mark.parametrize("bits,per_token", [(8, 2 * 2 * 132), (0, 2 * 2 * 256)])
def test_decode_step_bytes(bits, per_token):
    """Weights of what the step touched + the live K/V of ONE attention
    block + the live slots' state read and written."""
    f, h = fam(), hf()
    counters = {"kv_cache_bits": bits, "mean_live_tokens": 70000.5, "max_seqs": 128,
                "mean_occupancy": 120.5,
                "stats": {"moe_experts_touched_per_step": 100.25}}
    weights = 2 * (4 * (100.25 * EXPERT + 2 * H * FS + H * E) + 4 * M_BLOCK
                   + A_BLOCK + V * H)
    want = weights + per_token * 70000.5 + 2 * 120.5 * 4 * STATE
    assert f.decode_step_bytes(h, counters) == want
    # no live slot: the weights (and nothing of the state pool)
    idle = dict(counters, mean_occupancy=0.0, mean_live_tokens=0.0)
    assert f.decode_step_bytes(h, idle) == weights
    # without the routing counter every expert is charged
    assert f.decode_step_bytes(h, dict(counters, stats={})) \
        == want + 2 * 4 * 27.75 * EXPERT
    assert f.state_bytes_per_slot(h) == 4 * STATE
    assert f.kv_bytes_per_token(h, bits) == per_token


def test_the_recurrence_cost_functions():
    f, h = fam(), hf()
    assert f.ssm_state_bytes(h) == 64 * 64 * 128 * 4 == 2_097_152
    assert f.ssm_step_bytes(h, 100.0) == 2 * 100 * STATE
    per_token = 2 * (8 * 128 * 128 + 64 * 128 * 64 + 2 * 64 * 64 * 128)
    assert f.ssm_scan_flops(h, 512) == 512 * per_token == 1_744_830_464
    assert f.ssm_scan_bytes(h, 512) == 512 * (2 * (2 * 4096 + 2 * 1024) + 4 * 64) \
        + 2 * 2_097_152


# ---- the readers, on events shaped like the chip's ----------------------------

SCAN = ('%ssm_scan.5 = (f32[64,512,64]{2,1,0:T(8,128)}, f32[64,512,64]{2,1,0:T(8,128)}, '
        'f32[64,64,128]{2,1,0:T(8,128)}) custom-call(bf16[64,512,64]{2,1,0} %x), '
        'custom_call_target="tpu_custom_call"')
STEP = ('%ssm_step.4 = (f32[128,4,64,16]{3,2,1,0:T(8,128)S(1)}, f32[4,128,64,64,128]'
        '{4,3,2,1,0:T(8,128)}) custom-call(f32[128,4,64,16]{3,2,1,0} %bitcast.7), '
        'custom_call_target="tpu_custom_call"')
GMM = ('%moe_gmm.9 = bf16[3072,1856]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %b, '
       'bf16[3072,2688]{1,0} %x, bf16[4,128,1856,2688]{3,2,1,0:T(8,128)(2,1)} %p), '
       'custom_call_target="tpu_custom_call"')
ONE_HOT = ('%fusion.31 = bf16[128,128,1856]{2,1,0:T(8,128)(2,1)} fusion(bf16[128,128,2688]'
           '{2,1,0} %f.3, bf16[4,128,1856,2688]{3,2,1,0:T(8,128)(2,1)} %p.7), kind=kOutput')
OTHER = "%fusion.299 = bf16[128,10304]{1,0} fusion(bf16[128,2688]{1,0} %p)"


def test_the_family_finds_its_kernels_by_name():
    f, h = fam(), hf()
    assert f.ssm_kernel(SCAN) == "scan" and f.ssm_kernel(STEP) == "step"
    assert f.ssm_kernel(OTHER) is None and f.ssm_kernel(GMM) is None
    assert f.ssm_kernel("%ssm_step_like.1 = f32[4]{0} fusion(f32[4] %a)") is None
    assert f.expert_matmul(GMM, h) == (512, 1) and f.is_grouped_matmul(GMM)
    assert f.expert_matmul(ONE_HOT, h) == (128, 1) and not f.is_grouped_matmul(ONE_HOT)
    assert f.expert_matmul(OTHER, h) is None and f.expert_matmul(STEP, h) is None


def fake_run(events, modules, counters):
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OPS_LINE, "events": [list(e) for e in events]},
        {"name": trace_reduce.MODULES_LINE, "events": [list(m) for m in modules]}]}
    raw = {"planes": [plane, {"name": trace_reduce.HOST_PLANE, "lines": [
        {"name": "t", "events": [[trace_reduce.WINDOW_SPAN, 0.0, 1e9]]}]}]}
    return raw, {"trace": trace_reduce.reduce(raw), "family": fam(), "hf": hf(),
                 "peaks": peaks.peaks_for("TPU v5 lite"), "cell": {"name": CELL},
                 "counters": counters}


def test_the_readers_of_the_recurrence(monkeypatch):
    from benchmark.harness import program_spans
    f, h = fam(), hf()
    share, step, scan, live = (loadgen.load_module("layer_metrics", n) for n in (
        "sat_ssm_share_of_device", "sat_ssm_step_roofline", "sat_ssm_scan_roofline",
        "sat_state_share_of_live_cache"))
    counters = {"mean_occupancy": 100.0, "mean_live_tokens": 80000.0,
                "kv_cache_bits": 8}
    # two decode steps x 4 Mamba blocks at twice their memory floor, one
    # prefill x 4 blocks at four times its floor, and as much of other ops
    step_floor = f.ssm_step_bytes(h, 100.0) / 819e9
    scan_floor = max(f.ssm_scan_flops(h, 512) / 197e12, f.ssm_scan_bytes(h, 512) / 819e9)
    events, t = [], 0.0
    for _ in range(8):
        events.append((STEP, t, 2 * step_floor * 1e9)); t += 3 * step_floor * 1e9
    for _ in range(4):
        events.append((SCAN, t, 4 * scan_floor * 1e9)); t += 5 * scan_floor * 1e9
    ssm_s = 16 * step_floor + 16 * scan_floor
    events.append((OTHER, t, ssm_s * 1e9))
    modules = [("jit_step(1)", 0.0, 1e6), ("jit_step(1)", 2e6, 1e6),
               ("jit_prefill(2)", 4e6, 1e6)]
    raw, run = fake_run(events, modules, counters)
    monkeypatch.setattr(program_spans, "find_xplane", lambda cell: "a-trace")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) == pytest.approx(50.0)
    assert step.read(run) == pytest.approx(50.0)
    assert scan.read(run) == pytest.approx(25.0)
    state, kv = 100.0 * 4 * STATE, 2 * 2 * 132 * 80000.0
    assert live.read(run) == pytest.approx(100.0 * state / (state + kv))
    # a program without the kernels (another family's, the parent's): nothing
    raw, run = fake_run([(OTHER, 0.0, 1e6)], modules, counters)
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) is None and step.read(run) is None and scan.read(run) is None
    run["family"] = loadgen.load_family({"model_type": "mistral"})
    assert share.read(run) is None and live.read(run) is None


def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "batch-reasoning", 1)
    assert b["workloads"][-1] == cell and b["configs"][-1]["name"] == CONFIG
    assert b["configs"][-1]["reduced"] == ["num_hidden_layers", "hybrid_override_pattern"]
    t = loadgen.load_traffic("batch-reasoning")
    assert t["kind"] == "saturating" and t["requests"] == 1500
    assert t["prompt"] == {"median": 256, "sigma": 0.8, "min": 64, "max": 1024}
    assert t["output"] == {"median": 512, "sigma": 0.7, "min": 128, "max": 1536}
    assert t["prompt"]["max"] + t["output"]["max"] == 2560
    where = {m["name"]: m.get("workloads") for m in b["end_to_end"] + b["per_layer"]}
    assert where["serve_tokens_per_s"][-1] == CELL
    for name in ("sat_batch_occupancy", "sat_host_share_of_round",
                 "sat_decode_step_device_ms", "sat_decode_step_roofline",
                 "sat_prefill_share_of_device", "serve_hbm_in_use_gib",
                 "sat_host_bound_idle_share", "sat_moe_share_of_device",
                 "sat_moe_sorted_share_of_device",
                 "sat_moe_load_max_over_mean", "sat_moe_experts_touched"):
        assert where[name][-1] == CELL, name
    for name in ("sat_ssm_share_of_device", "sat_ssm_step_roofline",
                 "sat_ssm_scan_roofline", "sat_state_share_of_live_cache"):
        assert where[name] == [CELL], name
    # its reader evaluates hf["num_experts"], which this family does not have
    assert CELL not in where["sat_moe_ffn_roofline"]
    assert CELL not in where["sat_moe_sorted_ffn_roofline"]


def test_precision_below_rounds_every_operand_and_every_kind_of_state():
    """The control that has to come out not correct: the whole forward one
    precision below the stated one, not one leaf at a time."""
    import jax.numpy as jnp
    import numpy as np
    f, h = fam(), hf()
    below, plain = f.Reference(h, None, defect="precision_below"), f.Reference(h, None)
    a = jnp.asarray([0.013, 1.3, -0.7, 100.0], jnp.float32)
    assert np.array_equal(np.asarray(below._lo(a)), [0.013671875, 1.25, -0.75, 96.0])
    assert np.array_equal(np.asarray(plain._lo(a)), np.asarray(a))
    assert (below._bf16_state, below._kv_4bit) == (True, True)
    assert (plain._bf16_state, plain._kv_4bit) == (False, False)
    one = f.Reference(h, None, defect="bf16_state")
    assert one._operand is None and one._bf16_state and not one._kv_4bit


def test_the_defect_tool_judges_through_the_harness_check(monkeypatch):
    """``tools/nemotron_h_defects.py`` calls ``check_tokens_vs_reference``
    itself; its adapter tells the reference each request's prompt length."""
    import importlib.util
    import numpy as np
    from benchmark.harness import correct
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_defects", os.path.join(ROOT, "benchmark", "tools",
                                           "nemotron_h_defects.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    class Ref:
        prompt_len, seen = None, []

        def logits(self, ids):
            self.seen.append((self.prompt_len, len(ids)))
            lg = np.zeros((len(ids), 8), np.float32)
            lg[np.arange(len(ids) - 1), np.asarray(ids[1:])] = 1.0   # the next id
            return lg

    samples = [(np.asarray([1, 2, 3], np.int32), np.asarray([4, 5], np.int32)),
               (np.asarray([6], np.int32), np.asarray([7, 0, 2], np.int32))]
    ref = Ref()
    chk = correct.check_tokens_vs_reference(
        samples, tool.PerRequest(ref, samples), 0.05, 0.6, 0.92, 0.033)
    assert ref.seen == [(3, 5), (1, 4)]
    assert chk["ok"] and (chk["positions"], chk["mismatched"]) == (5, 0)
    src = open(os.path.join(ROOT, "benchmark", "tools", "nemotron_h_defects.py")).read()
    assert "correct.check_tokens_vs_reference(" in src and "def judge" not in src


def test_the_cell_rehearses_on_the_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seconds", "20", "--seed", "3000000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = [ln for ln in p.stdout.splitlines() if ln.startswith("REHEARSAL")][-1]
    assert '"correct": true' in last and "serve_tokens_per_s" in last
    assert "sat_state_share_of_live_cache" in last
    assert "benchmark.families.nemotron_h" in p.stdout
