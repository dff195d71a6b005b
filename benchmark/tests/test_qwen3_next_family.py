"""The Qwen3-Next family (families/qwen3_next.py) and its cell: the cost
model's arithmetic against hand counts (79.67 B at the published 48 layers /
512 experts / whole vocabulary, 5.423 B at the cut), ``decode_step_bytes`` on
hand-made counters, the readers of the recurrence's kernels and of the held
share on hand-built trace events, the cell's rehearsal, and the cell's
entries in ``BENCHMARK.json`` — tested with ``in``, never by position: a
later PR appends after them (PERF.md section 7)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen, peaks, trace_reduce  # noqa: E402

CONFIG = "qwen3-next-80b-a3b-serve"
CELL = CONFIG + ".batch-reasoning"
H, F, V, V_ALL = 2048, 512, 37984, 151936
EXPERT = 3 * H * F                                    # gate, up and down
GDN = H * 12288 + H * 64 + 8192 * 4 + 4096 * H        # the mixer alone
ATTN = H * 8192 + 2 * H * 512 + 4096 * H
E_SIDE = H * 512 + EXPERT + H                          # router, shared, its gate
STATE = 32 * 128 * 128 * 4 + 3 * 8192 * 2              # one slot, one G block


def hf():
    return common.hf_of(common.load_config(CONFIG))


def fam():
    return loadgen.load_family(hf())


def test_the_configuration_is_the_catalog_entry_cut_to_the_chips_share():
    h, cfg = hf(), common.load_config(CONFIG)
    assert h["model_type"] == "qwen3_next"
    assert (h["num_hidden_layers"], h["num_experts"], h["vocab_size"]) == (12, 128, V)
    assert (h["num_experts_router"], h["expert_first"],
            h["num_experts_per_tok"]) == (512, 0, 10)
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):        # every other published number as it is
        with open(path) as f:
            cat = next(json.loads(ln) for ln in f
                       if '"Qwen3-Next-80B-A3B-Instruct"' in ln)
        assert cfg["source"] == cat["source_url"]
        for k, v in cat["config"].items():
            if k not in cfg["reduced"]:
                assert h[k] == v, k
        assert (cat["config"]["num_hidden_layers"], cat["config"]["num_experts"],
                cat["config"]["vocab_size"]) == (48, 512, V_ALL)
    assert cfg["run"]["overrides"] == {} and cfg["run"]["init_serving"] == {}
    assert cfg["run"]["serving"] == {"max_seqs": 128, "max_model_len": 2560}
    assert cfg["run"]["expect"] == {
        "kv_cache_bits": 8, "num_experts": 128, "moe_router_width": 512,
        "top_k": 10, "recurrent_blocks": 9, "attention_blocks": 3,
        "state_pool_dtype": "float32"}
    for key in ("weights", "mtp", "head_pairing", "rotary", "router", "norms"):
        assert key in cfg["assumed"], key
    assert "4 chips share" in cfg["deployment"] or "4 chips" in cfg["deployment"]


def test_the_parameter_count_is_the_published_one_and_the_cuts():
    f, h = fam(), hf()
    assert (EXPERT, GDN, ATTN, E_SIDE) == (3_145_728, 33_718_272, 27_262_976,
                                           4_196_352)
    assert f.block_params(h, "gdn") == GDN and f.block_params(h, "attn") == ATTN
    assert f.block_params(h, "moe") == 128 * EXPERT + E_SIDE
    assert f.block_params(h, "moe", 2.5) == 2.5 * EXPERT + E_SIDE
    kinds = [k for k, _ in f.blocks(h)]
    assert kinds[:8] == ["gdn", "moe", "gdn", "moe", "gdn", "moe", "attn", "moe"]
    assert (kinds.count("gdn"), kinds.count("attn"), kinds.count("moe")) == (9, 3, 12)
    cut = 9 * GDN + 3 * ATTN + 12 * (128 * EXPERT + E_SIDE) + 2 * V * H
    assert f.param_count(h) == cut == 5_423_030_272       # 5.423 B
    assert round(2 * cut / 2 ** 30, 2) == 10.10           # GiB in bf16
    full = dict(h, num_hidden_layers=48, num_experts=512, vocab_size=V_ALL)
    whole = 36 * GDN + 12 * ATTN + 48 * (512 * EXPERT + E_SIDE) + 2 * V_ALL * H
    assert f.param_count(full) == whole == 79_674_179_584  # the published 80B
    assert f.router_width(h) == 512 and f.held_share(h) == 0.25
    assert f.router_width(full) == 512 and f.held_share(full) == 1.0


def test_the_toy_keeps_one_period_and_every_mechanism():
    f = fam()
    toy = common.hf_of(common.load_config(CONFIG), rehearsal=True)
    assert [k for k, _ in f.blocks(toy)] == ["gdn", "moe"] * 3 + ["attn", "moe"]
    assert (toy["num_experts"], toy["num_experts_router"]) == (8, 32)
    assert toy["num_experts_per_tok"] == 10
    assert toy["linear_num_value_heads"] == 2 * toy["linear_num_key_heads"]
    assert len(f.DEFECTS) == 12


@pytest.mark.parametrize("bits,per_token", [(8, 2 * 3 * 2 * 260), (0, 2 * 3 * 2 * 512)])
def test_decode_step_bytes(bits, per_token):
    """Other weights + the TOUCHED held experts + the head slice + the live
    K/V of the 3 attention blocks + the live slots' state and tails read and
    written."""
    f, h = fam(), hf()
    counters = {"kv_cache_bits": bits, "mean_live_tokens": 70000.5, "max_seqs": 128,
                "mean_occupancy": 120.5,
                "stats": {"moe_experts_touched_per_step": 117.75}}
    weights = 2 * (9 * GDN + 3 * ATTN + 12 * (117.75 * EXPERT + E_SIDE) + V * H)
    want = weights + per_token * 70000.5 + 2 * 120.5 * 9 * STATE
    assert f.decode_step_bytes(h, counters) == want
    idle = dict(counters, mean_occupancy=0.0, mean_live_tokens=0.0)
    assert f.decode_step_bytes(h, idle) == weights
    # without the routing counter every HELD expert is charged, never the 512
    assert f.decode_step_bytes(h, dict(counters, stats={})) \
        == want + 2 * 12 * 10.25 * EXPERT
    assert f.state_bytes_per_slot(h) == 9 * STATE
    assert f.kv_bytes_per_token(h, bits) == per_token


def test_the_recurrence_cost_functions():
    f, h = fam(), hf()
    assert f.gdn_state_bytes(h) == 32 * 128 * 128 * 4 == 2_097_152
    assert f.conv_tail_bytes(h) == 3 * 8192 * 2
    assert f.gdn_step_bytes(h, 100.0) == 2 * 100 * STATE
    per_token = 2 * (16 * 2 * 64 * 128
                     + 32 * (64 * 64 / 3 + 64 * 384 + 3 * 128 * 128))
    assert f.gdn_chunk_flops(h, 512) == 512 * per_token
    assert f.gdn_chunk_bytes(h, 512) == 512 * (2 * (2 * 2048 + 2 * 4096) + 8 * 32) \
        + 2 * 2_097_152
    # the expert layer's need is this chip's share of the router's rows
    assert f.moe_ffn_flops(h, 1280) == 2 * 320 * EXPERT
    assert f.moe_ffn_bytes(h, 1280, 118.0) == 2 * (118 * EXPERT + 2 * 320 * H)


# ---- the readers, on events shaped like the chip's ----------------------------

CHUNK = ('%gdn_chunk.5 = (f32[32,8,64,128]{3,2,1,0:T(8,128)}, f32[32,128,128]'
         '{2,1,0:T(8,128)}) custom-call(bf16[32,8,64,128]{3,2,1,0} %q), '
         'custom_call_target="tpu_custom_call"')
STEP = ('%gdn_step.4 = (f32[128,32,1,128]{3,2,1,0:T(1,128)}, f32[9,128,32,128,128]'
        '{4,3,2,1,0:T(8,128)}) custom-call(f32[128,2,128,16]{3,2,1,0} %bitcast.7), '
        'custom_call_target="tpu_custom_call"')
GMM = ('%moe_gmm.9 = bf16[5120,512]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %b, '
       'bf16[5120,2048]{1,0} %x, bf16[12,128,512,2048]{3,2,1,0:T(8,128)(2,1)} %p), '
       'custom_call_target="tpu_custom_call"')
ONE_HOT = ('%fusion.31 = bf16[128,128,512]{2,1,0:T(8,128)(2,1)} fusion(bf16[128,128,2048]'
           '{2,1,0} %f.3, bf16[12,128,512,2048]{3,2,1,0:T(8,128)(2,1)} %p.7, '
           'bf16[12,128,2048,512]{3,2,1,0:T(8,128)(2,1)} %p.8), kind=kOutput')
OTHER = "%fusion.299 = bf16[128,12288]{1,0} fusion(bf16[128,2048]{1,0} %p)"


def test_the_family_finds_its_kernels_by_name():
    f, h = fam(), hf()
    assert f.gdn_kernel(CHUNK) == "chunk" and f.gdn_kernel(STEP) == "step"
    assert f.gdn_kernel(OTHER) is None and f.gdn_kernel(GMM) is None
    assert f.gdn_kernel("%gdn_step_like.1 = f32[4]{0} fusion(f32[4] %a)") is None
    assert f.expert_matmul(GMM, h) == (512, 1) and f.is_grouped_matmul(GMM)
    assert f.expert_matmul(ONE_HOT, h) == (128, 2) and not f.is_grouped_matmul(ONE_HOT)
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
    share, step, chunk, live = (loadgen.load_module("layer_metrics", n) for n in (
        "sat_gdn_share_of_device", "sat_gdn_step_roofline", "sat_gdn_chunk_roofline",
        "sat_state_share_of_live_cache"))
    counters = {"mean_occupancy": 100.0, "mean_live_tokens": 80000.0,
                "kv_cache_bits": 8}
    # two decode steps x 9 blocks at twice their memory floor, one prefill of
    # 512 positions x 9 blocks at four times its floor, and as much of other ops
    step_floor = f.gdn_step_bytes(h, 100.0) / 819e9
    chunk_floor = max(f.gdn_chunk_flops(h, 512) / 197e12,
                      f.gdn_chunk_bytes(h, 512) / 819e9)
    events, t = [], 0.0
    for _ in range(18):
        events.append((STEP, t, 2 * step_floor * 1e9)); t += 3 * step_floor * 1e9
    for _ in range(9):
        events.append((CHUNK, t, 4 * chunk_floor * 1e9)); t += 5 * chunk_floor * 1e9
    gdn_s = 36 * step_floor + 36 * chunk_floor
    events.append((OTHER, t, gdn_s * 1e9))
    modules = [("jit_step(1)", 0.0, 1e6), ("jit_step(1)", 2e6, 1e6),
               ("jit_prefill(2)", 4e6, 1e6)]
    raw, run = fake_run(events, modules, counters)
    monkeypatch.setattr(program_spans, "find_xplane", lambda cell: "a-trace")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) == pytest.approx(50.0)
    assert step.read(run) == pytest.approx(50.0)
    assert chunk.read(run) == pytest.approx(25.0)
    state, kv = 100.0 * 9 * STATE, 2 * 3 * 2 * 260 * 80000.0
    assert live.read(run) == pytest.approx(100.0 * state / (state + kv))
    # a program without the kernels (another family's, the parent's): nothing
    raw, run = fake_run([(OTHER, 0.0, 1e6)], modules, counters)
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) is None and step.read(run) is None and chunk.read(run) is None
    run["family"] = loadgen.load_family({"model_type": "nemotron_h"})
    assert share.read(run) is None and step.read(run) is None and chunk.read(run) is None


def test_the_reader_of_the_held_share():
    reader = loadgen.load_module("layer_metrics", "sat_moe_held_assignment_share")
    run = {"counters": {"stats": {"moe_assignments_asked": 4000.0,
                                  "moe_assignments_held": 1010.0}}}
    assert reader.read(run) == pytest.approx(25.25)
    # an engine that holds every expert (every other cell, the parent) has
    # no such counters: nothing, and no error
    assert reader.read({"counters": {"stats": {"moe_assignments": 7.0}}}) is None
    assert reader.read({"counters": {}}) is None


def test_the_expert_rooflines_charge_the_held_experts_at_three_matrices(monkeypatch):
    """``sat_moe_ffn_roofline``'s existing reader on this family: a decode
    step's one-hot fusion that streams two of the three stacks of the 128
    HELD experts, at exactly its floor, reads 100 %."""
    from benchmark.harness import program_spans
    f, h = fam(), hf()
    reader = loadgen.load_module("layer_metrics", "sat_moe_ffn_roofline")
    counters = {"mean_occupancy": 128.0, "max_seqs": 128, "stats": {
        "moe_experts_touched_per_step": 128.0,
        "moe_experts_touched_per_prefill": 128.0}}
    floor = 2 / 3 * f.moe_ffn_bytes(h, 1280, 128.0) / 819e9
    assert f.moe_ffn_bytes(h, 1280, 128.0) > 2 * 128 * EXPERT       # 3 matrices each
    assert f.moe_ffn_flops(h, 1280) / 197e12 < f.moe_ffn_bytes(h, 1280, 128.0) / 819e9
    raw, run = fake_run([(ONE_HOT, 0.0, floor * 1e9)], [("jit_step(1)", 0.0, 1e6)],
                        counters)
    monkeypatch.setattr(program_spans, "find_xplane", lambda cell: "a-trace")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert reader.read(run) == pytest.approx(100.0)


def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "batch-reasoning", 1)
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert conf["source"] == common.load_config(CONFIG)["source"]
    where = {m["name"]: m.get("workloads") for m in b["end_to_end"] + b["per_layer"]}
    for name in ("serve_tokens_per_s", "sat_batch_occupancy", "sat_host_share_of_round",
                 "sat_decode_step_device_ms", "sat_decode_step_roofline",
                 "sat_prefill_share_of_device", "serve_hbm_in_use_gib",
                 "sat_host_bound_idle_share", "sat_ahead_covered_share",
                 "sat_round_max_over_median", "sat_moe_share_of_device",
                 "sat_moe_sorted_share_of_device", "sat_moe_load_max_over_mean",
                 "sat_moe_experts_touched", "sat_state_share_of_live_cache",
                 "sat_moe_ffn_roofline", "sat_moe_sorted_ffn_roofline",
                 "sat_gdn_share_of_device", "sat_gdn_step_roofline",
                 "sat_gdn_chunk_roofline", "sat_moe_held_assignment_share"):
        assert CELL in where[name], name
    for name in ("sat_gdn_share_of_device", "sat_gdn_step_roofline",
                 "sat_gdn_chunk_roofline", "sat_moe_held_assignment_share"):
        assert where[name] == [CELL], name
    # another family's recurrence, another family's loop: not this cell's
    for name in ("sat_ssm_share_of_device", "sat_ssm_step_roofline",
                 "sat_ssm_scan_roofline", "sat_loop_reread_share_of_step_bytes"):
        assert CELL not in where[name], name
    # the traffic is the one the other hybrid family runs, as it was
    t = loadgen.load_traffic("batch-reasoning")
    assert t["kind"] == "saturating" and t["requests"] == 1500
    assert t["prompt"]["max"] + t["output"]["max"] == 2560


def test_what_the_benchmark_had_before_this_cell_is_as_the_tests_before_hold_it(
        monkeypatch):
    """PR 37's ``test_the_entry_is_appended_and_agrees_with_its_header`` pins
    its five metrics as the LAST of ``per_layer`` and the exact cells two of
    them list; this PR appends four metrics and a cell behind them and may
    not edit that file. So each of its cases is run here on ``BENCHMARK.json``
    cut back BY ORDER to what it held before this cell: nothing it holds has
    moved."""
    import types
    import test_round_record_metrics as before
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    for group, first in (("configs", CONFIG), ("workloads", CELL),
                         ("per_layer", "sat_gdn_share_of_device")):
        names = [e["name"] for e in b[group]]
        b[group] = b[group][:names.index(first)]
    for m in b["end_to_end"] + b["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"][:m["workloads"].index(CELL)]
    assert (len(b["configs"]), len(b["workloads"]), len(b["per_layer"])) == (7, 7, 43)
    monkeypatch.setattr(before, "json", types.SimpleNamespace(load=lambda fh: b))
    for entry in before.ENTRIES:
        before.test_the_entry_is_appended_and_agrees_with_its_header(entry)


def test_precision_below_rounds_every_operand_and_every_kind_of_state():
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
    with pytest.raises(ValueError, match="one of"):
        f.Reference(h, None, defect="no_such_defect")


def test_the_defect_tool_judges_through_the_harness_check():
    src = open(os.path.join(ROOT, "benchmark", "tools", "qwen3_next_defects.py")).read()
    assert "correct.check_tokens_vs_reference(" in src and "def judge" not in src
    assert 'CELL = "' + CELL + '"' in src


def test_the_cell_rehearses_on_the_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seconds", "20", "--seed", "3000000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = [ln for ln in p.stdout.splitlines() if ln.startswith("REHEARSAL")][-1]
    assert '"correct": true' in last and "serve_tokens_per_s" in last
    assert "sat_state_share_of_live_cache" in last
    assert "sat_moe_held_assignment_share" in last
    assert "benchmark.families.qwen3_next" in p.stdout
    assert "gdn (3, 128, 4, 32, 32) float32" in p.stdout
