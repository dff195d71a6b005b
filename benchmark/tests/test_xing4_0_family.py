"""The xing4_0 family (families/xing4_0.py) and its cell: the cost model's
arithmetic against hand counts and against the program's own parameter tree at
the PUBLISHED widths (29.5 B whole, 3.93 B a token, 4 792.7 M at the cut),
``hc_bytes`` and ``decode_step_bytes`` against hand counts, the stream's ops
and the two readers on hand-built trace events, the configuration file's
sections, the defects tool, the cell's rehearsal, and the cell's entries in
``BENCHMARK.json`` — found by NAME, never by position: a later PR appends after
them. The program against the plain reference on logits, both orders of
attention and every seeded defect are in ``tests/unit/test_xing4_0.py`` and
``tests/unit/test_hyper_connections.py``."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import (  # noqa: E402
    common, loadgen, peaks, program_spans, trace_reduce)

CONFIG = "xing4.0-29b-a4b-serve"
CELL = CONFIG + ".batch-docqa"
H, F, FD, V, E, N = 3584, 1024, 9216, 131072, 64, 4
ATTN = H * 768 + 768 * 32 * 192 + H * 576 + 512 * 32 * 256 + 32 * 128 * H
EXPERT = 3 * H * F
DENSE = 3 * H * FD
HC = N * H * 24 + 24 + 3                              # a block's mappings
HC_OUT = N * H * N + N + 1                            # the closing read's
STREAM = 2 * N * H                                    # a token's stream, bf16

READ = ("%fusion.7 = f32[2048,3584]{1,0} fusion(bf16[1,2048,14336]{2,1,0} %x, "
        "f32[4,2048]{1,0} %pre), kind=kLoop")
WRITE = ("%fusion.9 = bf16[1,2048,14336]{2,1,0} fusion(bf16[1,2048,14336]{2,1,0} "
         "%x, bf16[1,2048,3584]{2,1,0} %y, f32[20,2048]{1,0} %w), kind=kLoop")
PROJECT = ("%convolution.3 = f32[2048,24]{1,0} convolution(bf16[2048,14336]{1,0} "
           "%x, bf16[14336,24]{1,0} %phi)")
STEP_WRITE = ("%fusion.4 = bf16[128,1,14336]{2,1,0} fusion(bf16[128,1,14336]{2,1,0} "
              "%x, bf16[128,1,3584]{2,1,0} %y), kind=kLoop")
# the expert layer's combine over top-k = 4: [tokens, 4, hidden], NOT the stream
COMBINE = ("%multiply_reduce_fusion.7 = bf16[128,3584]{1,0} fusion(f32[128,4,3584]{2,1,0} "
           "%reshape.21, f32[128,4]{1,0} %w), kind=kLoop")
KERNEL = ("%hc_write.3 = bf16[2048,512]{1,0} custom-call(bf16[2048,512]{1,0} %x), "
          'custom_call_target="tpu_custom_call"')
OTHER = ("%fusion.5 = bf16[2048,9216]{1,0} fusion(bf16[2048,3584]{1,0} %h, "
         "bf16[3584,9216]{1,0} %w), kind=kOutput")
MAPS = "%fusion.6 = f32[4,4,2048]{2,1,0} fusion(f32[24,2048]{1,0} %m), kind=kLoop"


def fam():
    return loadgen.load_family({"model_type": "xing4_0"})


def conf():
    return common.load_config(CONFIG)


def hf():
    return common.hf_of(conf())


def published():
    return {**hf(), "num_hidden_layers": 40, "first_k_dense_replace": 2}


def test_the_configuration_states_its_cuts_and_what_it_assumes():
    c = conf()
    assert set(c["reduced"]) == {"num_hidden_layers", "first_k_dense_replace",
                                 "num_nextn_predict_layers"}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["num_nextn_predict_layers"]) == (6, 1, 0)
    # no width, head, expert or vocabulary row is cut
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"],
            c["n_routed_experts"], c["num_experts_per_tok"], c["vocab_size"],
            c["num_attention_heads"], c["kv_lora_rank"], c["q_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["hc_mult"], c["hc_sinkhorn_iters"]) == (
        H, FD, F, E, 4, V, 32, 512, 768, 128, 64, 128, N, 20)
    assert c["rope_scaling"]["type"] == "yarn" and c["rope_scaling"]["factor"] == 64
    assert {"stream", "weights", "dtype", "rotary", "attention", "router",
            "num_experts"} <= set(c["assumed"])
    run = c["run"]
    assert run["overrides"] == {"norm_init_jitter": 0.5, "hc_init_std": 1.0}
    assert run["serving"] == {"max_seqs": 128, "max_model_len": 4864,
                              "prompt_bucket": 512}
    assert run["expect"]["hc_mult"] == 4 and run["expect"]["latent_planes"] == 6
    assert c["correct"]["sample_requests"] == 12
    assert set(fam().DEFECTS) >= {"precision_below", "sinkhorn_one_round",
                                  "close_by_sum", "no_mscale", "plain_rope"}
    for word in fam().DEFECTS:
        assert word in c["correct"]["why"], word


def test_the_parameter_count_is_the_published_one_and_the_cuts():
    f = fam()
    assert f.block_params(hf(), "latent") == ATTN + HC == 28_409_856 + 344_091
    assert f.block_params(hf(), "dense") == DENSE + HC
    assert f.block_params(hf(), "moe") == 65 * EXPERT + H * E + HC
    layer = ATTN + 65 * EXPERT + H * E                       # 744.29 M
    whole = 2 * (ATTN + DENSE) + 38 * layer + 80 * HC + HC_OUT + 2 * H * V
    assert f.param_count(published()) == whole and round(whole / 1e9, 1) == 29.5
    active = (2 * (ATTN + DENSE) + 38 * (ATTN + 5 * EXPERT + H * E) + 80 * HC
              + HC_OUT + H * V)
    assert f.active_params(published()) == active and round(active / 1e9, 2) == 3.93
    cut = ATTN + DENSE + 5 * layer + 12 * HC + HC_OUT + 2 * H * V
    assert f.param_count(hf()) == cut and round(cut / 1e6, 1) == 4792.7
    assert f.weight_bytes(hf()) == 2.0 * (cut - H * V)


def test_the_cost_model_counts_the_programs_own_tree():
    """The cut's parameter tree as ``init_params`` builds it, as shapes: what
    ``param_count`` leaves out are the norm scales and the correction bias."""
    import jax
    from deepspeed_tpu.models import make_model
    tree = jax.eval_shape(make_model(common.model_config(conf(), hf(), 4864)).init,
                          jax.random.PRNGKey(0))
    total = sum(x.size for x in jax.tree.leaves(tree))
    small = (12 * H + 6 * (768 + 512) + 5 * E + H)   # ln, q_a / kv_a norms, e_bias, final
    assert total - small == fam().param_count(hf())
    assert tree["layers"]["moe"]["hc_phi"].shape == (5, N * H, 24)
    assert tree["hc_out_phi"].shape == (N * H, N)


def test_the_streams_bytes_against_a_hand_count():
    f = fam()
    # a token: 12 blocks x (one read + one write of 28 672 B + the block's
    # 7 168-B input and output) + the closing read and its row
    per_token = 12 * (2 * STREAM + 2 * STREAM // N) + STREAM + STREAM // N
    assert per_token == 12 * 71_680 + 35_840
    maps = 2 * (12 * HC + HC_OUT)
    assert f.hc_bytes(hf(), 1000.0, 3.0) == 1000 * per_token + 3 * maps
    # ... in the stream's own itemsize where the run reports it
    c = {"stats": {"stream_bytes_per_token": 2.0 * STREAM}}
    assert f.hc_bytes(hf(), 10.0, 0.0, c) == 20 * per_token
    counters = {"mean_live_tokens": 300_000.0, "mean_occupancy": 126.0,
                "stats": {"moe_experts_touched_per_step": 63.0,
                          "latent_planes": 6.0, "latent_row_bytes": 1152.0}}
    weights = 2.0 * (ATTN + DENSE + 5 * (ATTN + 64 * EXPERT + H * E) + 12 * HC
                     + HC_OUT + H * V)
    assert f.decode_step_bytes(hf(), counters) == pytest.approx(
        weights + 300_000 * 6 * 1152 + 126 * per_token)


def test_the_family_finds_the_stream_in_a_trace():
    f = fam()
    for name in (READ, WRITE, PROJECT, STEP_WRITE, KERNEL):
        assert f.hc_op(name, hf()), name
    for name in (OTHER, MAPS, COMBINE):
        assert not f.hc_op(name, hf()), name
    assert [f.stream_tokens(n, hf()) for n in (READ, PROJECT, STEP_WRITE, COMBINE,
                                               OTHER)] == [2048, 2048, 128, 0, 0]
    # a program's tokens are read once, from its own ops, and counted a RUN
    ops = [(READ, 10.0, 5.0), (WRITE, 20.0, 5.0), (WRITE, 30.0, 5.0),
           (STEP_WRITE, 110.0, 5.0), (OTHER, 210.0, 5.0), (STEP_WRITE, 310.0, 5.0)]
    mods = [("jit_prefill(7)", 0.0, 100.0), ("jit_step(3)", 100.0, 100.0),
            ("jit_other(9)", 200.0, 100.0), ("jit_step(3)", 300.0, 100.0)]
    assert f.program_tokens(mods, ops, hf()) == (2048 + 128 + 128, 3)


def fake_run(events, modules, counters):
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OPS_LINE, "events": [list(e) for e in events]},
        {"name": trace_reduce.MODULES_LINE, "events": [list(m) for m in modules]}]}
    raw = {"planes": [plane, {"name": trace_reduce.HOST_PLANE, "lines": [
        {"name": "t", "events": [[trace_reduce.WINDOW_SPAN, 0.0, 1e12]]}]}]}
    return raw, {"trace": trace_reduce.reduce(raw), "family": fam(), "hf": hf(),
                 "peaks": peaks.peaks_for("TPU v5 lite"), "cell": {"name": CELL},
                 "counters": counters}


def test_the_two_readers_of_the_stream(monkeypatch):
    share, roof = (loadgen.load_module("layer_metrics", n) for n in (
        "sat_hc_share_of_device", "sat_hc_stream_roofline"))
    f = fam()
    # one prefill of 2048 tokens and two steps of 128 slots; the stream's ops
    # take FOUR times what their least bytes take, the other ops as long again
    tokens, runs = 2048 + 2 * 128, 3
    floor_ns = f.hc_bytes(hf(), tokens, runs) / 819e9 * 1e9
    events = [(READ, 1e3, floor_ns), (WRITE, 1e3 + 2 * floor_ns, 2 * floor_ns),
              (OTHER, 1e3 + 5 * floor_ns, 4 * floor_ns),
              (STEP_WRITE, 1e4 + 10 * floor_ns, floor_ns / 2),
              (STEP_WRITE, 2e4 + 12 * floor_ns, floor_ns / 2)]
    mods = [("jit_prefill(2)", 0.0, 1e4 + 9.5 * floor_ns),
            ("jit_step(1)", 1e4 + 9.9 * floor_ns, 2 * floor_ns),
            ("jit_step(1)", 2e4 + 11.9 * floor_ns, 2 * floor_ns)]
    raw, run = fake_run(events, mods, {"stats": {}})
    monkeypatch.setattr(program_spans, "find_xplane", lambda cell: "fake.pb")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) == pytest.approx(50.0)
    assert roof.read(run) == pytest.approx(25.0)
    # a family without the stream (another's; the parent cannot run this one)
    run["family"] = loadgen.load_family({"model_type": "glm4_moe_lite"})
    assert share.read(run) is None and roof.read(run) is None
    run["family"], run["trace"] = f, None
    assert share.read(run) is None and roof.read(run) is None


def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "batch-docqa", 1)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_nextn_predict_layers"]
    assert entry["source"] == conf()["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/" + CONFIG + ".json"
    where = {m["name"]: m.get("workloads") for m in b["end_to_end"] + b["per_layer"]}
    glm = "glm-4.7-flash-serve.batch-docqa"
    # beside GLM's cell in every list GLM's is in: the same traffic and engine
    for name, cells in where.items():
        if cells and glm in cells:
            assert CELL in cells, name
    for name in ("serve_tokens_per_s", "sat_decode_step_roofline",
                 "serve_hbm_in_use_gib", "sat_moe_ffn_roofline",
                 "sat_mla_share_of_device", "sat_mla_read_roofline",
                 "setup_trace_lower_s"):
        assert CELL in where[name], name
    for name in ("sat_hc_share_of_device", "sat_hc_stream_roofline"):
        assert where[name] == [CELL], name
        m = {m["name"]: m for m in b["per_layer"]}[name]
        reader = loadgen.load_module("layer_metrics", name)
        assert {k: m[k] for k in ("layer", "unit", "moves", "source", "better")} \
            == {k: reader.HEADER[k] for k in ("layer", "unit", "moves", "source",
                                              "better")}
    for name in ("sat_ssm_share_of_device", "sat_paged_read_roofline",
                 "sat_par_mixer_share_of_device", "ttft_p90_ms"):
        assert CELL not in where[name], name
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


def test_what_the_benchmark_had_before_this_cell_is_as_the_cell_before_holds_it(
        monkeypatch):
    """PR 52's ``test_glm4_moe_lite_family.py`` pins the two ``sat_mla_*``
    metrics to GLM's cell ALONE; this cell is latent attention too and is
    appended to both lists, and this PR may not edit that file. So its test of
    ``BENCHMARK.json`` is run here on the file cut back BY ORDER to what it
    held before this cell: nothing it holds has moved."""
    import types
    import test_glm4_moe_lite_family as before
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    for group, first in (("configs", CONFIG), ("workloads", CELL),
                         ("per_layer", "sat_hc_share_of_device")):
        names = [e["name"] for e in b[group]]
        b[group] = b[group][:names.index(first)]
    for m in b["end_to_end"] + b["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"][:m["workloads"].index(CELL)]
    assert (len(b["configs"]), len(b["workloads"]), len(b["per_layer"])) == (12, 12, 65)
    monkeypatch.setattr(before, "json", types.SimpleNamespace(load=lambda fh: b))
    before.test_benchmark_json_has_the_cell_and_its_metrics()


# ---- the host replay of a saturating window (tools/saturating_spread.py) ----

def _spread_tool():
    return loadgen.load_module("tools", "saturating_spread")


def test_the_window_replay_counts_a_round_by_hand():
    """Three slots, seven requests of three tokens each, prompts of 128, 64,
    64 tokens twice over and one more; quantum 2, a 10 ms step, 1 us a live
    row, 100 us a padded prompt token and 1 ms a program, buckets of 64. A
    round: the 128-token prompt fills a row of the longest bucket, the two of
    64 SHARE a second one (2 x (1 + 12.8) ms), three first tokens; two steps
    of 10 ms + (129 + 65 + 65) and (130 + 66 + 66) rows x 1 us = 20.521 ms,
    six tokens; all three are done. Two such rounds are 18 tokens in 96.242
    ms, and the window closes there (past 50 ms, at a round's edge) with one
    request queued; with none queued the run fails, as the harness's does."""
    import numpy as np
    tool = _spread_tool()
    prompts = np.array([128, 64, 64, 128, 64, 64, 64])
    w = tool.window((prompts, np.full(7, 3)), slots=3, seconds=0.05,
                    step_ms=10.0, row_ns=1000.0, prefill_us=100.0,
                    program_ms=1.0, bucket=64, quantum=2)
    assert (w["reached"], w["rounds"]) == (6, 2)
    assert w["tokens_per_s"] == pytest.approx(18 / 96.242e-3, rel=1e-9)
    with pytest.raises(RuntimeError, match="ran out"):
        tool.window((prompts[:6], np.full(6, 3)), 3, 0.05, 10.0, 0.0, 100.0,
                    1.0, bucket=64, quantum=2)


def test_the_set_spread_leaves_the_farthest_run_out():
    """100, 101, 102, 103, 104 and a stray 120: without the stray the
    quartiles (exclusive, as the contract's) are 100.5 and 103.5 about 102."""
    spread = _spread_tool().trimmed_spread
    assert spread([100, 104, 101, 120, 103, 102]) == pytest.approx(3.0 / 102)
    assert spread([5.0] * 6) == 0.0


@pytest.mark.parametrize("prefill_us,low,high", [(21.7, 0.6, 1.6), (0.0, 0.0, 0.3)])
def test_the_cells_spread_is_prefills_share_times_the_draw(prefill_us, low, high):
    """The cell's mix at the constants of its traced run (PERF.md section 5,
    PR 59: a 13.2 ms step + 16.2 ns a live row, 21.7 us a padded prompt token
    + 1.5 ms a program): tokens/s follows the seed's draw of lengths by about
    one per cent, and with prefill free by a fifth of that - host arithmetic
    over 24 seeds, a second in all."""
    tool = _spread_tool()
    traffic = loadgen.load_traffic("batch-docqa")
    import numpy as np
    tps = [tool.window(tool.lengths_of(traffic, 5900003000 + i), 128, 45.0, 13.2,
                       16.2, prefill_us, 1.5 if prefill_us else 0.0)["tokens_per_s"]
           for i in range(24)]
    sigma = 100 * float(np.std(np.log(tps)))
    assert low <= sigma <= high, sigma
    if prefill_us:
        assert 3300 < float(np.median(tps)) < 3600      # the chip read 3 380-3 504


def test_the_defect_tool_judges_through_the_harness_check():
    src = open(os.path.join(ROOT, "benchmark", "tools", "xing4_0_defects.py")).read()
    assert "correct.check_tokens_vs_reference(" in src and "def judge" not in src
    assert 'CELL = "' + CELL + '"' in src
    for word in fam().DEFECTS:
        assert "``" + word + "``" in src, word


def test_the_cell_rehearses_on_the_cpu():
    """40 s, as GLM's: the first round is 128 prefills of 128-512 tokens
    through eight blocks of a four-row stream, ~8 s alone; an answer is 24-96
    tokens, three to twelve rounds of ~4 s, and a request has to FINISH (at 30
    s it starved beside tier-1's other workers in the builder's whole run: one
    round in 41 s)."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seconds", "40", "--seed", "5900000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = [ln for ln in p.stdout.splitlines() if ln.startswith("REHEARSAL")][-1]
    assert '"correct": true' in last and "serve_tokens_per_s" in last
    assert "sat_moe_experts_touched" in last
    assert "benchmark.families.xing4_0" in p.stdout
    assert "latent (4, 1153, 64, 128) bfloat16" in p.stdout
    assert "kv_cache_bits=0" in p.stdout
