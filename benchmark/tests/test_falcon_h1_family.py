"""The Falcon-H1 family (families/falcon_h1.py) and its cell: the configuration
against the catalog entry, the cost model's arithmetic against hand counts
(33.64 B at the published 72 layers, 5.255 B at the cut) and against what an
engine ALLOCATES (the pool's shapes at the published widths; a toy engine's
``stats()`` is held to the cost model in ``tests/unit/test_falcon_h1.py``), the
readers of the layer's two mixers and of the head on hand-built trace events,
the defects tool (the cell's ``--rehearsal`` is a case of
``tests/unit/test_falcon_h1.py``), and the cell's entries in
``BENCHMARK.json`` — found by NAME and tested with ``in``, never by position:
a later PR appends after them."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen, peaks, trace_reduce  # noqa: E402

CONFIG = "falcon-h1-34b-serve"
CELL = CONFIG + ".batch-reasoning"
H, F, V, L = 5120, 21504, 261120, 6
ATTN = 2 * H * 20 * 128 + 2 * H * 4 * 128
MAMBA = H * (4096 + 5120 + 32) + 4096 * H
SMALL = 5 * 5120 + 3 * 32 + 4096 + 2 * H       # conv + bias, vectors, norms
MLP = 3 * H * F
STATE = 32 * 128 * 256 * 4 + 3 * 5120 * 2       # one slot, one layer
KV8 = 2 * 4 * (128 + 4)                         # one token, one layer, int8


def hf():
    return common.hf_of(common.load_config(CONFIG))


def fam():
    return loadgen.load_family(hf())


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return next(json.loads(ln) for ln in f if "Falcon-H1-34B-Instruct" in ln)


def test_the_configuration_is_the_catalog_entry_cut_in_depth_alone():
    h, cfg = hf(), common.load_config(CONFIG)
    assert h["model_type"] == "falcon_h1" and h["num_hidden_layers"] == L
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    row = _catalog()
    if row is not None:              # every published key verbatim but depth
        assert cfg["source"] == row["source_url"]
        assert {k: v for k, v in h.items() if k != "num_hidden_layers"} \
            == {k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
        assert row["config"]["num_hidden_layers"] == 72
    assert (h["hidden_size"], h["intermediate_size"], h["vocab_size"]) == (H, F, V)
    assert (h["mamba_n_heads"], h["mamba_d_head"], h["mamba_n_groups"],
            h["mamba_d_state"], h["mamba_d_conv"], h["mamba_d_ssm"]) \
        == (32, 128, 2, 256, 4, 4096)
    assert cfg["run"]["serving"] == {"max_seqs": 64, "max_model_len": 2560}
    assert cfg["run"]["overrides"] == {"norm_init_jitter": 0.5}
    assert cfg["run"]["init_serving"] == {}
    expect = cfg["run"]["expect"]
    assert (expect["kv_cache_bits"], expect["recurrent_blocks"],
            expect["attention_blocks"], expect["state_pool_dtype"]) \
        == (8, L, L, "float32")
    for key in ("weights", "dtype", "d_inner", "mup_vector", "gated_norm",
                "rotary", "time_step", "multipliers"):
        assert key in cfg["assumed"], key
    for key in ("sample_requests", "margin", "min_judged_share", "min_agreement",
                "max_mismatch_share", "why"):
        assert key in cfg["correct"], key


def test_the_parameter_count_is_the_published_one_and_the_cut():
    f, h = fam(), hf()
    assert (ATTN, MAMBA, MLP) == (31_457_280, 68_321_280, 330_301_440)
    assert (f.block_params(h, "attn"), f.block_params(h, "mamba"),
            f.block_params(h, "dense"), f.small_params(h)) == (ATTN, MAMBA, MLP, SMALL)
    layer = ATTN + MAMBA + MLP + SMALL
    assert round(layer / 1e6, 2) == 430.12
    assert f.param_count(dict(h, num_hidden_layers=72)) == 72 * layer + 2 * V * H + H
    assert round(f.param_count(dict(h, num_hidden_layers=72)) / 1e9, 2) == 33.64
    assert round(f.param_count(h) / 1e9, 3) == 5.255
    assert round(2 * f.param_count(h) / 2 ** 30, 2) == 9.79
    assert [k for k, _ in f.blocks(h)] == ["mamba", "attn", "dense"] * L
    # (the matrices a step reads: the tables' small vectors are not in it)
    assert f.weight_bytes(h) == 2 * (L * (ATTN + MAMBA + MLP) + V * H)
    assert round(f.weight_bytes(h) / 1e9, 2) == 7.83


def test_the_toy_keeps_the_structure():
    f = fam()
    toy = common.hf_of(common.load_config(CONFIG), rehearsal=True)
    assert toy["num_attention_heads"] // toy["num_key_value_heads"] == 5
    assert toy["num_key_value_heads"] == hf()["num_key_value_heads"] == 4
    nh, hd, G, N, d_inner, conv_dim, K = f.mamba_dims(toy)
    assert G == 2 and nh // G > 1 and d_inner == toy["mamba_d_ssm"] and K == 4
    assert toy["num_hidden_layers"] >= 2
    assert toy["attention_in_multiplier"] != 1 == hf()["attention_in_multiplier"]
    assert len(f.MULTIPLIERS) == 14 and len(f.DEFECTS) == 12
    h = f.without_multiplier(toy, "ssm_multipliers", 3)
    assert h["ssm_multipliers"][3] == 1.0 and toy["ssm_multipliers"][3] == 0.5
    assert h["ssm_multipliers"][:3] == toy["ssm_multipliers"][:3]


def test_the_two_kinds_of_state_and_decode_step_bytes():
    f, h = fam(), hf()
    assert f.ssm_state_bytes(h) == 4 * 2 ** 20 and f.conv_tail_bytes(h) == 30720
    assert f.state_bytes_per_slot(h) == L * STATE
    assert f.kv_bytes_per_token(h, 8) == L * KV8 == 6336
    assert f.kv_bytes_per_token(h, 0) == L * 2 * 4 * 256
    # the pools as the configuration sizes them
    assert round(64 * L * STATE / 2 ** 30, 2) == 1.51
    assert round((64 * 40 + 1) * 64 * L * KV8 / 2 ** 30, 2) == 0.97
    assert f.ssm_step_bytes(h, 64.0) == 2 * 64 * STATE
    c = {"kv_cache_bits": 8, "mean_occupancy": 63.0, "mean_live_tokens": 41000.0}
    assert f.decode_step_bytes(h, c) == (
        f.weight_bytes(h) + 6336 * 41000.0 + 2 * 63.0 * L * STATE)
    assert f.decode_step_bytes(h, dict(c, mean_occupancy=0.0, mean_live_tokens=0.0)) \
        == f.weight_bytes(h)
    # the scan: per position C B^T a group, L x a head, two products with the state
    assert f.ssm_scan_flops(h, 1) == 2 * (2 * 128 * 256 + 32 * 128 * 128
                                          + 2 * 32 * 128 * 256)
    assert f.ssm_scan_bytes(h, 1024) == 1024 * (2 * (2 * 4096 + 2 * 512) + 128) \
        + 2 * 4 * 2 ** 20


def test_the_cost_model_is_what_an_engine_allocates_at_the_published_widths():
    """Shapes only (``jax.eval_shape`` of the model's own ``init_paged_cache``
    at the cell's slots and blocks): the family's bytes a token and a slot are
    the pool's, int8 scale planes and convolution tails included."""
    import dataclasses
    import jax.numpy as jnp
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.models import make_model
    f, h, cfgf = fam(), hf(), common.load_config(CONFIG)
    serving = cfgf["run"]["serving"]
    cfg = dataclasses.replace(
        common.model_config(cfgf, h, serving["max_model_len"]), kv_cache_bits=8)
    model = make_model(cfg)
    S, bs = serving["max_seqs"], 64
    nb = S * (serving["max_model_len"] // bs) + 1
    tree = kv_cache.abstract_cache(model, nb, bs, dtype=jnp.bfloat16, max_seqs=S)
    assert tree["ssm"].shape == (L, S, 32, 128, 256)
    assert tree["k"].shape[:2] == (L, nb) and tree["k_scale"].shape == (L, nb, 4 * bs)
    held = kv_cache.cache_bytes(model, tree)
    assert held["kv"] == nb * bs * f.kv_bytes_per_token(h, 8)
    assert held["state"] == S * f.state_bytes_per_slot(h) and held["rings"] == 0
    assert (cfg.recurrent_blocks, cfg.attention_blocks) == (L, L)


# ---- the readers on hand-built trace events ----------------------------------

POOL = {"k": {"shape": (6, 2561, 4, 64, 128), "dtype": "int8"},
        "v": {"shape": (6, 2561, 4, 64, 128), "dtype": "int8"},
        "k_scale": {"shape": (6, 2561, 256), "dtype": "float32"},
        "v_scale": {"shape": (6, 2561, 256), "dtype": "float32"},
        "ssm": {"shape": (6, 64, 32, 128, 256), "dtype": "float32"},
        "conv": {"shape": (6, 64, 3, 5120), "dtype": "bfloat16"}}
STEP = ('%ssm_step.4 = (f32[64,8,128,4]{3,2,1,0:T(8,128)S(1)}, f32[6,64,32,128,256]'
        '{4,3,2,1,0:T(8,128)}) custom-call(f32[64,8,128,4]{3,2,1,0} %bitcast.7), '
        'custom_call_target="tpu_custom_call"')
SCAN = ('%ssm_scan.5 = (f32[32,512,128]{2,1,0:T(8,128)}, f32[32,512,128]{2,1,0:T(8,128)}, '
        'f32[32,128,256]{2,1,0:T(8,128)}) custom-call(bf16[32,512,128]{2,1,0} %x), '
        'custom_call_target="tpu_custom_call"')
FLASH = ('%flash_fwd.3 = bf16[1,512,20,128]{3,2,1,0} custom-call(bf16[1,512,20,128]{3,2,1,0} '
         '%q), custom_call_target="tpu_custom_call"')
GATHER = ('%fusion.77 = s8[640,64,4,128]{3,1,2,0:T(8,128)(4,1)} fusion(s8[15366,64,4,128]'
          '{3,1,2,0} %bitcast.45, s32[640]{0} %ids), kind=kCustom')
SCORES = ('%convolution.9 = s32[320,4,5,128]{3,2,1,0} convolution(s8[320,128,4,128]{3,1,2,0} '
          '%bitcast.9, s8[320,4,128,20]{3,2,1,0} %q)')
ROW_WRITE = ('%fusion.63 = s8[6,2561,4,64,128]{4,3,2,1,0:T(8,128)(4,1)} fusion('
             's8[6,2561,4,64,128]{4,3,2,1,0} %pools__k__.1, s32[64]{0} %blk), kind=kCustom')
BLOCK_WRITE = ('%fusion.2 = s8[6,2561,64,4,128]{4,2,3,1,0:T(8,128)(4,1)} fusion('
               's8[6,2561,64,4,128]{4,2,3,1,0} %bitcast.1, s8[6,8,64,4,128]{4,3,2,1,0} %kv)')
STATE_WRITE = ('%fusion.9 = f32[6,64,32,128,256]{4,3,2,1,0} fusion(f32[6,64,32,128,256]'
               '{4,3,2,1,0} %p, f32[32,128,256]{2,1,0} %s), kind=kLoop')
HEAD = ('%fusion.301 = f32[64,261120]{1,0:T(8,128)} fusion(bf16[64,5120]{1,0} %x, '
        'bf16[5120,261120]{1,0:T(8,128)(2,1)} %lm_head), kind=kOutput')
PICK = '%reduce.4 = (f32[64]{0}, s32[64]{0}) reduce(f32[64,261120]{1,0} %fusion.301, s32[] %c)'
EMBED = ('%gather.1 = bf16[64,5120]{1,0} gather(bf16[261120,5120]{1,0:T(8,128)(2,1)} '
         '%tok_embed, s32[64,1]{1,0} %ids)')
MLP_OP = "%fusion.299 = bf16[64,21504]{1,0} fusion(bf16[64,5120]{1,0} %p, bf16[6,5120,21504]{2,1,0} %w)"


def test_the_family_finds_the_two_mixers_and_the_head_in_a_trace():
    f, h = fam(), hf()
    c = {"pool": POOL}
    assert f.ssm_kernel(SCAN) == "scan" and f.ssm_kernel(STEP) == "step"
    assert f.ssm_kernel(MLP_OP) is None and f.ssm_kernel(FLASH) is None
    for op in (STEP, SCAN, FLASH, GATHER, SCORES, ROW_WRITE, BLOCK_WRITE,
               STATE_WRITE):
        assert f.mixer_op(op, c), op
    for op in (HEAD, PICK, EMBED, MLP_OP):
        assert not f.mixer_op(op, c), op
    # a pool declared token-major (a float pool, the toy's) is found the same
    flat = {"pool": dict(POOL, k={"shape": (6, 2561, 64, 4, 128), "dtype": "int8"})}
    assert f.mixer_op(GATHER, flat) and f.mixer_op(BLOCK_WRITE, flat)
    # without the run's pool shapes only the kernels have a name to go by
    assert f.mixer_op(STEP, {}) and not f.mixer_op(GATHER, {})
    assert f.head_op(HEAD, h) and f.head_op(PICK, h)
    for op in (EMBED, MLP_OP, STEP, GATHER):
        assert not f.head_op(op, h), op


def fake_run(events, modules, counters):
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OPS_LINE, "events": [list(e) for e in events]},
        {"name": trace_reduce.MODULES_LINE, "events": [list(m) for m in modules]}]}
    raw = {"planes": [plane, {"name": trace_reduce.HOST_PLANE, "lines": [
        {"name": "t", "events": [[trace_reduce.WINDOW_SPAN, 0.0, 1e9]]}]}]}
    return raw, {"trace": trace_reduce.reduce(raw), "family": fam(), "hf": hf(),
                 "peaks": peaks.peaks_for("TPU v5 lite"), "cell": {"name": CELL},
                 "counters": counters}


def test_the_readers_of_the_block_and_of_the_recurrence(monkeypatch):
    from benchmark.harness import program_spans
    f, h = fam(), hf()
    mixer, head, share, step, scan, live = (
        loadgen.load_module("layer_metrics", n) for n in (
            "sat_par_mixer_share_of_device", "sat_head_share_of_device",
            "sat_ssm_share_of_device", "sat_ssm_step_roofline",
            "sat_ssm_scan_roofline", "sat_state_share_of_live_cache"))
    counters = {"mean_occupancy": 60.0, "mean_live_tokens": 40000.0,
                "kv_cache_bits": 8, "pool": POOL}
    # two decode steps x 6 layers at twice their memory floor, one prefill x 6
    # layers at four times its floor; a read and a head op of 1 ms each; and
    # as much of other ops as all of those together
    step_floor = f.ssm_step_bytes(h, 60.0) / 819e9
    scan_floor = max(f.ssm_scan_flops(h, 512) / 197e12, f.ssm_scan_bytes(h, 512) / 819e9)
    events, t = [], 0.0
    for _ in range(12):
        events.append((STEP, t, 2 * step_floor * 1e9)); t += 3 * step_floor * 1e9
    for _ in range(6):
        events.append((SCAN, t, 4 * scan_floor * 1e9)); t += 5 * scan_floor * 1e9
    ssm_s = 24 * step_floor + 24 * scan_floor
    events.append((GATHER, t, 1e6)); t += 2e6
    events.append((HEAD, t, 1e6)); t += 2e6
    events.append((MLP_OP, t, (ssm_s + 2e-3) * 1e9))
    busy = 2 * (ssm_s + 2e-3)
    modules = [("jit_step(1)", 0.0, 1e6), ("jit_step(1)", 2e6, 1e6),
               ("jit_prefill(2)", 4e6, 1e6)]
    raw, run = fake_run(events, modules, counters)
    monkeypatch.setattr(program_spans, "find_xplane", lambda cell: "a-trace")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) == pytest.approx(100.0 * ssm_s / busy)
    assert mixer.read(run) == pytest.approx(100.0 * (ssm_s + 1e-3) / busy)
    assert head.read(run) == pytest.approx(100.0 * 1e-3 / busy)
    assert step.read(run) == pytest.approx(50.0)
    assert scan.read(run) == pytest.approx(25.0)
    state, kv = 60.0 * L * STATE, 6336 * 40000.0
    assert live.read(run) == pytest.approx(100.0 * state / (state + kv))
    # every share is of busy time: under 100 whatever the run
    assert 0 < mixer.read(run) < 100 and 0 < head.read(run) < 100
    # a program of another family (the parent's): the new readers read nothing
    run["family"] = loadgen.load_family({"model_type": "nemotron_h"})
    assert mixer.read(run) is None and head.read(run) is None
    # ... and nothing where the trace has none of their ops
    raw, run = fake_run([(MLP_OP, 0.0, 1e6)], modules, counters)
    assert mixer.read(run) is None and head.read(run) is None


def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "batch-reasoning", 1)
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf["source"] == common.load_config(CONFIG)["source"]
    t = loadgen.load_traffic("batch-reasoning")
    assert t["prompt"]["max"] + t["output"]["max"] == 2560 \
        == common.load_config(CONFIG)["run"]["serving"]["max_model_len"]
    where = {m["name"]: m.get("workloads") for m in b["end_to_end"] + b["per_layer"]}
    assert CELL in where["serve_tokens_per_s"] and where["setup_s"] is None
    for name in ("sat_batch_occupancy", "sat_host_share_of_round",
                 "sat_decode_step_device_ms", "sat_decode_step_roofline",
                 "sat_prefill_share_of_device", "serve_hbm_in_use_gib",
                 "sat_host_bound_idle_share", "sat_ahead_covered_share",
                 "sat_round_max_over_median", "setup_programs_built",
                 "setup_trace_lower_s", "setup_compile_or_load_s",
                 "setup_engine_init_s", "setup_unattributed_share",
                 "sat_ssm_share_of_device", "sat_ssm_step_roofline",
                 "sat_ssm_scan_roofline", "sat_state_share_of_live_cache",
                 "sat_par_mixer_share_of_device", "sat_head_share_of_device"):
        assert CELL in where[name], name
    for name in ("sat_par_mixer_share_of_device", "sat_head_share_of_device"):
        assert where[name] == [CELL], name
    # a dense model; 4 K/V heads are off the paged kernel's tiles (the engine
    # reads the planes through XLA's list read); no ring, no latent row
    for name in ("sat_moe_share_of_device", "sat_moe_ffn_roofline",
                 "sat_paged_read_roofline", "sat_gdn_share_of_device",
                 "sat_window_share_of_live_cache", "sat_mla_share_of_device"):
        assert CELL not in where[name], name


def test_what_the_benchmark_had_before_this_cell_is_as_the_tests_before_hold_it(
        monkeypatch):
    """PR 55's ``test_setup_metrics.py`` pins the NUMBER of serve cells, of
    cells and of per-layer metrics; this PR appends a cell (to those five
    metrics' lists too) and two metrics, and may not edit that file. So its
    two tests of ``BENCHMARK.json`` are run here on the file cut back BY ORDER
    to what it held before this cell: nothing they hold has moved."""
    import test_setup_metrics as before
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    for group, first in (("configs", CONFIG), ("workloads", CELL),
                         ("per_layer", "sat_par_mixer_share_of_device")):
        names = [e["name"] for e in b[group]]
        b[group] = b[group][:names.index(first)]
    for m in b["end_to_end"] + b["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"][:m["workloads"].index(CELL)]
    assert (len(b["configs"]), len(b["workloads"]), len(b["per_layer"])) == (11, 11, 63)
    monkeypatch.setattr(before, "benchmark_json", lambda: b)
    for name, unit in before.ENTRIES:
        before.test_the_entry_is_appended_and_agrees_with_its_header(name, unit)
    before.test_nothing_the_benchmark_had_moved()


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
    assert (below._bf16_state, below._kv_levels) == (True, 7.0)
    assert (plain._bf16_state, plain._kv_levels) == (False, None)
    one = f.Reference(h, None, defect="kv_4bit")
    assert one._operand is None and one._kv_levels == 7.0 and not one._bf16_state
    # the witnesses: the STATED precision, part by part — never below it
    stated = f.Reference(h, None, defect="stated_precision")
    assert stated._operand == jnp.bfloat16 and stated._kv_levels == 127.0
    assert stated._int8_read and not stated._bf16_state
    assert np.array_equal(np.asarray(stated._lo(a)),
                          [0.01300048828125, 1.296875, -0.69921875, 100.0])
    assert not set(f.WITNESSES) & set(f.DEFECTS)
    assert f.Reference(h, None, defect="no_key_multiplier").hf["key_multiplier"] == 1.0
    assert f.Reference(h, None, defect="no_mup_vector").hf["ssm_multipliers"] == [1.0] * 5
    assert h["key_multiplier"] != 1.0            # ... and the caller's is its own
    with pytest.raises(ValueError, match="one of"):
        f.Reference(h, None, defect="no_such_defect")


def test_the_defect_tool_judges_through_the_harness_check():
    src = open(os.path.join(ROOT, "benchmark", "tools", "falcon_h1_defects.py")).read()
    assert "correct.check_tokens_vs_reference(" in src and "def judge" not in src
    assert f'CELL = "{CELL}"' in src
