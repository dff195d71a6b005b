"""The afmoe family (families/afmoe.py) and its cell: the cost model's
arithmetic against hand counts (398.6 B at the published 60 layers / 256
experts / whole vocabulary, 4.322 B at the cut, 2 112 B a cached row, the
visible pairs of a band), ``decode_step_bytes`` by layer kind on hand-made
counters, the readers of the banded kernel and of the rings on hand-built
trace events and counters, each seeded defect on LOGITS at toy widths, the
cell's rehearsal, and the cell's entries in ``BENCHMARK.json`` — tested with
``in``, never by position: a later PR appends after them (PERF.md section
7)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen, peaks, trace_reduce  # noqa: E402

CONFIG = "trinity-large-serve"
CELL = CONFIG + ".batch-longcontext"
H, F, FD, V, V_ALL, W = 3072, 3072, 12288, 25024, 200192, 4096
EXPERT = 3 * H * F                                    # gate, up and down
ATTN = H * 12288 + 2 * H * 1024 + 6144 * H            # q + gate, k, v, o
DENSE = 3 * H * FD
E_SIDE = EXPERT + H * 256                             # shared expert, router
ROW = 2 * 8 * (128 + 4)                               # K and V of one position

BAND = ("%flash_fwd_band.3 = bf16[1,8,6,9216,128]{4,3,2,1,0:T(8,128)(2,1)} "
        'custom-call(bf16[1,8,6,9216,128]{4,3,2,1,0} %a, bf16[1,8,9216,128]{3,2,1,0} %b), '
        'custom_call_target="tpu_custom_call"')
FLASH = ("%flash_fwd.2 = (bf16[1,8,6,9216,128]{4,3,2,1,0}, f32[1,8,6,9216,1]{4,3,2,1,0}) "
         'custom-call(bf16[1,8,6,9216,128]{4,3,2,1,0} %a), custom_call_target="tpu_custom_call"')
RING_READ = ("%fusion.230 = s32[64,8,6,4096]{3,2,1,0} fusion(s8[64,4096,8,128]"
             "{3,2,1,0:T(8,128)(4,1)} %param.9, s8[64,8,128,8,6]{4,3,2,1,0} %q), kind=kOutput")
RING_WRITE = ("%fusion.77 = s8[64,4096,8,128]{3,2,1,0:T(8,128)(4,1)} fusion(s8[64,4096,8,128]"
              "{3,2,1,0} %param.9, s32[64]{0} %row), kind=kLoop, calls=%fused_scatter")
SCALE_WRITE = ("%fusion.78 = f32[64,32768]{1,0} fusion(f32[64,32768]{1,0} %p, s32[64]{0} %r)"
               ", kind=kLoop")
POOL_READ = ("%fusion.14 = s8[11264,64,8,128]{3,2,1,0:T(8,128)(4,1)} fusion(s8[11265,64,8,128]"
             "{3,2,1,0} %bitcast.271, s32[11264]{0} %ids), kind=kLoop")
OTHER = "%fusion.5 = bf16[64,3072]{1,0} fusion(bf16[64,3072]{1,0} %x), kind=kLoop"


def hf():
    return common.hf_of(common.load_config(CONFIG))


def fam():
    return loadgen.load_family(hf())


def test_the_configuration_is_the_catalog_entry_cut_to_the_chips_share():
    h, cfg = hf(), common.load_config(CONFIG)
    assert h["model_type"] == "afmoe"
    assert (h["num_hidden_layers"], h["num_dense_layers"], h["num_experts"],
            h["vocab_size"]) == (5, 1, 32, V)
    assert (h["num_experts_router"], h["expert_first"],
            h["num_experts_per_tok"], h["route_scale"]) == (256, 0, 4, 2.448)
    assert sorted(cfg["reduced"]) == ["num_dense_layers", "num_experts",
                                      "num_hidden_layers", "vocab_size"]
    # the published list is kept whole; the cut reads its first five entries
    assert len(h["layer_types"]) == 60 and h["layer_types"][:5] == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):        # every other published number as it is
        with open(path) as f:
            cat = next(json.loads(ln) for ln in f
                       if '"Trinity-Large-Preview"' in ln)
        assert cfg["source"] == cat["source_url"]
        for k, v in cat["config"].items():
            if k not in cfg["reduced"]:
                assert h[k] == v, k
        assert (cat["config"]["num_hidden_layers"], cat["config"]["num_dense_layers"],
                cat["config"]["num_experts"], cat["config"]["vocab_size"]) == (
            60, 6, 256, V_ALL)
    for key in ("gate_proj", "rotary", "expert_bias", "route_norm", "mup",
                "norms", "mtp", "weights"):
        assert key in cfg["assumed"], key
    assert "96 chips" in cfg["deployment"] and "NOT here" in cfg["deployment"]
    assert cfg["run"]["overrides"] == {"norm_init_jitter": 0.5, "post_norm_init": 0.5}
    assert cfg["run"]["init_serving"] == {}
    assert cfg["run"]["serving"] == {"max_seqs": 64, "max_model_len": 11264,
                                     "prompt_bucket": 1024}
    assert cfg["run"]["expect"] == {
        "kv_cache_bits": 8, "num_experts": 32, "moe_router_width": 256,
        "top_k": 4, "attention_blocks": 5, "window_blocks": 4, "kv_planes": 1}
    # floors of the model-configs guide: a whole period and four layers after
    # the dense one, >= 8 experts, >= 1/8 of the vocabulary
    assert h["num_hidden_layers"] - h["num_dense_layers"] >= 4
    assert h["num_experts"] >= 8 and 8 * V >= V_ALL


def test_the_parameter_count_is_the_published_one_and_the_cuts():
    f, h = fam(), hf()
    assert (EXPERT, ATTN, DENSE, E_SIDE) == (28_311_552, 62_914_560,
                                             113_246_208, 29_097_984)
    assert f.block_params(h, "attn") == f.block_params(h, "wattn") == ATTN
    assert f.block_params(h, "dense") == DENSE
    assert f.block_params(h, "moe") == 32 * EXPERT + E_SIDE
    assert f.block_params(h, "moe", 2.5) == 2.5 * EXPERT + E_SIDE
    kinds = [k for k, _ in f.blocks(h)]
    assert kinds == ["wattn", "dense", "wattn", "moe", "wattn", "moe",
                     "attn", "moe", "wattn", "moe"]
    cut = 5 * ATTN + DENSE + 4 * (32 * EXPERT + E_SIDE) + 2 * V * H
    assert f.param_count(h) == cut == 4_321_837_056         # 4.322 B
    assert round(2 * cut / 2 ** 30, 2) == 8.05              # GiB in bf16
    full = dict(h, num_hidden_layers=60, num_dense_layers=6, num_experts=256,
                vocab_size=V_ALL)
    whole = 60 * ATTN + 6 * DENSE + 54 * (256 * EXPERT + E_SIDE) + 2 * V_ALL * H
    assert f.param_count(full) == whole == 398_634_516_480   # the published 400B
    assert [k for k, _ in f.blocks(full)].count("wattn") == 45
    assert f.router_width(h) == 256 and f.held_share(h) == 0.125
    assert f.held_share(full) == 1.0


def test_the_toy_keeps_one_period_and_every_mechanism():
    f = fam()
    toy = common.hf_of(common.load_config(CONFIG), rehearsal=True)
    assert [k for k, _ in f.blocks(toy)] == [
        "wattn", "dense", "wattn", "moe", "wattn", "moe", "attn", "moe",
        "wattn", "moe"]
    assert (toy["num_experts"], toy["num_experts_router"],
            toy["num_experts_per_tok"]) == (8, 16, 4)
    assert toy["num_attention_heads"] == 6 * toy["num_key_value_heads"]
    assert toy["sliding_window"] == W and "sliding_window" not in f.TOY
    assert len(f.DEFECTS) == 11


def test_the_rows_the_rings_and_the_visible_pairs():
    f, h = fam(), hf()
    assert f.row_bytes(h, 8) == ROW == 2112 and f.row_bytes(h, 0) == 2 * 8 * 256
    assert f.kv_bytes_per_token(h, 8) == 1 * ROW             # the full plane
    assert f.ring_bytes_per_slot(h, 8) == 4 * W * ROW == 34_603_008
    assert round(64 * f.ring_bytes_per_slot(h, 8) / 2 ** 30, 2) == 2.06
    # query i sees keys max(0, i - W + 1) .. i
    for S, win in ((8, 3), (3, 8), (5, 5), (6, 1), (9216, 4096)):
        want = sum(min(i + 1, win) for i in range(S))
        assert f.band_pairs(S, win) == want, (S, win)
    assert f.band_pairs(9216, 4096) == 9216 * 4096 - 4096 * 4095 / 2
    assert f.flash_band_flops(h, 9216) == 4 * f.band_pairs(9216, W) * 48 * 128
    # the causal half of a full layer at the same length is 1.45 x the band
    full = f.flash_flops(h, 1, 9216)["fwd"]
    assert full / f.flash_band_flops(h, 9216) == pytest.approx(1.446, abs=2e-3)


def test_decode_step_bytes_by_layer_kind():
    f, h = fam(), hf()
    other = 5 * ATTN + DENSE + 4 * E_SIDE + V * H
    c = {"kv_cache_bits": 8, "mean_occupancy": 64.0,
         "mean_live_tokens": 64 * 7000.0,
         "stats": {"moe_experts_touched_per_step": 20.0}}
    # a full plane reads the live rows, a window plane min(context, window)
    assert f.window_rows_per_slot(h, c) == W
    assert f.decode_step_bytes(h, c) == (
        2 * (other + 4 * 20 * EXPERT) + ROW * 64 * 7000 + 64 * 4 * W * ROW)
    # below the window the rings are read as far as they are filled
    c["mean_live_tokens"] = 64 * 1000.0
    assert f.window_rows_per_slot(h, c) == 1000
    assert f.decode_step_bytes(h, c) == (
        2 * (other + 4 * 20 * EXPERT) + 5 * ROW * 64 * 1000)
    # no counter: every held expert; no live slot: the weights alone
    assert f.decode_step_bytes(h, {"kv_cache_bits": 8, "mean_live_tokens": 0.0}) \
        == 2 * (other + 4 * 32 * EXPERT)


def test_the_family_finds_its_kernels_and_its_rings_by_name():
    f, h = fam(), hf()
    assert f.flash_band_kernel(BAND) == 9216
    assert f.flash_band_kernel(FLASH) is None and f.flash_band_kernel(OTHER) is None
    assert f.flash_band_kernel(
        "%flash_fwd_band_like = bf16[1,8,6,64,128]{4,3,2,1,0} fusion(bf16[4] %a)") is None
    for name in (BAND, RING_READ, RING_WRITE, SCALE_WRITE):
        assert f.window_op(name, h), name
    for name in (FLASH, POOL_READ, OTHER):
        assert not f.window_op(name, h), name


def fake_run(events, modules, counters):
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OPS_LINE, "events": [list(e) for e in events]},
        {"name": trace_reduce.MODULES_LINE, "events": [list(m) for m in modules]}]}
    raw = {"planes": [plane, {"name": trace_reduce.HOST_PLANE, "lines": [
        {"name": "t", "events": [[trace_reduce.WINDOW_SPAN, 0.0, 1e9]]}]}]}
    return raw, {"trace": trace_reduce.reduce(raw), "family": fam(), "hf": hf(),
                 "peaks": peaks.peaks_for("TPU v5 lite"), "cell": {"name": CELL},
                 "counters": counters}


def test_the_readers_of_the_window_blocks(monkeypatch):
    from benchmark.harness import program_spans
    f, h = fam(), hf()
    share, roof, over, live = (loadgen.load_module("layer_metrics", n) for n in (
        "sat_attn_window_share_of_device", "sat_flash_band_roofline",
        "sat_window_read_over_window", "sat_window_share_of_live_cache"))
    stats = {"window_rows_read": 4096.0 * 640, "window_rows_in_window": 4000.0 * 640,
             "ring_bytes_per_slot": 4.0 * W * ROW, "kv_bytes_per_token": float(ROW)}
    counters = {"mean_occupancy": 60.0, "mean_live_tokens": 60 * 7000.0,
                "kv_cache_bits": 8, "stats": stats}
    # four band kernels of a 9216-token prompt at twice their compute floor,
    # ring reads and writes for as long again, and as much of other ops
    floor = f.flash_band_flops(h, 9216) / 197e12
    events, t = [], 0.0
    for _ in range(4):
        events.append((BAND, t, 2 * floor * 1e9)); t += 3 * floor * 1e9
    for name in (RING_READ, RING_WRITE):
        events.append((name, t, 4 * floor * 1e9)); t += 5 * floor * 1e9
    events.append((POOL_READ, t, 8 * floor * 1e9)); t += 9 * floor * 1e9
    events.append((OTHER, t, 8 * floor * 1e9))
    raw, run = fake_run(events, [("jit_prefill(2)", 0.0, 1e6)], counters)
    monkeypatch.setattr(program_spans, "find_xplane", lambda cell: "a-trace")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert share.read(run) == pytest.approx(50.0)
    assert roof.read(run) == pytest.approx(50.0)
    assert over.read(run) == pytest.approx(1.024)
    rings, kv = 60 * 4.0 * W * ROW, ROW * 60 * 7000.0
    assert live.read(run) == pytest.approx(100.0 * rings / (rings + kv))
    # a program without window blocks (another family's, the parent's): nothing
    raw, run = fake_run([(OTHER, 0.0, 1e6), (FLASH, 2e6, 1e6)], [],
                        {"mean_occupancy": 60.0, "mean_live_tokens": 1.0,
                         "stats": {"kv_bytes_per_token": 8.0}})
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    assert all(r.read(run) is None for r in (share, roof, over, live))
    run["family"] = loadgen.load_family({"model_type": "qwen3_next"})
    assert all(r.read(run) is None for r in (share, roof, over, live))
    assert over.read({"counters": {}}) is None and live.read({"counters": {}}) is None


def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "batch-longcontext", 1)
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "num_experts", "vocab_size"]
    assert conf["source"] == common.load_config(CONFIG)["source"]
    where = {m["name"]: m.get("workloads") for m in b["end_to_end"] + b["per_layer"]}
    for name in ("serve_tokens_per_s", "sat_batch_occupancy", "sat_host_share_of_round",
                 "sat_decode_step_device_ms", "sat_decode_step_roofline",
                 "sat_prefill_share_of_device", "serve_hbm_in_use_gib",
                 "sat_host_bound_idle_share", "sat_ahead_covered_share",
                 "sat_round_max_over_median", "sat_moe_share_of_device",
                 "sat_moe_sorted_share_of_device", "sat_moe_load_max_over_mean",
                 "sat_moe_experts_touched", "sat_moe_ffn_roofline",
                 "sat_moe_sorted_ffn_roofline", "sat_moe_held_assignment_share",
                 "sat_attn_window_share_of_device", "sat_flash_band_roofline",
                 "sat_window_read_over_window", "sat_window_share_of_live_cache"):
        assert CELL in where[name], name
    for name in ("sat_attn_window_share_of_device", "sat_flash_band_roofline",
                 "sat_window_read_over_window", "sat_window_share_of_live_cache"):
        assert where[name] == [CELL], name
    # other families' recurrences, another family's loop: not this cell's
    for name in ("sat_ssm_share_of_device", "sat_gdn_share_of_device",
                 "sat_state_share_of_live_cache", "sat_kv_gathered_over_live",
                 "sat_loop_reread_share_of_step_bytes"):
        assert CELL not in where[name], name
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    t = loadgen.load_traffic("batch-longcontext")
    assert t["kind"] == "saturating" and t["requests"] == 400
    assert t["prompt"] == {"median": 6144, "sigma": 0.2, "min": 4096, "max": 9216}
    assert t["output"] == {"median": 1024, "sigma": 0.25, "min": 512, "max": 2048}
    assert t["prompt"]["min"] >= W                 # every prompt wraps its rings
    assert t["prompt"]["max"] + t["output"]["max"] == 11264


def test_what_the_benchmark_had_before_this_cell_is_as_the_cell_before_holds_it(
        monkeypatch):
    """PR 40's ``test_benchmark_json_has_the_cell_and_its_metrics`` pins
    ``sat_moe_held_assignment_share`` to its cell ALONE; this PR's cell holds
    a share of its experts too and is appended to that list (ISSUE 44), and
    may not edit that file. So that test is run here on ``BENCHMARK.json``
    cut back BY ORDER to what it held before this cell: nothing it holds has
    moved."""
    import builtins
    import io
    import test_qwen3_next_family as before
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    for group, first in (("configs", CONFIG), ("workloads", CELL),
                         ("per_layer", "sat_attn_window_share_of_device")):
        names = [e["name"] for e in b[group]]
        b[group] = b[group][:names.index(first)]
    for m in b["end_to_end"] + b["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"][:m["workloads"].index(CELL)]
    assert (len(b["configs"]), len(b["workloads"]), len(b["per_layer"])) == (8, 8, 47)
    real_open = builtins.open

    def cut_back(path, *a, **kw):
        if os.path.basename(str(path)) == "BENCHMARK.json":
            return io.StringIO(json.dumps(b))
        return real_open(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", cut_back)
    before.test_benchmark_json_has_the_cell_and_its_metrics()


def test_precision_below_rounds_every_operand_and_the_cache():
    import jax.numpy as jnp
    import numpy as np
    f, h = fam(), hf()
    below, plain = f.Reference(h, None, defect="precision_below"), f.Reference(h, None)
    a = jnp.asarray([0.013, 1.3, -0.7, 100.0], jnp.float32)
    assert np.array_equal(np.asarray(below._lo(a)), [0.013671875, 1.25, -0.75, 96.0])
    assert np.array_equal(np.asarray(plain._lo(a)), np.asarray(a))
    assert below._kv_4bit and not plain._kv_4bit
    one = f.Reference(h, None, defect="kv_4bit")
    assert one._operand is None and one._kv_4bit
    with pytest.raises(ValueError, match="one of"):
        f.Reference(h, None, defect="no_such_defect")


@pytest.fixture(scope="module")
def toy_logits():
    """The plain reference's logits over 56 positions of a toy model with a
    window of 16, and a function that gives a defect's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    f = fam()
    toy = dict(common.hf_of(common.load_config(CONFIG), rehearsal=True),
               sliding_window=16, max_position_embeddings=256)
    cfg = hf_config_to_transformer(toy, dtype=jnp.float32, norm_init_jitter=0.5,
                                   post_norm_init=0.5)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    ids = np.random.default_rng(0).integers(0, toy["vocab_size"], 56)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None])[0])
    return got, lambda defect: f.Reference(toy, params, defect=defect).logits(
        ids, pad_to=64)


def test_the_program_is_the_plain_reference_on_logits(toy_logits):
    import numpy as np
    got, ref = toy_logits
    assert np.abs(got - ref(None)).max() < 1e-5


@pytest.mark.parametrize("defect", [
    "precision_below", "fp8_operands", "kv_4bit", "band_off_by_one",
    "rotary_on_full", "no_rotary_on_sliding", "no_out_gate", "no_route_scale",
    "renorm_over_held", "no_mup", "no_post_norm"])
def test_each_seeded_defect_fails_on_logits_at_toy_widths(toy_logits, defect):
    """Every defect the configuration's ``correct.why`` names — those a limit
    on TOKENS cannot tell apart at the published size too — moves the toy's
    logits (of size ~1) by more than a hundred times what the sound program
    differs from the plain reference by (6e-7)."""
    import numpy as np
    got, ref = toy_logits
    assert np.abs(got - ref(defect)).max() > 1e-3
    assert defect in fam().DEFECTS


def test_the_defect_tool_judges_through_the_harness_check():
    src = open(os.path.join(ROOT, "benchmark", "tools", "afmoe_defects.py")).read()
    assert "correct.check_tokens_vs_reference(" in src and "def judge" not in src
    assert 'CELL = "' + CELL + '"' in src


def test_the_cell_rehearses_on_the_cpu():
    """75 s: the first round is 64 prefills of 512-1152 tokens, ~10 s alone
    and ~40 s beside tier-1's other workers; a request has to FINISH."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seconds", "75", "--seed", "3000000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = [ln for ln in p.stdout.splitlines() if ln.startswith("REHEARSAL")][-1]
    assert '"correct": true' in last and "serve_tokens_per_s" in last
    assert "sat_window_read_over_window" in last
    assert "sat_window_share_of_live_cache" in last
    assert "sat_moe_held_assignment_share" in last
    assert "benchmark.families.afmoe" in p.stdout
    assert "wk/0 (64, 4096, 1, 32) int8" in p.stdout
