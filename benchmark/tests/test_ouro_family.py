"""The Ouro family (families/ouro.py) and its cell: the cost model against
pinned numbers and hand counts, the three counter readers on hand-built runs,
the reference against the program in float32, the family seam, and the
cell's rehearsal on the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen  # noqa: E402

CELL = "ouro-2.6b-serve.batch-worked-answers"
H, F, V, L, T, NH, HD = 2048, 5632, 49152, 48, 4, 16, 128
LAYER = 4 * H * H + 3 * H * F           # attention + the gated feed-forward
PER_TOKEN = T * L * 2 * NH * (HD + 4)   # int8 K and V + a scale a head, 192 planes
# the catalog's `config` of Ouro-2.6B (model-configs guide), verbatim
CATALOG = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
           "max_position_embeddings": 65536, "max_window_layers": 48,
           "model_type": "ouro", "num_attention_heads": 16,
           "num_hidden_layers": 48, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
           "sliding_window": None, "tie_word_embeddings": False,
           "total_ut_steps": 4, "early_exit_threshold": 1,
           "use_sliding_window": False, "vocab_size": 49152}


def hf():
    return common.hf_of(common.load_config("ouro-2.6b-serve"))


def fam():
    return loadgen.load_family(hf())


def test_the_configuration_is_the_catalog_entry_with_nothing_cut():
    cfg, h = common.load_config("ouro-2.6b-serve"), hf()
    assert h == CATALOG
    assert cfg["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):                 # the copy above is the row's
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
        assert CATALOG == row["config"] and cfg["source"] == row["source_url"]
    assert cfg["reduced"] == {}
    # the one thing the benchmark says of its weights: where the norm scales
    # start (the program's own initialiser starts them at 1)
    assert cfg["run"]["overrides"] == {"norm_init_jitter": 0.5, "post_norm_init": 0.1}
    assert (h["num_hidden_layers"], h["total_ut_steps"], h["early_exit_threshold"]) == (L, T, 1)
    assert cfg["run"]["serving"] == {"max_seqs": 16, "max_model_len": 1280, "num_blocks": 161}
    assert cfg["run"]["expect"] == {"kv_cache_bits": 8, "ut_steps": T, "kv_planes": T * L}
    assert {"sandwich_norm", "between_pass_norm", "exit_gate", "weights"} <= set(cfg["assumed"])
    # full residency for the mix: 16 slots x the 10 columns a request can
    # reach (192 + 416 tokens + the quantum's 8 rows) + the trash block
    mix = loadgen.load_traffic("batch-worked-answers")
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    assert -(-(longest + 8) // 64) == 10 and 16 * 10 + 1 == 161


def test_parameters_bytes_a_token_and_bytes_a_step():
    f, h = fam(), hf()
    assert f.layer_params(h) == LAYER == 51_380_224
    assert f.stored_params(h) == L * (LAYER + 4 * H) + 2 * V * H + H + (H + 1) \
        == 2_667_974_657                                    # the published 2.6B
    assert f.kv_planes(h) == 192
    assert f.kv_bytes_per_token(h, 8) == PER_TOKEN == 811_008
    assert f.kv_bytes_per_token(h, 0) == T * L * 2 * NH * HD * 2 == 1_572_864
    # a step reads the layers once per PASS and the head once: 19.93 GB
    assert f.weight_bytes(h) == 2 * (T * L * LAYER + V * H) == 19_931_332_608
    assert f.loop_reread_bytes(h) == 2 * (T - 1) * L * LAYER == 14_797_504_512
    counters = {"kv_cache_bits": 8, "mean_live_tokens": 5000.5, "max_seqs": 16}
    assert f.decode_step_bytes(h, counters) == 19_931_332_608 + PER_TOKEN * 5000.5
    # the pool of the cell: 161 blocks of 64 tokens, 8.36 GB
    assert 161 * 64 * PER_TOKEN == 8_356_626_432


def test_operations_count_every_pass():
    f, h = fam(), hf()
    attn = 3 * T * L * (2 * 2 * 1024 * NH * HD)             # causal half, 2 matmuls
    assert f.train_flops_per_token(h, 2048) == 6 * (T * L * LAYER + V * H) + attn
    assert f.flash_flops(h, batch=1, seq_len=128)["fwd"] == 2 * (2 * NH * 128 * 128 * HD / 2)


def fake_run(stats, **counters):
    return {"family": fam(), "hf": hf(), "cell": {"name": CELL},
            "counters": dict({"kv_cache_bits": 8, "max_seqs": 16, "stats": stats,
                              "pool": {"k": {"shape": (192, 161, 64, 16, 128), "dtype": "int8"}}},
                             **counters)}


def test_the_three_readers_on_hand_built_counters():
    reread = loadgen.load_module("layer_metrics", "sat_loop_reread_share_of_step_bytes")
    gathered = loadgen.load_module("layer_metrics", "sat_kv_gathered_over_live")
    exits = loadgen.load_module("layer_metrics", "sat_exit_step_expected")
    stats = {"kv_bytes_per_token": 811008.0, "exit_step_expected": 2.25,
             "step_shape_rounds": {"16x10": 30, "16x15": 10, "16x20": 0}}
    run = fake_run(stats, mean_live_tokens=5120.0)
    need = 19_931_332_608 + 811_008 * 5120.0
    assert reread.read(run) == pytest.approx(100 * 14_797_504_512 / need)
    assert 55 < reread.read(run) < 65
    # 30 rounds gathered 16 x 10 x 64 positions, 10 rounds 16 x 15 x 64
    assert gathered.read(run) == pytest.approx((30 * 10240 + 10 * 15360) / 40 / 5120.0)
    assert exits.read(run) == 2.25
    # the parent's program has no such counters: nothing, not zero
    bare = fake_run({"step_shape_rounds": {"16x10": 30}}, mean_live_tokens=5120.0)
    assert gathered.read(bare) is None and exits.read(bare) is None
    bare["family"] = loadgen.load_module("families", "mistral")
    assert reread.read(bare) is None


def test_benchmark_json_gains_the_cell_and_nothing_else_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ouro-2.6b-serve", "batch-worked-answers", 1)
    assert {c["name"]: c for c in b["configs"]}["ouro-2.6b-serve"]["reduced"] == []
    reports = {m["name"] for m in b["per_layer"] if CELL in m["workloads"]}
    assert reports == {
        "sat_batch_occupancy", "sat_host_share_of_round", "sat_decode_step_device_ms",
        "sat_decode_step_roofline", "sat_prefill_share_of_device", "serve_hbm_in_use_gib",
        "sat_host_bound_idle_share", "sat_loop_reread_share_of_step_bytes",
        "sat_kv_gathered_over_live", "sat_exit_step_expected"}
    # (membership, not position: the next cell is appended after this one)
    own = [m for m in b["per_layer"] if m["name"] in (
        "sat_loop_reread_share_of_step_bytes", "sat_kv_gathered_over_live",
        "sat_exit_step_expected")]
    assert len(own) == 3 and all(m["workloads"] == [CELL] for m in own)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"] and "workloads" not in e2e["setup_s"]
    for name in reports:                               # each reader's header agrees
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        header = loadgen.load_module("layer_metrics", name).HEADER
        assert {k: entry[k] for k in ("layer", "unit", "moves", "source", "better")} \
            == {k: header[k] for k in ("layer", "unit", "moves", "source", "better")}


def test_what_the_benchmark_had_up_to_the_cell_before_is_as_that_cells_test_holds_it(monkeypatch):
    """``test_nemotron_h_family.test_benchmark_json_has_the_cell_and_its_
    metrics`` pins PR 32's entries as the LAST of ``BENCHMARK.json``'s lists
    (``[-1] == CELL``), and a later PR has to append after them (the driver
    reads an entry put first or in the middle as a change to what was
    there) and may not edit that file. So the whole of that test, order pins
    included, is run here on ``BENCHMARK.json`` cut back to the entries up
    to and including PR 32's cell: whatever was appended since, nothing that
    test holds has moved. (Cut by ORDER, so the next cell's entries go too.)"""
    import types
    import test_nemotron_h_family as before
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    names = [w["name"] for w in b["workloads"]]
    later = set(names[names.index(before.CELL) + 1:])
    assert CELL in later
    b["workloads"] = [w for w in b["workloads"] if w["name"] not in later]
    used = {w["config"] for w in b["workloads"]}
    b["configs"] = [c for c in b["configs"] if c["name"] in used]
    for key in ("end_to_end", "per_layer"):
        kept = []
        for m in b[key]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"] if w not in later]
                if not m["workloads"]:
                    continue                            # a later cell's own metric
            kept.append(m)
        b[key] = kept
    monkeypatch.setattr(before, "json", types.SimpleNamespace(load=lambda fh: b))
    before.test_benchmark_json_has_the_cell_and_its_metrics()


def test_the_family_came_in_as_added_files_found_by_name():
    """Everything this family brings is a file of its own that the harness
    finds by name (that no file the benchmark had was edited is the
    driver's check, and PR 35's `git diff --stat` in CHANGES.md)."""
    for rel in ("families/ouro.py", "configs/ouro-2.6b-serve.json",
                "traffic/batch-worked-answers.json", "tools/ouro_defects.py",
                "layer_metrics/sat_loop_reread_share_of_step_bytes.py",
                "layer_metrics/sat_kv_gathered_over_live.py",
                "layer_metrics/sat_exit_step_expected.py"):
        assert os.path.exists(os.path.join(ROOT, "benchmark", rel)), rel
    family = loadgen.load_family({"model_type": "ouro"})
    assert family.__name__ == "benchmark.families.ouro"
    assert all(hasattr(family, name) for name in loadgen.FAMILY_PROTOCOL)
    assert loadgen.load_traffic("batch-worked-answers")["kind"] == "saturating"


def test_the_reference_agrees_with_the_program_in_float32():
    """At the family's toy widths, float32 "highest": the full forward and
    the serving path (prefill through the pool, then the engine's own decode
    steps) give the reference's logits and greedy tokens."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models import transformer as T_
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    f = fam()
    h = dict(hf(), **f.TOY)
    cfg = hf_config_to_transformer(
        h, max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="xla", **common.load_config("ouro-2.6b-serve")["run"]["overrides"])
    with jax.default_matmul_precision("highest"):
        srv = deepspeed_tpu.init_serving(
            make_model(cfg), config={}, dtype=jnp.float32, rng=jax.random.PRNGKey(7),
            serving=dict(max_seqs=2, block_size=16, max_model_len=128, decode_quantum=4,
                         prompt_bucket=16, decode_backend="xla"))
        ref = f.Reference(h, srv.engine.params)        # the engine's fused stacks
        ids = np.random.default_rng(1).integers(0, h["vocab_size"], 23).astype(np.int32)
        want = ref.logits(ids, pad_to=16)
        params = T_.unfuse_layer_stack(srv.engine.params, cfg)
        got = np.asarray(T_.forward(params, jnp.asarray(ids)[None], cfg)[0])
        assert np.abs(got - want).max() < 1e-4
        out = list(srv.run([(ids[:11], 9)]).values())[0]
        seq = list(ids[:11])
        for _ in range(9):
            seq.append(int(ref.logits(np.asarray(seq), pad_to=16)[-1].argmax()))
        np.testing.assert_array_equal(out[-9:], seq[-9:])
        p = ref.exit_distribution(np.asarray(seq[:-1]), pad_to=16)[10:]     # the 9 sampled positions
        assert srv.stats()["exit_step_expected"] == pytest.approx(
            float((p * np.arange(1, 5)).sum(axis=1).mean()), abs=1e-4)
        srv.close()


def test_the_cell_rehearses_on_the_cpu():
    # 10 s: the shortest answer is 96 / 8 = 12 tokens, two rounds of 8 steps
    # through four passes, on a CPU that the suite's workers share
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seconds", "10", "--seed", "3500000007", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = [l for l in p.stdout.splitlines() if l.startswith("REHEARSAL")][-1]
    shown = json.loads(last.split(") ", 1)[1])
    assert shown["correct"] is True and "serve_tokens_per_s" in shown["end_to_end"]
    assert {"sat_loop_reread_share_of_step_bytes", "sat_kv_gathered_over_live",
            "sat_exit_step_expected", "sat_batch_occupancy"} <= set(shown["per_layer"])
    assert "benchmark.families.ouro" in p.stdout
