"""The proof that a new model family enters the benchmark as ADDED files.

``fixtures/family_seam/`` holds what the next ``model_config`` PR brings: a
family file for a block the program serves but no reference covered (GPT-2:
LayerNorm with biases, learned positions, biased projections, GELU MLP, tied
head), a configuration file, a traffic mix (data: the chat mix cut to
GPT-2's 1024 positions) and their ``BENCHMARK.json`` entries. The tests
add them to a scratch copy of the benchmark, touching nothing that exists,
and run the serving job's rehearsal there. If this needs a harness edit, so
will that PR."""
import dataclasses
import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
from benchmark.harness import common, correct, loadgen, serve_job  # noqa: E402

SEAM = os.path.join(HERE, "fixtures", "family_seam")
LEFT_BEHIND = shutil.ignore_patterns("out", "__pycache__", ".pytest_cache")


def digests(top: str) -> dict:
    out = {}
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x not in ("out", "__pycache__")]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_family_added_as_files_runs_the_serve_rehearsal(tmp_path):
    # the benchmark as committed + the program, in a scratch checkout
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=LEFT_BEHIND)
    os.symlink(os.path.join(ROOT, "deepspeed_tpu"), tmp_path / "deepspeed_tpu")
    before = digests(str(tmp_path / "benchmark"))

    # ---- what a model_config PR does: ADD files and entries -------------
    added = {"families/gpt2.py", "configs/gpt2-serve.json", "traffic/chat-1k.json"}
    for rel in added:
        assert rel not in before
        shutil.copy(os.path.join(SEAM, rel), tmp_path / "benchmark" / rel)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(SEAM, "benchmark_entries.json")) as f:
        entries = json.load(f)
    bench["configs"] += entries["configs"]
    bench["workloads"] += entries["workloads"]
    cell = entries["workloads"][0]["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:    # report what the chat cell reports
        if entries["metrics_as"] in m.get("workloads", ()):
            m["workloads"].append(cell)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--rehearsal",
         "--seconds", "2"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL (not a result) ")
    shown = json.loads(last.split(") ", 1)[1])
    assert shown["correct"] is True
    assert {"setup_s", "ttft_p90_ms", "tpot_p90_ms"} <= set(shown["end_to_end"])
    assert "batch_occupancy" in shown["per_layer"]
    with open(tmp_path / "benchmark" / "out" / f"{cell}.seed0.trace0.json") as f:
        checks = {c["name"]: c for c in json.load(f)["checks"]}
    judged = checks["tokens_vs_reference"]
    assert judged["family"] == "benchmark.families.gpt2" and judged["positions"] > 0

    # nothing that existed was touched
    after = digests(str(tmp_path / "benchmark"))
    assert set(after) - set(before) == added
    assert {k: after[k] for k in before} == before


@pytest.fixture(scope="module")
def gpt2():
    spec = importlib.util.spec_from_file_location(
        "family_seam_gpt2", os.path.join(SEAM, "families", "gpt2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_fixture_family_has_the_whole_protocol(gpt2):
    assert all(hasattr(gpt2, n) for n in loadgen.FAMILY_PROTOCOL)
    with open(os.path.join(SEAM, "configs", "gpt2-serve.json")) as f:
        cfg = json.load(f)
    hf = common.hf_of(cfg)
    assert set(gpt2.TOY) <= set(hf)
    # GPT-2 small: 12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 50257 matmul
    # parameters (the position table and the embedding lookup do no matmul)
    params = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    assert gpt2.train_flops_per_token(hf, 1024) == 6.0 * params + 3 * 12 * 4 * 512 * 768
    assert gpt2.flash_flops(hf, 2, 1024)["total"] == 7 * 2.0 * 2 * 768 * 1024 * 1024 / 2
    assert gpt2.decode_step_bytes(hf, {"kv_cache_bits": 0, "mean_live_tokens": 100.0}) \
        == 2.0 * params + 2.0 * 12 * 768 * 2 * 100.0


def test_the_fixture_reference_agrees_with_the_program_and_a_dropped_bias_does_not(gpt2):
    """Float32 both ways through the program's serving path (paged cache,
    fused qkv), at tolerances a rehearsal's zeroed ones cannot show."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import make_model
    with open(os.path.join(SEAM, "configs", "gpt2-serve.json")) as f:
        cfg = json.load(f)
    hf = dict(common.hf_of(cfg), **gpt2.TOY)
    mcfg = dataclasses.replace(common.model_config(cfg, hf, 128), dtype=jnp.float32)
    model = make_model(mcfg, name="gpt2-seam")
    params = model.init(jax.random.PRNGKey(0))
    # init_params leaves every bias at zero, where dropping one shows nothing
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    def noisy(path, x):
        name = jax.tree_util.keystr(path)
        if "bias" in name or "'b" in name:
            return 0.1 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x
    params = jax.tree_util.tree_map_with_path(noisy, params)
    assert float(jnp.abs(params["layers"]["b_out"]).max()) > 0

    srv = deepspeed_tpu.init_serving(
        model, serving={"max_seqs": 4, "max_model_len": 128}, params=params,
        dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [loadgen.random_prompt(rng, n, hf["vocab_size"]) for n in (5, 23, 40, 64)]
    outs = srv.run([(p, 24) for p in prompts])
    # an output is prompt + generated, keyed by rid in submission order
    samples = [(p, outs[rid][p.size:]) for p, rid in zip(prompts, sorted(outs))]
    served = srv.engine.params
    srv.close()

    # judge every position whose lead exceeds float32 rounding of ~30 logits
    tol = dict(margin=1e-3, min_checked_share=0.9, min_agreement=0.99)
    good = correct.check_tokens_vs_reference(samples, gpt2.Reference(hf, served), **tol)
    assert good["ok"] and good["mismatched"] == 0 and good["positions"] == 4 * 24, good
    # ... and the comparison has teeth: the same reference without ONE bias
    # (the MLP's output bias read as zero) is caught
    layers = dict(served["layers"], b_out=jnp.zeros_like(served["layers"]["b_out"]))
    bad = correct.check_tokens_vs_reference(
        samples, gpt2.Reference(hf, dict(served, layers=layers)), **tol)
    assert not bad["ok"] and bad["mismatched"] > 0, bad


def test_expect_keys_resolve_by_attribute_name_and_an_unknown_one_raises():
    srv = types.SimpleNamespace(
        decode_backend="xla",
        model=types.SimpleNamespace(config=types.SimpleNamespace(kv_cache_bits=8)))
    assert serve_job.engine_attr(srv, "decode_backend") == "xla"
    assert serve_job.engine_attr(srv, "kv_cache_bits") == 8
    with pytest.raises(KeyError, match="no_such_setting"):
        serve_job.engine_attr(srv, "no_such_setting")
