"""The five readers of the engine's set-up record (PR 55): each ``read`` on
hand-built ``run`` dicts and on what a toy engine's ``stats()`` really holds,
nothing from an engine without the record (the parent commit), and the five
``BENCHMARK.json`` entries against their files' ``HEADER``s."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import loadgen  # noqa: E402

LAYER = "engine set-up (inference/engine.py, serving.py builds, runtime/engine.py)"
ENTRIES = [("setup_programs_built", "count"), ("setup_trace_lower_s", "s"),
           ("setup_compile_or_load_s", "s"), ("setup_engine_init_s", "s"),
           ("setup_unattributed_share", "%")]
NAMES = [e[0] for e in ENTRIES]
# a warm GLM-like run: seven buckets, four step shapes, a dozen small ones
SETUP = {"engine_init_s": 4.5, "weights_s": 3.1, "pools_s": 0.4,
         "init_build_s": 1.25, "programs": [], "programs_built": 24,
         "trace_lower_s": 6.5, "compile_or_load_s": 3.0, "overlap_s": 0.5,
         "cache_hits": 24, "built_after_first_reset": 1}
WANT = {"setup_programs_built": 23.0, "setup_trace_lower_s": 6.5,
        "setup_compile_or_load_s": 3.0, "setup_engine_init_s": 3.25,
        "setup_unattributed_share": 100.0 * (1.0 - (9.0 + 3.25) / 25.0)}
# what the parent commit's stats() gives: everything but the record
PARENT = {"completed": 3.0, "rounds_ahead": 400.0, "gc_ms_total": 0.0,
          "slow_rounds": []}


def reader(name):
    return loadgen.load_module("layer_metrics", name)


def run_with(stats, setup_s=25.0):
    return {"counters": {"stats": stats}, "trace": None,
            "e2e": {"setup_s": setup_s, "serve_tokens_per_s": 1000.0},
            "cell": {"name": "glm-4.7-flash-serve.batch-docqa"}}


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,unit", ENTRIES, ids=NAMES)
def test_the_entry_is_appended_and_agrees_with_its_header(name, unit):
    b = benchmark_json()
    names = [m["name"] for m in b["per_layer"]]
    # appended together, in this order, behind everything PR 53 left
    at = names.index(NAMES[0])
    assert names[at:at + 5] == NAMES
    assert at > names.index("sat_mla_read_roofline")
    serve = [w["name"] for w in b["workloads"]
             if loadgen_job(w["config"]) == "serve"]
    assert len(serve) == 8
    m = b["per_layer"][names.index(name)]
    # every serve cell, named: a metric without the list would have to be
    # reported in every cell a later PR adds
    assert m == {"name": name, "unit": unit, "better": "lower",
                 "source": "program_counter", "layer": LAYER,
                 "moves": "setup_s", "workloads": serve}
    h = reader(name).HEADER
    assert {k: h[k] for k in ("layer", "unit", "moves", "source", "better")} == {
        "layer": LAYER, "unit": unit, "moves": "setup_s",
        "source": "program_counter", "better": "lower"}
    assert h["jobs"] == ["serve"]
    assert len(LAYER) <= 200
    # the first metrics that move setup_s, and every cell reports setup_s
    assert [x["name"] for x in b["per_layer"] if x["moves"] == "setup_s"] == NAMES
    (e2e,) = [x for x in b["end_to_end"] if x["name"] == "setup_s"]
    assert "workloads" not in e2e


def loadgen_job(config: str) -> str:
    from benchmark.harness import common
    return common.load_config(config)["run"]["job"]


def test_nothing_the_benchmark_had_moved():
    """Only appended: cut back BY ORDER to the entries before the five, the
    file is the parent's, entry for entry (58 per-layer metrics, 11 cells)."""
    b = benchmark_json()
    names = [m["name"] for m in b["per_layer"]]
    assert len(names) == len(set(names)) == 63
    assert names.index(NAMES[0]) == 58 and len(b["workloads"]) == 11
    assert not any(n.startswith("setup_") for n in names[:58])


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_record(name):
    assert reader(name).read(run_with({"setup": dict(SETUP)})) == pytest.approx(
        WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_an_engine_without_the_record_reads_nothing(name):
    """The parent commit's ``stats()``, a run with no stats, a train job's
    counters: nothing, and no exception."""
    assert reader(name).read(run_with(dict(PARENT))) is None
    assert reader(name).read({"counters": {}, "trace": None, "e2e": {},
                              "cell": {"name": "x.y"}}) is None
    assert reader(name).read({"counters": {"stats": None}, "trace": None,
                              "cell": {"name": "x.y"}}) is None


def test_the_three_parts_and_the_share_add_up_to_the_setup():
    run = run_with({"setup": dict(SETUP)}, setup_s=25.0)
    parts = sum(reader(n).read(run) for n in NAMES[1:4])
    share = reader("setup_unattributed_share").read(run)
    # a lowering that ran beside a compile is in two parts and in the wall once
    assert parts - 0.5 + share / 100.0 * 25.0 == pytest.approx(25.0)
    # programs the constructors built are in the builds' seconds, not twice
    assert parts == pytest.approx(6.5 + 3.0 + 4.5 - 1.25)
    # a set-up of no length (never: the import alone takes seconds) divides
    # nothing
    assert reader("setup_unattributed_share").read(
        run_with({"setup": dict(SETUP)}, setup_s=0.0)) is None


@pytest.mark.parametrize("name", NAMES)
def test_on_a_toy_engines_own_stats(name):
    """What ``ServingEngine.stats()`` really holds, warmed and reset as
    ``serve_job.warm`` does it; the readers' three parts fit the set-up they
    were taken from."""
    st, setup_s = toy_stats()
    run = run_with(st, setup_s=setup_s)
    value = reader(name).read(run)
    assert value is not None and value >= 0.0
    setup = st["setup"]
    if name == "setup_programs_built":
        assert value == setup["programs_built"] >= 3    # a bucket, two steps
        assert setup["built_after_first_reset"] == 0
    elif name == "setup_unattributed_share":
        assert 0.0 < value < 100.0
    else:
        assert 0.0 < value < setup_s
    json.dumps(st["setup"])


_TOY = []


def toy_stats():
    if not _TOY:
        import time
        import jax.numpy as jnp
        import numpy as np
        import deepspeed_tpu
        from deepspeed_tpu.models import TransformerConfig, make_model
        t0 = time.perf_counter()
        model = make_model(TransformerConfig(
            vocab_size=128, hidden_size=64, num_layers=1, num_heads=4,
            num_kv_heads=2, max_seq_len=64, position_type="rotary",
            activation="silu_glu", norm_type="rmsnorm", tie_embeddings=False,
            dtype=jnp.float32, attention_impl="xla"))
        srv = deepspeed_tpu.init_serving(
            model, config={}, dtype=jnp.float32,
            serving=dict(max_seqs=2, block_size=16, max_model_len=64,
                         decode_quantum=2, prompt_bucket=16,
                         decode_backend="xla"))
        srv.run([(np.arange(9, dtype=np.int32), 4)])
        # ... and one more after the first has decoded, as the harness does:
        # its first token is scattered into a step's output array, a second
        # specialisation of a small program (the record names it: "scatter")
        srv.run([(np.arange(9, dtype=np.int32), 4)])
        srv.reset_stats()
        setup_s = time.perf_counter() - t0
        srv.run([(np.arange(7, dtype=np.int32), 4)])    # the "window"
        _TOY.append((srv.stats(), setup_s))
        srv.close()
    return _TOY[0]
