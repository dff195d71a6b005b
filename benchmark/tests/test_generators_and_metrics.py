"""Traffic kinds are pure functions of seed and parameters; percentile
arithmetic with the miss rule."""
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import loadgen, metrics  # noqa: E402

CTX = {"vocab_size": 32000, "seconds": 30.0, "max_model_len": 2048}
MIXES = ["seq2048", "chat", "batch-decode"]


def _flat(schedule):
    if isinstance(schedule, dict):
        return [a.tobytes() for a in schedule["pool"]]
    return [(r["due_s"], r["prompt"].tobytes(), r["max_new_tokens"]) for r in schedule]


@pytest.mark.parametrize("mix", MIXES)
def test_generator_is_a_pure_function_of_seed_and_parameters(mix):
    t = loadgen.load_traffic(mix)
    a = loadgen.generate(t, 7, CTX)
    b = loadgen.generate(t, 7, CTX)
    c = loadgen.generate(t, 8, CTX)
    assert _flat(a) == _flat(b)
    assert _flat(a) != _flat(c)


def test_chat_offers_the_same_load_under_every_seed():
    t = loadgen.load_traffic("chat")
    totals = []
    for seed in range(5):
        s = loadgen.generate(t, seed, CTX)
        assert len(s) == round(t["rate_per_s"] * CTX["seconds"])
        assert all(0 <= r["due_s"] <= CTX["seconds"] for r in s)
        assert [r["due_s"] for r in s] == sorted(r["due_s"] for r in s)
        p = [r["prompt"].size for r in s]
        o = [r["max_new_tokens"] for r in s]
        assert min(p) >= t["prompt"]["min"] and max(p) <= t["prompt"]["max"]
        assert min(o) >= t["output"]["min"] and max(o) <= t["output"]["max"]
        assert all(r["prompt"].size + r["max_new_tokens"] <= CTX["max_model_len"] for r in s)
        totals.append((sum(p), sum(o)))
    tot = np.array(totals, float)
    assert (tot.std(axis=0) / tot.mean(axis=0) < 0.01).all()      # stratified lengths
    assert abs(np.median(p) - t["prompt"]["median"]) < 0.15 * t["prompt"]["median"]


def test_saturating_is_all_due_at_zero_and_train_pool_has_the_token_batch():
    s = loadgen.generate(loadgen.load_traffic("batch-decode"), 3, CTX)
    assert {r["due_s"] for r in s} == {0.0}
    b = loadgen.generate(loadgen.load_traffic("seq2048"), 3, CTX)
    assert b["sequences_per_step"] * b["seq_len"] == b["tokens_per_step"] == 8192
    assert len(b["pool"]) == 8 and b["pool"][0].shape == (4, 2048)
    assert b["pool"][0].dtype == np.int32 and b["pool"][0].max() < 32000


def test_percentile_is_a_sample_and_misses_rank_above_everything():
    v = list(range(1, 101))                     # 1..100
    assert metrics.percentile(v, 50) == 50
    assert metrics.percentile(v, 90) == 90
    assert metrics.percentile(v, 100) == 100
    assert metrics.samples_beyond(100, 90) == 10
    assert metrics.samples_beyond(400, 90) == 40
    # 90 finished + 10 misses: p90 is the slowest finished one, p91 a miss
    fin = list(range(1, 91))
    assert metrics.percentile(fin, 90, n_miss=10, miss_value=9999.0) == 90
    assert metrics.percentile(fin, 91, n_miss=10, miss_value=9999.0) == 9999.0
    assert metrics.percentile(fin, 91, n_miss=10) == math.inf
    # a miss moves the tail up, never down
    assert metrics.percentile(fin, 90, n_miss=1, miss_value=9999.0) >= metrics.percentile(fin, 90)
    with pytest.raises(ValueError):
        metrics.percentile([], 50)
