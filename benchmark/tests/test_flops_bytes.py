"""flops.py / bytes.py against hand counts for the four cells."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import bytes as nbytes, common, flops  # noqa: E402

ATTN = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096          # 41,943,040
MLP = 3 * 4096 * 14336                                      # 176,160,768
HEAD = 4096 * 32000                                         # 131,072,000
MOE_ALL = 8 * MLP + 4096 * 8                                # every expert + router
MOE_ACTIVE = 2 * MLP + 4096 * 8


def hf(name):
    return common.hf_of(common.load_config(name))


@pytest.mark.parametrize("name,layers", [("mistral-7b-train", 2),
                                         ("mistral-7b-zero3", 8),
                                         ("mistral-7b-serve", 16)])
def test_mistral_matmul_params_leave_the_embedding_out(name, layers):
    h = hf(name)
    assert h["num_hidden_layers"] == layers
    assert flops.matmul_params(h) == layers * (ATTN + MLP) + HEAD


def test_train_flops_per_token():
    h = hf("mistral-7b-train")
    # 6 per matmul parameter + causal attention: 3 x 2 layers x (2 matmuls x
    # 2 FLOPs x 1024 keys on average x 4096)
    want = 6 * (2 * (ATTN + MLP) + HEAD) + 3 * 2 * (4 * 1024 * 4096)
    assert flops.train_flops_per_token(h, 2048) == want
    assert want / 1e9 == pytest.approx(3.5046, abs=1e-3)
    z = flops.train_flops_per_token(hf("mistral-7b-zero3"), 2048)
    assert z / 1e9 == pytest.approx(11.657, abs=1e-2)


def test_flash_flops_count_the_causal_half_once():
    f = flops.flash_flops(hf("mistral-7b-train"), batch=4, seq_len=2048)
    one = 2 * 4 * 32 * 2048 * 2048 * 128 / 2
    assert f == {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


def test_mixtral_counts_active_experts_for_flops_and_all_for_bytes():
    h = hf("mixtral-8x7b-serve")
    assert flops.matmul_params(h) == 4 * (ATTN + MOE_ACTIVE) + HEAD
    assert flops.matmul_params(h, active=False) == 4 * (ATTN + MOE_ALL) + HEAD
    assert nbytes.weight_bytes(h) == 2 * (4 * (ATTN + MOE_ALL) + HEAD)
    assert nbytes.weight_bytes(h) / 1e9 == pytest.approx(11.87, abs=0.01)


def test_decode_step_bytes():
    h = hf("mistral-7b-serve")
    assert nbytes.weight_bytes(h) == 2 * (16 * (ATTN + MLP) + HEAD)
    # int8 pool: K and V, 16 layers, 8 kv heads, 128 bytes + one f32 scale
    assert nbytes.kv_bytes_per_token(h, 8) == 2 * 16 * 8 * (128 + 4)
    assert nbytes.kv_bytes_per_token(h, 0) == 2 * 16 * 8 * 256
    live = 48 * 400
    assert nbytes.decode_step_bytes(h, 8, live) == \
        nbytes.weight_bytes(h) + 2 * 16 * 8 * 132 * live
