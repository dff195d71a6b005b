"""The cost model, reached the way a run reaches it (through the family
loader), against pinned numbers and hand counts for the four cells."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.families import mistral  # noqa: E402
from benchmark.harness import common, loadgen  # noqa: E402

ATTN = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096          # 41,943,040
MLP = 3 * 4096 * 14336                                      # 176,160,768
HEAD = 4096 * 32000                                         # 131,072,000
MOE_ALL = 8 * MLP + 4096 * 8                                # every expert + router
MOE_ACTIVE = 2 * MLP + 4096 * 8
FLASH = {"fwd": 137438953472.0, "bwd": 343597383680.0, "total": 481036337152.0}


def hf(name):
    return common.hf_of(common.load_config(name))


# what harness/flops.py and harness/bytes.py gave at the published widths
# before the family files took them over (PR 25): FLOPs per trained token at
# S = 2048, bytes of a decode step over an int8 / a bf16 pool at 12345.5 live
# rows. Numbers, not a call to the old functions: those are gone.
@pytest.mark.parametrize("name,train,step_int8,step_bf16", [
    ("mistral-7b-train", 3504340992.0, 1186706624.0, 1235693568.0),
    ("mistral-7b-zero3", 11658067968.0, 3960394496.0, 4156342272.0),
    ("mistral-7b-serve", 22529703936.0, 7658644992.0, 8050540544.0),
    ("mixtral-8x7b-serve", 10450894848.0, 11976534400.0, 12074508288.0)])
def test_the_family_gives_the_pinned_costs_at_published_widths(
        name, train, step_int8, step_bf16):
    h = hf(name)
    fam = loadgen.load_family(h)
    assert fam.train_flops_per_token(h, 2048) == train
    assert fam.flash_flops(h, batch=4, seq_len=2048) == FLASH
    for bits, want in ((8, step_int8), (0, step_bf16)):
        counters = {"kv_cache_bits": bits, "mean_live_tokens": 12345.5,
                    "max_seqs": 48, "stats": {}}        # the family takes what it needs
        assert fam.decode_step_bytes(h, counters) == want


@pytest.mark.parametrize("name,layers", [("mistral-7b-train", 2),
                                         ("mistral-7b-zero3", 8),
                                         ("mistral-7b-serve", 16)])
def test_mistral_matmul_params_leave_the_embedding_out(name, layers):
    h = hf(name)
    assert h["num_hidden_layers"] == layers
    assert mistral.matmul_params(h) == layers * (ATTN + MLP) + HEAD


def test_train_flops_per_token():
    h = hf("mistral-7b-train")
    # 6 per matmul parameter + causal attention: 3 x 2 layers x (2 matmuls x
    # 2 FLOPs x 1024 keys on average x 4096)
    want = 6 * (2 * (ATTN + MLP) + HEAD) + 3 * 2 * (4 * 1024 * 4096)
    assert mistral.train_flops_per_token(h, 2048) == want
    assert want / 1e9 == pytest.approx(3.5046, abs=1e-3)
    z = mistral.train_flops_per_token(hf("mistral-7b-zero3"), 2048)
    assert z / 1e9 == pytest.approx(11.657, abs=1e-2)


def test_flash_flops_count_the_causal_half_once():
    f = mistral.flash_flops(hf("mistral-7b-train"), batch=4, seq_len=2048)
    one = 2 * 4 * 32 * 2048 * 2048 * 128 / 2
    assert f == {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one} == FLASH


def test_mixtral_counts_active_experts_for_flops_and_all_for_bytes():
    h = hf("mixtral-8x7b-serve")
    assert mistral.matmul_params(h) == 4 * (ATTN + MOE_ACTIVE) + HEAD
    assert mistral.matmul_params(h, active=False) == 4 * (ATTN + MOE_ALL) + HEAD
    assert mistral.weight_bytes(h) == 2 * (4 * (ATTN + MOE_ALL) + HEAD)
    assert mistral.weight_bytes(h) / 1e9 == pytest.approx(11.87, abs=0.01)


def test_decode_step_bytes():
    h = hf("mistral-7b-serve")
    assert mistral.weight_bytes(h) == 2 * (16 * (ATTN + MLP) + HEAD)
    # int8 pool: K and V, 16 layers, 8 kv heads, 128 bytes + one f32 scale
    assert mistral.kv_bytes_per_token(h, 8) == 2 * 16 * 8 * (128 + 4)
    assert mistral.kv_bytes_per_token(h, 0) == 2 * 16 * 8 * 256
    live = 48 * 400
    assert mistral.decode_step_bytes(h, {"kv_cache_bits": 8, "mean_live_tokens": live}) == \
        mistral.weight_bytes(h) + 2 * 16 * 8 * 132 * live
