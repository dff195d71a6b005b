"""Bytes a decode step must move, computed from shapes.

A decode step reads every weight the batch touches once and the live KV
rows of every running sequence. With a full batch every expert of an MoE
layer is hit (32 tokens x top-2 over 8 experts: P(an expert idle) =
(6/8)^32 ~ 1e-4), so all experts count. Writes (one KV row per sequence)
are four orders of magnitude smaller and are left out."""
from . import flops


def weight_bytes(hf: dict, bytes_per_param: float = 2.0) -> float:
    """Layer stack + output head as served (bf16). The embedding table is
    read one row per token and is not counted."""
    return flops.matmul_params(hf, active=False) * bytes_per_param


def kv_bytes_per_token(hf: dict, kv_bits: int) -> float:
    """K and V of one cached position across all layers. int8 pools carry
    one f32 scale per (position, kv head) for each of K and V."""
    _, _, nkv, hd = flops.dims(hf)
    L = hf["num_hidden_layers"]
    if kv_bits == 8:
        per_head = hd * 1 + 4
    else:
        per_head = hd * 2
    return 2.0 * L * nkv * per_head


def decode_step_bytes(hf: dict, kv_bits: int, live_tokens: float) -> float:
    """Least bytes one decode step reads: weights once + the live cache."""
    return weight_bytes(hf) + kv_bytes_per_token(hf, kv_bits) * live_tokens
