"""Operations a model needs, computed from the published config's shapes.

Counts matrix-multiplication work only: 2 FLOPs per multiply-add. The input
embedding is a row lookup and does no matmul, so its table is NOT in the
count (``bench._count_params`` includes it: at 2 layers that is 131 M of
698 M parameters, an MFU overstated by ~18 %). The output head is a matmul
whether tied or not. Recomputed operations (remat replays) never count.

Attention is causal: a query at position i needs keys 0..i, so the required
work is HALF the S x S square. Every function here counts that half once
and says so; the PaLM "12 L H S" term counts the full square.
"""


def dims(hf: dict):
    """(hidden, query heads, kv heads, head size) of a published config."""
    H = hf["hidden_size"]
    nh = hf["num_attention_heads"]
    nkv = hf.get("num_key_value_heads") or nh
    hd = hf.get("head_dim") or H // nh
    return H, nh, nkv, hd


def attn_proj_params(hf: dict) -> int:
    H, nh, nkv, hd = dims(hf)
    return H * nh * hd + 2 * H * nkv * hd + nh * hd * H


def ffn_params(hf: dict, active: bool = True) -> int:
    """One layer's feed-forward matmul parameters (gated: three matrices).
    MoE: ``active`` counts the experts one token uses plus the router;
    otherwise every expert (what a decode step must READ)."""
    H, F = hf["hidden_size"], hf["intermediate_size"]
    E = hf.get("num_local_experts", 1)
    if E <= 1:
        return 3 * H * F
    k = hf["num_experts_per_tok"] if active else E
    return k * 3 * H * F + H * E


def head_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def matmul_params(hf: dict, active: bool = True) -> int:
    """Parameters that take part in a matmul for one token."""
    L = hf["num_hidden_layers"]
    return L * (attn_proj_params(hf) + ffn_params(hf, active)) + head_params(hf)


def attn_flops_per_token_fwd(hf: dict, seq_len: int) -> float:
    """QK^T and PV of ONE layer's forward, per token, causal half: a token
    at a uniformly random position sees seq_len / 2 keys on average."""
    _, nh, _, hd = dims(hf)
    return 2 * 2 * (seq_len / 2) * nh * hd


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """Forward + backward (= 3x forward) for one token of a seq_len
    sequence: 6 FLOPs per matmul parameter plus causal attention."""
    L = hf["num_hidden_layers"]
    return 6.0 * matmul_params(hf) + 3.0 * L * attn_flops_per_token_fwd(hf, seq_len)


def flash_flops(hf: dict, batch: int, seq_len: int) -> dict:
    """FLOPs the flash-attention kernels of ONE layer need for one step.

    Forward: QK^T and PV (2 matmuls). Backward: dV, dP, dQ, dK and the
    score recompute that replaces the stored probabilities (5 matmuls), the
    usual FlashAttention accounting (backward = 2.5 x forward). Each matmul
    is 2 * S^2 * hd per head over the full square; causal halves it, counted
    once. A remat replay of the forward is extra kernel TIME and zero
    required FLOPs."""
    _, nh, _, hd = dims(hf)
    one = 2.0 * batch * nh * seq_len * seq_len * hd / 2.0   # one causal matmul
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


def decode_flops_per_token(hf: dict, context: int) -> float:
    """One generated token: every active matmul parameter once, plus the
    attention over ``context`` cached keys in every layer."""
    _, nh, _, hd = dims(hf)
    L = hf["num_hidden_layers"]
    return 2.0 * matmul_params(hf) + L * 2 * 2 * context * nh * hd
