"""External checkpoint import/export: HuggingFace <-> deepspeed_tpu trees.

Reference: ``deepspeed/runtime/state_dict_factory.py:189`` (MegatronSDLoader —
merge/split external state dicts across model parallel ranks) and
``deepspeed/module_inject/load_checkpoint.py`` (HF layer-by-layer weight
loading into injected modules).

TPU-native re-design: the reference manually slices each tensor per TP rank.
Here conversion produces ONE logical tree of numpy arrays (streamed shard by
shard off disk so peak host memory is one safetensors shard, not the model),
and TP/FSDP "slicing" is `jax.device_put(leaf, NamedSharding)` — GSPMD moves
only each device's slice to it. The same table run backwards exports our tree
to an HF-layout state dict (the zero_to_fp32/16-bit-export interop path).

Supported families: Llama/Mistral (GQA, rotary, silu-GLU, rmsnorm), Mixtral
(MoE), GPT-2 (fused-qkv Conv1D, learned positions), OPT, BLOOM (alibi,
embed-LN, interleaved fused qkv), BERT/RoBERTa (bidirectional post-LN
encoder, segment embeddings), GPT-J (parallel block, shared LN, partial
interleaved rotary, head bias), GPT-NeoX (parallel residual, two LNs,
partial rotary). Reference coverage: the per-architecture policy containers
in ``deepspeed/module_inject/containers/``.
"""

import json
import math
import os
import re
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import numpy as np

from deepspeed_tpu.utils.logging import logger

__all__ = ["load_hf_params", "export_hf_state_dict",
           "hf_config_to_transformer", "load_peft_adapter"]


# --------------------------------------------------------------------------
# streaming state-dict sources
# --------------------------------------------------------------------------

def _iter_state_dict(src) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (hf_key, numpy array) from a dict, a torch state_dict, an HF
    model object, or a checkpoint directory (safetensors / pytorch_model.bin,
    sharded or not). Directory shards stream one file at a time."""
    if hasattr(src, "state_dict"):  # transformers PreTrainedModel / nn.Module
        src = src.state_dict()
    if isinstance(src, dict):
        for k, v in src.items():
            yield k, _to_numpy(v)
        return
    path = os.fspath(src)
    if os.path.isfile(path):
        yield from _iter_file(path)
        return
    if not os.path.isdir(path):
        raise FileNotFoundError(f"checkpoint path {path!r} does not exist")
    # index json (sharded) or single-file conventions
    for index_name in ("model.safetensors.index.json",
                       "pytorch_model.bin.index.json"):
        idx = os.path.join(path, index_name)
        if os.path.exists(idx):
            with open(idx) as f:
                weight_map = json.load(f)["weight_map"]
            for shard in sorted(set(weight_map.values())):
                yield from _iter_file(os.path.join(path, shard))
            return
    for name in ("model.safetensors", "pytorch_model.bin"):
        p = os.path.join(path, name)
        if os.path.exists(p):
            yield from _iter_file(p)
            return
    shards = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    if not shards:
        raise FileNotFoundError(f"no model weights found under {path!r}")
    for shard in shards:
        yield from _iter_file(os.path.join(path, shard))


def _iter_file(path: str) -> Iterator[Tuple[str, np.ndarray]]:
    if path.endswith(".safetensors"):
        from safetensors import safe_open
        with safe_open(path, framework="numpy") as f:
            for k in f.keys():
                yield k, f.get_tensor(k)
    else:
        import torch
        sd = torch.load(path, map_location="cpu", weights_only=True)
        for k, v in sd.items():
            yield k, _to_numpy(v)


def _to_numpy(v) -> np.ndarray:
    if isinstance(v, np.ndarray):
        return v
    try:
        import torch
        if isinstance(v, torch.Tensor):
            if v.dtype == torch.bfloat16:
                return v.float().numpy()
            return v.numpy()
    except ImportError:
        pass
    return np.asarray(v)


# --------------------------------------------------------------------------
# key-mapping tables
# --------------------------------------------------------------------------

# Each entry: hf key regex -> (dest path fn, transform fn). Dest path is
# ("layers", name, layer_idx) for stacked per-layer params or (name,) for
# top-level; transform maps the HF array to our layout (torch Linear stores
# [out, in]; our matmuls are x @ W so weights are [in, out]).

def _t(x):
    return np.ascontiguousarray(x.T)


def _llama_table(cfg):
    L = [
        (r"^(?:model\.)?embed_tokens\.weight$", ("tok_embed",), None),
        (r"^(?:model\.)?norm\.weight$", ("final_norm_scale",), None),
        (r"^lm_head\.weight$", ("lm_head",), _t),
        (r"^(?:model\.)?layers\.(\d+)\.input_layernorm\.weight$",
         ("layers", "ln1_scale"), None),
        (r"^(?:model\.)?layers\.(\d+)\.post_attention_layernorm\.weight$",
         ("layers", "ln2_scale"), None),
        (r"^(?:model\.)?layers\.(\d+)\.self_attn\.q_proj\.weight$",
         ("layers", "wq"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.self_attn\.k_proj\.weight$",
         ("layers", "wk"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.self_attn\.v_proj\.weight$",
         ("layers", "wv"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.self_attn\.o_proj\.weight$",
         ("layers", "wo"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.mlp\.gate_proj\.weight$",
         ("layers", "w_gate"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.mlp\.up_proj\.weight$",
         ("layers", "w_in"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.mlp\.down_proj\.weight$",
         ("layers", "w_out"), _t),
    ]
    return L


def _mixtral_table(cfg):
    """Llama backbone + block-sparse MoE: per-expert w1 (gate), w2 (down),
    w3 (up) stack onto the leading expert dim of moe_w_gate/out/in; the
    router Linear becomes wg. Reference coverage: the MoE containers in
    ``module_inject/containers`` + ``deepspeed/moe/layer.py`` weight layout."""
    L = [r for r in _llama_table(cfg)
         if "mlp" not in r[0]]  # dense MLP rows replaced by experts
    L += [
        (r"^(?:model\.)?layers\.(\d+)\.block_sparse_moe\.gate\.weight$",
         ("layers", "wg"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w1\.weight$",
         ("layers", "moe_w_gate"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w2\.weight$",
         ("layers", "moe_w_out"), _t),
        (r"^(?:model\.)?layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w3\.weight$",
         ("layers", "moe_w_in"), _t),
    ]
    return L


def _olmoe_table(cfg):
    """OLMoE (allenai/OLMoE-1B-7B): the Llama backbone with an expert layer
    under ``mlp`` (router ``mlp.gate``, experts ``mlp.experts.N.{gate,up,
    down}_proj``) and an RMSNorm over the whole q and the whole k projection
    (``self_attn.{q,k}_norm``)."""
    L = [r for r in _llama_table(cfg) if "mlp" not in r[0]]
    pre = r"^(?:model\.)?layers\.(\d+)\."
    L += [
        (pre + r"self_attn\.q_norm\.weight$", ("layers", "q_norm"), None),
        (pre + r"self_attn\.k_norm\.weight$", ("layers", "k_norm"), None),
        (pre + r"mlp\.gate\.weight$", ("layers", "wg"), _t),
        (pre + r"mlp\.experts\.(\d+)\.gate_proj\.weight$",
         ("layers", "moe_w_gate"), _t),
        (pre + r"mlp\.experts\.(\d+)\.up_proj\.weight$",
         ("layers", "moe_w_in"), _t),
        (pre + r"mlp\.experts\.(\d+)\.down_proj\.weight$",
         ("layers", "moe_w_out"), _t),
    ]
    return L


def _ouro_table(cfg):
    """Ouro (ByteDance/Ouro): the Llama backbone with a second norm after
    each sublayer (``input_layernorm_2`` after attention,
    ``post_attention_layernorm_2`` after the feed-forward) and the exit gate
    (``early_exit_gate``, a Linear [1, H] with a bias) beside the final
    norm."""
    pre = r"^(?:model\.)?layers\.(\d+)\."
    return _llama_table(cfg) + [
        (pre + r"input_layernorm_2\.weight$",
         ("layers", "ln1_post_scale"), None),
        (pre + r"post_attention_layernorm_2\.weight$",
         ("layers", "ln2_post_scale"), None),
        (r"^(?:model\.)?early_exit_gate\.weight$", ("exit_gate_w",), _t),
        (r"^(?:model\.)?early_exit_gate\.bias$", ("exit_gate_b",), None),
    ]


def _opt_table(cfg):
    S = cfg.max_seq_len

    def pos_slice(w):
        # OPTLearnedPositionalEmbedding carries a +2 offset: rows 0/1 are
        # padding artifacts; row i+2 is position i
        return w[2:2 + S]

    pre = r"^(?:model\.)?decoder\."
    lyr = pre + r"layers\.(\d+)\."
    L = [
        (pre + r"embed_tokens\.weight$", ("tok_embed",), None),
        (pre + r"embed_positions\.weight$", ("pos_embed",), pos_slice),
        (pre + r"final_layer_norm\.weight$", ("final_norm_scale",), None),
        (pre + r"final_layer_norm\.bias$", ("final_norm_bias",), None),
        (r"^lm_head\.weight$", ("lm_head",), _t),
        (lyr + r"self_attn_layer_norm\.weight$", ("layers", "ln1_scale"), None),
        (lyr + r"self_attn_layer_norm\.bias$", ("layers", "ln1_bias"), None),
        (lyr + r"self_attn\.q_proj\.weight$", ("layers", "wq"), _t),
        (lyr + r"self_attn\.q_proj\.bias$", ("layers", "bq"), None),
        (lyr + r"self_attn\.k_proj\.weight$", ("layers", "wk"), _t),
        (lyr + r"self_attn\.k_proj\.bias$", ("layers", "bk"), None),
        (lyr + r"self_attn\.v_proj\.weight$", ("layers", "wv"), _t),
        (lyr + r"self_attn\.v_proj\.bias$", ("layers", "bv"), None),
        (lyr + r"self_attn\.out_proj\.weight$", ("layers", "wo"), _t),
        (lyr + r"self_attn\.out_proj\.bias$", ("layers", "bo"), None),
        (lyr + r"final_layer_norm\.weight$", ("layers", "ln2_scale"), None),
        (lyr + r"final_layer_norm\.bias$", ("layers", "ln2_bias"), None),
        (lyr + r"fc1\.weight$", ("layers", "w_in"), _t),
        (lyr + r"fc1\.bias$", ("layers", "b_in"), None),
        (lyr + r"fc2\.weight$", ("layers", "w_out"), _t),
        (lyr + r"fc2\.bias$", ("layers", "b_out"), None),
    ]
    return L


def _bloom_table(cfg):
    """BLOOM: alibi positions, embedding layernorm, per-head-INTERLEAVED
    fused qkv ([nh, 3, hd, H] row blocks, unlike GPT-2's [q|k|v] concat)."""
    nh, hd = cfg.num_heads, cfg.dim_per_head

    def split_qkv(w):  # [3H, H] -> three [H, H] (ours: x @ W)
        w = w.reshape(nh, 3, hd, w.shape[-1])
        return [np.ascontiguousarray(w[:, i].reshape(nh * hd, -1).T)
                for i in range(3)]

    def split_qkv_bias(b):
        b = b.reshape(nh, 3, hd)
        return [np.ascontiguousarray(b[:, i].reshape(-1)) for i in range(3)]

    pre = r"^(?:transformer\.)?"
    lyr = pre + r"h\.(\d+)\."
    return [
        (pre + r"word_embeddings\.weight$", ("tok_embed",), None),
        (pre + r"word_embeddings_layernorm\.weight$",
         ("embed_norm_scale",), None),
        (pre + r"word_embeddings_layernorm\.bias$",
         ("embed_norm_bias",), None),
        (pre + r"ln_f\.weight$", ("final_norm_scale",), None),
        (pre + r"ln_f\.bias$", ("final_norm_bias",), None),
        (r"^lm_head\.weight$", ("lm_head",), _t),
        (lyr + r"input_layernorm\.weight$", ("layers", "ln1_scale"), None),
        (lyr + r"input_layernorm\.bias$", ("layers", "ln1_bias"), None),
        (lyr + r"post_attention_layernorm\.weight$",
         ("layers", "ln2_scale"), None),
        (lyr + r"post_attention_layernorm\.bias$",
         ("layers", "ln2_bias"), None),
        (lyr + r"self_attention\.query_key_value\.weight$",
         ("layers", ("wq", "wk", "wv")), split_qkv),
        (lyr + r"self_attention\.query_key_value\.bias$",
         ("layers", ("bq", "bk", "bv")), split_qkv_bias),
        (lyr + r"self_attention\.dense\.weight$", ("layers", "wo"), _t),
        (lyr + r"self_attention\.dense\.bias$", ("layers", "bo"), None),
        (lyr + r"mlp\.dense_h_to_4h\.weight$", ("layers", "w_in"), _t),
        (lyr + r"mlp\.dense_h_to_4h\.bias$", ("layers", "b_in"), None),
        (lyr + r"mlp\.dense_4h_to_h\.weight$", ("layers", "w_out"), _t),
        (lyr + r"mlp\.dense_4h_to_h\.bias$", ("layers", "b_out"), None),
    ]


def _gpt2_table(cfg):
    H = cfg.hidden_size
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head

    def split_qkv(w):  # Conv1D weight [in, 3H] -> three [in, H]
        return np.split(w, [nh * hd, nh * hd + nkv * hd], axis=-1)

    def split_qkv_bias(b):
        return np.split(b, [nh * hd, nh * hd + nkv * hd], axis=-1)

    L = [
        (r"^(?:transformer\.)?wte\.weight$", ("tok_embed",), None),
        (r"^(?:transformer\.)?wpe\.weight$", ("pos_embed",), None),
        (r"^lm_head\.weight$", ("lm_head",), _t),
        (r"^(?:transformer\.)?ln_f\.weight$", ("final_norm_scale",), None),
        (r"^(?:transformer\.)?ln_f\.bias$", ("final_norm_bias",), None),
        (r"^(?:transformer\.)?h\.(\d+)\.ln_1\.weight$", ("layers", "ln1_scale"), None),
        (r"^(?:transformer\.)?h\.(\d+)\.ln_1\.bias$", ("layers", "ln1_bias"), None),
        (r"^(?:transformer\.)?h\.(\d+)\.ln_2\.weight$", ("layers", "ln2_scale"), None),
        (r"^(?:transformer\.)?h\.(\d+)\.ln_2\.bias$", ("layers", "ln2_bias"), None),
        # GPT-2 Conv1D stores [in, out] — no transpose, but qkv is fused
        (r"^(?:transformer\.)?h\.(\d+)\.attn\.c_attn\.weight$",
         ("layers", ("wq", "wk", "wv")), split_qkv),
        (r"^(?:transformer\.)?h\.(\d+)\.attn\.c_attn\.bias$",
         ("layers", ("bq", "bk", "bv")), split_qkv_bias),
        (r"^(?:transformer\.)?h\.(\d+)\.attn\.c_proj\.weight$",
         ("layers", "wo"), None),
        (r"^(?:transformer\.)?h\.(\d+)\.attn\.c_proj\.bias$",
         ("layers", "bo"), None),
        (r"^(?:transformer\.)?h\.(\d+)\.mlp\.c_fc\.weight$",
         ("layers", "w_in"), None),
        (r"^(?:transformer\.)?h\.(\d+)\.mlp\.c_fc\.bias$",
         ("layers", "b_in"), None),
        (r"^(?:transformer\.)?h\.(\d+)\.mlp\.c_proj\.weight$",
         ("layers", "w_out"), None),
        (r"^(?:transformer\.)?h\.(\d+)\.mlp\.c_proj\.bias$",
         ("layers", "b_out"), None),
    ]
    return L


def _bert_table(cfg):
    """BERT/RoBERTa encoder (reference: module_inject/containers/bert.py):
    post-LN blocks — attention.output.LayerNorm is our ln1 (applied after
    the attention residual), output.LayerNorm our ln2. The pooler and MLM
    head are out of scope (hidden states + tied-embedding logits)."""
    pre = r"^(?:bert\.|roberta\.)?"
    lyr = pre + r"encoder\.layer\.(\d+)\."
    att = lyr + r"attention\."

    def pos_check(w):
        # A bare RoBERTa encoder dict (no 'roberta.' prefix) detects as
        # BERT; its position table has exactly max_seq_len+2 rows (HF's
        # padding_idx offset). Loading it unsliced would shift every
        # position embedding by two rows — refuse instead of drifting.
        if w.shape[0] == cfg.max_seq_len + 2:
            raise ValueError(
                f"position-embedding table has {w.shape[0]} rows = "
                f"max_seq_len+2 — this looks like a bare RoBERTa state "
                "dict whose rows carry the padding_idx+1=2 offset; pass "
                "family='roberta' so the offset slice is applied")
        return w

    return [
        (pre + r"embeddings\.word_embeddings\.weight$", ("tok_embed",), None),
        (pre + r"embeddings\.position_embeddings\.weight$",
         ("pos_embed",), pos_check),
        (pre + r"embeddings\.token_type_embeddings\.weight$",
         ("tok_type_embed",), None),
        (pre + r"embeddings\.LayerNorm\.weight$", ("embed_norm_scale",), None),
        (pre + r"embeddings\.LayerNorm\.bias$", ("embed_norm_bias",), None),
        (att + r"self\.query\.weight$", ("layers", "wq"), _t),
        (att + r"self\.query\.bias$", ("layers", "bq"), None),
        (att + r"self\.key\.weight$", ("layers", "wk"), _t),
        (att + r"self\.key\.bias$", ("layers", "bk"), None),
        (att + r"self\.value\.weight$", ("layers", "wv"), _t),
        (att + r"self\.value\.bias$", ("layers", "bv"), None),
        (att + r"output\.dense\.weight$", ("layers", "wo"), _t),
        (att + r"output\.dense\.bias$", ("layers", "bo"), None),
        (att + r"output\.LayerNorm\.weight$", ("layers", "ln1_scale"), None),
        (att + r"output\.LayerNorm\.bias$", ("layers", "ln1_bias"), None),
        (lyr + r"intermediate\.dense\.weight$", ("layers", "w_in"), _t),
        (lyr + r"intermediate\.dense\.bias$", ("layers", "b_in"), None),
        (lyr + r"output\.dense\.weight$", ("layers", "w_out"), _t),
        (lyr + r"output\.dense\.bias$", ("layers", "b_out"), None),
        (lyr + r"output\.LayerNorm\.weight$", ("layers", "ln2_scale"), None),
        (lyr + r"output\.LayerNorm\.bias$", ("layers", "ln2_bias"), None),
    ]


def _roberta_table(cfg):
    """RoBERTa = BERT layout with position rows offset by padding_idx+1=2
    (HF's create_position_ids_from_input_ids). Detection needs the
    'roberta.' key prefix; for bare encoder state dicts pass
    family="roberta" explicitly."""
    S = cfg.max_seq_len

    def pos_slice(w):
        return w[2:2 + S]

    table = []
    for pat, dest, tf in _bert_table(cfg):
        if dest == ("pos_embed",):
            tf = pos_slice
        table.append((pat, dest, tf))
    return table


def _clip_table(cfg):
    """CLIP text encoder (reference: module_inject/containers/clip.py —
    HFCLIPLayerPolicy over CLIPEncoderLayer): pre-LN causal text tower,
    quick_gelu MLP, learned positions, final layer norm, no LM head.
    Accepts a bare CLIPTextModel dict or the text half of a full CLIPModel
    (vision keys are skipped; models/clip_vision.py imports that tower)."""
    pre = r"^(?:text_model\.)?"
    lyr = pre + r"encoder\.layers\.(\d+)\."
    att = lyr + r"self_attn\."
    return [
        (pre + r"embeddings\.token_embedding\.weight$", ("tok_embed",),
         None),
        (pre + r"embeddings\.position_embedding\.weight$", ("pos_embed",),
         None),
        (pre + r"final_layer_norm\.weight$", ("final_norm_scale",), None),
        (pre + r"final_layer_norm\.bias$", ("final_norm_bias",), None),
        (att + r"q_proj\.weight$", ("layers", "wq"), _t),
        (att + r"q_proj\.bias$", ("layers", "bq"), None),
        (att + r"k_proj\.weight$", ("layers", "wk"), _t),
        (att + r"k_proj\.bias$", ("layers", "bk"), None),
        (att + r"v_proj\.weight$", ("layers", "wv"), _t),
        (att + r"v_proj\.bias$", ("layers", "bv"), None),
        (att + r"out_proj\.weight$", ("layers", "wo"), _t),
        (att + r"out_proj\.bias$", ("layers", "bo"), None),
        (lyr + r"layer_norm1\.weight$", ("layers", "ln1_scale"), None),
        (lyr + r"layer_norm1\.bias$", ("layers", "ln1_bias"), None),
        (lyr + r"layer_norm2\.weight$", ("layers", "ln2_scale"), None),
        (lyr + r"layer_norm2\.bias$", ("layers", "ln2_bias"), None),
        (lyr + r"mlp\.fc1\.weight$", ("layers", "w_in"), _t),
        (lyr + r"mlp\.fc1\.bias$", ("layers", "b_in"), None),
        (lyr + r"mlp\.fc2\.weight$", ("layers", "w_out"), _t),
        (lyr + r"mlp\.fc2\.bias$", ("layers", "b_out"), None),
    ]


def _gptj_table(cfg):
    """GPT-J (reference: module_inject/containers/gptj.py): parallel
    attn+MLP block with ONE shared LN — ln_1 fills both our ln1 and ln2
    slots; bias-free attention projections; lm_head carries a bias."""
    pre = r"^(?:transformer\.)?"
    lyr = pre + r"h\.(\d+)\."
    return [
        (pre + r"wte\.weight$", ("tok_embed",), None),
        (pre + r"ln_f\.weight$", ("final_norm_scale",), None),
        (pre + r"ln_f\.bias$", ("final_norm_bias",), None),
        (r"^lm_head\.weight$", ("lm_head",), _t),
        (r"^lm_head\.bias$", ("lm_head_bias",), None),
        (lyr + r"ln_1\.weight$",
         ("layers", ("ln1_scale", "ln2_scale")), lambda w: [w, w]),
        (lyr + r"ln_1\.bias$",
         ("layers", ("ln1_bias", "ln2_bias")), lambda b: [b, b]),
        (lyr + r"attn\.q_proj\.weight$", ("layers", "wq"), _t),
        (lyr + r"attn\.k_proj\.weight$", ("layers", "wk"), _t),
        (lyr + r"attn\.v_proj\.weight$", ("layers", "wv"), _t),
        (lyr + r"attn\.out_proj\.weight$", ("layers", "wo"), _t),
        (lyr + r"mlp\.fc_in\.weight$", ("layers", "w_in"), _t),
        (lyr + r"mlp\.fc_in\.bias$", ("layers", "b_in"), None),
        (lyr + r"mlp\.fc_out\.weight$", ("layers", "w_out"), _t),
        (lyr + r"mlp\.fc_out\.bias$", ("layers", "b_out"), None),
    ]


def _gptneo_table(cfg):
    """GPT-Neo (reference: module_inject/containers/gptneo.py): GPT-2-shaped
    block but with nn.Linear projections ([out, in] — transposed, unlike
    GPT-2's Conv1D), un-fused q/k/v with NO biases, and alternating
    global/local attention (handled by cfg.attn_windows, not weights)."""
    pre = r"^(?:transformer\.)?"
    lyr = pre + r"h\.(\d+)\."
    att = lyr + r"attn\.attention\."
    return [
        (pre + r"wte\.weight$", ("tok_embed",), None),
        (pre + r"wpe\.weight$", ("pos_embed",), None),
        (r"^lm_head\.weight$", ("lm_head",), _t),
        (pre + r"ln_f\.weight$", ("final_norm_scale",), None),
        (pre + r"ln_f\.bias$", ("final_norm_bias",), None),
        (lyr + r"ln_1\.weight$", ("layers", "ln1_scale"), None),
        (lyr + r"ln_1\.bias$", ("layers", "ln1_bias"), None),
        (lyr + r"ln_2\.weight$", ("layers", "ln2_scale"), None),
        (lyr + r"ln_2\.bias$", ("layers", "ln2_bias"), None),
        (att + r"q_proj\.weight$", ("layers", "wq"), _t),
        (att + r"k_proj\.weight$", ("layers", "wk"), _t),
        (att + r"v_proj\.weight$", ("layers", "wv"), _t),
        (att + r"out_proj\.weight$", ("layers", "wo"), _t),
        (att + r"out_proj\.bias$", ("layers", "bo"), None),
        (lyr + r"mlp\.c_fc\.weight$", ("layers", "w_in"), _t),
        (lyr + r"mlp\.c_fc\.bias$", ("layers", "b_in"), None),
        (lyr + r"mlp\.c_proj\.weight$", ("layers", "w_out"), _t),
        (lyr + r"mlp\.c_proj\.bias$", ("layers", "b_out"), None),
    ]


def _distilbert_table(cfg):
    """DistilBERT (reference: module_inject/containers/distil_bert.py):
    BERT-shaped post-LN encoder, no token-type embeddings; sa_layer_norm is
    our ln1 (after the attention residual), output_layer_norm our ln2."""
    pre = r"^(?:distilbert\.)?"
    lyr = pre + r"transformer\.layer\.(\d+)\."
    att = lyr + r"attention\."
    return [
        (pre + r"embeddings\.word_embeddings\.weight$", ("tok_embed",), None),
        (pre + r"embeddings\.position_embeddings\.weight$",
         ("pos_embed",), None),
        (pre + r"embeddings\.LayerNorm\.weight$", ("embed_norm_scale",), None),
        (pre + r"embeddings\.LayerNorm\.bias$", ("embed_norm_bias",), None),
        (att + r"q_lin\.weight$", ("layers", "wq"), _t),
        (att + r"q_lin\.bias$", ("layers", "bq"), None),
        (att + r"k_lin\.weight$", ("layers", "wk"), _t),
        (att + r"k_lin\.bias$", ("layers", "bk"), None),
        (att + r"v_lin\.weight$", ("layers", "wv"), _t),
        (att + r"v_lin\.bias$", ("layers", "bv"), None),
        (att + r"out_lin\.weight$", ("layers", "wo"), _t),
        (att + r"out_lin\.bias$", ("layers", "bo"), None),
        (lyr + r"sa_layer_norm\.weight$", ("layers", "ln1_scale"), None),
        (lyr + r"sa_layer_norm\.bias$", ("layers", "ln1_bias"), None),
        (lyr + r"ffn\.lin1\.weight$", ("layers", "w_in"), _t),
        (lyr + r"ffn\.lin1\.bias$", ("layers", "b_in"), None),
        (lyr + r"ffn\.lin2\.weight$", ("layers", "w_out"), _t),
        (lyr + r"ffn\.lin2\.bias$", ("layers", "b_out"), None),
        (lyr + r"output_layer_norm\.weight$", ("layers", "ln2_scale"), None),
        (lyr + r"output_layer_norm\.bias$", ("layers", "ln2_bias"), None),
    ]


def _gptneox_table(cfg):
    """GPT-NeoX (reference: module_inject/containers/gptneox.py): parallel
    residual with two LNs, per-head-interleaved fused qkv like BLOOM."""
    nh, hd = cfg.num_heads, cfg.dim_per_head

    def split_qkv(w):  # [3H, H], rows interleaved [nh, 3, hd]
        w = w.reshape(nh, 3, hd, w.shape[-1])
        return [np.ascontiguousarray(w[:, i].reshape(nh * hd, -1).T)
                for i in range(3)]

    def split_qkv_bias(b):
        b = b.reshape(nh, 3, hd)
        return [np.ascontiguousarray(b[:, i].reshape(-1)) for i in range(3)]

    pre = r"^(?:gpt_neox\.)?"
    lyr = pre + r"layers\.(\d+)\."
    return [
        (pre + r"embed_in\.weight$", ("tok_embed",), None),
        (pre + r"final_layer_norm\.weight$", ("final_norm_scale",), None),
        (pre + r"final_layer_norm\.bias$", ("final_norm_bias",), None),
        (r"^embed_out\.weight$", ("lm_head",), _t),
        (lyr + r"input_layernorm\.weight$", ("layers", "ln1_scale"), None),
        (lyr + r"input_layernorm\.bias$", ("layers", "ln1_bias"), None),
        (lyr + r"post_attention_layernorm\.weight$",
         ("layers", "ln2_scale"), None),
        (lyr + r"post_attention_layernorm\.bias$",
         ("layers", "ln2_bias"), None),
        (lyr + r"attention\.query_key_value\.weight$",
         ("layers", ("wq", "wk", "wv")), split_qkv),
        (lyr + r"attention\.query_key_value\.bias$",
         ("layers", ("bq", "bk", "bv")), split_qkv_bias),
        (lyr + r"attention\.dense\.weight$", ("layers", "wo"), _t),
        (lyr + r"attention\.dense\.bias$", ("layers", "bo"), None),
        (lyr + r"mlp\.dense_h_to_4h\.weight$", ("layers", "w_in"), _t),
        (lyr + r"mlp\.dense_h_to_4h\.bias$", ("layers", "b_in"), None),
        (lyr + r"mlp\.dense_4h_to_h\.weight$", ("layers", "w_out"), _t),
        (lyr + r"mlp\.dense_4h_to_h\.bias$", ("layers", "b_out"), None),
    ]


_SKIP = re.compile(r"(rotary_emb\.inv_freq|\.attn\.(bias|masked_bias)$"
                   r"|\.attention\.(bias|masked_bias|rotary_emb)"
                   r"|pooler\.dense\.|cls\.|position_ids$"
                   # full-CLIP extras: the vision tower loads through
                   # models/clip_vision.py; projections are out of scope
                   r"|^vision_model\.|^visual_projection\."
                   r"|^text_projection\.|^logit_scale$"
                   # DistilBERT MLM/classification heads: hidden states +
                   # tied-embedding logits, as with BERT's cls.* head
                   r"|^vocab_(transform|layer_norm|projector)\."
                   r"|^(pre_)?classifier\.|^qa_outputs\.)")


_TABLES = {"llama": _llama_table, "gpt2": _gpt2_table,
           "mixtral": _mixtral_table, "olmoe": _olmoe_table,
           "ouro": _ouro_table, "opt": _opt_table,
           "bloom": _bloom_table, "bert": _bert_table,
           "roberta": _roberta_table, "clip": _clip_table,
           "gptj": _gptj_table, "gpt_neox": _gptneox_table,
           "gpt_neo": _gptneo_table, "distilbert": _distilbert_table}


def _detect_family(keys) -> str:
    # order matters: OPT has self_attn.q_proj too (under decoder.), BERT has
    # word_embeddings (BLOOM's marker), NeoX has dense_h_to_4h (also
    # BLOOM's) — test the distinctive keys first
    for k in keys:
        if "block_sparse_moe" in k:
            return "mixtral"
        if ".mlp.experts." in k or ".self_attn.q_norm." in k:
            return "olmoe"
        if "input_layernorm_2" in k or "early_exit_gate" in k:
            return "ouro"
        if k.startswith("roberta."):
            return "roberta"
        if "text_model." in k or "token_embedding" in k:
            return "clip"
        if (k.startswith("distilbert.") or "sa_layer_norm" in k
                or "output_layer_norm" in k or ".q_lin." in k
                or ".ffn.lin1." in k):
            return "distilbert"
        if "encoder.layer." in k or "token_type_embeddings" in k:
            return "bert"
        if ("gpt_neox." in k or "embed_in." in k or "embed_out." in k
                or (".attention.query_key_value" in k
                    and "self_attention" not in k)):
            return "gpt_neox"
        if "decoder.embed_positions" in k or "decoder.layers." in k:
            return "opt"
        # bloom-DISTINCTIVE only: plain word_embeddings is also BERT's and
        # dense_h_to_4h is also NeoX's — those must stay pending
        if "word_embeddings_layernorm" in k or "self_attention." in k:
            return "bloom"
    for k in keys:
        if "decoder." in k:
            continue  # OPT-shaped: wait for a distinctive decoder key
        if ("self_attn.q_proj" in k or "embed_tokens" in k
                or k.startswith(("model.layers.", "layers."))):
            return "llama"
        # GPT-J: bias-free separated projections under .attn. (GPT-2's are
        # fused c_attn; llama's sit under .self_attn.)
        if ".self_attn." not in k and (
                ".attn.q_proj" in k or ".attn.k_proj" in k
                or ".attn.v_proj" in k or ".attn.out_proj" in k
                or ".mlp.fc_in." in k or ".mlp.fc_out." in k):
            return "gptj"
        # GPT-Neo: un-fused projections under .attn.attention. (GPT-2's are
        # fused c_attn; shares wpe/ln_2/mlp.c_fc with GPT-2, so only this
        # marker is distinctive)
        if ".attn.attention." in k:
            return "gpt_neo"
        # gpt2 needs a DISTINCTIVE marker, not just the h.* prefix (BLOOM
        # also uses h.N., GPT-J shares wte/ln_1, GPT-Neo shares
        # wpe/ln_2/mlp.c_* — their keys must stay pending until a
        # family-distinctive key streams by)
        if ".attn.c_attn." in k or ".attn.c_proj." in k:
            return "gpt2"
    raise ValueError("unrecognized checkpoint family; expected Llama/Mixtral/"
                     "OPT/BLOOM/GPT-2/BERT/GPT-J/GPT-NeoX-style keys")


# --------------------------------------------------------------------------
# import
# --------------------------------------------------------------------------

def load_hf_params(src, cfg, *, shardings=None, dtype=None,
                   family: Optional[str] = None,
                   strict: bool = True) -> Dict[str, Any]:
    """Convert an HF checkpoint to this framework's param tree.

    src: directory / file / state_dict / HF model. cfg: TransformerConfig
    matching the checkpoint's architecture. shardings: optional pytree of
    NamedSharding (same structure as the params) — each finished leaf is
    device_put with its sharding immediately, so a TP/FSDP-sharded load never
    holds more than the host staging copy of the model.
    """
    dtype = np.dtype(dtype) if dtype is not None else np.float32
    Lcount = cfg.num_layers

    # preallocate stacked per-layer buffers; fill as shards stream by
    out: Dict[str, Any] = {"layers": {}}
    table = None
    fam = family
    seen_layers: Dict[str, set] = {}
    import jax

    def _commit(path_keys, arr):
        """Move a finished leaf to device NOW (sharded, so only each device's
        slice transfers) — this is what keeps peak host memory at ~one
        parameter + one shard instead of the whole model."""
        if shardings is None:
            return arr
        sh = shardings
        for k in path_keys:
            sh = sh[k]
        return jax.device_put(arr, sh)

    E = cfg.num_experts

    def place(dest, layer_idx, arr, expert_idx=None):
        if dest[0] == "lm_head" and cfg.tie_embeddings:
            return  # tied checkpoints carry a redundant copy of the embedding
        arr = arr.astype(dtype, copy=False)
        if dest[0] == "layers":
            name = dest[1]
            buf = out["layers"].get(name)
            if expert_idx is None:
                if buf is None:
                    buf = np.empty((Lcount,) + arr.shape, dtype)
                    out["layers"][name] = buf
                buf[layer_idx] = arr
                key = layer_idx
                full = Lcount
            else:  # per-expert stacked weights: [L, E, ...]
                if expert_idx >= E:
                    raise ValueError(f"checkpoint expert {expert_idx} >= "
                                     f"cfg.num_experts {E}")
                if buf is None:
                    buf = np.empty((Lcount, E) + arr.shape, dtype)
                    out["layers"][name] = buf
                buf[layer_idx, expert_idx] = arr
                key = (layer_idx, expert_idx)
                full = Lcount * E
            seen = seen_layers.setdefault(name, set())
            seen.add(key)
            if len(seen) == full:
                out["layers"][name] = _commit(("layers", name), buf)
        else:
            # tied-lm_head special case is resolved after the loop; keep the
            # embedding on host until then
            if dest[0] == "tok_embed" and shardings is not None:
                out[dest[0]] = arr
            else:
                out[dest[0]] = _commit((dest[0],), arr)

    n_loaded = 0

    def process(key, arr):
        nonlocal n_loaded
        matched = False
        for pat, dest, tf in table:
            m = re.match(pat, key)
            if not m:
                continue
            matched = True
            groups = m.groups()
            layer_idx = int(groups[0]) if groups else None
            expert_idx = int(groups[1]) if len(groups) > 1 else None
            if layer_idx is not None and layer_idx >= Lcount:
                raise ValueError(
                    f"checkpoint layer {layer_idx} >= cfg.num_layers {Lcount}")
            val = tf(arr) if tf is not None else arr
            if isinstance(dest[1] if len(dest) > 1 else None, tuple):
                for sub, v in zip(dest[1], val):
                    place(("layers", sub), layer_idx, v)
            else:
                place(dest, layer_idx, val, expert_idx)
            n_loaded += 1
            break
        if not matched and not _SKIP.search(key):
            if strict:
                raise ValueError(
                    f"hf import: unmapped key {key!r} — the checkpoint has "
                    "weights this architecture mapping would silently drop "
                    "(pass strict=False to skip them)")
            logger.warning(f"hf import: unmapped key {key!r} (skipped)")

    # family detection may need more than the first key (e.g. a shard that
    # starts with lm_head.weight) — buffer until a distinctive key shows up,
    # but bounded: an unrecognized checkpoint must fail fast, not stream every
    # shard into host RAM on the way to the error.
    _PENDING_CAP = 64
    pending = []
    for key, arr in _iter_state_dict(src):
        if table is None:
            if len(pending) >= _PENDING_CAP:
                raise ValueError(
                    f"unrecognized checkpoint family after {_PENDING_CAP} "
                    "keys; expected Llama-style (self_attn.q_proj) or "
                    "GPT-2-style (attn.c_attn) keys")
            pending.append((key, arr))
            try:
                fam = fam or _detect_family([k for k, _ in pending])
            except ValueError:
                continue
            if fam == "llama" and cfg.num_experts > 1:
                # llama backbone + experts in the config: the first keys of
                # a shard (embed_tokens, layer 0's attention) look alike
                fam = "olmoe" if cfg.qk_norm else "mixtral"
            if fam == "llama" and cfg.sandwich_norm:
                fam = "ouro"            # likewise: its own keys come later
            table = _TABLES[fam](cfg)
            logger.info(f"hf import: detected {fam}-family checkpoint")
            for k, a in pending:
                process(k, a)
            pending = []
            continue
        process(key, arr)
    if table is None:
        raise ValueError("unrecognized checkpoint family; no distinctive "
                         "Llama/GPT-2 keys found")

    if cfg.tie_embeddings:
        out.pop("lm_head", None)
    elif "lm_head" not in out and "tok_embed" in out:
        # some checkpoints tie but the config says untied: clone the embedding
        out["lm_head"] = _t(out["tok_embed"])
        logger.info("hf import: lm_head absent in checkpoint; using tied "
                    "tok_embed")
    if n_loaded == 0:
        raise ValueError("no weights matched the mapping table")
    for name, idxs in seen_layers.items():
        per_expert = bool(idxs) and isinstance(next(iter(idxs)), tuple)
        expected = Lcount * E if per_expert else Lcount
        if len(idxs) != expected:
            if per_expert:
                missing_l = sorted(
                    {(l, e) for l in range(Lcount) for e in range(E)} - idxs)
            else:
                missing_l = sorted(set(range(Lcount)) - idxs)
            raise ValueError(f"hf import: layers.{name} missing indices "
                             f"{missing_l[:8]} (num_layers={Lcount}, "
                             f"num_experts={E})")

    # validate against a reference tree structure
    from deepspeed_tpu.models.transformer import init_params
    import jax
    ref_shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    ref_leaves = _leaves_with_path(ref_shapes)
    got = {jax.tree_util.keystr(p) for p, _ in _leaves_with_path(out)}
    missing = [jax.tree_util.keystr(p) for p, _ in ref_leaves
               if jax.tree_util.keystr(p) not in got]
    if missing:
        raise ValueError(f"hf import: checkpoint missing params {missing}")
    for p, leaf in ref_leaves:
        k = jax.tree_util.keystr(p)
        have = _tree_get(out, p).shape
        if tuple(have) != tuple(leaf.shape):
            raise ValueError(f"hf import: {k} shape {have} != expected "
                             f"{tuple(leaf.shape)}")

    if shardings is not None:
        out = jax.tree.map(lambda a, s: jax.device_put(a, s), out, shardings)
    return out


def _leaves_with_path(tree, is_leaf=None):
    """jax.tree.leaves_with_path with a jax<=0.4.37 fallback: the alias
    only landed on the ``jax.tree`` namespace later — same compat mold as
    the ``ring_attention`` tree-API fix (PR 15). Both spellings accept
    ``is_leaf``."""
    import jax
    fn = getattr(jax.tree, "leaves_with_path", None)
    if fn is None:
        fn = jax.tree_util.tree_leaves_with_path
    return fn(tree, is_leaf=is_leaf)


def _tree_get(tree, path):
    node = tree
    for p in path:
        node = node[getattr(p, "key", getattr(p, "idx", p))]
    return node


# --------------------------------------------------------------------------
# PEFT LoRA adapters (ISSUE 17: multi-tenant serving)
# --------------------------------------------------------------------------

# PEFT names each factor under the wrapped module's path, e.g.
#   base_model.model.model.layers.3.self_attn.q_proj.lora_A.weight
# (the leading wrapper prefix varies by how the model was wrapped, so only
# the stable tail is matched). torch Linear stores [out, in]: lora_A is
# [r, in] and lora_B is [out, r]; our matmuls are x @ W, so both transpose.
_PEFT_KEY_RE = re.compile(
    r"layers\.(\d+)\.self_attn\.([qkvo])_proj\.lora_([AB])\.weight$")


def load_peft_adapter(src, cfg, adapter_config: Optional[dict] = None):
    """Load a PEFT LoRA checkpoint into the serving engine's table layout.

    ``src`` is anything ``_iter_state_dict`` accepts — a state dict, an
    ``adapter_model.safetensors`` file, or a PEFT output directory (where
    ``adapter_config.json`` is read for ``r``/``lora_alpha`` unless
    ``adapter_config`` is passed explicitly). Returns ``(tables, alpha)``
    with ``tables[proj] = (A [L, In, r], B [L, r, Out])`` — exactly what
    ``ServingEngine.register_adapter`` takes::

        srv.register_adapter(7, *load_peft_adapter(peft_dir, cfg))

    Every layer must carry the same projections at the same rank (the
    device slot pool has ONE shape); partial or ragged checkpoints raise.
    """
    path = None
    if not isinstance(src, dict) and not hasattr(src, "state_dict"):
        path = os.fspath(src)
        if os.path.isdir(path):
            cand = os.path.join(path, "adapter_model.safetensors")
            if not os.path.exists(cand):
                cand = os.path.join(path, "adapter_model.bin")
            if adapter_config is None:
                cfg_path = os.path.join(path, "adapter_config.json")
                if os.path.exists(cfg_path):
                    with open(cfg_path) as f:
                        adapter_config = json.load(f)
            src = cand

    L = cfg.num_layers
    # {proj: {layer: {"A"/"B": arr}}}
    raw: Dict[str, Dict[int, Dict[str, np.ndarray]]] = {}
    for key, arr in _iter_state_dict(src):
        m = _PEFT_KEY_RE.search(key)
        if m is None:
            continue
        layer, proj, which = int(m.group(1)), m.group(2), m.group(3)
        if layer >= L:
            raise ValueError(f"peft import: {key!r} indexes layer {layer} "
                             f"but the model has {L} layers")
        raw.setdefault(proj, {}).setdefault(layer, {})[which] = _t(arr)
    if not raw:
        raise ValueError("peft import: no lora_A/lora_B attention-projection "
                         "tensors found (expected keys like "
                         "'...layers.N.self_attn.q_proj.lora_A.weight')")

    rank = None
    tables: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
    for proj, per_layer in sorted(raw.items()):
        missing = [i for i in range(L)
                   if set(per_layer.get(i, ())) != {"A", "B"}]
        if missing:
            raise ValueError(f"peft import: {proj}_proj missing lora_A/B "
                             f"at layers {missing} — every layer must "
                             "carry the adapter (one pool shape)")
        a = np.stack([per_layer[i]["A"] for i in range(L)])  # [L, In, r]
        b = np.stack([per_layer[i]["B"] for i in range(L)])  # [L, r, Out]
        r = a.shape[-1]
        if rank is None:
            rank = r
        if r != rank or b.shape[1] != rank:
            raise ValueError(f"peft import: {proj}_proj rank {r} != {rank} "
                             "elsewhere — mixed-rank adapters don't fit "
                             "one slot pool")
        tables[proj] = (np.asarray(a, np.float32), np.asarray(b, np.float32))

    alpha = None
    if adapter_config is not None:
        cfg_r = adapter_config.get("r")
        if cfg_r is not None and int(cfg_r) != rank:
            raise ValueError(f"peft import: adapter_config.json r={cfg_r} "
                             f"but tensors have rank {rank}")
        if adapter_config.get("lora_alpha") is not None:
            alpha = float(adapter_config["lora_alpha"])
    return tables, alpha


# --------------------------------------------------------------------------
# export (our tree -> HF layout)
# --------------------------------------------------------------------------

def export_hf_state_dict(params, cfg, *, family: Optional[str] = None
                         ) -> Dict[str, np.ndarray]:
    """Inverse mapping: emit an HF-layout state dict (numpy) from our tree.
    Completes the interop contract (load_hf_params round-trips through it)."""
    import jax
    params = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), params)
    if (family in ("opt", "bloom", "mixtral", "olmoe", "bert", "roberta",
                   "gptj", "gpt_neox", "gpt_neo", "distilbert")
            or cfg.num_experts > 1
            or cfg.activation == "relu" or cfg.position_type == "alibi"
            or cfg.parallel_block or not cfg.causal or not cfg.qkv_bias
            or cfg.type_vocab_size or cfg.head_bias or cfg.attn_windows
            or cfg.sandwich_norm or cfg.exit_gate):
        raise NotImplementedError(
            "export_hf_state_dict covers the Llama and GPT-2 layouts; "
            "Mixtral/OPT/BLOOM/BERT/GPT-J/GPT-NeoX export is import-only "
            "for now (a gelu-OPT tree is structurally gpt2-shaped — pass "
            "family='opt' to get this error instead of a gpt2-layout dict)")
    fam = family or ("gpt2" if cfg.position_type == "learned" else "llama")
    sd: Dict[str, np.ndarray] = {}
    lp = params["layers"]
    if fam == "llama":
        sd["model.embed_tokens.weight"] = params["tok_embed"]
        sd["model.norm.weight"] = params["final_norm_scale"]
        if "lm_head" in params:
            sd["lm_head.weight"] = _t(params["lm_head"])
        names = [("input_layernorm.weight", "ln1_scale", None),
                 ("post_attention_layernorm.weight", "ln2_scale", None),
                 ("self_attn.q_proj.weight", "wq", _t),
                 ("self_attn.k_proj.weight", "wk", _t),
                 ("self_attn.v_proj.weight", "wv", _t),
                 ("self_attn.o_proj.weight", "wo", _t),
                 ("mlp.gate_proj.weight", "w_gate", _t),
                 ("mlp.up_proj.weight", "w_in", _t),
                 ("mlp.down_proj.weight", "w_out", _t)]
        for i in range(cfg.num_layers):
            for hf_name, ours, tf in names:
                if ours not in lp:
                    continue
                v = lp[ours][i]
                sd[f"model.layers.{i}.{hf_name}"] = tf(v) if tf else v
    else:
        sd["transformer.wte.weight"] = params["tok_embed"]
        if "pos_embed" in params:
            sd["transformer.wpe.weight"] = params["pos_embed"]
        sd["transformer.ln_f.weight"] = params["final_norm_scale"]
        if "final_norm_bias" in params:
            sd["transformer.ln_f.bias"] = params["final_norm_bias"]
        if "lm_head" in params:
            sd["lm_head.weight"] = _t(params["lm_head"])
        for i in range(cfg.num_layers):
            pre = f"transformer.h.{i}"
            sd[f"{pre}.ln_1.weight"] = lp["ln1_scale"][i]
            sd[f"{pre}.ln_1.bias"] = lp["ln1_bias"][i]
            sd[f"{pre}.ln_2.weight"] = lp["ln2_scale"][i]
            sd[f"{pre}.ln_2.bias"] = lp["ln2_bias"][i]
            sd[f"{pre}.attn.c_attn.weight"] = np.concatenate(
                [lp["wq"][i], lp["wk"][i], lp["wv"][i]], axis=-1)
            sd[f"{pre}.attn.c_attn.bias"] = np.concatenate(
                [lp["bq"][i], lp["bk"][i], lp["bv"][i]], axis=-1)
            sd[f"{pre}.attn.c_proj.weight"] = lp["wo"][i]
            sd[f"{pre}.attn.c_proj.bias"] = lp["bo"][i]
            sd[f"{pre}.mlp.c_fc.weight"] = lp["w_in"][i]
            sd[f"{pre}.mlp.c_fc.bias"] = lp["b_in"][i]
            sd[f"{pre}.mlp.c_proj.weight"] = lp["w_out"][i]
            sd[f"{pre}.mlp.c_proj.bias"] = lp["b_out"][i]
    return sd


# --------------------------------------------------------------------------
# HF config -> TransformerConfig
# --------------------------------------------------------------------------

def _even_rotary(head_dim: int, pct: float) -> int:
    rd = int(head_dim * pct)
    if rd % 2:
        raise ValueError(
            f"rotary_pct {pct} of head_dim {head_dim} gives odd "
            f"rotary_dim {rd}; rotation pairs dims — use an even value")
    return max(2, rd)


def _nemotron_h_kwargs(get) -> dict:
    """``nemotron_h`` (NVIDIA Nemotron-H / Nemotron-3): a hybrid stack whose
    ``hybrid_override_pattern`` names each block's ONE mixer — ``M`` Mamba-2,
    ``E`` an expert feed-forward (sigmoid router with a correction bias,
    non-gated relu^2 experts, a shared expert), ``*`` attention without a
    positional embedding. ``-`` (a dense MLP block, the older Nemotron-H
    checkpoints) and any other letter are refused: nothing here computes
    them."""
    pattern = get("hybrid_override_pattern")
    if not pattern:
        raise ValueError("nemotron_h needs `hybrid_override_pattern`")
    bad = sorted(set(pattern) - set("ME*"))
    if bad:
        raise ValueError(
            f"nemotron_h hybrid_override_pattern has {bad}: only M (Mamba-2), "
            "E (experts) and * (attention) blocks are supported"
            + (" — '-' is a dense MLP block" if "-" in bad else ""))
    L = get("num_hidden_layers")
    if L is not None and L != len(pattern):
        raise ValueError(f"nemotron_h: num_hidden_layers={L} but "
                         f"hybrid_override_pattern has {len(pattern)} blocks")
    for key, want in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                      ("n_group", 1), ("topk_group", 1)):
        if get(key, want) != want:
            raise ValueError(f"nemotron_h {key}={get(key)!r} is not supported "
                             f"(the published config has {want!r})")
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias"):
        if get(key, False):
            raise ValueError(f"nemotron_h {key}=true is not supported")
    if not get("use_conv_bias", True):
        raise ValueError("nemotron_h use_conv_bias=false is not supported")
    if (get("n_shared_experts", 1) or 0) > 1:
        raise ValueError("nemotron_h: more than one shared expert")
    return dict(
        vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
        num_layers=len(pattern), block_pattern=pattern,
        num_heads=get("num_attention_heads"),
        num_kv_heads=get("num_key_value_heads"),
        head_dim=get("head_dim") or get("attention_head_dim"),
        max_seq_len=get("max_position_embeddings", 4096),
        norm_eps=float(get("layer_norm_epsilon", get("norm_eps", 1e-5))),
        # the attention blocks apply no rotary: rope_theta and
        # partial_rotary_factor are in the config and are not read
        position_type="none", norm_type="rmsnorm", activation="relu2",
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        # the E blocks: `moe_intermediate_size` is ONE expert's width
        intermediate_size=get("moe_intermediate_size",
                              get("intermediate_size")),
        num_experts=get("n_routed_experts"),
        top_k=get("num_experts_per_tok"),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        moe_scoring="sigmoid",
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        moe_shared_size=(get("moe_shared_expert_intermediate_size", 0)
                         if get("n_shared_experts", 1) else 0),
        drop_tokens=False, use_residual=False,
        # the M blocks: the inner width is heads x head dim, not
        # expand x hidden (4096 against 5376 at the published sizes)
        mamba_num_heads=get("mamba_num_heads"),
        mamba_head_dim=get("mamba_head_dim"),
        mamba_n_groups=get("n_groups", 1),
        ssm_state_size=get("ssm_state_size"),
        conv_kernel=get("conv_kernel", 4),
        mamba_chunk=get("chunk_size", 128),
        time_step_min=float(get("time_step_min", 0.001)),
        time_step_max=float(get("time_step_max", 0.1)),
        time_step_floor=float(get("time_step_floor", 1e-4)))


def _qwen3_next_kwargs(get) -> dict:
    """``qwen3_next`` (Qwen3-Next): every layer is a token mixer followed by
    an expert layer, so a layer is TWO blocks of a hybrid stack — ``G``
    (Gated DeltaNet) or, every ``full_attention_interval``-th layer, ``*``
    (gated softmax attention: q/k RMSNorm per head, rotary over the first
    ``partial_rotary_factor`` of a head, a sigmoid output gate), then ``E``
    (softmax router, top-k renormalised, SwiGLU experts, a gated shared
    expert). The multi-token-prediction module has no config key and is not
    part of the next-token forward. Dense layers (``mlp_only_layers``,
    ``decoder_sparse_step`` > 1), rope scaling, a sliding window and biases
    are refused: nothing here computes them.

    THE CHIP'S SHARE: ``num_experts`` is the experts held; a
    ``num_experts_router`` key beside it (not a published key: a benchmark
    configuration cut to one chip's share states it) is the router's width,
    ``expert_first`` the first expert held. Absent: all experts held."""
    L, interval = get("num_hidden_layers"), get("full_attention_interval", 4)
    for key, want in (("decoder_sparse_step", 1), ("hidden_act", "silu"),
                      ("rope_scaling", None)):
        if get(key, want) != want:
            raise ValueError(f"qwen3_next {key}={get(key)!r} is not supported "
                             f"(the published config has {want!r})")
    if get("mlp_only_layers"):
        raise ValueError("qwen3_next mlp_only_layers: dense layers are not "
                         "supported (the published config has [])")
    for key in ("attention_bias", "use_sliding_window"):
        if get(key, False):
            raise ValueError(f"qwen3_next {key}=true is not supported")
    pattern = "".join(("*" if (i + 1) % interval == 0 else "G") + "E"
                      for i in range(L))
    kinds = get("layer_types")
    if kinds is not None and list(kinds) != [
            "full_attention" if (i + 1) % interval == 0 else "linear_attention"
            for i in range(L)]:
        raise ValueError("qwen3_next layer_types disagrees with "
                         f"full_attention_interval={interval}")
    held = get("num_experts")
    width, first = get("num_experts_router", held), get("expert_first", 0)
    if not 0 <= first <= width - held:
        raise ValueError(f"qwen3_next: experts {first} .. {first + held - 1} "
                         f"held of num_experts_router={width}")
    head_dim = get("head_dim") or get("hidden_size") // get("num_attention_heads")
    return dict(
        vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
        num_layers=len(pattern), block_pattern=pattern,
        num_heads=get("num_attention_heads"),
        num_kv_heads=get("num_key_value_heads"), head_dim=head_dim,
        max_seq_len=get("max_position_embeddings", 4096),
        norm_eps=float(get("rms_norm_eps", 1e-6)),
        position_type="rotary", norm_type="rmsnorm", activation="silu_glu",
        rope_theta=float(get("rope_theta", 10000.0)),
        rotary_dim=int(head_dim * float(get("partial_rotary_factor", 1.0))),
        qk_norm_per_head=True, attn_out_gate=True,
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        # the E blocks: `moe_intermediate_size` is ONE expert's width
        # (`intermediate_size`, a dense layer's, is unread)
        intermediate_size=get("moe_intermediate_size"),
        num_experts=held, top_k=get("num_experts_per_tok"),
        moe_router_experts=width if width != held else None,
        moe_held_first=first,
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        moe_shared_size=get("shared_expert_intermediate_size", 0) or 0,
        moe_shared_gate=bool(get("shared_expert_intermediate_size", 0)),
        drop_tokens=False, use_residual=False,
        moe_aux_loss_weight=float(get("router_aux_loss_coef", 0.001)),
        # the G blocks
        gdn_num_k_heads=get("linear_num_key_heads"),
        gdn_num_v_heads=get("linear_num_value_heads"),
        gdn_head_k_dim=get("linear_key_head_dim"),
        gdn_head_v_dim=get("linear_value_head_dim"),
        conv_kernel=get("linear_conv_kernel_dim", 4))


def _afmoe_kwargs(get) -> dict:
    """``afmoe`` (Arcee Trinity): every layer is an attention block then a
    feed-forward block of a hybrid stack, both under sandwich norms (four
    RMSNorms a layer). Attention — q/k RMSNorm per head, a sigmoid output
    gate from a separate full-width ``gate_proj`` (stored here as a head's
    columns [q | gate] in ``wq``) — is ``W`` on a ``sliding_attention`` layer
    (rotary over the whole head, the last ``sliding_window`` positions) and
    ``*`` on a ``full_attention`` one (NO positional embedding); the
    feed-forward is ``D`` (SwiGLU of ``intermediate_size``) on the first
    ``num_dense_layers`` layers and ``E`` after them: a sigmoid router whose
    choice adds ``expert_bias``, ``route_norm`` / ``route_scale`` on the
    weights, SwiGLU experts of ``moe_intermediate_size`` and an ungated
    shared expert. ``mup_enabled`` scales the embeddings by
    sqrt(hidden_size). Expert groups, rope scaling and biases are refused:
    nothing here computes them.

    THE CHIP'S SHARE: as ``qwen3_next`` — ``num_experts`` counts the experts
    held, ``num_experts_router`` the router's width, ``expert_first`` the
    first one held."""
    L, every = get("num_hidden_layers"), get("global_attn_every_n_layers", 4)
    for key, want in (("hidden_act", "silu"), ("rope_scaling", None),
                      ("score_func", "sigmoid"), ("n_group", 1),
                      ("num_expert_groups", 1), ("topk_group", 1),
                      ("num_limited_groups", 1), ("num_shared_experts", 1)):
        if get(key, want) != want:
            raise ValueError(f"afmoe {key}={get(key)!r} is not supported "
                             f"(the published config has {want!r})")
    for key in ("attention_bias", "mlp_bias"):
        if get(key, False):
            raise ValueError(f"afmoe {key}=true is not supported")
    # a config cut in depth keeps the published list: its first L entries
    kinds = list(get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else "sliding_attention"
        for i in range(L)])[:L]
    bad = sorted(set(kinds) - {"full_attention", "sliding_attention"})
    if bad or len(kinds) != L:
        raise ValueError(f"afmoe layer_types: >= {L} entries of "
                         f"full_attention | sliding_attention, got "
                         f"{len(kinds)} with {bad}")
    dense, sw = get("num_dense_layers", 0), get("sliding_window")
    if "sliding_attention" in kinds and not sw:
        raise ValueError("afmoe: sliding_attention layers need sliding_window")
    pattern = "".join(("W" if kind == "sliding_attention" else "*")
                      + ("D" if i < dense else "E")
                      for i, kind in enumerate(kinds))
    held = get("num_experts")
    width, first = get("num_experts_router", held), get("expert_first", 0)
    if not 0 <= first <= width - held:
        raise ValueError(f"afmoe: experts {first} .. {first + held - 1} "
                         f"held of num_experts_router={width}")
    H = get("hidden_size")
    return dict(
        vocab_size=get("vocab_size"), hidden_size=H,
        num_layers=len(pattern), block_pattern=pattern,
        attn_windows=tuple(int(sw) if b == "W" else 0 for b in pattern),
        num_heads=get("num_attention_heads"),
        num_kv_heads=get("num_key_value_heads"),
        head_dim=get("head_dim") or H // get("num_attention_heads"),
        max_seq_len=get("max_position_embeddings", 4096),
        norm_eps=float(get("rms_norm_eps", 1e-5)),
        # the rule of the "*" blocks; a "W" block is always rotary
        position_type="none", rope_theta=float(get("rope_theta", 10000.0)),
        norm_type="rmsnorm", activation="silu_glu", sandwich_norm=True,
        qk_norm_per_head=True, attn_out_gate=True,
        embed_scale=math.sqrt(H) if get("mup_enabled", False) else 1.0,
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        # `moe_intermediate_size` is ONE expert's width and the shared
        # expert's, `intermediate_size` a dense layer's
        intermediate_size=get("moe_intermediate_size"),
        dense_ffn_size=get("intermediate_size"),
        num_experts=held, top_k=get("num_experts_per_tok"),
        moe_router_experts=width if width != held else None,
        moe_held_first=first, moe_scoring="sigmoid",
        norm_topk_prob=bool(get("route_norm", True)),
        routed_scaling_factor=float(get("route_scale", 1.0)),
        moe_shared_size=get("moe_intermediate_size"),
        drop_tokens=False, use_residual=False,
        moe_aux_loss_weight=float(get("load_balance_coeff", 0.0)))


def afmoe_weight_names(cfg) -> Dict[str, tuple]:
    """The tensors of an HF ``afmoe`` checkpoint of ``cfg``'s shape, by the
    names ``modeling_afmoe`` registers them under -> where each lives in the
    hybrid tree: ``(kind, block index within its kind, leaf, part)`` —
    ``kind`` None for the three leaves outside the layers; ``part`` the
    expert for an expert stack (counted from ``moe_held_first``: a chip loads
    the experts it holds), ``"q"`` / ``"gate"`` for the two projections whose
    columns share ``wq`` head by head ([q | gate]), else None. Every matrix is
    stored transposed ([in, out]) but the experts' up projection
    (``moe_w_in_t`` keeps HF's [F, H]). ``load_hf_params`` stacks
    homogeneous layers and does not read this table yet."""
    from deepspeed_tpu.models import hybrid
    out = {"model.embed_tokens.weight": (None, 0, "tok_embed", None),
           "model.norm.weight": (None, 0, "final_norm_scale", None),
           "lm_head.weight": (None, 0, "lm_head", None)}
    blocks = hybrid.blocks(cfg)
    for layer in range(len(blocks) // 2):
        pre = f"model.layers.{layer}."
        (akind, aj), (fkind, fj) = blocks[2 * layer], blocks[2 * layer + 1]
        for name, leaf, part in (
                ("input_layernorm", "ln_scale", None),
                ("post_attention_layernorm", "post_ln_scale", None),
                ("self_attn.q_proj", "wq", "q"),
                ("self_attn.gate_proj", "wq", "gate"),
                ("self_attn.k_proj", "wk", None),
                ("self_attn.v_proj", "wv", None),
                ("self_attn.o_proj", "wo", None),
                ("self_attn.q_norm", "q_norm", None),
                ("self_attn.k_norm", "k_norm", None)):
            out[pre + name + ".weight"] = (akind, aj, leaf, part)
        out[pre + "pre_mlp_layernorm.weight"] = (fkind, fj, "ln_scale", None)
        out[pre + "post_mlp_layernorm.weight"] = (fkind, fj, "post_ln_scale",
                                                  None)
        if fkind == "dense":
            for name, leaf in (("gate_proj", "w_gate"), ("up_proj", "w_in"),
                               ("down_proj", "w_out")):
                out[pre + f"mlp.{name}.weight"] = (fkind, fj, leaf, None)
            continue
        out[pre + "mlp.router.gate.weight"] = (fkind, fj, "wg", None)
        out[pre + "mlp.expert_bias"] = (fkind, fj, "e_bias", None)
        for name, leaf in (("gate_proj", "w_gate"), ("up_proj", "w_in"),
                           ("down_proj", "w_out")):
            out[pre + f"mlp.shared_experts.{name}.weight"] = (
                fkind, fj, "shared_" + leaf, None)
            stack = "moe_w_in_t" if leaf == "w_in" else "moe_" + leaf
            for e in range(cfg.num_experts):
                out[pre + f"mlp.experts.{cfg.moe_held_first + e}.{name}"
                    ".weight"] = (fkind, fj, stack, e)
    return out


def _glm4_moe_lite_kwargs(get) -> dict:
    """``glm4_moe_lite`` (Z.ai GLM-4.7-Flash; DeepSeek-V3's layer): every
    layer is a latent-attention block (``L``, ``models/latent_attention.py``:
    q through ``q_lora_rank`` with an RMSNorm between, K and V from ONE
    normed row of ``kv_lora_rank`` beside a shared rotary key of
    ``qk_rope_head_dim``) then a feed-forward block of a hybrid stack: ``D``
    (SwiGLU of ``intermediate_size``) on the first ``first_k_dense_replace``
    layers, ``E`` after them — ``noaux_tc``: sigmoid scores, the choice the
    top ``num_experts_per_tok`` of score + ``e_score_correction_bias``, the
    weights the scores, divided by their sum (``norm_topk_prob``) and times
    ``routed_scaling_factor``; ``n_shared_experts`` shared experts as one of
    that many times ``moe_intermediate_size``. Rotary over all the rope dims
    in the family's interleaved pairing (2i, 2i + 1). Expert groups, rope
    scaling, biases and a next-token-prediction module are refused: nothing
    here computes them (the published forward does not run the module:
    ``num_nextn_predict_layers`` 0 says it is not there)."""
    for key, want in (("hidden_act", "silu"), ("rope_scaling", None),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("partial_rotary_factor", 1),
                      ("attention_bias", False),
                      ("num_nextn_predict_layers", 0)):
        if get(key, want) != want:
            raise ValueError(f"glm4_moe_lite {key}={get(key)!r} is not "
                             f"supported (this importer takes {want!r})")
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim"):
        if not get(key):
            raise ValueError(f"glm4_moe_lite needs {key}, got {get(key)!r}")
    if get("num_experts") not in (None, get("n_routed_experts")):
        raise ValueError(
            f"glm4_moe_lite num_experts={get('num_experts')!r} beside "
            f"n_routed_experts={get('n_routed_experts')!r}: the published "
            "key is n_routed_experts, and every routed expert is held")
    heads = get("num_attention_heads")
    if get("num_key_value_heads", heads) != heads:
        raise ValueError("glm4_moe_lite: latent attention expands K and V "
                         "for every query head (num_key_value_heads = "
                         "num_attention_heads)")
    return _latent_expert_stack(get, norm_eps=1e-5)


def _latent_expert_stack(get, norm_eps: float) -> dict:
    """What ``glm4_moe_lite`` and ``xing4_0`` share, DeepSeek-V3's layer under
    its published keys: the ``L`` + ``D`` / ``E`` pattern, the latent ranks and
    head widths, the sigmoid ``noaux_tc`` router with its shared expert, plain
    interleaved rotary. ``norm_eps``: the family's default ``rms_norm_eps``."""
    L, dense = get("num_hidden_layers"), get("first_k_dense_replace", 0)
    pattern = "".join("L" + ("D" if i < dense else "E") for i in range(L))
    return dict(
        vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
        num_layers=len(pattern), block_pattern=pattern,
        num_heads=get("num_attention_heads"),
        head_dim=get("qk_nope_head_dim") + get("qk_rope_head_dim"),
        q_lora_rank=get("q_lora_rank"), kv_lora_rank=get("kv_lora_rank"),
        qk_nope_head_dim=get("qk_nope_head_dim"),
        qk_rope_head_dim=get("qk_rope_head_dim"),
        v_head_dim=get("v_head_dim"),
        max_seq_len=get("max_position_embeddings", 4096),
        norm_eps=float(get("rms_norm_eps", norm_eps)),
        position_type="rotary", rope_theta=float(get("rope_theta", 10000.0)),
        rotary_interleaved=True,
        norm_type="rmsnorm", activation="silu_glu",
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        # `moe_intermediate_size` is ONE expert's width, `intermediate_size`
        # a dense layer's
        intermediate_size=get("moe_intermediate_size"),
        dense_ffn_size=get("intermediate_size"),
        num_experts=get("n_routed_experts"), top_k=get("num_experts_per_tok"),
        moe_scoring="sigmoid", norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        moe_shared_size=get("moe_intermediate_size")
        * get("n_shared_experts", 0),
        drop_tokens=False, use_residual=False, moe_aux_loss_weight=0.0)


def glm4_moe_lite_weight_names(cfg) -> Dict[str, tuple]:
    """The tensors of an HF ``glm4_moe_lite`` checkpoint of ``cfg``'s shape,
    by the names ``modeling_glm4_moe_lite`` registers them under -> where each
    lives in the hybrid tree, as ``afmoe_weight_names`` gives them: ``(kind,
    block index within its kind, leaf, part)`` — ``part`` the expert for an
    expert stack, else None. Every matrix is stored transposed ([in, out]) but
    the experts' up projection (``moe_w_in_t`` keeps HF's [F, H]); the
    ``n_shared_experts`` shared experts are ONE module of their summed width.
    The next-token-prediction layer's tensors (``model.layers.<num_hidden_
    layers>.*``) have no place: HF's classes ignore them at load too."""
    from deepspeed_tpu.models import hybrid
    out = {"model.embed_tokens.weight": (None, 0, "tok_embed", None),
           "model.norm.weight": (None, 0, "final_norm_scale", None),
           "lm_head.weight": (None, 0, "lm_head", None)}
    blocks = hybrid.blocks(cfg)
    for layer in range(len(blocks) // 2):
        pre = f"model.layers.{layer}."
        (akind, aj), (fkind, fj) = blocks[2 * layer], blocks[2 * layer + 1]
        for name, leaf in (("input_layernorm", "ln_scale"),
                           ("self_attn.q_a_proj", "wq_a"),
                           ("self_attn.q_a_layernorm", "q_a_norm"),
                           ("self_attn.q_b_proj", "wq_b"),
                           ("self_attn.kv_a_proj_with_mqa", "wkv_a"),
                           ("self_attn.kv_a_layernorm", "kv_a_norm"),
                           ("self_attn.kv_b_proj", "wkv_b"),
                           ("self_attn.o_proj", "wo")):
            out[pre + name + ".weight"] = (akind, aj, leaf, None)
        out[pre + "post_attention_layernorm.weight"] = (fkind, fj, "ln_scale",
                                                        None)
        if fkind == "dense":
            for name, leaf in (("gate_proj", "w_gate"), ("up_proj", "w_in"),
                               ("down_proj", "w_out")):
                out[pre + f"mlp.{name}.weight"] = (fkind, fj, leaf, None)
            continue
        out[pre + "mlp.gate.weight"] = (fkind, fj, "wg", None)
        out[pre + "mlp.gate.e_score_correction_bias"] = (fkind, fj, "e_bias",
                                                         None)
        for name, leaf in (("gate_proj", "w_gate"), ("up_proj", "w_in"),
                           ("down_proj", "w_out")):
            out[pre + f"mlp.shared_experts.{name}.weight"] = (
                fkind, fj, "shared_" + leaf, None)
            stack = "moe_w_in_t" if leaf == "w_in" else "moe_" + leaf
            for e in range(cfg.num_experts):
                out[pre + f"mlp.experts.{e}.{name}.weight"] = (fkind, fj,
                                                               stack, e)
    return out


class Xing40Unsupported(NotImplementedError):
    """An ``xing4_0`` config key whose value nothing here computes (``.key``,
    ``.value``): refused at the import, not at the first step."""

    def __init__(self, key: str, value, want):
        self.key, self.value = key, value
        super().__init__(f"xing4_0 {key}={value!r} is not supported (this "
                         f"importer takes {want!r})")


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """DeepSeek's ``yarn_get_mscale``: 0.1 mscale ln(factor) + 1 (1 up to a
    factor of 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _xing4_0_kwargs(get) -> dict:
    """``xing4_0`` (XingChen Xing4.0-29B-A4B): ``glm4_moe_lite``'s stack —
    DeepSeek-V3's layer: a latent-attention block ``L`` then ``D`` on the
    first ``first_k_dense_replace`` layers and ``E`` after them, the sigmoid
    ``noaux_tc`` router, a shared expert, the interleaved rotary pairing —
    with three things of its own. (1) The residual stream is ``hc_mult`` rows
    wide and every block reads, writes and carries it through
    manifold-constrained hyper-connections (``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min`` / ``_max``; ``TransformerConfig.hc_mult``,
    ``models/hybrid.py``). (2) ``v_head_dim`` may be narrower than
    ``qk_nope_head_dim + qk_rope_head_dim``. (3) ``rope_scaling`` of type
    ``yarn``, as DeepSeek-V3 reads it: the stretched table on the rope dims,
    cos and sin times ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``, and the softmax scale ``(nope + rope)^-1/2`` times
    ``mscale(factor, mscale_all_dim)^2`` where ``mscale_all_dim`` is stated
    (``attn_scale``: both orders of latent attention read it). Refused, typed
    (``Xing40Unsupported``): expert groups, an expert-parallel degree, a bias,
    a next-token-prediction module, another rope scaling, another router."""
    from deepspeed_tpu.models.transformer import RopeTable
    for key, want in (("hidden_act", "silu"), ("topk_method", "noaux_tc"),
                      ("scoring_func", "sigmoid"), ("n_group", 1),
                      ("topk_group", 1), ("ep_size", 1),
                      ("moe_layer_freq", 1), ("attention_bias", False),
                      ("mlp_bias", False), ("num_nextn_predict_layers", 0)):
        if get(key, want) != want:
            raise Xing40Unsupported(key, get(key), want)
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "hc_mult"):
        if not get(key):
            raise Xing40Unsupported(key, get(key), "a positive size")
    if get("num_experts") not in (None, get("n_routed_experts")):
        raise Xing40Unsupported("num_experts", get("num_experts"),
                                get("n_routed_experts"))
    heads = get("num_attention_heads")
    if get("num_key_value_heads", heads) != heads:
        raise Xing40Unsupported("num_key_value_heads",
                                get("num_key_value_heads"), heads)
    dn, dr = get("qk_nope_head_dim"), get("qk_rope_head_dim")
    theta = float(get("rope_theta", 10000.0))
    table, scale = RopeTable(theta), None
    rs = get("rope_scaling")
    if rs is not None:
        kind = rs.get("type", rs.get("rope_type"))
        if kind != "yarn":
            raise Xing40Unsupported("rope_scaling.type", kind, "yarn")
        factor = float(rs["factor"])
        all_dim = float(rs.get("mscale_all_dim") or 0.0)
        table = RopeTable(
            theta, factor, int(rs["original_max_position_embeddings"]),
            float(rs.get("beta_fast") or 32), float(rs.get("beta_slow") or 1),
            yarn_mscale(factor, float(rs.get("mscale") or 1.0))
            / yarn_mscale(factor, all_dim))
        if all_dim:
            scale = (dn + dr) ** -0.5 * yarn_mscale(factor, all_dim) ** 2
    return dict(
        _latent_expert_stack(get, norm_eps=1e-6),
        rope_tables=(("latent", table),), attn_scale=scale,
        hc_mult=int(get("hc_mult")),
        hc_sinkhorn_iters=int(get("hc_sinkhorn_iters", 20)),
        hc_eps=float(get("hc_eps", 1e-6)),
        hc_res_clamp=(float(get("mhc_h_res_clamp_min", -30.0)),
                      float(get("mhc_h_res_clamp_max", 30.0))))


def xing4_0_weight_names(cfg) -> Dict[str, tuple]:
    """The tensors of an ``xing4_0`` checkpoint of ``cfg``'s shape -> where
    each lives in the hybrid tree (``glm4_moe_lite_weight_names``' form and,
    for everything but the stream's mappings, its names: the layer is
    DeepSeek-V3's). The mappings' names are ASSUMED (the catalog row carries
    the config, not the tensor index): a layer's ``hc_attn_fn`` / ``hc_ffn_fn``
    [2n + n^2, n H] (a Linear's [out, in]: stored transposed as ``hc_phi``),
    ``hc_attn_base`` / ``hc_ffn_base`` (``hc_b``) and ``hc_attn_scale`` /
    ``hc_ffn_scale`` (``hc_a``: pre, post, res), and the closing read's
    ``model.hc_head_fn`` / ``_base`` / ``_scale``."""
    from deepspeed_tpu.models import hybrid
    out = glm4_moe_lite_weight_names(cfg)
    blocks = hybrid.blocks(cfg)
    for i, (kind, j) in enumerate(blocks):
        pre = f"model.layers.{i // 2}.hc_{'ffn' if i % 2 else 'attn'}_"
        for name, leaf in (("fn", "hc_phi"), ("base", "hc_b"),
                           ("scale", "hc_a")):
            out[pre + name] = (kind, j, leaf, None)
    for name, leaf in (("fn", "hc_out_phi"), ("base", "hc_out_b"),
                       ("scale", "hc_out_a")):
        out["model.hc_head_" + name] = (None, 0, leaf, None)
    return out


class FalconH1Unsupported(NotImplementedError):
    """A ``falcon_h1`` config key whose value nothing here computes
    (``.key``, ``.value``): refused at the import, not at the first step."""

    def __init__(self, key: str, value, want):
        self.key, self.value = key, value
        super().__init__(f"falcon_h1 {key}={value!r} is not supported (this "
                         f"importer takes {want!r})")


def _falcon_h1_kwargs(get) -> dict:
    """``falcon_h1`` (TII Falcon-H1): every layer is a ``P`` block — a
    Mamba-2 mixer and rotary GQA attention side by side on ONE RMSNorm, their
    outputs scaled and summed into one residual — then a ``D`` block (SwiGLU
    of ``intermediate_size``). The muP multipliers stay what they are
    published as, static scalars of the forward (``TransformerConfig``): the
    stored tensors are the checkpoint's. Refused, typed
    (``FalconH1Unsupported``): a Mamba mixer without its gated RMSNorm or
    with the norm BEFORE the gate, a layer without the feed-forward, rope
    scaling, attention on some layers only, any bias but the convolution's,
    another activation than silu."""
    for key, want in (("mamba_rms_norm", True),
                      ("mamba_norm_before_gate", False),
                      ("mamba_use_mlp", True), ("rope_scaling", None),
                      ("attn_layer_indices", None), ("hidden_act", "silu"),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("mamba_proj_bias", False), ("projectors_bias", False),
                      ("mamba_conv_bias", True)):
        if get(key, want) != want:
            raise FalconH1Unsupported(key, get(key), want)
    H, nh, hd = get("hidden_size"), get("mamba_n_heads"), get("mamba_d_head")
    d_ssm = get("mamba_d_ssm") or get("mamba_expand", 2) * H
    if d_ssm != nh * hd:
        raise FalconH1Unsupported(
            "mamba_d_ssm", d_ssm, f"mamba_n_heads x mamba_d_head = {nh * hd}")
    L = get("num_hidden_layers")
    return dict(
        vocab_size=get("vocab_size"), hidden_size=H,
        num_layers=2 * L, block_pattern="PD" * L,
        num_heads=get("num_attention_heads"),
        num_kv_heads=get("num_key_value_heads"),
        head_dim=get("head_dim") or H // get("num_attention_heads"),
        max_seq_len=get("max_position_embeddings", 4096),
        norm_eps=float(get("rms_norm_eps", 1e-5)),
        position_type="rotary", rope_theta=float(get("rope_theta", 10000.0)),
        norm_type="rmsnorm", activation="silu_glu",
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        intermediate_size=get("intermediate_size"),
        mamba_num_heads=nh, mamba_head_dim=hd,
        mamba_n_groups=get("mamba_n_groups", 1),
        ssm_state_size=get("mamba_d_state"),
        conv_kernel=get("mamba_d_conv", 4),
        mamba_chunk=get("mamba_chunk_size", 128),
        embed_scale=float(get("embedding_multiplier", 1.0)),
        lm_head_multiplier=float(get("lm_head_multiplier", 1.0)),
        attention_in_multiplier=float(get("attention_in_multiplier", 1.0)),
        attention_out_multiplier=float(get("attention_out_multiplier", 1.0)),
        key_multiplier=float(get("key_multiplier", 1.0)),
        ssm_in_multiplier=float(get("ssm_in_multiplier", 1.0)),
        ssm_out_multiplier=float(get("ssm_out_multiplier", 1.0)),
        ssm_multipliers=tuple(float(m) for m in
                              get("ssm_multipliers", (1.0,) * 5)),
        mlp_multipliers=tuple(float(m) for m in
                              get("mlp_multipliers", (1.0, 1.0))))


def _rope_table(kind: str, params: dict):
    """One entry of a published ``rope_parameters`` group -> a ``RopeTable``
    (``rope_type`` ``default`` or ``yarn``; ``attention_factor`` as
    ``transformers`` defaults it, 0.1 ln(factor) + 1)."""
    from deepspeed_tpu.models.transformer import RopeTable
    rope_type = params.get("rope_type", "default")
    theta = float(params.get("rope_theta", 10000.0))
    if rope_type == "default":
        return RopeTable(theta)
    if rope_type != "yarn":
        raise ValueError(f"mellum rope_parameters[{kind!r}]: rope_type "
                         f"{rope_type!r} is not supported (default | yarn)")
    factor = float(params["factor"])
    af = params.get("attention_factor")
    return RopeTable(
        theta, factor, int(params["original_max_position_embeddings"]),
        float(params.get("beta_fast") or 32), float(params.get("beta_slow") or 1),
        float(af) if af is not None else 0.1 * math.log(factor) + 1.0)


def _mellum_kwargs(get) -> dict:
    """``mellum`` (JetBrains Mellum 2): every layer is an attention block then
    an expert block of a hybrid stack, pre-norm (two RMSNorms a layer).
    Attention — q/k RMSNorm per head, no gate, no bias — is ``W`` on a
    ``sliding_attention`` layer (the last ``sliding_window`` positions) and
    ``*`` on a ``full_attention`` one, rotary on BOTH with the table
    ``rope_parameters`` states for the layer's type (plain on the sliding
    layers, YaRN on the full ones); the feed-forward is ``E`` on every layer
    (``mlp_layer_types`` all ``sparse``): a softmax router, the top-k weights
    renormalised (``norm_topk_prob``), SwiGLU experts of
    ``moe_intermediate_size``, no shared expert, no auxiliary loss (the
    config keys no coefficient). ``intermediate_size`` (a dense layer's) and
    ``max_window_layers`` are unread; dense layers, biases and other rope
    types are refused: nothing here computes them.

    THE CHIP'S SHARE: as ``qwen3_next`` — ``num_experts`` counts the experts
    held, ``num_experts_router`` the router's width, ``expert_first`` the
    first one held."""
    L = get("num_hidden_layers")
    for key, want in (("hidden_act", "silu"), ("attention_bias", False)):
        if get(key, want) != want:
            raise ValueError(f"mellum {key}={get(key)!r} is not supported "
                             f"(the published config has {want!r})")
    if set((get("mlp_layer_types") or ["sparse"])[:L]) != {"sparse"}:
        raise ValueError("mellum mlp_layer_types: only sparse layers are "
                         "supported (the published config has no other)")
    kinds = list(get("layer_types") or [])[:L]
    bad = sorted(set(kinds) - {"full_attention", "sliding_attention"})
    if bad or len(kinds) != L:
        raise ValueError(f"mellum layer_types: >= {L} entries of "
                         f"full_attention | sliding_attention, got "
                         f"{len(kinds)} with {bad}")
    sw = get("sliding_window")
    if "sliding_attention" in kinds and not (
            sw and get("use_sliding_window", True)):
        raise ValueError("mellum: sliding_attention layers need "
                         "sliding_window and use_sliding_window")
    rope = get("rope_parameters") or {}
    missing = sorted(set(kinds) - set(rope))
    if missing:
        raise ValueError(f"mellum rope_parameters has no group for {missing}")
    pattern = "".join(("W" if kind == "sliding_attention" else "*") + "E"
                      for kind in kinds)
    held = get("num_experts")
    width, first = get("num_experts_router", held), get("expert_first", 0)
    if not 0 <= first <= width - held:
        raise ValueError(f"mellum: experts {first} .. {first + held - 1} "
                         f"held of num_experts_router={width}")
    H = get("hidden_size")
    tables = {"attn": rope.get("full_attention"),
              "wattn": rope.get("sliding_attention")}
    return dict(
        vocab_size=get("vocab_size"), hidden_size=H,
        num_layers=len(pattern), block_pattern=pattern,
        attn_windows=tuple(int(sw) if b == "W" else 0 for b in pattern),
        num_heads=get("num_attention_heads"),
        num_kv_heads=get("num_key_value_heads"),
        head_dim=get("head_dim") or H // get("num_attention_heads"),
        max_seq_len=get("max_position_embeddings", 8192),
        norm_eps=float(get("rms_norm_eps", 1e-6)),
        position_type="rotary", norm_type="rmsnorm", activation="silu_glu",
        rope_tables=tuple((kind, _rope_table(kind, group))
                          for kind, group in tables.items()
                          if group is not None),
        qk_norm_per_head=True,
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        # `moe_intermediate_size` is ONE expert's width
        intermediate_size=get("moe_intermediate_size"),
        num_experts=held, top_k=get("num_experts_per_tok"),
        moe_router_experts=width if width != held else None,
        moe_held_first=first,
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        drop_tokens=False, use_residual=False, moe_aux_loss_weight=0.0)


def mellum_weight_names(cfg) -> Dict[str, tuple]:
    """The tensors of an HF ``mellum`` checkpoint of ``cfg``'s shape, by the
    names its lineage (Qwen3-MoE) registers them under -> where each lives in
    the hybrid tree, as ``afmoe_weight_names`` gives them: ``(kind, block
    index within its kind, leaf, expert or None)``. Every matrix is stored
    transposed ([in, out]) but the experts' up projection (``moe_w_in_t``
    keeps HF's [F, H]). ``load_hf_params`` does not read this table yet."""
    from deepspeed_tpu.models import hybrid
    out = {"model.embed_tokens.weight": (None, 0, "tok_embed", None),
           "model.norm.weight": (None, 0, "final_norm_scale", None),
           "lm_head.weight": (None, 0, "lm_head", None)}
    blocks = hybrid.blocks(cfg)
    for layer in range(len(blocks) // 2):
        pre = f"model.layers.{layer}."
        (akind, aj), (fkind, fj) = blocks[2 * layer], blocks[2 * layer + 1]
        out[pre + "input_layernorm.weight"] = (akind, aj, "ln_scale", None)
        for name, leaf in (("q_proj", "wq"), ("k_proj", "wk"),
                           ("v_proj", "wv"), ("o_proj", "wo"),
                           ("q_norm", "q_norm"), ("k_norm", "k_norm")):
            out[pre + f"self_attn.{name}.weight"] = (akind, aj, leaf, None)
        out[pre + "post_attention_layernorm.weight"] = (fkind, fj, "ln_scale",
                                                        None)
        out[pre + "mlp.gate.weight"] = (fkind, fj, "wg", None)
        for name, stack in (("gate_proj", "moe_w_gate"),
                            ("up_proj", "moe_w_in_t"),
                            ("down_proj", "moe_w_out")):
            for e in range(cfg.num_experts):
                out[pre + f"mlp.experts.{cfg.moe_held_first + e}.{name}"
                    ".weight"] = (fkind, fj, stack, e)
    return out


class EarlyExitUnsupported(NotImplementedError):
    """A looped model whose ``early_exit_threshold`` is below 1: a token
    would leave the stack at the first pass whose cumulative exit
    probability reaches the threshold and write no K/V for the passes it
    skipped, and which K/V later tokens then read there is a cache policy
    the config does not give. Only the published threshold 1 (every pass
    runs) is served."""

    def __init__(self, threshold):
        super().__init__(
            f"early_exit_threshold={threshold!r} < 1 is not supported: a "
            "token that leaves a looped stack early writes no K/V for the "
            "passes it skips, and the config does not say what later tokens "
            "read there. The published value 1 runs every pass")
        self.threshold = threshold


def _ouro_kwargs(get) -> dict:
    """``ouro`` (ByteDance Ouro, a looped language model): the
    ``num_hidden_layers`` layers run ``total_ut_steps`` times over the same
    weights, each pass with K/V planes of its own; a block norms its
    sublayers' outputs as well as their inputs; the final norm ends every
    pass; an exit gate reads each pass's output."""
    threshold = get("early_exit_threshold", 1.0)
    if threshold is not None and float(threshold) < 1.0:
        raise EarlyExitUnsupported(threshold)
    bad = sorted(set(get("layer_types") or ()) - {"full_attention"})
    if bad:
        raise ValueError(f"ouro layer_types has {bad}: only full_attention "
                         "layers are supported")
    if get("use_sliding_window", False):
        raise ValueError("ouro use_sliding_window=true is not supported")
    if get("rope_scaling") is not None:
        raise ValueError(f"ouro rope_scaling={get('rope_scaling')!r} is not "
                         "supported (the published config has null)")
    if get("hidden_act", "silu") != "silu":
        raise ValueError(f"ouro hidden_act={get('hidden_act')!r} is not "
                         "supported (the published config has 'silu')")
    for key in ("attention_bias", "mlp_bias"):
        if get(key, False):
            raise ValueError(f"ouro {key}=true is not supported")
    return dict(
        vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
        num_layers=get("num_hidden_layers"),
        num_heads=get("num_attention_heads"),
        num_kv_heads=get("num_key_value_heads"), head_dim=get("head_dim"),
        intermediate_size=get("intermediate_size"),
        max_seq_len=get("max_position_embeddings", 4096),
        rope_theta=float(get("rope_theta", 10000.0)),
        norm_eps=get("rms_norm_eps", 1e-6),
        position_type="rotary", activation="silu_glu", norm_type="rmsnorm",
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        ut_steps=int(get("total_ut_steps", 1)), sandwich_norm=True,
        exit_gate=True)


def hf_config_to_transformer(hf_cfg, **overrides):
    """Build a TransformerConfig from a transformers PretrainedConfig (or a
    config.json dict)."""
    from deepspeed_tpu.models.transformer import TransformerConfig
    get = (hf_cfg.get if isinstance(hf_cfg, dict)
           else lambda k, d=None: getattr(hf_cfg, k, d))
    mt = (get("model_type") or "").lower()
    if mt == "qwen2":
        # qwen2 is llama-shaped EXCEPT for attention biases, which the rmsnorm
        # param tree does not carry — importing would silently drop them.
        raise ValueError("qwen2 attention biases are not supported yet; "
                         "convert without biases explicitly if acceptable")
    if mt in ("llama", "mistral", "mixtral", "olmoe"):
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 4096),
            rope_theta=float(get("rope_theta", 10000.0)),
            norm_eps=get("rms_norm_eps", 1e-5),
            position_type="rotary", activation="silu_glu",
            norm_type="rmsnorm",
            tie_embeddings=bool(get("tie_word_embeddings", False)))
        if mt == "mixtral":
            kw.update(
                num_experts=get("num_local_experts", 8),
                top_k=get("num_experts_per_tok", 2),
                moe_aux_loss_weight=float(get("router_aux_loss_coef", 0.02)),
                use_residual=False)
        elif mt == "olmoe":
            # what the model IS: every layer an expert layer, no shared
            # expert, dropless, the top-k weights NOT renormalised unless
            # the config says so, q/k RMSNorm, `intermediate_size` the width
            # of ONE expert
            if get("clip_qkv") is not None:
                raise ValueError("olmoe clip_qkv is not supported (the "
                                 "published 1B-7B config has null)")
            if get("attention_bias", False):
                raise ValueError("olmoe attention_bias=true is not supported")
            kw.update(
                num_experts=get("num_experts", 64),
                top_k=get("num_experts_per_tok", 8),
                norm_topk_prob=bool(get("norm_topk_prob", False)),
                drop_tokens=False, qk_norm=True, use_residual=False,
                moe_aux_loss_weight=float(get("router_aux_loss_coef", 0.01)))
    elif mt == "nemotron_h":
        kw = _nemotron_h_kwargs(get)
    elif mt == "ouro":
        kw = _ouro_kwargs(get)
    elif mt == "qwen3_next":
        kw = _qwen3_next_kwargs(get)
    elif mt == "afmoe":
        kw = _afmoe_kwargs(get)
    elif mt == "mellum":
        kw = _mellum_kwargs(get)
    elif mt == "glm4_moe_lite":
        kw = _glm4_moe_lite_kwargs(get)
    elif mt == "falcon_h1":
        kw = _falcon_h1_kwargs(get)
    elif mt == "xing4_0":
        kw = _xing4_0_kwargs(get)
    elif mt == "opt":
        if get("word_embed_proj_dim", get("hidden_size")) != get("hidden_size"):
            raise ValueError(
                "OPT word_embed_proj_dim != hidden_size (the 350m-style "
                "embedding projection) is not supported")
        if not get("do_layer_norm_before", True):
            raise ValueError("OPT do_layer_norm_before=False (the 350m "
                             "post-norm variant) is not supported")
        act = get("activation_function", "relu")
        if act not in ("relu", "gelu"):
            raise ValueError(f"unsupported OPT activation {act!r}")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            intermediate_size=get("ffn_dim"),
            max_seq_len=get("max_position_embeddings", 2048),
            position_type="learned", activation=act,
            norm_type="layernorm",
            tie_embeddings=bool(get("tie_word_embeddings", True)))
    elif mt == "bloom":
        H = get("hidden_size") or get("n_embed")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=H,
            num_layers=get("n_layer") or get("num_hidden_layers"),
            num_heads=get("n_head") or get("num_attention_heads"),
            intermediate_size=4 * H,
            max_seq_len=get("seq_length", 2048),
            norm_eps=get("layer_norm_epsilon", 1e-5),
            position_type="alibi", activation="gelu",
            norm_type="layernorm", embed_norm=True,
            tie_embeddings=bool(get("tie_word_embeddings", True)))
    elif mt in ("bert", "roberta"):
        # encoder family (reference: module_inject/containers/bert.py +
        # distilbert.py): bidirectional, post-LN, segment embeddings.
        # RoBERTa's learned-position table carries a padding_idx+1=2 row
        # offset (its import table slices it off), so usable positions are
        # max_position_embeddings - 2.
        max_pos = get("max_position_embeddings", 512)
        if mt == "roberta":
            max_pos -= 2
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=max_pos,
            norm_eps=get("layer_norm_eps", 1e-12),
            position_type="learned", activation="gelu",
            norm_type="layernorm", causal=False, norm_style="post",
            embed_norm=True, final_norm=False,
            type_vocab_size=get("type_vocab_size", 2) or 0,
            tie_embeddings=True)
    elif mt == "distilbert":
        # reference: module_inject/containers/distil_bert.py — BERT-shaped
        # post-LN encoder, no token-type embeddings
        if get("sinusoidal_pos_embds", False):
            raise ValueError("distilbert sinusoidal_pos_embds=True is not "
                             "supported (learned-position table expected)")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("dim"),
            num_layers=get("n_layers"), num_heads=get("n_heads"),
            intermediate_size=get("hidden_dim"),
            max_seq_len=get("max_position_embeddings", 512),
            norm_eps=1e-12,
            position_type="learned", activation="gelu",
            norm_type="layernorm", causal=False, norm_style="post",
            embed_norm=True, final_norm=False, type_vocab_size=0,
            tie_embeddings=True)
    elif mt == "gpt_neo":
        # reference: module_inject/containers/gptneo.py — GPT-2-shaped block
        # with alternating global/local attention (attention_layers pattern;
        # local layers see a window_size band)
        H = get("hidden_size")
        att_layers = get("attention_layers")
        if not att_layers:
            # raw config.json dicts carry the documented attention_types
            # form [[[kinds...], repeat], ...]; HF derives attention_layers
            att_layers = [a for kinds, rep in (get("attention_types") or [])
                          for _ in range(rep) for a in kinds]
        window = int(get("window_size", 256))
        wins = tuple(window if a == "local" else 0
                     for a in att_layers) or None
        if wins is not None and all(w == 0 for w in wins):
            wins = None
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=H,
            num_layers=get("num_layers"),
            num_heads=get("num_heads"),
            intermediate_size=get("intermediate_size") or 4 * H,
            max_seq_len=get("max_position_embeddings", 2048),
            norm_eps=get("layer_norm_epsilon", 1e-5),
            position_type="learned", activation="gelu",
            norm_type="layernorm", qkv_bias=False, attn_out_bias=True,
            attn_windows=wins,
            attn_scale=1.0,   # GPT-Neo trains UNSCALED (HF softmax_scale=1)
            tie_embeddings=bool(get("tie_word_embeddings", True)))
    elif mt in ("clip", "clip_text_model"):
        # CLIP text tower (reference: module_inject/containers/clip.py).
        # A full CLIPModel config nests it under text_config.
        tc = get("text_config") if mt == "clip" else None
        if tc is not None and not isinstance(tc, dict):
            tc = getattr(tc, "to_dict", lambda: vars(tc))()
        g2 = (lambda k, d=None: tc.get(k, d)) if tc else get
        act = g2("hidden_act", "quick_gelu")
        kw = dict(
            vocab_size=g2("vocab_size"), hidden_size=g2("hidden_size"),
            num_layers=g2("num_hidden_layers"),
            num_heads=g2("num_attention_heads"),
            intermediate_size=g2("intermediate_size"),
            max_seq_len=g2("max_position_embeddings", 77),
            norm_eps=g2("layer_norm_eps", 1e-5),
            position_type="learned",
            activation="quick_gelu" if act == "quick_gelu" else "gelu",
            norm_type="layernorm", causal=True, qkv_bias=True,
            final_norm=True, tie_embeddings=True)
    elif mt == "gptj":
        # reference: module_inject/containers/gptj.py — parallel attn+MLP
        # residual, single shared LN, partial interleaved rotary, head bias
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("n_embd"),
            num_layers=get("n_layer"), num_heads=get("n_head"),
            intermediate_size=get("n_inner") or 4 * get("n_embd"),
            max_seq_len=get("n_positions", 2048),
            norm_eps=get("layer_norm_epsilon", 1e-5),
            position_type="rotary", rotary_dim=get("rotary_dim", 64),
            rotary_interleaved=True, parallel_block=True,
            activation="gelu", norm_type="layernorm", qkv_bias=False,
            tie_embeddings=False, head_bias=True)
    elif mt == "gpt_neox":
        # reference: module_inject/containers/gptneox.py — parallel residual
        # (two LNs), rotary over rotary_pct of the head dim
        if not get("use_parallel_residual", True):
            raise ValueError("gpt_neox use_parallel_residual=False is not "
                             "supported (sequential NeoX variant)")
        hd = get("hidden_size") // get("num_attention_heads")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048),
            norm_eps=get("layer_norm_eps", 1e-5),
            position_type="rotary",
            rotary_dim=_even_rotary(hd, float(get("rotary_pct", 0.25))),
            rope_theta=float(get("rotary_emb_base", 10000.0)),
            parallel_block=True, activation="gelu",
            norm_type="layernorm",
            tie_embeddings=bool(get("tie_word_embeddings", False)))
    elif mt in ("gpt2", ""):
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("n_embd"),
            num_layers=get("n_layer"), num_heads=get("n_head"),
            intermediate_size=get("n_inner") or 4 * get("n_embd"),
            max_seq_len=get("n_positions", 1024),
            norm_eps=get("layer_norm_epsilon", 1e-5),
            position_type="learned", activation="gelu",
            norm_type="layernorm", tie_embeddings=True)
    else:
        raise ValueError(f"unsupported model_type {mt!r}")
    kw.update(overrides)
    sw = get("sliding_window")
    if mt == "mistral" and sw and kw["max_seq_len"] > sw \
            and "attn_windows" not in overrides:
        # every layer slides: the per-layer band mask keeps logits
        # HF-exact beyond the window
        kw["attn_windows"] = (int(sw),) * kw["num_layers"]
        logger.warning(
            f"mistral sliding_window={sw} < max_seq_len="
            f"{kw['max_seq_len']}: per-layer band masks keep logits "
            "HF-exact, but windowed layers take the O(S^2) XLA attention "
            "path (no flash/ring kernel band support yet) — pass "
            "max_seq_len<=sliding_window to stay on the flash path "
            "within the window")
    return TransformerConfig(**kw)


# --------------------------------------------------------------------------
# Megatron-LM TP-rank checkpoint merge
# --------------------------------------------------------------------------

def _flatten_nested(d, prefix=""):
    for k, v in d.items():
        key = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, dict):
            yield from _flatten_nested(v, key)
        else:
            yield key, v


def load_megatron_params(sources, cfg, dtype=None) -> Dict[str, Any]:
    """Merge Megatron-LM tensor-parallel rank checkpoints into one tree.

    Reference: ``deepspeed/runtime/state_dict_factory.py:189``
    (MegatronSDLoader.merge_state_dict — qkv/mlp column merges, attention
    dense / mlp output row merges). `sources`: one state dict (or .pt path /
    nested Megatron checkpoint dict) per TP rank, rank order. Column-parallel
    weights concat on the output dim, row-parallel on the input dim; fused
    qkv is per-head interleaved ([nh/tp, 3, hd, H] per rank). Splitting to a
    HIGHER tp degree needs no tool here: the merged tree re-shards onto any
    mesh via NamedSharding (load_hf_params(shardings=...) semantics).
    """
    nh, hd = cfg.num_heads, cfg.dim_per_head
    if cfg.kv_heads != nh:
        raise ValueError("megatron merge supports MHA only (the fused qkv "
                         f"interleave assumes kv_heads == num_heads; got "
                         f"{cfg.kv_heads} != {nh})")
    rank_sds = []
    for src in sources:
        if isinstance(src, dict) and not any(
                hasattr(v, "shape") for v in src.values()):
            # nested megatron layout ({'model': {'language_model': ...}});
            # drop non-tensor metadata (iteration, args, rng_state, ...)
            sd = {k: _to_numpy(v) for k, v in _flatten_nested(src)
                  if hasattr(v, "shape")}
        elif isinstance(src, dict):
            sd = {k: _to_numpy(v) for k, v in src.items()
                  if hasattr(v, "shape")}
        else:
            sd = {}
            for k, v in _iter_state_dict(src):
                sd[k] = v
        # strip wrapper prefixes down to language_model.*
        out = {}
        for k, v in sd.items():
            for pre in ("model.language_model.", "module.language_model.",
                        "language_model."):
                if k.startswith(pre):
                    k = k[len(pre):]
                    break
            out[k] = v
        rank_sds.append(out)

    tp = len(rank_sds)
    if nh % tp:
        raise ValueError(f"num_heads {nh} not divisible by tp degree {tp}")

    def gather(key):
        vals = [sd[key] for sd in rank_sds if key in sd]
        if len(vals) not in (0, tp):
            raise ValueError(f"megatron merge: key {key!r} present in "
                             f"{len(vals)}/{tp} ranks")
        return vals

    def merge_qkv(vals):
        """Per-rank fused qkv [3H/tp, H] (heads interleaved) -> wq/wk/wv."""
        qs, ks, vs = [], [], []
        for w in vals:
            per = nh // tp
            if w.ndim == 2:
                w4 = w.reshape(per, 3, hd, w.shape[-1])
                qs.append(w4[:, 0].reshape(per * hd, -1))
                ks.append(w4[:, 1].reshape(per * hd, -1))
                vs.append(w4[:, 2].reshape(per * hd, -1))
            else:  # bias [3H/tp]
                b3 = w.reshape(per, 3, hd)
                qs.append(b3[:, 0].reshape(-1))
                ks.append(b3[:, 1].reshape(-1))
                vs.append(b3[:, 2].reshape(-1))
        cat = [np.concatenate(x, axis=0) for x in (qs, ks, vs)]
        if cat[0].ndim == 2:
            return [_t(c) for c in cat]
        return cat

    L = cfg.num_layers
    layers: Dict[str, list] = {}
    params: Dict[str, Any] = {}

    def put_layer(name, i, arr):
        layers.setdefault(name, [None] * L)[i] = arr

    lyr = re.compile(r"^(?:encoder|transformer)\.layers\.(\d+)\.(.+)$")
    for key in sorted(set().union(*[sd.keys() for sd in rank_sds])):
        vals = gather(key)
        if not vals:
            continue
        if key in ("embedding.word_embeddings.weight",):
            params["tok_embed"] = np.concatenate(vals, axis=0)[:cfg.vocab_size]
            continue
        if key == "embedding.position_embeddings.weight":
            params["pos_embed"] = vals[0]
            continue
        m = lyr.match(key)
        if m is None:
            if key.endswith("final_layernorm.weight"):
                params["final_norm_scale"] = vals[0]
            elif key.endswith("final_layernorm.bias"):
                params["final_norm_bias"] = vals[0]
            elif "output_layer" in key or "lm_head" in key:
                # vocab dim may be Megatron-padded (divisible-by rounding)
                params["lm_head"] = _t(
                    np.concatenate(vals, axis=0)[:cfg.vocab_size])
            elif "_extra_state" in key or "rotary" in key:
                continue
            else:
                logger.warning(f"megatron merge: unmapped key {key!r}")
            continue
        i, rest = int(m.group(1)), m.group(2)
        if rest == "input_layernorm.weight":
            put_layer("ln1_scale", i, vals[0])
        elif rest == "input_layernorm.bias":
            put_layer("ln1_bias", i, vals[0])
        elif rest == "post_attention_layernorm.weight":
            put_layer("ln2_scale", i, vals[0])
        elif rest == "post_attention_layernorm.bias":
            put_layer("ln2_bias", i, vals[0])
        elif rest in ("attention.query_key_value.weight",
                      "self_attention.query_key_value.weight"):
            q, k, v = merge_qkv(vals)
            put_layer("wq", i, q), put_layer("wk", i, k), put_layer("wv", i, v)
        elif rest in ("attention.query_key_value.bias",
                      "self_attention.query_key_value.bias"):
            q, k, v = merge_qkv(vals)
            put_layer("bq", i, q), put_layer("bk", i, k), put_layer("bv", i, v)
        elif rest in ("attention.dense.weight", "self_attention.dense.weight"):
            put_layer("wo", i, _t(np.concatenate(vals, axis=1)))  # row-par
        elif rest in ("attention.dense.bias", "self_attention.dense.bias"):
            put_layer("bo", i, vals[0])
        elif rest == "mlp.dense_h_to_4h.weight":
            put_layer("w_in", i, _t(np.concatenate(vals, axis=0)))  # col-par
        elif rest == "mlp.dense_h_to_4h.bias":
            put_layer("b_in", i, np.concatenate(vals, axis=0))
        elif rest == "mlp.dense_4h_to_h.weight":
            put_layer("w_out", i, _t(np.concatenate(vals, axis=1)))
        elif rest == "mlp.dense_4h_to_h.bias":
            put_layer("b_out", i, vals[0])
        elif "_extra_state" in rest or "rotary" in rest:
            continue
        else:
            logger.warning(f"megatron merge: unmapped layer key {key!r}")

    want = np.dtype("float32") if dtype is None else np.dtype(dtype)
    for name, stack in layers.items():
        missing = [i for i, a in enumerate(stack) if a is None]
        if missing:
            raise ValueError(f"megatron merge: layer param {name!r} missing "
                             f"for layers {missing}")
        params.setdefault("layers", {})[name] = np.stack(stack).astype(want)
    params = {k: (v.astype(want) if hasattr(v, "astype") else v)
              for k, v in params.items()}
    if cfg.tie_embeddings:
        params.pop("lm_head", None)
    return params
