"""Decoder-only transformer family (GPT-2, Llama, ...) — TPU-first.

This is the in-tree model zoo equivalent of the reference's model
implementations (``deepspeed/model_implementations/transformers/ds_transformer
.py:18`` and the test fixtures ``tests/unit/simple_model.py``), re-designed for
XLA:

- layers are *stacked* (leading `layers` dim) and executed with `lax.scan`,
  so compile time is O(1) in depth and pipeline stages can slice the stack;
- every parameter carries logical axis names consumed by
  parallel/partitioning.py (TP = megatron col/row splits fall out of the
  ("embed","heads"/"mlp") annotations; ZeRO-3 shards "embed");
- attention dispatches to the Pallas flash kernel when available, with a
  pure-XLA fallback (same math, fp32 softmax);
- GQA (n_kv_heads < n_heads), rotary or learned positions, gelu MLP or
  silu-GLU, layernorm or rmsnorm — covering GPT-2 and Llama with one code
  path.
"""

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

# the Pallas kernels are imported at module scope on purpose: a Pallas API
# change must fail `import deepspeed_tpu`, not silently route every model
# through the O(S^2) XLA attention
from deepspeed_tpu.ops.decode_attention import (paged_decode_attention,
                                                paged_decode_int8)
from deepspeed_tpu.ops.flash_attention import (flash_attention,
                                                flash_attention_packed)
from deepspeed_tpu.moe import sharded_moe as _moe
from deepspeed_tpu.models import looped as _looped

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class RopeTable:
    """One rotary table: the frequencies ``theta^(-2i/d)`` and, where
    ``factor`` > 1, YaRN's stretch of them as ``transformers`` computes it
    (``_compute_yarn_parameters``): pair i turns ``factor`` times slower from
    pair ``hi`` on, keeps its frequency up to pair ``lo``, a linear ramp
    between — ``lo`` / ``hi`` the pairs that make ``beta_fast`` /
    ``beta_slow`` turns over ``original_max_position`` positions, rounded
    outward — and cos and sin are BOTH multiplied by ``attention_factor``
    (so q.k carries its square)."""
    theta: float = 10000.0
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def band(self, dim: int):
        """(lo, hi): the first and last pair of the ramp, of ``dim // 2``."""
        def turns(r):
            return (dim * math.log(self.original_max_position
                                   / (r * 2 * math.pi))
                    / (2 * math.log(self.theta)))
        return (max(math.floor(turns(self.beta_fast)), 0),
                min(math.ceil(turns(self.beta_slow)), dim - 1))

    def frequencies(self, dim: int):
        """float32 [dim // 2]."""
        half = dim // 2
        freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                        * (math.log(self.theta) / half))
        if self.factor == 1.0:
            return freqs
        lo, hi = self.band(dim)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo)
                        / max(hi - lo, 1e-3), 0.0, 1.0)
        return freqs / self.factor * ramp + freqs * (1.0 - ramp)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None          # GQA; None -> num_heads
    head_dim: Optional[int] = None              # None -> hidden // heads
    intermediate_size: Optional[int] = None     # None -> 4*hidden (gelu) / 8/3 (glu)
    max_seq_len: int = 1024
    position_type: str = "learned"              # learned | rotary | alibi | none
    activation: str = "gelu"                    # gelu | silu_glu | gelu_glu | relu | relu2
    norm_type: str = "layernorm"                # layernorm | rmsnorm
    norm_eps: float = 1e-5
    # layernorm right after the token embedding (BLOOM's
    # word_embeddings_layernorm)
    embed_norm: bool = False
    # encoder family (BERT/RoBERTa; reference:
    # module_inject/containers/bert.py): bidirectional attention,
    # post-layernorm blocks, segment (token-type) embeddings
    causal: bool = True
    norm_style: str = "pre"             # pre | post (BERT is post-LN)
    type_vocab_size: int = 0            # >0 -> tok_type_embed param
    # GPT-J / GPT-NeoX block shape (reference: containers/{gptj,gptneox}.py):
    # x + attn(ln1(x)) + mlp(ln2(x)) in ONE residual (GPT-J shares one LN —
    # its import writes ln_1 into both slots), rotary over only the first
    # rotary_dim dims, GPT-J's interleaved (rotate-every-two) pairing
    parallel_block: bool = False
    rotary_dim: Optional[int] = None
    rotary_interleaved: bool = False
    head_bias: bool = False             # GPT-J lm_head carries a bias
    qkv_bias: bool = True               # layernorm models: attn proj biases
    # attention out-projection bias when qkv biases are absent (GPT-Neo:
    # bias-free q/k/v but out_proj.bias exists). None -> follows qkv_bias.
    attn_out_bias: Optional[bool] = None
    final_norm: bool = True             # BERT has no final LN (post-LN covers)
    rope_theta: float = 10000.0
    # ARCHITECTURE (OLMoE): RMSNorm over the WHOLE q projection and the whole
    # k projection (`q_norm` [nh*hd], `k_norm` [nkv*hd]), before the split
    # into heads and before RoPE
    qk_norm: bool = False
    # ARCHITECTURE (qwen3_next, the attention blocks of a hybrid stack):
    # RMSNorm of q and of k over each HEAD's dims (`q_norm`, `k_norm` [hd])
    # before rotary, and a sigmoid gate on the attention output whose
    # columns are projected beside q (`wq` [H, heads x 2 x hd], a head's
    # columns [q | gate]): out = wo (attn . sigmoid(gate))
    qk_norm_per_head: bool = False
    attn_out_gate: bool = False
    tie_embeddings: bool = True
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16                   # activation/compute dtype
    param_dtype: Any = jnp.float32              # storage dtype (engine may cast)
    attention_impl: str = "auto"                # auto | pallas | xla
    # block-sparse attention (reference: ops/sparse_attention; configs from
    # sparsity_config.py). e.g. {"mode": "bigbird", "block": 128,
    # "num_random_blocks": 1, ...}; None -> dense/flash attention.
    sparse_attention: Optional[Dict[str, Any]] = None
    # int8 weight-only quantized inference (reference: the int8 weight path
    # of csrc/transformer/inference + model_implementations quantization):
    # layer-stack weights live in HBM as {"q": int8, "scale": f32} and the
    # scan body dequantizes ONE layer's slice — peak bf16 weight residency is
    # a single layer. Convert with models.quantize_layer_stack.
    quantized_weights: bool = False
    # weight-ONLY int8 decode matmuls (ISSUE 17, InferenceConfig.weight_bits):
    # with quantized_weights the {"q","scale"} stacks stay int8 THROUGH the
    # matmul — the convert fuses into the weight read and the per-out-channel
    # scale multiplies the result rows (ops/quantizer.weight_matmul), so no
    # dequantized layer copy ever materializes (vs quantize_bits' dequant-
    # before-matmul). 0 = off, 8 = int8. MoE expert stacks fall back to
    # dequant-on-use (the gathered dispatch einsum has no epilogue seam).
    weight_only_bits: int = 0
    # int8 KV cache for decode (additive over the reference's fp16 decode
    # workspace, inference_context.h): ring buffers live in HBM as int8
    # with per-(batch, head, position) f32 scales. The scale factors out of
    # the d-contraction, so attention reads HALF the cache bytes — at long
    # context the KV read is the decode bound. 0 = off, 8 = int8.
    kv_cache_bits: int = 0
    # per-layer local-attention windows (reference families: GPT-Neo's
    # alternating global/local pattern, module_inject/containers/gptneo.py;
    # Mistral's sliding_window). Length num_layers; 0 = global. The band
    # mask key j is visible to query i iff i - j < window.
    attn_windows: Optional[Tuple[int, ...]] = None
    # softmax scale override; None -> 1/sqrt(head_dim). GPT-Neo trains with
    # NO scaling (HF softmax_scale=1.0).
    attn_scale: Optional[float] = None
    # MoE (reference: deepspeed/moe/*; config keys from MoEConfig)
    num_experts: int = 1
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_policy: str = None               # None | Jitter | RSample
    drop_tokens: bool = True
    # ARCHITECTURE: divide the k kept router weights by their sum (Mixtral)
    # or take the softmax's values as they are, summing to < 1 (OLMoE,
    # `norm_topk_prob: false`)
    norm_topk_prob: bool = True
    use_residual: bool = False                  # PR-MoE
    moe_aux_loss_weight: float = 0.01
    # ARCHITECTURE (nemotron_h): how the router scores. "softmax" over all
    # experts (Mixtral, OLMoE), or "sigmoid" per expert: the CHOICE is the
    # top-k of score + a stored correction bias (`e_bias` [E]), the WEIGHTS
    # are the scores themselves, and after `norm_topk_prob` they are
    # multiplied by `routed_scaling_factor`. `moe_shared_size` > 0: an expert
    # of that width, of the experts' own form, that every token passes.
    moe_scoring: str = "softmax"                # softmax | sigmoid
    routed_scaling_factor: float = 1.0
    moe_shared_size: int = 0
    # `moe_shared_gate` (qwen3_next): the shared expert's output is scaled
    # per token by sigmoid(w_s . x) (`shared_gate` [H]).
    moe_shared_gate: bool = False
    # THE CHIP'S SHARE of a deployment's experts (models/hybrid.py stacks
    # only): the router scores `moe_router_experts` experts (None: all of
    # `num_experts`, every other configuration), the stacks hold
    # `num_experts` of them, the experts `moe_held_first` .. + num_experts
    # - 1. The expert layer routes over all, weighs over all the chosen and
    # computes the held experts' part of the result; what the absent ones
    # would add is added on no chip here (moe/sharded_moe.py moe_ffn).
    moe_router_experts: Optional[int] = None
    moe_held_first: int = 0
    # ARCHITECTURE (nemotron_h): a HYBRID stack. One letter a block — "M" a
    # Mamba-2 mixer, "E" an expert feed-forward, "*" attention — and every
    # block is h + mixer(norm(h)): ONE mixer, not attention + FFN. None is
    # the homogeneous stack of every other family. models/hybrid.py walks
    # the pattern; models/mamba.py is the M mixer (heads x head dim inner
    # width, `mamba_n_groups` B/C groups of `ssm_state_size`, a causal
    # depthwise convolution of `conv_kernel`, a chunked scan of
    # `mamba_chunk`); time_step_* shape the initialisation of dt_bias only.
    # "G" (qwen3_next) is a Gated DeltaNet mixer, models/gated_deltanet.py:
    # `gdn_num_k_heads` key heads of `gdn_head_k_dim` serving
    # `gdn_num_v_heads` value heads of `gdn_head_v_dim`, a float32 matrix
    # state [dk, dv] per value head, the same `conv_kernel`, chunks of
    # `gdn_chunk` in prefill. A hybrid stack's attention may be rotary
    # (`position_type`, `rotary_dim`).
    # "W" (afmoe) is attention over the last `attn_windows` positions,
    # always rotary, its K/V a ring per serving slot; "*" beside it follows
    # `position_type` (afmoe: "none"), so the rotary rule is per KIND. "D"
    # is a dense feed-forward of width `dense_ffn_size` (0: the experts'
    # `intermediate_size`). `embed_scale` multiplies the token embeddings
    # (afmoe's muP factor sqrt(hidden_size); 1: every other family).
    # `rope_tables` (mellum) states a rotary table PER KIND of attention
    # block, (("attn", RopeTable), ("wattn", RopeTable)): plain on the window
    # blocks, YaRN on the blocks that see the whole history. None: the rule
    # above with the one `rope_theta` (models/hybrid.py rope_table).
    # "L" (glm4_moe_lite; DeepSeek-V2/V3's multi-head LATENT attention,
    # models/latent_attention.py): q through a low-rank pair with an RMSNorm
    # between (`q_lora_rank`), K and V both expanded from ONE normed row of
    # `kv_lora_rank` a token, beside it one rotary key of `qk_rope_head_dim`
    # shared by all heads; a head is `qk_nope_head_dim` + `qk_rope_head_dim`
    # wide in q.k (the softmax scale counts both) and `v_head_dim` in the
    # output. The CACHE is that row, [c | rope(k_r)], one plane a block that
    # is both K and V (`latent_planes`, `latent_row_width`), in the block
    # pool like K/V: it belongs to no slot. 0: no such block.
    # "P" (falcon_h1) is a block with TWO mixers on ONE norm, side by side:
    # h + ssm_out_multiplier Mamba2(n) + attention_out_multiplier Attn(n
    # attention_in_multiplier), n = RMSNorm(h); it owns a K/V plane of the
    # block pool AND a layer of the per-slot state pool. The family's muP
    # multipliers are STATIC scalars of the forward, never folded into the
    # stored tensors (a checkpoint loads as published): `embed_scale`,
    # `lm_head_multiplier` on the logits, `key_multiplier` on k,
    # `ssm_in_multiplier` on the Mamba mixer's input and `ssm_multipliers`
    # (five: z | x | B | C | dt) on its input projection's columns,
    # `mlp_multipliers` (two: on the gate before its activation, on the "D"
    # block's output). 1 / None: every other family, whose programs carry
    # no multiply for them.
    # A MANY-ROW RESIDUAL STREAM (xing4_0; manifold-constrained
    # hyper-connections, arXiv:2512.24880 over arXiv:2409.19606; hybrid
    # stacks only): `hc_mult` n > 1 rows of `hidden_size` a token. Every
    # block READS the stream through a sigmoid row H_pre [n] (its input is
    # H_pre X), WRITES its output back through 2 sigmoid row H_post [n] and
    # carries the stream through H_res [n, n], `hc_sinkhorn_iters` rounds of
    # Sinkhorn-Knopp (rows, then columns; `hc_eps` in the denominators) on
    # logits clipped to `hc_res_clamp`: doubly stochastic. The three come
    # from ONE projection of the RMS-normed whole stream (a block's `hc_phi`
    # [n H, 2n + n^2], `hc_b`, `hc_a`), in float32 whatever the stream's
    # dtype; the stream is opened by repeating the embedding and closed by
    # one more sigmoid read (`hc_out_*`) before the final norm
    # (models/hybrid.py `_hc_read`, `_hc_write`). 1: every other family,
    # whose programs hold nothing of this. `hc_init_std` is an INITIALISER
    # (`init_params` only): 0 draws the mappings at the near-identity start
    # (phi 0, H_post 1, H_res ~ I, H_pre summing to 1), s > 0 draws them
    # AWAY from it (phi normal of std s / sqrt(n H), so the projection is of
    # order s; b normal of std s; a in U(0.5, 1.5)).
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    hc_init_std: float = 0.0
    block_pattern: Optional[str] = None
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Optional[Tuple[float, ...]] = None
    mlp_multipliers: Optional[Tuple[float, float]] = None
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_tables: Optional[Tuple[Tuple[str, RopeTable], ...]] = None
    dense_ffn_size: int = 0
    embed_scale: float = 1.0
    gdn_num_k_heads: int = 0
    gdn_num_v_heads: int = 0
    gdn_head_k_dim: int = 0
    gdn_head_v_dim: int = 0
    gdn_chunk: int = 64
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    ssm_state_size: int = 0
    conv_kernel: int = 4
    mamba_chunk: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # ARCHITECTURE (ouro): a LOOPED stack. The `num_layers` layers run
    # `ut_steps` times over the SAME weights, the final norm is applied at
    # the end of every pass and what it gives is what the next pass starts
    # from (the last pass's goes to the head). A pass keeps K/V of its own:
    # the cache has `kv_planes` = ut_steps x layers planes, pass t's layer i
    # at plane t * num_layers + i. `sandwich_norm`: an RMSNorm AFTER each
    # sublayer too, before its output joins the residual (`ln1_post_scale`,
    # `ln2_post_scale`). `exit_gate`: a linear gate [H, 1] + bias on each
    # pass's output, whose sigmoid gives the exit distribution over passes
    # (models/looped.py); every pass always runs, and a serving engine
    # reads the distribution as a counter. 1 / False: every other family.
    ut_steps: int = 1
    sandwich_norm: bool = False
    exit_gate: bool = False
    # INITIALISER of the norm scales (`init_params` only; a checkpoint
    # brings its own). 0 / 1: every scale starts at 1, as a trained-from-
    # scratch model's does. `norm_init_jitter` j draws the block's norm
    # scales and the final norm's in U(1 - j, 1 + j) times their start;
    # `post_norm_init` is the start of the scales AFTER a sublayer
    # (`sandwich_norm`), the branch scale of a deep residual stack.
    norm_init_jitter: float = 0.0
    post_norm_init: float = 1.0
    # INITIALISER of the token embeddings (`init_params` only): drawn at
    # 0.02 x this. At 1 the first layers' stream of a seeded model is mostly
    # what near-uniform attention adds — nearly ONE vector for a thousand
    # neighbouring positions, 1-3 x the embedding — and a random router
    # behind it loads its experts unevenly (load cv 0.7 at 64 experts top-8,
    # against 0.06 over the embeddings alone; PERF.md section 6, PR 48); a
    # trained model's early stream is its tokens' and its router balanced.
    embed_init_scale: float = 1.0
    remat: bool = False
    # none | full | dots_saveable | save_nothing | dots_with_no_batch_dims |
    # offload_dots: what XLA may keep of a block between its forward and its
    # backward; any other name raises. Under EVERY one the flash kernels' O
    # and log-sum-exp are kept besides (`_remat_policy` has the bytes):
    # save_nothing keeps a block's input and those two. A caller who wants
    # their bytes back has attention_impl="xla" and nothing else.
    remat_policy: str = "none"
    scan_layers: bool = True
    # fused attention backward block (ops/flash_attention fused_backward):
    # the delta epilogue runs inside the backward grids — no separate XLA
    # delta pass between the forward and the dQ/dKV kernels. Set via the
    # engine's `transformer.fused_backward` config section.
    fused_backward: bool = False
    # Random-LTD (reference: runtime/data_pipeline/data_routing/basic_layer.py
    # RandomLayerTokenDrop): middle layers process a random kept-token subset
    # during training. random_ltd_keep is a SHAPE (static); the engine's
    # RandomLTDScheduler rebuilds the model per schedule bucket. First and
    # last layers always run dense, matching the reference's reserved layers.
    random_ltd: bool = False
    random_ltd_keep: int = 0
    # QAT activation quantization (reference: compression/basic_layer.py
    # QuantAct): fake-quant (STE) the post-norm activations feeding the
    # attention and MLP matmuls. Set by the engine's compression wiring
    # when activation_quantization's schedule_offset is reached. 0 = off.
    activation_quant_bits: int = 0
    # chunked cross-entropy: compute head matmul + CE per sequence chunk so
    # the fp32 [B,S,V] logits never materialize (12*B*S*V bytes -> 12*B*c*V).
    # The chunk body is rematerialized in backward. 0 = off.
    loss_chunk: int = 0
    # Progressive Layer Drop (reference: runtime/progressive_layer_drop.py +
    # the PLD paper): during training, layer i survives with probability
    # 1 - (i+1)/L * (1 - theta), theta following the engine's exp-decay
    # schedule (passed per step as batch["_pld_theta"]). Dropped layers are
    # identity — a real lax.cond, so the FLOPs are actually saved.
    progressive_layer_drop: bool = False
    # ZeRO-Infinity param offload: stacked layer weights live in pinned host
    # DRAM; each scan step transfers ONE layer into HBM (and the remat replay
    # re-fetches it during backward), so peak HBM holds ~1 layer of params.
    # Reference: runtime/swap_tensor/partitioned_param_swapper.py:35 (the
    # fetch-on-use coordinator); here the transfer is a compiled memory-space
    # move XLA overlaps with compute.
    offload_params: bool = False

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def recurrent_blocks(self) -> int:
        """Blocks that keep a recurrent state per serving slot (the "M", "G"
        and "P" blocks of a hybrid stack); 0 for every homogeneous model."""
        pattern = self.block_pattern or ""
        return pattern.count("M") + pattern.count("G") + pattern.count("P")

    @property
    def window_blocks(self) -> int:
        """Blocks whose K/V is a ring of the last ``attn_windows`` positions
        per serving slot (the "W" blocks of a hybrid stack)."""
        return (self.block_pattern or "").count("W")

    @property
    def slot_state_blocks(self) -> int:
        """Blocks that keep something PER SERVING SLOT beside the K/V block
        pool: a recurrent state or a window ring (the leaves themselves are
        ``ModelSpec.slot_leaves``, which is what the serving engine asks)."""
        return self.recurrent_blocks + self.window_blocks

    @property
    def latent_planes(self) -> int:
        """Planes of LATENT rows a token keeps in the block pool: one per "L"
        block of a hybrid stack, each both K and V (0: none)."""
        return (self.block_pattern or "").count("L")

    @property
    def latent_row_width(self) -> int:
        """Values of one latent row: the normed latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def moe_router_width(self) -> int:
        """Experts the router scores: the model's, of which this chip holds
        ``num_experts``."""
        return self.moe_router_experts or self.num_experts

    @property
    def attention_blocks(self) -> int:
        """Blocks of softmax attention: every layer of a homogeneous stack,
        the "*", "P", "W" and "L" blocks of a hybrid one (a "P" block counts
        here AND among the ``recurrent_blocks``: it holds both mixers)."""
        if self.block_pattern is None:
            return self.num_layers
        return (self.block_pattern.count("*") + self.block_pattern.count("P")
                + self.window_blocks + self.latent_planes)

    @property
    def kv_planes(self) -> int:
        """Planes of K/V a token keeps IN THE BLOCK POOL: one per pass and
        block that owns K/V there (a "W" block keeps a ring per slot
        instead: ``window_blocks``; an "L" block a latent row:
        ``latent_planes``). THE number every cache, pool, byte
        count and handoff geometry is sized by."""
        return self.ut_steps * (self.attention_blocks - self.window_blocks
                                - self.latent_planes)

    @property
    def dim_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def ffn_dim(self) -> int:
        if self.intermediate_size:
            return self.intermediate_size
        if "glu" in self.activation:
            # llama convention: 2/3 * 4h rounded to 256
            d = int(8 * self.hidden_size / 3)
            return 256 * ((d + 255) // 256)
        return 4 * self.hidden_size


# Presets (model zoo)
def gpt2_config(size: str = "125m", **overrides) -> TransformerConfig:
    dims = {
        "125m": dict(hidden_size=768, num_layers=12, num_heads=12),
        "350m": dict(hidden_size=1024, num_layers=24, num_heads=16),
        "760m": dict(hidden_size=1536, num_layers=24, num_heads=16),
        "1.3b": dict(hidden_size=2048, num_layers=24, num_heads=32),
    }[size]
    base = dict(vocab_size=50257, max_seq_len=1024, position_type="learned",
                activation="gelu", norm_type="layernorm", tie_embeddings=True)
    base.update(dims)
    base.update(overrides)
    return TransformerConfig(**base)


def llama_config(size: str = "7b", **overrides) -> TransformerConfig:
    dims = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=4, num_kv_heads=2,
                     intermediate_size=768, vocab_size=32000, max_seq_len=2048),
        "350m": dict(hidden_size=1024, num_layers=24, num_heads=16,
                     num_kv_heads=8, intermediate_size=2816, vocab_size=32000,
                     max_seq_len=4096),
        "1b": dict(hidden_size=2048, num_layers=16, num_heads=32, num_kv_heads=8,
                   intermediate_size=5632, vocab_size=32000, max_seq_len=4096),
        "3b": dict(hidden_size=3072, num_layers=28, num_heads=24, num_kv_heads=8,
                   intermediate_size=8192, vocab_size=32000, max_seq_len=4096),
        "7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                   intermediate_size=11008, vocab_size=32000, max_seq_len=4096),
        "13b": dict(hidden_size=5120, num_layers=40, num_heads=40,
                    intermediate_size=13824, vocab_size=32000, max_seq_len=4096),
        "70b": dict(hidden_size=8192, num_layers=80, num_heads=64, num_kv_heads=8,
                    intermediate_size=28672, vocab_size=32000, max_seq_len=4096),
    }[size]
    base = dict(position_type="rotary", activation="silu_glu", norm_type="rmsnorm",
                norm_eps=1e-5, tie_embeddings=False)
    base.update(dims)
    base.update(overrides)
    return TransformerConfig(**base)


def mixtral_config(size: str = "8x7b", **overrides) -> TransformerConfig:
    """Mixtral-style MoE (top-2, 8 experts) — the BASELINE.json MoE config."""
    dims = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=4, num_kv_heads=2,
                     intermediate_size=512, vocab_size=32000, max_seq_len=2048,
                     num_experts=4),
        "8x7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                     num_kv_heads=8, intermediate_size=14336, vocab_size=32000,
                     max_seq_len=4096, num_experts=8),
    }[size]
    base = dict(position_type="rotary", activation="silu_glu",
                norm_type="rmsnorm", tie_embeddings=False, top_k=2)
    base.update(dims)
    base.update(overrides)
    return TransformerConfig(**base)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(key, cfg: TransformerConfig) -> Params:
    if cfg.block_pattern:
        from deepspeed_tpu.models import hybrid
        return hybrid.init_params(key, cfg)
    H, L = cfg.hidden_size, cfg.num_layers
    nh, nkv, hd, F = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head, cfg.ffn_dim
    k = iter(jax.random.split(key, 16))
    dt = cfg.param_dtype
    std = 0.02

    def normal(key, shape, scale=std):
        return (jax.random.normal(key, shape) * scale).astype(dt)

    def norm_scale(key, shape, start=1.0):
        j = cfg.norm_init_jitter
        if not j:
            return jnp.full(shape, start, dt)
        return jax.random.uniform(key, shape, jnp.float32, start * (1 - j),
                                  start * (1 + j)).astype(dt)

    # per-layer params, stacked on a leading L dim
    lkeys = jax.random.split(next(k), 12)

    def stacked(key, shape, scale=std):
        return (jax.random.normal(key, (L,) + shape) * scale).astype(dt)

    out_scale = std / math.sqrt(2 * L)  # gpt-2 residual init scaling
    skeys = jax.random.split(lkeys[11], 4)
    layers = {
        "ln1_scale": norm_scale(skeys[0], (L, H)),
        "ln2_scale": norm_scale(skeys[2], (L, H)),
        "wq": stacked(lkeys[0], (H, nh * hd)),
        "wk": stacked(lkeys[1], (H, nkv * hd)),
        "wv": stacked(lkeys[2], (H, nkv * hd)),
        "wo": stacked(lkeys[3], (nh * hd, H), scale=out_scale),
        "w_in": stacked(lkeys[4], (H, F)),
        "w_out": stacked(lkeys[5], (F, H), scale=out_scale),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, nh * hd), dt)
        layers["k_norm"] = jnp.ones((L, nkv * hd), dt)
    if cfg.sandwich_norm:
        layers["ln1_post_scale"] = norm_scale(skeys[1], (L, H), cfg.post_norm_init)
        layers["ln2_post_scale"] = norm_scale(skeys[3], (L, H), cfg.post_norm_init)
    if cfg.num_experts > 1:
        E = cfg.num_experts
        layers["wg"] = stacked(lkeys[7], (H, E))
        layers["moe_w_in"] = (jax.random.normal(lkeys[8], (L, E, H, F)) * std).astype(dt)
        layers["moe_w_out"] = (jax.random.normal(lkeys[9], (L, E, F, H)) * out_scale).astype(dt)
        if "glu" in cfg.activation:
            layers["moe_w_gate"] = (jax.random.normal(lkeys[10], (L, E, H, F)) * std).astype(dt)
        if not cfg.use_residual:
            # experts REPLACE the dense MLP; PR-MoE keeps both
            del layers["w_in"], layers["w_out"]
        else:
            layers["moe_coef"] = jnp.zeros((L, H, 2), dt)
    if "glu" in cfg.activation and "w_in" in layers:
        layers["w_gate"] = stacked(lkeys[6], (H, F))
    if cfg.norm_type == "layernorm":
        layers["ln1_bias"] = jnp.zeros((L, H), dt)
        layers["ln2_bias"] = jnp.zeros((L, H), dt)
        if cfg.qkv_bias:
            layers["bq"] = jnp.zeros((L, nh * hd), dt)
            layers["bk"] = jnp.zeros((L, nkv * hd), dt)
            layers["bv"] = jnp.zeros((L, nkv * hd), dt)
        if cfg.qkv_bias or cfg.attn_out_bias:
            layers["bo"] = jnp.zeros((L, H), dt)
        if "w_in" in layers:
            layers["b_in"] = jnp.zeros((L, F), dt)
            layers["b_out"] = jnp.zeros((L, H), dt)

    params: Params = {
        "tok_embed": normal(next(k), (cfg.vocab_size, H),
                            std * cfg.embed_init_scale),
        "layers": layers,
    }
    gk = (jax.random.split(next(k), 3)
          if cfg.exit_gate or cfg.norm_init_jitter else (None,))
    if cfg.final_norm:
        params["final_norm_scale"] = norm_scale(gk[0], (H,))
    if cfg.exit_gate:
        # the gate starts scale-free: a unit-RMS stream times std
        # 2 / sqrt(H) gives logits of std ~2 at any width, so the exit
        # distribution starts spread over the passes
        params["exit_gate_w"] = normal(gk[1], (H, 1), scale=2 / math.sqrt(H))
        params["exit_gate_b"] = normal(gk[2], (1,), scale=1.0)
    if cfg.position_type == "learned":
        params["pos_embed"] = normal(next(k), (cfg.max_seq_len, H), scale=0.01)
    if cfg.type_vocab_size:
        params["tok_type_embed"] = normal(next(k), (cfg.type_vocab_size, H),
                                          scale=0.01)
    if cfg.head_bias and not cfg.tie_embeddings:
        params["lm_head_bias"] = jnp.zeros((cfg.vocab_size,), dt)
    if cfg.embed_norm:
        params["embed_norm_scale"] = jnp.ones((H,), dt)
        if cfg.norm_type == "layernorm":
            params["embed_norm_bias"] = jnp.zeros((H,), dt)
    if cfg.norm_type == "layernorm" and cfg.final_norm:
        params["final_norm_bias"] = jnp.zeros((H,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(next(k), (H, cfg.vocab_size))
    return params


def logical_axes(cfg: TransformerConfig) -> Params:
    """Pytree of logical-axis tuples, same structure as init_params output."""
    if cfg.block_pattern:
        from deepspeed_tpu.models import hybrid
        return hybrid.logical_axes(cfg)
    layers = {
        "ln1_scale": ("layers", "unmodeled"),
        "ln2_scale": ("layers", "unmodeled"),
        "wq": ("layers", "embed", "qkv"),
        "wk": ("layers", "embed", "qkv"),
        "wv": ("layers", "embed", "qkv"),
        "wo": ("layers", "heads", "embed"),
        "w_in": ("layers", "embed", "mlp"),
        "w_out": ("layers", "mlp", "embed"),
    }
    if cfg.qk_norm:
        # split like the projection's columns; the norm's mean square is a
        # reduction over the whole (global) projection under GSPMD
        layers["q_norm"] = ("layers", "qkv")
        layers["k_norm"] = ("layers", "qkv")
    if cfg.sandwich_norm:
        layers["ln1_post_scale"] = ("layers", "unmodeled")
        layers["ln2_post_scale"] = ("layers", "unmodeled")
    if cfg.num_experts > 1:
        layers["wg"] = ("layers", "embed", None)
        layers["moe_w_in"] = ("layers", "expert", "embed", "mlp")
        layers["moe_w_out"] = ("layers", "expert", "mlp", "embed")
        if "glu" in cfg.activation:
            layers["moe_w_gate"] = ("layers", "expert", "embed", "mlp")
        if not cfg.use_residual:
            del layers["w_in"], layers["w_out"]
        else:
            layers["moe_coef"] = ("layers", "embed", None)
    if "glu" in cfg.activation and "w_in" in layers:
        layers["w_gate"] = ("layers", "embed", "mlp")
    if cfg.norm_type == "layernorm":
        layers.update({
            "ln1_bias": ("layers", "unmodeled"),
            "ln2_bias": ("layers", "unmodeled"),
        })
        if cfg.qkv_bias:
            layers.update({
                "bq": ("layers", "qkv"), "bk": ("layers", "qkv"),
                "bv": ("layers", "qkv"),
            })
        if cfg.qkv_bias or cfg.attn_out_bias:
            layers["bo"] = ("layers", "unmodeled")
        if "w_in" in layers:
            layers["b_in"] = ("layers", "mlp")
            layers["b_out"] = ("layers", "unmodeled")
    axes: Params = {
        "tok_embed": ("vocab", "embed"),
        "layers": layers,
    }
    if cfg.final_norm:
        axes["final_norm_scale"] = ("unmodeled",)
    if cfg.exit_gate:
        axes["exit_gate_w"] = ("unmodeled", None)
        axes["exit_gate_b"] = (None,)
    if cfg.position_type == "learned":
        axes["pos_embed"] = (None, "embed")
    if cfg.type_vocab_size:
        axes["tok_type_embed"] = (None, "embed")
    if cfg.head_bias and not cfg.tie_embeddings:
        axes["lm_head_bias"] = ("vocab",)
    if cfg.embed_norm:
        axes["embed_norm_scale"] = ("unmodeled",)
        if cfg.norm_type == "layernorm":
            axes["embed_norm_bias"] = ("unmodeled",)
    if cfg.norm_type == "layernorm" and cfg.final_norm:
        axes["final_norm_bias"] = ("unmodeled",)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# --------------------------------------------------------------------------
# building blocks
# --------------------------------------------------------------------------

def _constrain_batch_axes(x):
    """Pin an activation [B, S, ...] to the canonical batch-sharded layout.

    The embedding gather reads a vocab/embed-sharded table, and without a
    constraint GSPMD propagates the *weight's* sharding onto the activation —
    the layer-scan carry then runs layernorm on a hidden-sharded tensor and
    SPMD falls back to full rematerialization resharding it for attention
    ("Involuntary full rematerialization", spmd_partitioner.cc). One
    constraint at the model boundary keeps every downstream activation
    batch-sharded; weights stay fsdp/tensor-sharded and XLA inserts the
    all-gathers on use (the ZeRO-3 contract).

    No-op outside a mesh context, on 1-device meshes, and inside shard_map
    bodies (manual axes see per-shard views the constraint must not touch).
    """
    from deepspeed_tpu.parallel.context import (in_manual_region,
                                                physical_mesh_env)
    env_mesh, shape, _ = physical_mesh_env()
    if env_mesh is None or env_mesh.size == 1 or in_manual_region():
        return x
    from deepspeed_tpu.parallel.mesh import BATCH_AXES
    batch = tuple(a for a in BATCH_AXES if shape.get(a, 1) > 1)
    if not batch:
        return x
    dp = 1
    for a in batch:
        dp *= shape[a]
    if x.shape[0] % dp:  # ad-hoc small batches (inference) stay unsharded
        return x
    seq_ax = "seq" if shape.get("seq", 1) > 1 else None
    if seq_ax and x.shape[1] % shape["seq"]:
        seq_ax = None
    return jax.lax.with_sharding_constraint(x, P(batch, seq_ax))


def _norm(x, scale, bias, cfg: TransformerConfig):
    x32 = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * lax.rsqrt(var + cfg.norm_eps)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * lax.rsqrt(var + cfg.norm_eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _rms_whole(x, scale, eps: float):
    """RMSNorm over the last dim whatever `cfg.norm_type` is (q/k norm)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (BLOOM convention: geometric series from the
    closest power of two, odd-index fill for non-power-of-two head counts)."""
    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]
    if math.log2(n_heads).is_integer():
        return jnp.asarray(pow2(n_heads), jnp.float32)
    cp2 = 2 ** int(math.floor(math.log2(n_heads)))
    extra = pow2(2 * cp2)[0::2][: n_heads - cp2]
    return jnp.asarray(pow2(cp2) + extra, jnp.float32)


def rotary_embed(x, positions, theta: float, rotary_dim: Optional[int] = None,
                 interleaved: bool = False, table: Optional[RopeTable] = None):
    """x: [B, S, N, D]. Default: rotate pairs (d, d + D/2) — llama
    convention. rotary_dim: rotate only the first `rotary_dim` dims (GPT-J/
    GPT-NeoX partial rotary). interleaved: pair (2d, 2d+1) instead — GPT-J's
    rotate-every-two. table: its frequencies and attention factor in place
    of plain ``theta``'s."""
    B, S, N, D = x.shape
    rd = rotary_dim if rotary_dim else D
    if rd % 2:
        raise ValueError(f"rotary_dim must be even, got {rd} (the rotation "
                         "pairs dims)")
    x_rot, x_pass = x[..., :rd], x[..., rd:]
    half = rd // 2
    table = table or RopeTable(theta)
    freqs = table.frequencies(rd)
    angles = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if table.attention_factor != 1.0:
        cos, sin = cos * table.attention_factor, sin * table.attention_factor
    if interleaved:
        x1 = x_rot[..., 0::2].astype(jnp.float32)
        x2 = x_rot[..., 1::2].astype(jnp.float32)
        r1, r2 = x1 * cos - x2 * sin, x2 * cos + x1 * sin
        out = jnp.stack([r1, r2], axis=-1).reshape(B, S, N, rd)
    else:
        x1 = x_rot[..., :half].astype(jnp.float32)
        x2 = x_rot[..., half:].astype(jnp.float32)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    out = out.astype(x.dtype)
    if rd < D:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


def _use_pallas(cfg: TransformerConfig, seq_len: int) -> bool:
    if cfg.attention_impl == "xla":
        return False
    if cfg.dtype == jnp.float16:
        return False  # Mosaic has no f16; fp16 models take the XLA path
    if cfg.position_type == "alibi":
        return False  # additive score bias not in the flash kernel yet
    if jax.default_backend() != "tpu":
        return cfg.attention_impl == "pallas"  # explicit opt-in (interpret mode)
    return seq_len % 128 == 0 and cfg.dim_per_head >= 64


def flash_takes(cfg: TransformerConfig, seq_len: int) -> bool:
    """Whether a causal row of ``seq_len`` without a band is the flash
    kernel's (``_attention_kernel``'s rule; with segment ids it is then the
    packed forward's, and a serving engine counts its tiles)."""
    from deepspeed_tpu.parallel.context import seq_parallel_degree
    return (_use_pallas(cfg, seq_len) and not cfg.sparse_attention
            and seq_parallel_degree() <= 1)


def _flash_shards(q, k):
    """(mesh, its axes, the dp axes the batch splits over, the heads' axis
    or None) for a flash call on q / k [B, S, N, D] in the ambient mesh."""
    from deepspeed_tpu.parallel.context import kernel_mesh
    from deepspeed_tpu.parallel.mesh import BATCH_AXES
    mesh, axes = kernel_mesh()
    batch = tuple(a for a in BATCH_AXES if axes.get(a, 1) > 1)
    if q.shape[0] % math.prod(axes[a] for a in batch):
        batch = ()   # ad-hoc small batches stay replicated (inference)
    tp = axes.get("tensor", 1)
    if tp > 1 and k.shape[2] % tp:
        raise ValueError(
            f"flash attention under tensor parallelism {tp}: kv_heads="
            f"{k.shape[2]} must divide by it (each chip runs the kernel on "
            "a whole kv-head slice) — use attention_impl='xla'")
    return mesh, axes, batch, "tensor" if tp > 1 else None


def _flash_per_shard(q, k, v, mask, **kw):
    """The flash kernel mapped over the ambient mesh: batch over the dp axes,
    heads over `tensor` (the kernel is independent over both; same layout
    as ring_attention's shard_map). See parallel.context.kernel_mesh for
    why the call cannot stay bare under jit."""
    mesh, axes, batch, heads = _flash_shards(q, k)
    if not batch and heads is None:
        return flash_attention(q, k, v, kv_mask=mask, **kw)
    from deepspeed_tpu.comm.schedule import shard_map_compat
    spec = P(batch or None, None, heads, None)
    masks = () if mask is None else (mask,)
    return shard_map_compat(
        lambda q, k, v, *m: flash_attention(
            q, k, v, kv_mask=m[0] if m else None, **kw),
        mesh, in_specs=(spec,) * 3 + (P(batch or None, None),) * len(masks),
        out_specs=spec, manual_axes=axes)(q, k, v, *masks)


def _flash_packed_per_shard(q, k, v, mask, segment_ids, sm_scale):
    """``_flash_per_shard`` for rows that hold several sequences: the packed
    forward, its ``segment_ids`` [B, S] split with the batch like a mask."""
    mesh, axes, batch, heads = _flash_shards(q, k)
    if not batch and heads is None:
        return flash_attention_packed(q, k, v, segment_ids, kv_mask=mask,
                                      sm_scale=sm_scale)
    from deepspeed_tpu.comm.schedule import shard_map_compat
    spec = P(batch or None, None, heads, None)
    masks = () if mask is None else (mask,)
    return shard_map_compat(
        lambda q, k, v, ids, *m: flash_attention_packed(
            q, k, v, ids, kv_mask=m[0] if m else None, sm_scale=sm_scale),
        mesh,
        in_specs=(spec,) * 3 + (P(batch or None, None),) * (1 + len(masks)),
        out_specs=spec, manual_axes=axes)(q, k, v, segment_ids, *masks)


def attention(q, k, v, mask=None, *, causal: bool = True, cfg: TransformerConfig,
              segment_ids=None, window=None):
    """q: [B,S,Nq,D], k/v: [B,S,Nkv,D] -> [B,S,Nq,D].

    window: local-attention band width (key j visible to query i iff
    i - j < window); a traced scalar — <= 0 means global — takes the XLA
    path (the ring and sparse kernels have no band mask); a STATIC length
    (a Python int, causal, no mask or segments) takes the flash kernel's
    banded forward where the flash kernel would run.

    segment_ids: int [B, S], several sequences packed into a row one after
    the other, numbered from 0 — so the ids NEVER FALL along a row, which
    every path may rely on (``_packed_row``'s do not) — key j is visible to
    query i only where the two ids are equal (and causality, the band and
    ``mask`` allow it). The ids are traced, so one program serves a row of
    one segment and a row of several. On the XLA path they are one select
    more on the scores; the flash kernel takes a causal row in ONE
    forward-only call that walks the key tiles a query tile's segments
    reach (``flash_attention_packed``), so a row that is all one segment
    walks the causal tiles it walks without ids; the ring and sparse
    kernels mask no keys and leave such rows to the XLA path."""
    kernel = _attention_kernel(q, k, v, mask, causal, cfg, segment_ids, window)
    if kernel is not None:
        return kernel()
    return _xla_attention(q, k, v, mask, causal, cfg, segment_ids, window)


def _repeat_kv(k, v, Nq: int):
    """GQA for the paths that are not GQA-native: the kv heads repeated."""
    rep = Nq // k.shape[2]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _attention_kernel(q, k, v, mask, causal: bool, cfg: TransformerConfig,
                      segment_ids, window):
    """The kernel that takes these rows in place of the XLA path, as the
    call to make — or None where none does."""
    from deepspeed_tpu.parallel.context import seq_parallel_degree, current_mesh
    S, Nq, D = q.shape[1:]
    sm = cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(D)
    if window is not None:
        if (isinstance(window, int) and window > 0 and causal and mask is None
                and segment_ids is None and flash_takes(cfg, S)):
            return lambda: _flash_per_shard(q, k, v, None, causal=True,
                                            sm_scale=sm, window=window)
        return None
    # the Pallas flash kernel is GQA-native (K/V never repeated in HBM) and
    # handles key-padding masks in-kernel; other paths get the repeated view
    if flash_takes(cfg, S):
        if segment_ids is None:
            return lambda: _flash_per_shard(
                q, k, v, mask, causal=causal, sm_scale=sm,
                fused_backward=cfg.fused_backward)
        if causal:      # ONE call, walked over the tiles the segments reach
            return lambda: _flash_packed_per_shard(q, k, v, mask,
                                                   segment_ids, sm)
    if segment_ids is not None:
        return None
    # sequence parallelism: ring attention over the seq mesh axis
    if seq_parallel_degree() > 1 and mask is None:
        from deepspeed_tpu.ops.ring_attention import ring_attention
        return lambda: ring_attention(q, *_repeat_kv(k, v, Nq), current_mesh(),
                                      causal=causal, sm_scale=sm)
    if cfg.sparse_attention and mask is None:
        if q.dtype == jnp.float16 and jax.default_backend() == "tpu":
            raise ValueError("sparse_attention kernels cannot run fp16 on "
                             "TPU (Mosaic has no f16) — use bf16")
        from deepspeed_tpu.ops.sparse_attention import (
            get_sparsity_config, sparse_attention as _sparse_attn)
        sa = dict(cfg.sparse_attention)
        mode = sa.pop("mode", "fixed")
        return lambda: _sparse_attn(q, *_repeat_kv(k, v, Nq),
                                    get_sparsity_config(mode, **sa),
                                    causal=causal, sm_scale=sm)
    return None


def _xla_attention(q, k, v, mask, causal: bool, cfg: TransformerConfig,
                   segment_ids, window):
    """Attention through materialised scores: every mask there is."""
    B, S, Nq, D = q.shape
    sm = cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(D)
    k, v = _repeat_kv(k, v, Nq)
    scores = jnp.einsum("bsnd,btnd->bnst", q, k).astype(jnp.float32)
    scores = scores * sm
    if cfg.position_type == "alibi":
        pos = jnp.arange(S)
        rel = (pos[None, :] - pos[:, None]).astype(jnp.float32)  # k - q
        scores = scores + alibi_slopes(Nq)[None, :, None, None] * rel[None, None]
    if causal:
        cm = jnp.tril(jnp.ones((S, S), jnp.bool_))
        scores = jnp.where(cm[None, None], scores, -1e30)
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        pos = jnp.arange(S)
        band = (pos[:, None] - pos[None, :]) < w  # i - j < window
        scores = jnp.where((w <= 0) | band[None, None], scores, -1e30)
    if mask is not None:  # [B, S] padding mask over keys
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]  # [B, S, S]
        scores = jnp.where(same[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", probs, v)


def _activation(x, gate, cfg: TransformerConfig):
    if cfg.activation == "silu_glu":
        return jax.nn.silu(gate) * x
    if cfg.activation == "gelu_glu":
        return jax.nn.gelu(gate) * x
    if cfg.activation == "relu":   # OPT family
        return jax.nn.relu(x)
    if cfg.activation == "relu2":  # nemotron_h: relu(x)^2, no gate
        return jnp.square(jax.nn.relu(x))
    if cfg.activation == "quick_gelu":   # CLIP text encoder
        return x * jax.nn.sigmoid(1.702 * x)
    return jax.nn.gelu(x)


def _idx_col(v):
    """Decode cursor as a broadcastable column: the one-shot loop carries a
    SCALAR position (all rows in lockstep), the paged serving path a
    per-slot [B] vector. Scalars pass through (identical program to the
    pre-paged path); vectors become [B, 1] so masks over [.., T] broadcast
    per row."""
    a = jnp.asarray(v, jnp.int32)
    return a[:, None] if a.ndim else a


def _decode_attention(q, ck, cv, index, cfg: TransformerConfig = None,
                      kv_row=None, kv_scale=None, kv_suffix=None,
                      window=None):
    """Single-token GQA attention against a KV ring buffer, with NO repeat of
    the kv heads in memory (reference's decode kernels repeat in registers:
    ``csrc/transformer/inference/csrc/pt_binding.cpp:1716-1780``).

    q: [B, 1, Nq, D]; ck/cv: [B, Nkv, T, D]; index: current position —
    a scalar (one-shot decode loop, rows in lockstep) or a per-row [B]
    vector (the paged serving path, where every slot sits at its own
    sequence length).

    kv_row: the CURRENT token's (k, v) [B, Nkv, 1, D], kept OUT of the
    buffer — its logit joins the softmax separately and the caller writes
    the row into the cache afterwards. This is what makes the decode loop's
    cache update O(row) instead of O(buffer): inserting the row here would
    force XLA to rewrite (copy) the whole ring buffer every token (the
    reference's fixed decode workspace has the same do-not-reallocate
    property, inference_context.h).

    This is the XLA decode path; its length-awareness comes from the decode
    loop's static read windows. The serving tier's paged layout has Pallas
    kernels of its own (ops/decode_attention.py: one a pool dtype), chosen
    at engine init by a price or a micro-bench (``_paged_attention``) — the
    old contiguous-layout kernel lost to this path end-to-end on v5e and was
    deleted.
    """
    B, _, Nq, D = q.shape
    Nkv, T = ck.shape[1], ck.shape[2]
    rep = Nq // Nkv
    sm = (cfg.attn_scale if cfg is not None and cfg.attn_scale is not None
          else 1.0 / math.sqrt(D))
    qg = q.reshape(B, Nkv, rep, D)
    if kv_scale is not None:
        # int8 cache, int8 MATH: a dequantize-then-bf16-dot would
        # materialize the converted cache and read MORE bytes than the
        # bf16 path. Instead the single-token q is quantized per row
        # (cheap, O(B*Nq*D)) and the contraction runs on the int8 MXU
        # (int8 x int8 -> int32); the q/k scales multiply the SCORES.
        q32 = qg.astype(jnp.float32)
        qs = jnp.maximum(jnp.max(jnp.abs(q32), axis=-1) / 127.0, 1e-8)
        qi = jnp.clip(jnp.round(q32 / qs[..., None]), -127, 127
                      ).astype(jnp.int8)
        scores = jnp.einsum("bgrd,bgtd->bgrt", qi, ck,
                            preferred_element_type=jnp.int32
                            ).astype(jnp.float32)
        scores = scores * qs[..., None] * kv_scale[0][:, :, None, :]
    else:
        scores = jnp.einsum("bgrd,bgtd->bgrt", qg, ck
                            ).astype(jnp.float32)
    scores = scores * sm
    if cfg is not None and cfg.position_type == "alibi":
        rel = (jnp.arange(T)[None, :] - _idx_col(index)
               ).astype(jnp.float32)                             # k - q
        slopes = alibi_slopes(Nq).reshape(Nkv, rep)
        scores = scores + slopes[None, :, :, None] * rel[:, None, None, :]
    if kv_row is not None:
        k_row, v_row = kv_row                    # [B, Nkv, 1, D]
        if kv_suffix is not None:
            # two-level cache: the big buffer is a FROZEN prefix (scan
            # invariant, read in place) and the tokens of the current
            # segment live in the small suffix carry — XLA double-buffers
            # scan carries, so carrying the full ring buffer copied O(T)
            # bytes per token (the ctx-2048 decode cliff, round 5 form)
            sk, sv, count = kv_suffix            # [B, Nkv, Ssuf, D]
            prefix_len = index - count
        else:
            prefix_len = index
        # buffer rows at >= prefix_len are stale; the current token's logit
        # comes from the fresh row (rel distance 0 — no alibi term)
        keep = jnp.arange(T)[None, :] < _idx_col(prefix_len)
        if window is not None:
            # local band: buffer position t (absolute) visible iff
            # index - t < window; <= 0 means global
            w = jnp.asarray(window, jnp.int32)
            keep = keep & ((w <= 0)
                           | (_idx_col(index) - jnp.arange(T)[None, :] < w))
        valid = keep[:, None, None, :]
        scores = jnp.where(valid, scores, -1e30)
        s_self = jnp.einsum("bgrd,bgtd->bgrt", qg,
                            k_row.astype(qg.dtype)).astype(jnp.float32)
        s_self = s_self * sm
        if kv_suffix is not None:
            Ssuf = sk.shape[2]
            s_suf = jnp.einsum("bgrd,bgtd->bgrt", qg,
                               sk.astype(qg.dtype)).astype(jnp.float32)
            s_suf = s_suf * sm
            if cfg is not None and cfg.position_type == "alibi":
                rel_suf = (_idx_col(prefix_len) + jnp.arange(Ssuf)[None, :]
                           - _idx_col(index)).astype(jnp.float32)
                slopes = alibi_slopes(Nq).reshape(Nkv, rep)
                s_suf = s_suf + slopes[None, :, :, None] * \
                    rel_suf[:, None, None, :]
            skeep = jnp.broadcast_to(jnp.arange(Ssuf) < count, (1, Ssuf))
            if window is not None:
                w = jnp.asarray(window, jnp.int32)
                abs_pos = _idx_col(prefix_len) + jnp.arange(Ssuf)[None, :]
                skeep = skeep & ((w <= 0) | (_idx_col(index) - abs_pos < w))
            s_suf = jnp.where(skeep[:, None, None, :], s_suf, -1e30)
            scores = jnp.concatenate([scores, s_suf, s_self], axis=-1)
            probs = jax.nn.softmax(scores, axis=-1)
            out = _decode_pv(probs[..., :T], cv, kv_scale, q.dtype)
            out = out + jnp.einsum(
                "bgrt,bgtd->bgrd", probs[..., T:T + Ssuf].astype(q.dtype),
                sv.astype(q.dtype))
            out = out + probs[..., T + Ssuf:].astype(q.dtype) * \
                v_row.astype(q.dtype)
            return out.reshape(B, 1, Nq, D)
        scores = jnp.concatenate([scores, s_self], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _decode_pv(probs[..., :T], cv, kv_scale, q.dtype)
        out = out + probs[..., T:].astype(q.dtype) * v_row.astype(q.dtype)
        return out.reshape(B, 1, Nq, D)
    keep = jnp.arange(T)[None, :] <= _idx_col(index)
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        keep = keep & ((w <= 0)
                       | (_idx_col(index) - jnp.arange(T)[None, :] < w))
    scores = jnp.where(keep[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _decode_pv(probs, cv, kv_scale, q.dtype)
    return out.reshape(B, 1, Nq, D)


class BlockList(NamedTuple):
    """A decode round's live blocks as ONE flat list, the form the XLA read
    of the paged pool works on (``_paged_list_attention``): what a step
    gathers follows the list's length — the SUM of the blocks the slots
    hold — not slots x the longest table.

    The list's unit is a RUN of ``c`` consecutive columns of one slot (a
    slot's last run padded with 0, the trash block; ``c`` = 1: a block).
    ids: [R * c] int32 block ids, run after run; where: [R] int32, a run's
    place in the per-slot view, ``slot * W + its number in the slot``
    (``S * W`` for a padding run: no place); inv: [S, W] int32, the other
    way round — the run that holds a slot's columns, R where the slot has
    none; W = the runs of a full table. ``S``, ``W``, ``R`` and ``c`` are
    read off the shapes. Built on the host by
    ``ServingEngine._tables_device``; a rectangular table ``ids[S, MB]`` is
    the list of all its entries, a block each (``_as_block_list``)."""
    ids: Any
    where: Any
    inv: Any

    @property
    def run(self) -> int:
        """Blocks of a run."""
        return self.ids.shape[0] // self.where.shape[0]


def _as_block_list(tables) -> BlockList:
    """Block tables [S, MB] as the list of all their S x MB entries, row by
    row (iotas and a bitcast: nothing is computed)."""
    if isinstance(tables, BlockList):
        return tables
    S, MB = tables.shape
    place = jnp.arange(S * MB, dtype=jnp.int32)
    return BlockList(tables.reshape(-1), place, place.reshape(S, MB))


def _block_at(tables, col):
    """The block id in column ``col[s]`` of every slot's table -> [S]:
    where a step writes its fresh row. tables: [S, MB] or a ``BlockList``
    (a slot without that column reads some block of the list: the caller
    masks inactive slots to the trash block)."""
    if isinstance(tables, BlockList):
        c = tables.run
        n = jnp.take_along_axis(tables.inv, (col // c)[:, None], axis=1)[:, 0]
        return jnp.take(tables.ids, n * c + col % c, mode="clip")
    return jnp.take_along_axis(tables, col[:, None], axis=1)[:, 0]


def _gather_blocks(pool, ids, layer=None):
    """Blocks as stored: the token-major pool read through block ids of any
    shape [...] as [..., bs, Nkv, D], ONE gather and nothing around it.

    pool: one layer's [NB, bs, Nkv, D], or the WHOLE leaf
    [L, NB, bs, Nkv, D] with ``layer`` (traced) the layer to read. Inside a
    layer scan the whole leaf is the one to pass: a ``dynamic_index_in_dim``
    ahead of the gather is a COPY of the layer's slice of each pool on every
    layer (100 MB a pool in the chat cell: 4.4 ms a step, PERF.md §5-6,
    PR 27), while the layer as an offset into the leaf viewed
    [L*NB, bs, Nkv, D] — merging the two MAJOR dims is a bitcast — costs
    nothing. The ids are block ids the allocator issued or 0, the trash
    block: always in bounds, so the gather clips and selects no fill value;
    what a slot may see is decided by the length mask on the scores. The
    decode step hands the flat list of a round's live blocks [N] (a
    ``BlockList``: the read is sized by what the slots hold), a span its
    rectangular tables [S, MB]."""
    if layer is not None:
        NB = pool.shape[1]
        pool = pool.reshape((-1,) + pool.shape[2:])
        ids = layer * NB + ids
    return jnp.take(pool, ids, axis=0, mode="clip")


def _gather_scales(scale, ids, Nkv, layer=None):
    """A scale plane [NB, Nkv*bs] (or the whole [L, NB, Nkv*bs] with
    ``layer``) read through block ids [...] as [..., Nkv, bs]. The layer is
    a coordinate of the gather: the planes live in a layout of their own,
    where merging [L, NB] copies the plane."""
    idx = (ids,) if layer is None else (layer, ids)
    g = scale.at[idx].get(mode="clip")           # [..., Nkv*bs]
    return g.reshape(ids.shape + (Nkv, -1))


def _table_view(g):
    """Scales gathered per block, [A, B, Nkv, bs] — a slot's table columns,
    a run's blocks —, as [A, Nkv, B*bs]: position-major within a head."""
    A, B, Nkv, bs = g.shape
    return g.transpose(0, 2, 1, 3).reshape(A, Nkv, B * bs)


def _quant_query(q32):
    """Per-row symmetric int8 of a float32 query [..., D] -> (int8, scale
    [...]): the ``_decode_attention`` recipe, shared by the paged reads."""
    qs = jnp.maximum(jnp.max(jnp.abs(q32), axis=-1) / 127.0, 1e-8)
    qi = jnp.clip(jnp.round(q32 / qs[..., None]), -127, 127).astype(jnp.int8)
    return qi, qs


def _quant_probs(pv):
    """Probabilities x V scale [..., T] (non-negative) requantised per row
    -> (int8, scale [...]): the ``_decode_pv`` recipe."""
    ps = jnp.maximum(jnp.max(pv, axis=-1) / 127.0, 1e-20)
    return jnp.clip(jnp.round(pv / ps[..., None]), 0, 127).astype(jnp.int8), ps


def _paged_attention(q, pool_k, pool_v, tables, index, cfg: TransformerConfig,
                     kv_row, kv_scale=None, backend="xla", window=None,
                     layer=None):
    """Single-token attention against the PAGED block pool.

    q: [S, 1, Nq, D] (one in-flight token per slot); pool_k/pool_v:
    [NB, bs, Nkv, D], one layer's slice of the shared block pool, stored
    TOKEN-major (see ``init_paged_cache``) — or, with ``layer`` (a traced
    index), the WHOLE leaves [L, NB, bs, Nkv, D] and scale planes, which is
    what a layer scan passes (``_gather_blocks`` says why); tables: a
    ``BlockList``, the flat list of the blocks the slots hold (what the
    serving engine hands the XLA backend), or rectangular [S, MB] int32
    block ids (0 = the reserved trash block, masked by the length), which
    read as the list of all their entries; index: per-slot sequence
    length [S].

    backend="pallas" (rectangular tables): a kernel reads the pool — only
    blocks covering the valid prefix ever cross HBM->VMEM, nothing
    materializes. An int8 pool (``kv_scale`` given): the whole leaves and
    scale planes go to ``ops/decode_attention.paged_decode_int8`` with the
    layer as a scalar, a slot's live blocks streamed through VMEM under the
    XLA read's int8 recipe. A float pool: the block-table gather is resolved
    inside the index maps of ``paged_decode_attention``.
    backend="xla": ONE ``jnp.take`` per pool materializes the LISTED blocks
    as stored ([N, bs, Nkv, D]: N follows the sum of what the slots hold,
    not slots x the longest table) and ``_paged_list_attention`` contracts
    them as gathered, block by block, with one softmax a slot. Its
    arithmetic is the ring-buffer path's (``_decode_attention`` with a
    per-slot cursor) operation for operation, which is what keeps
    paged-vs-contiguous decode bit-for-bit comparable in tests. The backend
    is chosen at serving-engine init (``ServingEngine._select_backend``): an
    int8 pool's by a price computed from the engine's shapes
    (``ops/decode_attention.paged_read_price``), a float pool's by a measured
    micro-bench — neither by a config flag the default leaves set.

    Multi-token queries (q [S, T, Nq, D] with T > 1 — the speculation
    verify / chunked-prefill span path, ``decode_span_paged``) route to
    ``_paged_span_attention``: every position's math is the single-token
    chain's (the Pallas kernel is single-token only and is never selected
    for spans).
    """
    Nkv = pool_k.shape[-2]
    if q.shape[1] > 1:
        return _paged_span_attention(q, pool_k, pool_v, tables, index, cfg,
                                     kv_row, kv_scale=kv_scale,
                                     window=window, layer=layer)
    use_pallas = (backend == "pallas"
                  and not isinstance(tables, BlockList)
                  and window is None and q.dtype != jnp.float16
                  and (cfg is None or (cfg.position_type != "alibi"
                                       and cfg.attn_scale is None)))
    if use_pallas and kv_scale is not None:
        # the int8 pool: the leaves whole, the layer a scalar of the kernel
        # (one chip: ``ServingEngine._select_backend`` keeps a ``tensor``
        # mesh on the XLA read)
        if layer is None:
            pool_k, pool_v, *kv_scale = (a[None] for a in
                                         (pool_k, pool_v, *kv_scale))
        return paged_decode_int8(q, pool_k, pool_v, *kv_scale, tables, index,
                                 0 if layer is None else layer, kv_row=kv_row)
    if use_pallas:
        if layer is not None:        # a kernel's operand is a whole buffer
            pool_k, pool_v = (lax.dynamic_index_in_dim(p, layer, 0,
                                                       keepdims=False)
                              for p in (pool_k, pool_v))
        # heads over `tensor` like the pools themselves: each chip runs the
        # kernel on its kv-head slice (parallel.context.kernel_mesh)
        from deepspeed_tpu.parallel.context import kernel_mesh
        mesh, axes = kernel_mesh()
        if axes.get("tensor", 1) == 1:
            return paged_decode_attention(q, pool_k, pool_v, tables, index,
                                          kv_row=kv_row)
        from deepspeed_tpu.comm.schedule import shard_map_compat
        # q [S, 1, Nq, D] and the pool slices [NB, bs, Nkv, D] both carry
        # their heads on dim 2; the fresh rows [S, Nkv, 1, D] on dim 1
        hs, rs = P(None, None, "tensor", None), P(None, "tensor", None, None)
        return shard_map_compat(
            lambda q, pk, pv, t, ln, kr, vr: paged_decode_attention(
                q, pk, pv, t, ln, kv_row=(kr, vr)),
            mesh, in_specs=(hs, hs, hs, P(), P(), rs, rs), out_specs=hs,
            manual_axes=axes)(q, pool_k, pool_v, tables, index, *kv_row)

    blocks = _as_block_list(tables)
    sc = None
    with jax.named_scope("kv_gather"):
        if kv_scale is not None:
            sc = tuple(_gather_scales(s, blocks.ids, Nkv, layer)
                       for s in kv_scale)
        vk, vv = (_gather_blocks(pool_k, blocks.ids, layer),
                  _gather_blocks(pool_v, blocks.ids, layer))
    return _paged_list_attention(q, vk, vv, blocks, index, cfg, kv_row, sc,
                                 window)


def _head_groups():
    """How many groups of kv heads a block-diagonal contraction keeps apart:
    the ambient mesh's ``tensor`` degree (the pools and the heads are sharded
    over it, and a contraction across kv heads would sum across chips), 1
    without a mesh."""
    from deepspeed_tpu.parallel.context import kernel_mesh
    return kernel_mesh()[1].get("tensor", 1)


def _paged_list_attention(q, vk, vv, blocks: BlockList, index, cfg, kv_row,
                          kv_scale, window):
    """One token per slot against the round's live blocks, gathered as ONE
    flat list.

    q: [S, 1, Nq, D]; vk/vv: the blocks of ``blocks.ids`` as
    ``_gather_blocks`` returns them, [R * c, block, Nkv, D], TOKEN-major as
    stored — read as R runs of ``bs`` = c x block positions, [R, bs, Nkv,
    D], a bitcast; kv_row: the fresh (k, v) [S, Nkv, 1, D], folded into the
    same softmax; kv_scale: the int8 pool's gathered (k, v) scales
    [R * c, Nkv, block], or None.

    Everything sized by the K/V — the two gathers, the scores, P.V — is
    sized by the LIST: nothing of ``S x MB x block x Nkv x D`` exists.
    Scores are taken per run against its slot's query (``q[slot]``), laid
    into the per-slot view [S, Nkv, rep, W*bs] through ``blocks.inv``
    (float32 scores: a sixteenth to a sixty-fourth of the int8 K/V's bytes
    a position), and the mask, the ONE softmax per (slot, head) over the
    pool positions and the fresh row and the requantisation per ROW run
    there, as they always did; the int8 probabilities go back to their
    runs (``blocks.where``), P.V is taken per run, and a slot's partial
    sums are added up through ``blocks.inv`` again. Places of the view
    that no run fills lie past the slot's length (the blocks cover it):
    masked like stale rows.

    The recipe is ``_decode_attention``'s to the letter — query quantised
    per row, int8 x int8 -> int32, q and k scales multiplied into the
    scores, probabilities x v-scale requantised per row, int32 P.V — and
    the int32 sums are exact in any order, so the results are the ring
    buffer's bit for bit, whatever the list's length and order. A float
    pool's P.V is summed per run in float32 and then over the runs: the
    same products in another order.

    The int8 contractions are written block-diagonally so that they are
    matmuls at ANY number of query heads per kv head, over the view as
    gathered (no transpose, no widened view: PR 27). Scores: the quantised
    query is laid out [R, Nkv, D, Nkv*rep] with zeros off the diagonal and
    contracted with a run over (Nkv, D). P.V: the probabilities are laid
    out [R, Nkv*rep, bs, Nkv] with zeros off the diagonal and contracted
    with the run over (bs, Nkv) — the run read as the matrix [bs*Nkv, D]
    it is stored as — so a run's partial is [Nkv*rep, D] (every (query
    head, kv head) pair first and the diagonal afterwards would be Nkv
    times that in int32, per RUN: with a block a run half the bytes of the
    K and V gathered, PERF.md section 6, PR 38). The zeros add exact zeros
    to an int32 sum. Under a ``tensor`` mesh the diagonal is laid per
    group of local kv heads (``_head_groups``), so no sum crosses chips.
    """
    S, _, Nq, D = q.shape
    Nkv = vk.shape[2]
    R, W = blocks.where.shape[0], blocks.inv.shape[1]    # runs; a slot's
    c = blocks.run
    bs = c * vk.shape[1]                                 # positions of a run
    vk, vv = (a.reshape(R, bs, Nkv, D) for a in (vk, vv))
    if kv_scale is not None:       # [R*c, Nkv, block] -> [R, Nkv, bs]
        kv_scale = tuple(_table_view(a.reshape(R, c, Nkv, -1))
                         for a in kv_scale)
    T = W * bs
    rep = Nq // Nkv
    sm = (cfg.attn_scale if cfg is not None and cfg.attn_scale is not None
          else 1.0 / math.sqrt(D))
    qg = q.reshape(S, Nkv, rep, D)
    k_row, v_row = kv_row                        # [S, Nkv, 1, D]
    # a padding entry has no slot: it reads the last slot's query and its
    # scores and partial sums are never looked at (no place of `inv` names it)
    slot = jnp.minimum(blocks.where // W, S - 1)                # [R]
    held = (blocks.inv < R)[:, :, None, None]                    # [S, W,..]
    inv = jnp.minimum(blocks.inv, R - 1)

    def to_view(x):        # [R, Nkv, r, bs] -> [S, Nkv, r, W*bs]
        v = jnp.take(x, inv, axis=0)             # [S, W, Nkv, r, bs]
        return v.transpose(0, 2, 3, 1, 4).reshape(S, Nkv, x.shape[2], T)

    if kv_scale is not None:
        X = _head_groups()
        G = Nkv // X                             # kv heads of one group
        eye = jnp.eye(G, dtype=jnp.int8)
        qi, qs = _quant_query(qg.astype(jnp.float32))
        # [S, X, G, rep, D] x eye[G, H] -> [S, X, G, D, H, rep]
        qd = jnp.einsum("sxgrd,gh->sxgdhr", qi.reshape(S, X, G, rep, D), eye)
        scores = jnp.einsum("ntxgd,nxgdhr->nxhrt",
                            vk.reshape(R, bs, X, G, D), qd[slot],
                            preferred_element_type=jnp.int32
                            ).reshape(R, Nkv, rep, bs).astype(jnp.float32)
        scores = scores * qs[slot][..., None] * kv_scale[0][:, :, None, :]
    else:
        scores = jnp.einsum("ngrd,ntgd->ngrt", qg[slot], vk
                            ).astype(jnp.float32)
    scores = to_view(scores) * sm
    index = jnp.asarray(index, jnp.int32)[:, None]
    if cfg is not None and cfg.position_type == "alibi":
        rel = (jnp.arange(T)[None, :] - index).astype(jnp.float32)  # k - q
        slopes = alibi_slopes(Nq).reshape(Nkv, rep)
        scores = scores + slopes[None, :, :, None] * rel[:, None, None, :]
    # rows at >= index are stale (or another request's, or the trash
    # block's, or no block's); the current token's logit comes from the
    # fresh row
    keep = jnp.arange(T)[None, :] < index
    if window is not None:
        # local band: position t visible iff index - t < window; <= 0 global
        w = jnp.asarray(window, jnp.int32)
        keep = keep & ((w <= 0) | (index - jnp.arange(T)[None, :] < w))
    scores = jnp.where(keep[:, None, None, :], scores, -1e30)
    s_self = jnp.einsum("bgrd,bgtd->bgrt", qg,
                        k_row.astype(qg.dtype)).astype(jnp.float32)
    s_self = s_self * sm
    probs = jax.nn.softmax(jnp.concatenate([scores, s_self], axis=-1),
                           axis=-1)
    pp = probs[..., :T]

    def to_list(x):        # [S, Nkv, rep, W*bs] -> [R, Nkv, rep, bs]
        v = x.reshape(S, Nkv, rep, W, bs).transpose(0, 3, 1, 2, 4)
        return jnp.take(v.reshape(S * W, Nkv, rep, bs), blocks.where,
                        axis=0, mode="clip")

    def per_slot(part):    # [R, Nkv, rep, D] -> [S, Nkv, rep, D]
        return jnp.sum(jnp.where(held, jnp.take(part, inv, axis=0)
                                 .reshape(S, W, Nq, D), 0),
                       axis=1).reshape(S, Nkv, rep, D)

    if kv_scale is not None:
        # fold the per-position V scale into the probs, requantize per row,
        # keep the contraction on the int8 MXU (the _decode_pv recipe)
        pvi, ps = _quant_probs(pp * to_view(kv_scale[1][:, :, None, :]))
        # [R, X, H, rep, bs] x eye[H, G] -> [R, X, H, rep, bs, G]
        pd = jnp.einsum("nxhrt,hg->nxhrtg",
                        to_list(pvi).reshape(R, X, G, rep, bs), eye)
        acc = jnp.einsum("nxhrtg,ntxgd->nxhrd", pd,
                         vv.reshape(R, bs, X, G, D),
                         preferred_element_type=jnp.int32)
        out = (per_slot(acc.reshape(R, Nkv, rep, D)).astype(jnp.float32)
               * ps[..., None]).astype(q.dtype)
    else:
        acc = jnp.einsum("ngrt,ntgd->ngrd", to_list(pp.astype(q.dtype)), vv,
                         preferred_element_type=jnp.float32)
        out = per_slot(acc).astype(q.dtype)
    out = out + probs[..., T:].astype(q.dtype) * v_row.astype(q.dtype)
    return out.reshape(S, 1, Nq, D)


def _paged_span_attention(q, pool_k, pool_v, tables, prior_lens,
                          cfg: TransformerConfig, kv_row, kv_scale=None,
                          window=None, layer=None):
    """T-token attention for a span appended at each slot's cursor.

    q: [S, T, Nq, D]; kv_row: the span's fresh (k, v) [S, Nkv, T, D];
    prior_lens: [S] rows already in the pool. Position ``prior + t``
    attends the pool prefix [0, prior), the earlier span rows [0, t) and
    itself. Serves both chunked prefill (T = chunk) and the speculation
    verify step (T = K + 1).

    BATCHED over the T positions (one pool einsum + one intra-span einsum
    per layer, not T sequential passes — a chunk must cost like a prefill,
    not like T decode steps, or chunking could never beat the monolithic
    prefill it replaces): scores over the gathered pool view with the
    per-slot prefix mask, scores over the span itself with the causal
    ``u <= t`` mask, ONE softmax over their concatenation. Masked slots
    contribute exact zeros, so each position's visible logits are exactly
    the single-token chain's values — span-computed rows/logits match
    stepping the same tokens one at a time to reduction-order rounding
    (greedy argmax equality is what the K=0/K>0 and warm/cold parity
    tests pin; bit-exactness of the float logits is NOT promised, the
    softmax width differs). int8 pools: the pool read runs the same
    quantized-MXU path as ``_decode_attention``; the span's own fresh
    rows are read as floats where sequential steps would re-read them
    quantized — same relaxation as the contiguous int8 cache's re-prefill
    path, and the reason the int8 parity tests carry a weaker bar.
    """
    S, T = q.shape[0], q.shape[1]
    Nkv, D = pool_k.shape[-2:]
    Nq = q.shape[2]
    rep = Nq // Nkv
    chunk_k, chunk_v = kv_row                    # [S, Nkv, T, D]
    sm = (cfg.attn_scale if cfg is not None and cfg.attn_scale is not None
          else 1.0 / math.sqrt(D))

    # the pool view stays token-major [S, Tp, Nkv, D], as gathered
    with jax.named_scope("kv_gather"):
        vk, vv = (_gather_blocks(p, tables, layer).reshape(S, -1, Nkv, D)
                  for p in (pool_k, pool_v))
        Tp = vk.shape[1]
        if kv_scale is not None:
            ksg, vsg = (_table_view(_gather_scales(s, tables, Nkv, layer))
                        for s in kv_scale)
    qg = q.transpose(0, 2, 1, 3).reshape(S, Nkv, rep, T, D)
    pos = prior_lens[:, None] + jnp.arange(T)[None, :]       # [S, T] abs
    if kv_scale is not None:
        # int8 pool, int8 math — the _decode_attention recipe batched
        # over T: quantize each query row, contract on the int8 MXU, fold
        # q/k scales into the scores
        qi, qs_ = _quant_query(qg.astype(jnp.float32))
        sp = jnp.einsum("bgrtd,bsgd->bgrts", qi, vk,
                        preferred_element_type=jnp.int32
                        ).astype(jnp.float32)
        sp = sp * qs_[..., None] * ksg[:, :, None, None, :]
    else:
        sp = jnp.einsum("bgrtd,bsgd->bgrts", qg, vk).astype(jnp.float32)
    sp = sp * sm
    if cfg is not None and cfg.position_type == "alibi":
        rel = (jnp.arange(Tp)[None, None, :]
               - pos[:, :, None]).astype(jnp.float32)      # [S, T, Tp]
        slopes = alibi_slopes(Nq).reshape(Nkv, rep)
        sp = sp + slopes[None, :, :, None, None] * rel[:, None, None]
    keep = jnp.arange(Tp)[None, None, :] < prior_lens[:, None, None]
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        keep = keep & ((w <= 0)
                       | (pos[:, :, None] - jnp.arange(Tp)[None, None, :]
                          < w))
    sp = jnp.where(keep[:, None, None], sp, -1e30)

    # intra-span scores: query t sees span rows u <= t (earlier rows +
    # itself — the scan arrangement's suffix and self terms in one block)
    sq = jnp.einsum("bgrtd,bgud->bgrtu", qg,
                    chunk_k.astype(qg.dtype)).astype(jnp.float32) * sm
    if cfg is not None and cfg.position_type == "alibi":
        rel_c = (jnp.arange(T)[None, :] - jnp.arange(T)[:, None]
                 ).astype(jnp.float32)                     # u - t
        slopes = alibi_slopes(Nq).reshape(Nkv, rep)
        sq = sq + slopes[None, :, :, None, None] * rel_c[None, None, None]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]   # [t, u]
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        causal = causal & ((w <= 0)
                           | (jnp.arange(T)[:, None]
                              - jnp.arange(T)[None, :] < w))
    sq = jnp.where(causal[None, None, None], sq, -1e30)

    probs = jax.nn.softmax(jnp.concatenate([sp, sq], axis=-1), axis=-1)
    pp, pc = probs[..., :Tp], probs[..., Tp:]
    if kv_scale is not None:
        # fold the per-position V scale into the probs, requantize, keep
        # the contraction on the int8 MXU (the _decode_pv recipe)
        pvi, ps = _quant_probs(pp * vsg[:, :, None, None, :])
        acc = jnp.einsum("bgrts,bsgd->bgrtd", pvi, vv,
                         preferred_element_type=jnp.int32
                         ).astype(jnp.float32)
        out = (acc * ps[..., None]).astype(q.dtype)
    else:
        out = jnp.einsum("bgrts,bsgd->bgrtd", pp.astype(q.dtype), vv)
    out = out + jnp.einsum("bgrtu,bgud->bgrtd", pc.astype(q.dtype),
                           chunk_v.astype(q.dtype))
    # [S, Nkv, rep, T, D] -> [S, T, Nq, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(S, T, Nq, D)


def _decode_pv(probs, cv, kv_scale, dtype):
    """probs @ V. int8 cache: fold the per-position V scale into the probs,
    re-quantize them per row, and keep the contraction on the int8 MXU —
    the V bytes stay int8 end to end."""
    if kv_scale is None:
        return jnp.einsum("bgrt,bgtd->bgrd", probs.astype(dtype), cv)
    pv = probs * kv_scale[1][:, :, None, :]
    ps = jnp.maximum(jnp.max(pv, axis=-1) / 127.0, 1e-20)
    pvi = jnp.clip(jnp.round(pv / ps[..., None]), 0, 127).astype(jnp.int8)
    out = jnp.einsum("bgrt,bgtd->bgrd", pvi, cv,
                     preferred_element_type=jnp.int32).astype(jnp.float32)
    return (out * ps[..., None]).astype(dtype)


def _maybe_dequant(p, cfg: TransformerConfig):
    """int8 weight-only inference: {"q", "scale"} leaves -> compute dtype.
    Called on ONE layer's slice inside the scan, so the dequantized bf16
    weights of only that layer are ever live.

    weight_only_bits=8 keeps the dense projection stacks AS {"q","scale"}
    dicts — ``_wmat``/``_wrow`` run the matmul against the int8 payload
    with the scale in the epilogue, so the weights never leave int8. Only
    the MoE expert stacks (and coef) still dequantize here: their gathered
    dispatch einsum has no per-column epilogue seam."""
    if not cfg.quantized_weights:
        return p
    epilogue = cfg.weight_only_bits == 8

    def one(k, v):
        if isinstance(v, dict) and "q" in v and "scale" in v:
            if epilogue and not k.startswith("moe_"):
                return v
            return (v["q"].astype(cfg.dtype)
                    * v["scale"].astype(cfg.dtype))
        return v
    return {k: one(k, v) for k, v in p.items()}


def _wmat(h, w):
    """h @ w for a weight that may be an epilogue-quantized {"q","scale"}
    dict (cfg.weight_only_bits, see ops/quantizer.weight_matmul) or a
    plain array — call sites stay branch-free."""
    if isinstance(w, dict):
        from deepspeed_tpu.ops.quantizer import weight_matmul
        return weight_matmul(h, w["q"], w["scale"])
    return h @ w.astype(h.dtype)


def _wrow(x, w):
    """Row-parallel twin of ``_wmat``: the per-out-channel scale factors
    out of the contraction, so it applies AFTER the tensor-axis reduction
    (the out columns of wo/w_out are unsharded under the Megatron rules —
    one replicated row multiply, exact)."""
    if isinstance(w, dict):
        y = x @ w["q"].astype(x.dtype)
        return y * jnp.reshape(w["scale"],
                               w["scale"].shape[-1:]).astype(x.dtype)
    return x @ w.astype(x.dtype)


def _lora_delta(h, ab, idx):
    """Gathered multi-adapter LoRA delta: (h @ A[idx]) @ B[idx].

    ``ab``: one layer's slot tables (A [NS, In, r], B [NS, r, Out]);
    ``idx``: [B] int32 adapter-slot per batch row. The gather + batched
    einsum serves a batch whose rows use DIFFERENT adapters in ONE
    dispatch — the same ragged trick as the MoE dispatch — so the
    compiled program is shaped by the slot pool, never by which adapters
    are resident (slot 0 is the all-zero null adapter: base-model rows
    add an exact zero). Rank is tiny, so the low-rank product goes
    through the rank bottleneck first."""
    a, b = ab
    ga = jnp.take(a, idx, axis=0).astype(h.dtype)      # [B, In, r]
    gb = jnp.take(b, idx, axis=0).astype(h.dtype)      # [B, r, Out]
    t = jnp.einsum("bsi,bir->bsr", h, ga)
    return jnp.einsum("bsr,bro->bso", t, gb)


def quantize_layer_stack(params: Params, bits: int = 8) -> Params:
    """Convert the stacked layer weights to int8 + per-(layer, out-channel)
    scales, for cfg.quantized_weights inference. Norm scales/biases stay
    full precision."""
    if bits != 8:
        raise ValueError("weight-only inference quantization supports int8")

    def one(w):
        # matmul weights only: [L, In, Out] (+MoE [L, E, In, Out]); norm
        # scales/biases ([L, H]) stay full precision
        if not hasattr(w, "ndim") or w.ndim < 3 or w.dtype == jnp.int8:
            return w
        w32 = jnp.asarray(w, jnp.float32)
        amax = jnp.max(jnp.abs(w32), axis=tuple(range(1, w.ndim - 1)),
                       keepdims=True)  # per (layer, out-col)
        scale = jnp.maximum(amax / 127.0, 1e-12)
        q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale.astype(jnp.float32)}

    out = dict(params)
    out["layers"] = {k: one(v) for k, v in params["layers"].items()}
    return out


def quantized_logical_axes(cfg: TransformerConfig,
                           base_axes: Optional[Params] = None) -> Params:
    """logical_axes variant matching the quantize_layer_stack structure."""
    axes = base_axes if base_axes is not None else logical_axes(cfg)

    def one(a):
        if a is None or len(a) < 3:
            return a
        return {"q": a, "scale": (a[0],) + (None,) * (len(a) - 2) + (a[-1],)}
    axes = dict(axes)
    axes["layers"] = {k: one(v) for k, v in axes["layers"].items()}
    return axes


def fuse_layer_stack(params: Params, cfg: TransformerConfig) -> Params:
    """Inference weight fusion: wq/wk/wv -> wqkv, w_in/w_gate -> w_in_gate.

    Decode at short context is op-latency bound (L layers x ~7 thin GEMVs
    per token); fusing cuts that to ~4 launches per layer. The reference's
    decode path fuses identically (qkv_gemm / fused_gemm_gelu,
    ``csrc/transformer/inference/csrc/pt_binding.cpp:1716-1780``). Apply
    BEFORE quantize_layer_stack; tensor-parallel layouts must stay unfused
    (the concat dim would interleave head shards).
    """
    if cfg.num_experts > 1:
        return params  # PR-MoE reads w_in/w_gate in its residual branch
    L = dict(params["layers"])
    if "wq" in L:
        L["wqkv"] = jnp.concatenate(
            [L.pop("wq"), L.pop("wk"), L.pop("wv")], axis=-1)
        if "bq" in L:
            L["bqkv"] = jnp.concatenate(
                [L.pop("bq"), L.pop("bk"), L.pop("bv")], axis=-1)
    if "w_gate" in L and "w_in" in L and "b_in" not in L:
        L["w_in_gate"] = jnp.concatenate(
            [L.pop("w_in"), L.pop("w_gate")], axis=-1)
    return {**params, "layers": L}


def unfuse_layer_stack(params: Params, cfg: TransformerConfig) -> Params:
    """Inverse of fuse_layer_stack (e.g. re-sharding fused weights onto a
    tensor-parallel mesh, which needs the per-projection layout)."""
    L = dict(params["layers"])
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    if "wqkv" in L:
        w = L.pop("wqkv")
        L["wq"] = w[..., :nh * hd]
        L["wk"] = w[..., nh * hd:(nh + nkv) * hd]
        L["wv"] = w[..., (nh + nkv) * hd:]
        if "bqkv" in L:
            b = L.pop("bqkv")
            L["bq"] = b[..., :nh * hd]
            L["bk"] = b[..., nh * hd:(nh + nkv) * hd]
            L["bv"] = b[..., (nh + nkv) * hd:]
    if "w_in_gate" in L:
        w = L.pop("w_in_gate")
        half = w.shape[-1] // 2
        L["w_in"], L["w_gate"] = w[..., :half], w[..., half:]
    return {**params, "layers": L}


def fused_logical_axes(cfg: TransformerConfig) -> Params:
    """logical_axes matching the fuse_layer_stack structure."""
    axes = logical_axes(cfg)
    if cfg.num_experts > 1:
        return axes
    layers = dict(axes["layers"])
    if "wq" in layers:
        layers["wqkv"] = ("layers", "embed", "qkv")
        for k in ("wq", "wk", "wv"):
            layers.pop(k, None)
        if "bq" in layers:
            layers["bqkv"] = ("layers", "qkv")
            for k in ("bq", "bk", "bv"):
                layers.pop(k, None)
    if "w_gate" in layers and "w_in" in layers and "b_in" not in layers:
        layers["w_in_gate"] = ("layers", "embed", "mlp")
        layers.pop("w_in"), layers.pop("w_gate")
    return {**axes, "layers": layers}


def transformer_layer(x, layer_params, cfg: TransformerConfig, mask=None,
                      positions=None, dropout_rng=None, deterministic=True,
                      cache=None, return_kv: bool = False, attn_window=None,
                      paged=None, lora=None, segment_ids=None):
    """One pre-norm block: x + attn(ln1(x)); x + mlp(ln2(x)). With the
    after-sublayer scales in the tree (``sandwich_norm``):
    x + ln1_post(attn(ln1(x))); x + ln2_post(mlp(ln2(x))).

    cache=(ck, cv, index[, read_len]): decode mode — x is [B, 1, H]. The
    buffer is NOT modified: attention treats the fresh (k, v) row as a
    separate softmax term (rows >= index in the buffer are stale), and the
    third return value is that (k_row, v_row) [B, nkv, 1, hd] for the
    CALLER to write at `index` (decode_step batches all layers' rows into
    one tiny column update). return_kv: also return the (post-rotary) K/V
    so a prefill pass can seed the cache.

    paged=(block_tables, backend, layer): the cache tuple carries the
    WHOLE block pools ([L, NB, bs, nkv, hd], and the whole scale planes of
    an int8 pool) instead of per-batch ring buffers, `layer` is this
    layer's (traced) index into them and `index` the per-slot
    sequence-length vector — attention gathers the layer's blocks straight
    out of the whole pool through the block table, or the flat list of the
    slots' blocks a decode step is handed (decode_step_paged;
    ``_gather_blocks`` says why the pool is not sliced first).

    lora=({proj: (A, B)}, idx): one layer's adapter slot tables + the
    per-row adapter-slot index — each projection in the dict gains the
    gathered low-rank delta (``_lora_delta``), batching rows that use
    DIFFERENT adapters in the same dispatch (multi-LoRA serving).

    segment_ids: int [B, S], several sequences in a row (no cache) —
    attention stays inside each (``attention``).
    """
    p = _maybe_dequant(layer_params, cfg)
    B, S, H = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head

    post = cfg.norm_style == "post"
    # post-LN (BERT): attention consumes x directly; the LN sits after each
    # residual add. pre-LN (GPT/llama): LN feeds each sublayer.
    h = x if post else _norm(x, p["ln1_scale"], p.get("ln1_bias"), cfg)
    if cfg.activation_quant_bits:
        from deepspeed_tpu.ops.quantizer import fake_quant
        h = fake_quant(h, bits=cfg.activation_quant_bits)
    if "wqkv" in p:
        # fused projection (see fuse_layer_stack): one GEMV instead of three
        # — decode at short context is op-latency bound, and the reference
        # fuses the same way (qkv_gemm, pt_binding.cpp)
        qkv = _wmat(h, p["wqkv"])
        if "bqkv" in p:
            qkv = qkv + p["bqkv"].astype(h.dtype)
        q = qkv[..., :nh * hd]
        k = qkv[..., nh * hd:(nh + nkv) * hd]
        v = qkv[..., (nh + nkv) * hd:]
    else:
        q = _wmat(h, p["wq"])
        k = _wmat(h, p["wk"])
        v = _wmat(h, p["wv"])
        if "bq" in p:
            q, k, v = (q + p["bq"].astype(h.dtype),
                       k + p["bk"].astype(h.dtype),
                       v + p["bv"].astype(h.dtype))
    if lora is not None:
        tabs, aidx = lora
        if "q" in tabs:
            q = q + _lora_delta(h, tabs["q"], aidx)
        if "k" in tabs:
            k = k + _lora_delta(h, tabs["k"], aidx)
        if "v" in tabs:
            v = v + _lora_delta(h, tabs["v"], aidx)
    if "q_norm" in p:
        # over the whole projection, all heads together: under `tensor` the
        # mean square is reduced across the axis by GSPMD (these are global
        # arrays), never per shard
        q = _rms_whole(q, p["q_norm"], cfg.norm_eps)
        k = _rms_whole(k, p["k_norm"], cfg.norm_eps)
    q = q.reshape(B, S, nh, hd)
    k = k.reshape(B, S, nkv, hd)
    v = v.reshape(B, S, nkv, hd)
    if cfg.position_type == "rotary":
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        q = rotary_embed(q, positions, cfg.rope_theta, cfg.rotary_dim,
                         cfg.rotary_interleaved)
        k = rotary_embed(k, positions, cfg.rope_theta, cfg.rotary_dim,
                         cfg.rotary_interleaved)
    new_kv = None
    if cache is not None:
        ck, cv, index = cache[:3]           # [B, nkv, T, hd]
        read_len = cache[3] if len(cache) > 3 else None
        kv_scale = cache[4] if len(cache) > 4 else None   # int8 cache
        kv_suffix = cache[5] if len(cache) > 5 else None  # two-level decode
        # the fresh row stays FLOAT (exact): its logit joins the softmax
        # separately. int8 caches carry rows in compute dtype (the decode
        # loop quantizes before the write); float caches keep the cache's
        # own dtype so a non-cfg.dtype cache (e.g. f32 cache under a bf16
        # model) still writes without a dtype mismatch.
        if kv_suffix is not None:
            row_dtype = kv_suffix[0].dtype   # rows land in the suffix
        elif kv_scale is not None:
            row_dtype = cfg.dtype            # int8 cache: loop quantizes
        else:
            row_dtype = ck.dtype
        k_row = jnp.swapaxes(k, 1, 2).astype(row_dtype)   # [B, nkv, 1, hd]
        v_row = jnp.swapaxes(v, 1, 2).astype(row_dtype)
        # the buffer is NOT modified here: the fresh row joins the softmax
        # separately and the decode loop writes all layers' rows with one
        # O(L*B*nkv*hd) update — rewriting the ring buffer per layer would
        # copy the whole cache every token (the ctx-2048 decode cliff)
        # windowed decode: attention reads a STATIC prefix of the ring
        # buffer (the decode loop guarantees index < read_len), so XLA only
        # touches O(read_len) bytes instead of max_len
        if paged is not None:
            tables, backend, layer = paged
            with jax.named_scope("attn"):
                attn_out = _paged_attention(q, ck, cv, tables, index, cfg,
                                            kv_row=(k_row, v_row),
                                            kv_scale=kv_scale,
                                            backend=backend,
                                            window=attn_window, layer=layer)
        elif read_len is not None and read_len < ck.shape[2]:
            sc = (tuple(s[:, :, :read_len] for s in kv_scale)
                  if kv_scale is not None else None)
            with jax.named_scope("attn"):
                attn_out = _decode_attention(q, ck[:, :, :read_len],
                                             cv[:, :, :read_len], index, cfg,
                                             kv_row=(k_row, v_row),
                                             kv_scale=sc, kv_suffix=kv_suffix,
                                             window=attn_window)
        else:
            with jax.named_scope("attn"):
                attn_out = _decode_attention(q, ck, cv, index, cfg,
                                             kv_row=(k_row, v_row),
                                             kv_scale=kv_scale,
                                             kv_suffix=kv_suffix,
                                             window=attn_window)
        new_kv = (k_row, v_row)
    else:
        if return_kv:
            new_kv = (k, v)
        # named scope: the perf doctor's trace join buckets everything under
        # attn/ as attention time (flash kernel, softmax chain) — the QKV/O
        # projections outside it stay in the matmul bucket by design
        with jax.named_scope("attn"):
            attn_out = attention(q, k, v, mask=mask, causal=cfg.causal,
                                 cfg=cfg, window=attn_window,
                                 segment_ids=segment_ids)
    attn_flat = attn_out.reshape(B, S, nh * hd)
    attn_out = _wrow(attn_flat, p["wo"])
    if lora is not None and "o" in lora[0]:
        attn_out = attn_out + _lora_delta(attn_flat, lora[0]["o"], lora[1])
    if "bo" in p:
        attn_out = attn_out + p["bo"].astype(h.dtype)
    if "ln1_post_scale" in p:
        # sandwich norm: the sublayer's output is normed before it joins
        # the residual
        attn_out = _norm(attn_out, p["ln1_post_scale"], None, cfg)
    if cfg.parallel_block:
        # GPT-J/NeoX: one residual, both sublayers read the SAME input x
        # (GPT-J shares a single LN — its import fills both slots with ln_1)
        h = _norm(x, p["ln2_scale"], p.get("ln2_bias"), cfg)
    else:
        x = x + _dropout(attn_out, cfg, dropout_rng, deterministic, 0)
        if post:
            x = _norm(x, p["ln1_scale"], p.get("ln1_bias"), cfg)
        h = x if post else _norm(x, p["ln2_scale"], p.get("ln2_bias"), cfg)
    if cfg.activation_quant_bits:
        from deepspeed_tpu.ops.quantizer import fake_quant
        h = fake_quant(h, bits=cfg.activation_quant_bits)
    aux = jnp.float32(0.0)
    if "wg" in p:  # MoE layer (reference: deepspeed/moe/layer.py MoE)
        from deepspeed_tpu.moe.sharded_moe import moe_ffn
        from deepspeed_tpu.moe.mappings import drop_tokens, gather_tokens
        from deepspeed_tpu.parallel.context import current_plan
        with jax.named_scope("moe"):
            moe_params = {"wg": p["wg"], "w_in": p["moe_w_in"],
                          "w_out": p["moe_w_out"]}
            if "moe_w_gate" in p:
                moe_params["w_gate"] = p["moe_w_gate"]
            plan = current_plan()
            tp_moe = plan is not None and getattr(plan, "tensor", 1) > 1
            if tp_moe:
                # split tokens across the TP group for the gate/dispatch
                # region (reference: moe/mappings.py drop/gather around MoE)
                h = drop_tokens(h, dim=1)
            moe_out, aux = moe_ffn(moe_params, h, cfg, rng=dropout_rng,
                                   train=not deterministic)
            if tp_moe:
                moe_out = gather_tokens(moe_out, dim=1)
            if "w_in" in p:  # PR-MoE residual (reference: use_residual)
                up = _wmat(h, p["w_in"])
                if "b_in" in p:
                    up = up + p["b_in"].astype(h.dtype)
                gate = (_wmat(h, p["w_gate"])
                        if "w_gate" in p else None)
                dense_out = _wmat(_activation(up, gate, cfg), p["w_out"])
                if "b_out" in p:
                    dense_out = dense_out + p["b_out"].astype(h.dtype)
                coef = jax.nn.softmax(
                    (h @ p["moe_coef"].astype(h.dtype)).astype(jnp.float32),
                    axis=-1)
                out = dense_out * coef[..., 0:1].astype(h.dtype) + \
                    moe_out * coef[..., 1:2].astype(h.dtype)
            else:
                out = moe_out
    elif "w_in_gate" in p:
        # fused up+gate projection (see fuse_layer_stack)
        with jax.named_scope("mlp"):
            ug = _wmat(h, p["w_in_gate"])
            half = ug.shape[-1] // 2
            act = _activation(ug[..., :half], ug[..., half:], cfg)
            out = _wrow(act, p["w_out"])
            if "b_out" in p:
                out = out + p["b_out"].astype(h.dtype)
    else:
        with jax.named_scope("mlp"):
            up = _wmat(h, p["w_in"])
            if "b_in" in p:
                up = up + p["b_in"].astype(h.dtype)
            gate = _wmat(h, p["w_gate"]) if "w_gate" in p else None
            act = _activation(up, gate, cfg)
            out = _wrow(act, p["w_out"])
            if "b_out" in p:
                out = out + p["b_out"].astype(h.dtype)
    if "ln2_post_scale" in p:
        out = _norm(out, p["ln2_post_scale"], None, cfg)
    if cfg.parallel_block:
        x = (x + _dropout(attn_out, cfg, dropout_rng, deterministic, 0)
             + _dropout(out, cfg, dropout_rng, deterministic, 1))
    else:
        x = x + _dropout(out, cfg, dropout_rng, deterministic, 1)
        if post:
            x = _norm(x, p["ln2_scale"], p.get("ln2_bias"), cfg)
    if cache is not None or return_kv:
        return x, aux, new_kv
    return x, aux


def _dropout(x, cfg, rng, deterministic, salt: int):
    if deterministic or cfg.dropout_rate == 0.0 or rng is None:
        return x
    rng = jax.random.fold_in(rng, salt)
    keep = 1.0 - cfg.dropout_rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0).astype(x.dtype)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

# what XLA may keep of a rematerialised block, by `remat_policy`'s names
_REMAT_POLICIES = {
    "none": jax.checkpoint_policies.nothing_saveable,   # with remat=True
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    "save_nothing": jax.checkpoint_policies.nothing_saveable,
    "dots_with_no_batch_dims":
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "offload_dots": jax.checkpoint_policies.offload_dot_with_no_batch_dims(
        "device", "pinned_host"),
}
# what a flash kernel hands its own backward (ops/flash_attention._flash_fwd,
# _flash_band_fwd name them)
_KERNEL_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse")


def _remat_policy(cfg: TransformerConfig):
    """The `jax.checkpoint` policy of a block, None where nothing is
    rematerialised.

    A policy speaks of what XLA can recompute from the block's input; what a
    Pallas kernel hands its own backward is kept under every one. To
    `jax.checkpoint` a flash forward is one more op to replay (a custom-vjp
    call is no dot), so under the bare policies the backward ran the whole
    online-softmax kernel a second time for O and the log-sum-exp the first
    call had already written: 9.8 ms of Mellum's 8k-token full layer, 4.4 of
    a banded one (PERF.md section 6, PR 58). Keeping them costs B x S x N x D
    in the activations' type + B x N x S float32 a layer and micro-batch; the
    replay grows with S^2 and the bytes with S, so the trade has one sign at
    every shape a flash kernel takes and no caller chooses. With
    attention_impl="xla", and on the ring and sparse paths, nothing is named
    and the policy is the bare one.

    The join is written out because `save_from_both_policies` refuses the
    marks `offload_dots` returns (`Offloadable` / `Recompute`, no bools):
    a kept name is True, everything else is the named policy's answer."""
    if cfg.remat_policy in ("none", None) and not cfg.remat:
        return None
    try:
        base = _REMAT_POLICIES[cfg.remat_policy or "none"]
    except KeyError:
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}: one of "
            f"{sorted(_REMAT_POLICIES)}") from None

    def policy(prim, *args, **params):
        return (_KERNEL_RESIDUALS(prim, *args, **params)
                or base(prim, *args, **params))

    return policy


def _hold_expert_stacks(layers: Params, cfg: TransformerConfig,
                        deterministic: bool = True):
    """(layers without the expert stacks, the expert stacks) for a layer
    scan of a dropless MoE at inference that may see MANY tokens (a prompt
    in ``forward``, a chunk in ``decode_span_paged``): the scan slices
    everything else per layer, and ``_held_layer`` hands the expert stacks
    to the layer WHOLE with the layer's index (``_moe.LayerOf``), because
    the grouped-matmul kernel would otherwise be fed a fresh copy of the
    layer's experts (2.4 GB a layer at OLMoE's widths). A one-token-a-slot
    step hands them whole BESIDE its slices (``decode_step_paged``: it
    reaches the kernel only where its rows are expected to touch few
    experts); training keeps its slices: a whole stack closed over by a
    scan body gets a whole-stack cotangent per iteration."""
    if not (deterministic and cfg.num_experts > 1 and not cfg.drop_tokens
            and not cfg.quantized_weights and not cfg.offload_params):
        return layers, {}
    held = {k: v for k, v in layers.items() if k.startswith("moe_w_")}
    return {k: v for k, v in layers.items() if k not in held}, held


def _held_layer(layer_p: Params, held: Params, i) -> Params:
    if not held:
        return layer_p
    return {**layer_p, **{k: _moe.LayerOf(v, i) for k, v in held.items()}}


def _fetch_layer(layer_p, cfg: TransformerConfig):
    """ZeRO-Infinity param residency: move ONE layer's weights host -> HBM.
    Inside the remat region backward re-fetches instead of keeping them live.
    Host copies stay fp32 (sub-word host DMA is broken on some TPU
    transports); cast to compute dtype after the transfer. NOTE for decode:
    this runs per generated token — offloaded decode is host-DMA-bound."""
    from jax.memory import Space
    return jax.tree.map(
        lambda a: jax.device_put(a, Space.Device).astype(cfg.dtype), layer_p)


def _pass_end(x, params: Params, cfg: TransformerConfig):
    """What ends each pass of a looped stack (``looped.walk``): the final
    norm — its output is what the next pass starts from, and the last pass's
    goes to the head, which therefore norms nothing again — and the exit
    gate's value on it, for a listening engine (else None)."""
    x = _norm(x, params["final_norm_scale"], params.get("final_norm_bias"),
              cfg)
    return x, _looped.gate(x, params)


def _walk_layers(body, x, params: Params, cfg: TransformerConfig):
    """``looped.walk`` for a decode walk: ``body(x, i, t)`` over the layers'
    indices (the weights at ``i``, the cache at ``looped.plane(i, t, L)``),
    once per pass."""
    return _looped.walk(body, x, cfg.num_layers, cfg.ut_steps,
                        lambda x: _pass_end(x, params, cfg))


def _head_norm(x, params: Params, cfg: TransformerConfig):
    """The final norm ahead of the head. A looped stack's last pass has
    applied it (``_pass_end``)."""
    if cfg.final_norm and cfg.ut_steps == 1:
        x = _norm(x, params["final_norm_scale"],
                  params.get("final_norm_bias"), cfg)
    return x


def forward(params: Params, input_ids, cfg: TransformerConfig, *,
            attention_mask=None, positions=None, token_type_ids=None,
            dropout_rng=None,
            deterministic: bool = True, layer_override=None,
            return_aux: bool = False, return_kv: bool = False,
            return_hidden: bool = False, pld_theta=None,
            inputs_embeds=None, segment_ids=None):
    """input_ids: [B, S] int32 -> logits [B, S, vocab] (in fp32).

    return_kv: also return the per-layer (post-rotary) K/V stacked on a
    leading layer dim — the prefill path's cache seed. token_type_ids:
    segment ids for encoder models (type_vocab_size > 0); None -> zeros.
    inputs_embeds: pre-computed [B, S, H] embeddings instead of a token
    lookup (vision towers / soft prompts); positions still apply.
    segment_ids: int [B, S], several sequences packed into a row: attention
    stays inside each (``attention``); the caller restarts ``positions``."""
    if cfg.block_pattern:
        if segment_ids is not None:
            raise NotImplementedError(
                "segment_ids on a hybrid stack: its recurrent blocks scan "
                "the whole row from a zero state")
        from deepspeed_tpu.models import hybrid
        return hybrid.forward(
            params, input_ids, cfg, deterministic=deterministic,
            dropout_rng=dropout_rng, return_aux=return_aux,
            return_hidden=return_hidden, positions=positions,
            attention_mask=attention_mask, token_type_ids=token_type_ids,
            layer_override=layer_override, return_kv=return_kv,
            pld_theta=pld_theta, inputs_embeds=inputs_embeds)
    with jax.named_scope("embed"):
        if inputs_embeds is not None:
            B, S = inputs_embeds.shape[:2]
            x = inputs_embeds.astype(cfg.dtype)
        else:
            B, S = input_ids.shape
            x = params["tok_embed"][input_ids].astype(cfg.dtype)
        if cfg.position_type == "learned":
            pos = positions if positions is not None else jnp.arange(S)[None]
            x = x + params["pos_embed"][pos].astype(cfg.dtype)
        if "tok_type_embed" in params:
            tt = (token_type_ids if token_type_ids is not None
                  else jnp.zeros((B, S), jnp.int32))
            x = x + params["tok_type_embed"][tt].astype(cfg.dtype)
        if cfg.embed_norm:
            x = _norm(x, params["embed_norm_scale"],
                      params.get("embed_norm_bias"), cfg)
        x = _constrain_batch_axes(x)

    layers = layer_override if layer_override is not None else params["layers"]

    # per-layer local-attention windows ride the scan xs as a traced [L]
    # operand (a static per-layer mask would force unrolling the stack).
    # COST: under scan every layer sees a traced window and takes the
    # O(S^2) XLA attention path — including global (w=0) layers. For
    # alternating-window models (GPT-Neo, Mistral-style) set
    # scan_layers=False: the unrolled path below passes each layer its
    # STATIC window, so global layers keep the flash/Pallas kernel.
    if cfg.attn_windows and len(cfg.attn_windows) != cfg.num_layers:
        raise ValueError(f"attn_windows has {len(cfg.attn_windows)} entries "
                         f"for {cfg.num_layers} layers")
    wins = (jnp.asarray(cfg.attn_windows, jnp.int32)
            if cfg.attn_windows else None)

    layers, held = _hold_expert_stacks(layers, cfg, deterministic)
    if held:        # the layer's index rides the scan with its slices
        layers = {**layers, "_layer": jnp.arange(
            jax.tree.leaves(layers)[0].shape[0])}

    def body(carry, xs):
        layer_p, w = xs if wins is not None else (xs, None)
        if held:
            layer_p = dict(layer_p)
            layer_p = _held_layer(layer_p, held, layer_p.pop("_layer"))
        x_c, rng, aux_acc = carry
        if cfg.offload_params:
            layer_p = _fetch_layer(layer_p, cfg)
        if rng is not None:
            rng, sub = jax.random.split(rng)
        else:
            sub = None
        with _moe.layer_load_tap() as tap:
            out = transformer_layer(x_c, layer_p, cfg, mask=attention_mask,
                                    positions=positions, dropout_rng=sub,
                                    deterministic=deterministic,
                                    return_kv=return_kv, attn_window=w,
                                    segment_ids=segment_ids)
        if return_kv:
            y, aux, kv = out
        else:
            (y, aux), kv = out, None
        if tap is not None:     # a serving prefill reads the expert load
            kv = (kv, tap.stacked())
        return (y, rng, aux_acc + aux), kv

    if cfg.remat or cfg.remat_policy not in ("none", None):
        policy = _remat_policy(cfg)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    use_ltd = (cfg.random_ltd and cfg.random_ltd_keep > 0
               and not deterministic and dropout_rng is not None
               and not return_kv)
    use_pld = (cfg.progressive_layer_drop and pld_theta is not None
               and not deterministic and dropout_rng is not None
               and not return_kv and not use_ltd)
    if use_pld and not cfg.scan_layers:
        raise NotImplementedError("progressive_layer_drop requires "
                                  "scan_layers=True")
    if cfg.ut_steps > 1 and (use_pld or use_ltd or not cfg.scan_layers):
        raise _looped.LoopedModelUnsupported(
            "an unrolled, token-dropping or layer-dropping walk")
    aux_total = jnp.float32(0.0)
    kv_stack = None
    listening = _moe.expert_load_wanted()
    if cfg.scan_layers and use_pld:
        L = jax.tree.leaves(layers)[0].shape[0]
        theta = jnp.asarray(pld_theta, jnp.float32)

        def pld_body(carry, xs):
            lxs, li = xs
            # deeper layers drop more: keep = 1 - (i+1)/L * (1 - theta)
            keep_p = 1.0 - (li + 1).astype(jnp.float32) / L * (1.0 - theta)
            coin = jax.random.bernoulli(
                jax.random.fold_in(dropout_rng, 7919 + li), keep_p)
            # real branch (collective-free): a dropped layer costs nothing
            return lax.cond(coin, lambda c: body(c, lxs),
                            lambda c: (c, None), carry)

        with jax.named_scope("layers"):
            (x, _, aux_total), kv_stack = lax.scan(
                pld_body, (x, dropout_rng, aux_total),
                ((layers, wins) if wins is not None else layers,
                 jnp.arange(L)))
    elif cfg.scan_layers and not use_ltd:
        # "layers" scope: under scan every layer shares the one traced body,
        # so the trace join attributes the stack in aggregate (per-layer
        # splits need scan_layers=False — the unrolled path names each one).
        # A looped stack scans the same slices once per pass; its K/V come
        # back one plane per (pass, layer)
        def end(c):
            x_n, lam = _pass_end(c[0], params, cfg)
            return (x_n,) + c[1:], lam

        (x, _, aux_total), kv_stack = _looped.walk(
            lambda c, xs, t: body(c, xs), (x, dropout_rng, aux_total),
            (layers, wins) if wins is not None else layers, cfg.ut_steps,
            end)
    else:
        n_layers = jax.tree.leaves(layers)[0].shape[0]
        carry = (x, dropout_rng, aux_total)
        kvs = []
        for i in range(n_layers):
            layer_p = jax.tree.map(lambda a: a[i], layers)
            if use_ltd and 1 <= i < n_layers - 1:
                from deepspeed_tpu.runtime.data_pipeline import (
                    random_ltd_layer)
                x_c, rng, aux_acc = carry
                rng, sub, sel_rng = jax.random.split(rng, 3)
                win_i = (cfg.attn_windows[i] or None) if cfg.attn_windows \
                    else None

                def ltd_step(x_in, lp):
                    if cfg.offload_params:
                        lp = _fetch_layer(lp, cfg)

                    def layer_fn(xs, positions=None, mask=None):
                        return transformer_layer(
                            xs, lp, cfg, mask=mask, positions=positions,
                            dropout_rng=sub, deterministic=deterministic,
                            attn_window=win_i)

                    return random_ltd_layer(
                        x_in, layer_fn, cfg.random_ltd_keep, sel_rng,
                        positions=positions, mask=attention_mask)

                if cfg.remat or cfg.remat_policy not in ("none", None):
                    ltd_step = jax.checkpoint(ltd_step,
                                              policy=_remat_policy(cfg),
                                              prevent_cse=False)
                y, aux = ltd_step(x_c, layer_p)
                carry, kv = (y, rng, aux_acc + aux), None
            else:
                # unrolled layers take the STATIC per-layer window (0 ->
                # None, as decode_step_suffix does) so global layers keep
                # the flash/Pallas kernel instead of paying the windowed
                # XLA path for a band mask they don't have
                win_i = ((cfg.attn_windows[i] or None)
                         if cfg.attn_windows else None)
                with jax.named_scope(f"layer{i}"):
                    carry, kv = body(
                        carry, (layer_p, win_i) if wins is not None
                        else layer_p)
            kvs.append(kv)
        x, aux_total = carry[0], carry[2]
        if return_kv or listening:
            kv_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)
    if listening:
        kv_stack, loads = kv_stack
        _moe.record_expert_load(loads)

    x = _head_norm(x, params, cfg)
    if return_hidden:
        return x, aux_total
    with jax.named_scope("lm_head"):
        logits = lm_head_logits(x, params)
    if return_kv:
        return logits, kv_stack
    if return_aux:
        return logits, aux_total
    return logits


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fwd_only_constraint(x, spec):
    return jax.lax.with_sharding_constraint(x, spec)


def _fwd_only_constraint_fwd(x, spec):
    return _fwd_only_constraint(x, spec), None


def _fwd_only_constraint_bwd(spec, _, g):
    # the cotangent stays unconstrained: transposing the constraint onto
    # d(logits) forces the partitioner into a copy it can only realize by
    # involuntary full rematerialization on some fsdp x tensor meshes
    # (observed at fsdp=2 x tensor=2), and the backward contraction
    # partitions fine on its own
    return (g,)


_fwd_only_constraint.defvjp(_fwd_only_constraint_fwd,
                            _fwd_only_constraint_bwd)


def _constrain_tied_logits(logits):
    """Pin tied-head logits' vocab dim to the embedding table's own axes.

    On fsdp x tensor meshes the stage-3 rules shard the table's vocab dim
    over BOTH axes. Left to itself the partitioner tries to re-shard the
    table for the head contraction (vocab-(fsdp, tensor) -> embed-tensor)
    inside the microbatch loop — a mixed-axes tile reordering it can only
    do by involuntary full rematerialization (the r5 MULTICHIP DIAGNOSIS).
    Constraining the output's vocab dim to the same (fsdp, tensor) order
    keeps the table stationary: each shard contracts its vocab slice
    against the (small, all-gathered) hidden states, and the CE's
    logsumexp/one-hot reductions already partition over a sharded vocab.
    Only the failing combination is pinned — single-axis meshes keep the
    strategy the partitioner picks on its own."""
    from deepspeed_tpu.parallel.context import physical_mesh_env
    env_mesh, shape, bound = physical_mesh_env()
    if env_mesh is None or env_mesh.size == 1:
        return logits
    vocab_axes = tuple(a for a in ("fsdp", "tensor")
                       if shape.get(a, 1) > 1 and a not in bound)
    if len(vocab_axes) < 2:   # single-axis meshes partition this fine
        return logits
    denom = 1
    for a in vocab_axes:
        denom *= shape[a]
    if logits.shape[-1] % denom:
        return logits
    spec = (None,) * (logits.ndim - 1) + (vocab_axes,)
    return _fwd_only_constraint(logits, P(*spec))


def tied_head_logits(x, table):
    """fp32 logits from the UNtransposed [V, H] embedding table, contracted
    on its embed dim + the fwd-only vocab constraint. THE tied-head
    contraction — every site (full forward, decode, pipeline head, chunked
    CE, infinity top block) must go through here: materializing
    ``table.T`` instead makes GSPMD re-shard the (vocab, embed)-sharded
    table on fsdp x tensor meshes, an involuntary full rematerialization
    every step (the r5 MULTICHIP DIAGNOSIS)."""
    logits = lax.dot_general(
        x, table.astype(x.dtype),
        (((x.ndim - 1,), (1,)), ((), ()))).astype(jnp.float32)
    return _constrain_tied_logits(logits)


def lm_head_logits(x, params):
    """Final projection to fp32 vocab logits, shared by every head site.

    Tied models contract the embedding table directly (tied_head_logits);
    the untransposed contraction partitions natively — each shard contracts
    its slice and SPMD inserts the one reduction the math needs.
    """
    head = params.get("lm_head")
    if head is not None:
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    else:
        logits = tied_head_logits(x, params["tok_embed"])
    if "lm_head_bias" in params:
        logits = logits + params["lm_head_bias"].astype(jnp.float32)
    return logits


def _gold_logit(logits, safe_labels):
    """logits[..., safe_labels] via a one-hot contraction, not a gather.

    take_along_axis differentiates to a scatter-add, which XLA SPMD cannot
    partition when the vocab axis is tensor-sharded — it replicates the full
    [B,S,V] f32 tensor every step ("Involuntary full rematerialization").
    The one-hot masked reduction keeps the contraction local to each vocab
    shard (each chip sums its chunk, SPMD inserts one psum of [B,S]), and its
    transpose is a broadcast-multiply, which shards cleanly. Exact for f32:
    the mask selects a single element, no summation error. where() rather
    than a one-hot multiply: 0 * inf = NaN, so -inf-masked vocab entries
    would silently NaN the loss under a multiply-by-mask.
    """
    iota = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    picked = jnp.where(iota == safe_labels[..., None], logits,
                       jnp.zeros((), logits.dtype))
    return jnp.sum(picked, axis=-1)


def cross_entropy_loss(logits, labels, ignore_index: int = -100):
    """Mean next-token CE. logits [B,S,V] fp32; labels [B,S] (already aligned —
    caller shifts, or pass input_ids as labels and we shift here via
    lm_loss)."""
    V = logits.shape[-1]
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = _gold_logit(logits, safe_labels)
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


# --------------------------------------------------------------------------
# KV-cache decode (reference: csrc/transformer/inference/includes/
# inference_context.h — the fixed workspace the decode kernels write K/V
# into — and model_implementations/transformers/ds_transformer.py:18)
# --------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch_size: int, max_len: int,
               dtype=None) -> Params:
    """Preallocated KV buffers [planes, B, n_kv, max_len, head_dim] + cursor
    (``cfg.kv_planes``: a plane per layer, and per pass of a looped stack).

    Fixed shapes so prefill/decode each compile exactly once; the kv-head dim
    carries the "heads" logical axis so TP shards the cache like the weights.
    Sequence-major last two dims ([T, hd]) give the decode kernel legal
    (sublane, lane) tiles without a transpose.

    kv_cache_bits=8: buffers are int8 with per-(b, head, t) f32 scales —
    attention reads half the bytes (see _quant_kv / _decode_attention).
    """
    dtype = dtype or cfg.dtype
    L, nkv, hd = cfg.kv_planes, cfg.kv_heads, cfg.dim_per_head
    out = {"index": jnp.zeros((), jnp.int32)}
    if cfg.kv_cache_bits == 8:
        out["k"] = jnp.zeros((L, batch_size, nkv, max_len, hd), jnp.int8)
        out["v"] = jnp.zeros((L, batch_size, nkv, max_len, hd), jnp.int8)
        out["k_scale"] = jnp.zeros((L, batch_size, nkv, max_len),
                                   jnp.float32)
        out["v_scale"] = jnp.zeros((L, batch_size, nkv, max_len),
                                   jnp.float32)
    else:
        out["k"] = jnp.zeros((L, batch_size, nkv, max_len, hd), dtype)
        out["v"] = jnp.zeros((L, batch_size, nkv, max_len, hd), dtype)
    return out


def cache_logical_axes(cfg: Optional[TransformerConfig] = None) -> Params:
    out = {"k": ("layers", "batch", "heads", None, None),
           "v": ("layers", "batch", "heads", None, None),
           "index": None}
    if cfg is not None and cfg.kv_cache_bits == 8:
        out["k_scale"] = ("layers", "batch", "heads", None)
        out["v_scale"] = ("layers", "batch", "heads", None)
    return out


def _quant_kv(x):
    """Per-(…, position) symmetric int8: x [..., T, D] float ->
    (int8 [..., T, D], f32 scale [..., T]). The scale multiplies OUT of the
    d-contraction, so both attention einsums consume the int8 bytes
    directly (shared with the paged block pool — ops/quantizer)."""
    from deepspeed_tpu.ops.quantizer import quantize_rows
    return quantize_rows(x)


def _packed_row(starts, lengths, S: int):
    """What a row of S tokens holding several prompts is walked with.
    Segment k is rows ``[starts[k], starts[k] + lengths[k])`` (int32 [K],
    both traced; a segment of length 0 is not there), and the rows behind a
    prompt, up to the next start or the row's end, are its pad rows as a
    bucket's are one prompt's: in its segment, behind every real row.
    Returns (segment ids [1, S], positions [1, S] counted from each start,
    the real rows [1, S] bool, the last real row of each segment [1, S]
    bool, and those rows' indices [K])."""
    rows = jnp.arange(S)[None]
    last = starts + lengths - 1
    begun = (lengths > 0)[:, None] & (rows >= starts[:, None])       # [K, S]
    real = begun & (rows <= last[:, None])
    first = jnp.max(jnp.where(begun, starts[:, None], 0), axis=0)
    return ((jnp.sum(begun, axis=0, dtype=jnp.int32) - 1)[None],
            rows - first, jnp.any(real, axis=0)[None],
            jnp.any(real & (rows == last[:, None]), axis=0)[None],
            jnp.maximum(last, 0))


def prefill(params: Params, input_ids, cfg: TransformerConfig, cache: Params,
            attention_mask=None, length: Optional[int] = None, segments=None
            ) -> Tuple[jnp.ndarray, Params]:
    """Process the prompt, seed the cache, return logits at the last real
    position [B, V].

    The prompt K/V come out of the same scan that computes the logits (the ys
    of the layer scan), so prefill costs one forward pass. `length` marks the
    true prompt length when input_ids is right-padded for shape bucketing:
    causality keeps logits at length-1 exact, and the cursor is set so decode
    overwrites the pad rows before they can ever be attended.

    segments=(starts [K], lengths [K]) in place of ``length``: the ONE row
    holds several prompts (``_packed_row``), each attends to itself alone
    and counts its positions from its start, and the logits come back at
    every segment's last real position, [K, V] (whatever, for a segment of
    length 0).
    """
    S = input_ids.shape[1]
    packed = {}
    if segments is None:
        # traced length is fine: the index ops below are dynamic, so one
        # program serves every prompt length in the same padded-shape bucket
        true_len = jnp.asarray(S if length is None else length, jnp.int32)
        real = jnp.arange(S)[None] < true_len
        sampled = jnp.arange(S)[None] == true_len - 1
    else:
        if input_ids.shape[0] != 1 or length is not None:
            raise ValueError("segments share ONE row, and take the place "
                             "of length")
        starts, lengths = (jnp.asarray(a, jnp.int32) for a in segments)
        seg, pos, real, sampled, last_rows = _packed_row(starts, lengths, S)
        packed = {"segment_ids": seg, "positions": pos}
        true_len = jnp.max(starts + lengths)
    # an expert-load tap counts the real prompt tokens, not the bucket's pad
    # ... and an exit-gate tap the position whose logits are returned
    with _moe.counted_tokens(jnp.broadcast_to(real, input_ids.shape)), \
        _looped.counted_tokens(jnp.broadcast_to(sampled, input_ids.shape)):
        logits, kv = forward(params, input_ids, cfg,
                             attention_mask=attention_mask, return_kv=True,
                             **packed)
    k, v = kv  # [planes, B, S, nkv, hd] -> cache layout [planes, B, nkv, S, hd]
    k, v = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
    if cfg.kv_cache_bits == 8:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        new_cache = {
            "k": lax.dynamic_update_slice(cache["k"], kq, (0, 0, 0, 0, 0)),
            "v": lax.dynamic_update_slice(cache["v"], vq, (0, 0, 0, 0, 0)),
            "k_scale": lax.dynamic_update_slice(cache["k_scale"], ks,
                                                (0, 0, 0, 0)),
            "v_scale": lax.dynamic_update_slice(cache["v_scale"], vs,
                                                (0, 0, 0, 0)),
            "index": true_len,
        }
    else:
        new_cache = {
            "k": lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0)),
            "v": lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0)),
            "index": true_len,
        }
    if segments is not None:
        return logits[0, last_rows], new_cache
    last = lax.dynamic_index_in_dim(logits, true_len - 1, axis=1,
                                    keepdims=False)
    return last, new_cache


def decode_step(params: Params, token, cfg: TransformerConfig,
                cache: Params, read_len: Optional[int] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """One incremental decode step. token: [B] or [B,1] int32 -> logits [B, V].

    O(cache_len) per token (vs O(n^2) full recompute); the layer scan carries
    each layer's cache slice through `xs` and re-stacks the updated buffers.
    read_len: static upper bound on the valid prefix (index < read_len) —
    attention reads only that window of the ring buffer.
    """
    if token.ndim == 1:
        token = token[:, None]
    B = token.shape[0]
    index = cache["index"]
    x = params["tok_embed"][token].astype(cfg.dtype)
    if cfg.position_type == "learned":
        x = x + params["pos_embed"][index[None, None]].astype(cfg.dtype)
    if cfg.embed_norm:
        x = _norm(x, params["embed_norm_scale"],
                  params.get("embed_norm_bias"), cfg)
    positions = jnp.broadcast_to(index[None, None], (B, 1))

    int8_kv = cfg.kv_cache_bits == 8

    # The cache and the weight stack are CAPTURED and dynamically indexed
    # by the layer counter, NOT threaded through scan xs: scan operands get
    # staged into the loop's buffers, which copied the ENTIRE cache (and
    # weight stack) every token — measured as per-token cost scaling with
    # cache SIZE even when read_len was tiny. Captured arrays are read
    # in place via fused dynamic-slices.
    def at_layer(tree, i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree)

    wins = (jnp.asarray(cfg.attn_windows, jnp.int32)
            if cfg.attn_windows else None)

    def body(x_c, i, t):
        layer_p = at_layer(params["layers"], i)
        # the weights at layer i, the cache at the plane of (pass, layer)
        pl = _looped.plane(i, t, cfg.num_layers)
        ck = lax.dynamic_index_in_dim(cache["k"], pl, 0, keepdims=False)
        cv = lax.dynamic_index_in_dim(cache["v"], pl, 0, keepdims=False)
        if int8_kv:
            sc = (lax.dynamic_index_in_dim(cache["k_scale"], pl, 0,
                                           keepdims=False),
                  lax.dynamic_index_in_dim(cache["v_scale"], pl, 0,
                                           keepdims=False))
            c = (ck, cv, index, read_len, sc)
        else:
            c = (ck, cv, index, read_len)
        if cfg.offload_params:
            layer_p = _fetch_layer(layer_p, cfg)
        y, _, (k_row, v_row) = transformer_layer(
            x_c, layer_p, cfg, positions=positions, deterministic=True,
            cache=c, return_kv=False,
            attn_window=None if wins is None else wins[i])
        return y, (k_row, v_row)

    x, (k_rows, v_rows) = _walk_layers(body, x, params, cfg)
    # one tiny [planes, B, nkv, 1, hd] column write — the ring buffers update
    # in place (XLA aliases the dus when the cache is a loop carry /
    # donated input), instead of the scan re-stacking full buffers
    if int8_kv:
        kq, ks_ = _quant_kv(k_rows)
        vq, vs_ = _quant_kv(v_rows)
        new_k = lax.dynamic_update_slice(cache["k"], kq,
                                         (0, 0, 0, index, 0))
        new_v = lax.dynamic_update_slice(cache["v"], vq,
                                         (0, 0, 0, index, 0))
        new_scales = {
            "k_scale": lax.dynamic_update_slice(cache["k_scale"], ks_,
                                                (0, 0, 0, index)),
            "v_scale": lax.dynamic_update_slice(cache["v_scale"], vs_,
                                                (0, 0, 0, index)),
        }
    else:
        new_k = lax.dynamic_update_slice(cache["k"], k_rows,
                                         (0, 0, 0, index, 0))
        new_v = lax.dynamic_update_slice(cache["v"], v_rows,
                                         (0, 0, 0, index, 0))
    x = _head_norm(x, params, cfg)
    logits = lm_head_logits(x, params)
    new_cache = {"k": new_k, "v": new_v, "index": index + 1}
    if int8_kv:
        new_cache.update(new_scales)
    return logits[:, 0, :], new_cache


def init_suffix(cfg: TransformerConfig, batch_size: int, seg_len: int,
                cache: Optional[Params] = None) -> Params:
    """Per-segment suffix buffers for two-level decode: the current
    segment's K/V rows + a written-row count. Small enough
    ([L, B, nkv, seg, hd]) that carrying it through the token scan costs
    O(seg) per token instead of the ring buffer's O(T). Float caches keep
    the suffix in the CACHE's dtype (merge is a plain cast-free write);
    int8 caches keep it in compute dtype (merge quantizes)."""
    if cfg.ut_steps > 1:
        # decode_step_suffix unrolls its layers in Python: a looped stack
        # would unroll ut_steps x layers of them. generate() decodes a
        # looped model through decode_step (make_model offers no suffix)
        raise _looped.LoopedModelUnsupported("the two-level suffix decode")
    L, nkv, hd = cfg.num_layers, cfg.kv_heads, cfg.dim_per_head
    dtype = cfg.dtype
    if cache is not None and cache["k"].dtype != jnp.int8:
        dtype = cache["k"].dtype
    return {"k": jnp.zeros((L, batch_size, nkv, seg_len, hd), dtype),
            "v": jnp.zeros((L, batch_size, nkv, seg_len, hd), dtype),
            "count": jnp.zeros((), jnp.int32)}


def decode_step_suffix(params: Params, token, cfg: TransformerConfig,
                       cache: Params, suffix: Params,
                       read_len: Optional[int] = None
                       ) -> Tuple[jnp.ndarray, Params]:
    """One decode step against a FROZEN prefix cache + the segment suffix.

    ``cache`` is read-only here (a scan invariant — XLA double-buffers
    scan carries, so threading the full ring buffer through the token
    scan copied O(T) bytes per token; see BENCH r4's ctx-2048 cliff).
    Writes go to the small ``suffix`` carry; ``merge_suffix`` folds a
    finished segment into the prefix. Reference analogue: the fixed
    decode workspace of inference_context.h, which likewise never
    reallocates the big buffer inside the token loop.
    """
    if cfg.ut_steps > 1:
        raise _looped.LoopedModelUnsupported("the two-level suffix decode")
    if token.ndim == 1:
        token = token[:, None]
    B = token.shape[0]
    index = cache["index"] + suffix["count"]     # absolute position
    x = params["tok_embed"][token].astype(cfg.dtype)
    if cfg.position_type == "learned":
        x = x + params["pos_embed"][index[None, None]].astype(cfg.dtype)
    if cfg.embed_norm:
        x = _norm(x, params["embed_norm_scale"],
                  params.get("embed_norm_bias"), cfg)
    positions = jnp.broadcast_to(index[None, None], (B, 1))
    int8_kv = cfg.kv_cache_bits == 8
    count = suffix["count"]

    # STATIC python-unrolled layer loop: on this XLA stack dynamic-sliced
    # loop reads (scan xs, dynamic_index of captures) MATERIALIZE the full
    # per-layer cache slice every iteration — per-token cost scaled with
    # the BUFFER size, not the read window. Static slices fuse into the
    # attention einsums, so only the window bytes actually move.
    T_full = cache["k"].shape[3]
    W = read_len if read_len and read_len < T_full else T_full

    k_rows_l, v_rows_l = [], []
    for i in range(cfg.num_layers):
        layer_p = jax.tree.map(lambda a: a[i], params["layers"])
        ck = cache["k"][i, :, :, :W]
        cv = cache["v"][i, :, :, :W]
        sk = suffix["k"][i]
        sv = suffix["v"][i]
        sc = ((cache["k_scale"][i, :, :, :W],
               cache["v_scale"][i, :, :, :W]) if int8_kv else None)
        c = (ck, cv, index, None, sc, (sk, sv, count))
        if cfg.offload_params:
            layer_p = _fetch_layer(layer_p, cfg)
        x, _, (k_row, v_row) = transformer_layer(
            x, layer_p, cfg, positions=positions, deterministic=True,
            cache=c, return_kv=False,
            # `or None`: a static 0 (global layer) must not disable the
            # Pallas decode kernel / add a dead band mask
            attn_window=((cfg.attn_windows[i] or None)
                         if cfg.attn_windows else None))
        k_rows_l.append(k_row)
        v_rows_l.append(v_row)
    k_rows = jnp.stack(k_rows_l)
    v_rows = jnp.stack(v_rows_l)
    new_suffix = {
        "k": lax.dynamic_update_slice(suffix["k"], k_rows,
                                      (0, 0, 0, count, 0)),
        "v": lax.dynamic_update_slice(suffix["v"], v_rows,
                                      (0, 0, 0, count, 0)),
        "count": count + 1,
    }
    if cfg.final_norm:
        x = _norm(x, params["final_norm_scale"],
                  params.get("final_norm_bias"), cfg)
    logits = lm_head_logits(x, params)
    return logits[:, 0, :], new_suffix


def merge_suffix(cfg: TransformerConfig, cache: Params,
                 suffix: Params) -> Params:
    """Fold a finished segment's suffix rows into the prefix cache (one
    O(seg) write per SEGMENT, outside the token scan) and advance the
    cursor. int8 caches quantize the rows here."""
    index = cache["index"]
    new_cache = dict(cache)
    if cfg.kv_cache_bits == 8:
        kq, ks = _quant_kv(suffix["k"])
        vq, vs = _quant_kv(suffix["v"])
        new_cache["k"] = lax.dynamic_update_slice(cache["k"], kq,
                                                  (0, 0, 0, index, 0))
        new_cache["v"] = lax.dynamic_update_slice(cache["v"], vq,
                                                  (0, 0, 0, index, 0))
        new_cache["k_scale"] = lax.dynamic_update_slice(
            cache["k_scale"], ks, (0, 0, 0, index))
        new_cache["v_scale"] = lax.dynamic_update_slice(
            cache["v_scale"], vs, (0, 0, 0, index))
    else:
        new_cache["k"] = lax.dynamic_update_slice(
            cache["k"], suffix["k"].astype(cache["k"].dtype),
            (0, 0, 0, index, 0))
        new_cache["v"] = lax.dynamic_update_slice(
            cache["v"], suffix["v"].astype(cache["v"].dtype),
            (0, 0, 0, index, 0))
    new_cache["index"] = index + suffix["count"]
    return new_cache


# --------------------------------------------------------------------------
# Paged KV cache (serving tier): fixed-size blocks in a shared pool,
# per-sequence block tables, gather-based attention reads. The decode step
# compiles ONCE for the pool shape and admits variable-length multi-tenant
# batches — the vLLM idea on TPU (reference capability bar: the fixed decode
# workspace of inference_context.h, which this generalizes from one
# contiguous region per batch to a block pool shared across requests).
# --------------------------------------------------------------------------


def init_paged_cache(cfg: TransformerConfig, num_blocks: int,
                     block_size: int, dtype=None) -> Params:
    """Block pools, stored TOKEN-major: ``k``, ``v``
    [planes, NB, block_size, n_kv, head_dim] (``cfg.kv_planes``: a plane
    per layer, and per pass of a looped stack; written L below). One
    token's row is the whole
    (n_kv, head_dim) minor tile, so the row a decode step, a span or a
    prefill writes is scattered IN PLACE. Head-major
    ([.., n_kv, block_size, head_dim]) a row is one sub-tile line of every
    head, and the TPU compiler moved the WHOLE pool to another layout and
    back around every such write (4 x 1.6 GB a step at 16 x 1537 blocks,
    PERF.md §5-6, PR 24). The attention read gathers a layer's blocks out
    of the whole pool and contracts them token-major, as stored
    (``_gather_blocks``, ``_paged_list_attention``).

    Block 0 is the reserved TRASH block: null block-table entries point at
    it and inactive slots write into it, so the compiled step needs no
    scatter masking — trash contents are never read (masked by the
    per-slot length).

    kv_cache_bits=8: int8 payloads + per-(block, head, row) f32 scales in
    planes [L, NB, n_kv * block_size] (head-major within a block: 4-D
    planes are relayouted around the row write whatever the order of their
    axes, this one is written in place and pads no lanes) — the attention
    read consumes the int8 bytes directly with dequant fused into the score
    scaling (see _paged_list_attention / ops/quantizer).

    ``paged_blocks_to_logical`` / ``paged_blocks_from_logical`` translate
    whole blocks to and from the head-major order [.., n_kv, block_size,
    head_dim] that KV handoff payloads keep."""
    dtype = dtype or cfg.dtype
    L, nkv, hd = cfg.kv_planes, cfg.kv_heads, cfg.dim_per_head
    shape = (L, num_blocks, block_size, nkv, hd)
    if cfg.kv_cache_bits == 8:
        plane = (L, num_blocks, nkv * block_size)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(plane, jnp.float32),
                "v_scale": jnp.zeros(plane, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_cache_logical_axes(cfg: Optional[TransformerConfig] = None
                             ) -> Params:
    """TP shards the pool over kv heads exactly like the weights; the block
    dim stays unsharded (any block serves any sequence). A scale plane's
    last dim is head-major, so an even split of it is a split by heads."""
    out = {"k": ("layers", None, None, "heads", None),
           "v": ("layers", None, None, "heads", None)}
    if cfg is not None and cfg.kv_cache_bits == 8:
        out["k_scale"] = ("layers", None, "heads")
        out["v_scale"] = ("layers", None, "heads")
    return out


def paged_blocks_to_logical(blocks: Params) -> Params:
    """Pool blocks (any [L, n, ...] slice of the pool tree) in the
    head-major order of the KV handoff payload: ``k``, ``v``
    [L, n, n_kv, block_size, head_dim], scales [L, n, n_kv, block_size]."""
    L, n, bs, nkv, _ = blocks["k"].shape
    return {name: (a.transpose(0, 1, 3, 2, 4) if a.ndim == 5
                   else a.reshape(L, n, nkv, bs))
            for name, a in blocks.items()}


def paged_blocks_from_logical(blocks: Params) -> Params:
    """Inverse of ``paged_blocks_to_logical``."""
    return {name: (a.transpose(0, 1, 3, 2, 4) if a.ndim == 5
                   else a.reshape(a.shape[:2] + (-1,)))
            for name, a in blocks.items()}


def _scatter_rows(pools: Params, blk, off, rows: Params) -> Params:
    """Write one K/V row per (blk[i], off[i]) pair into every layer of the
    pool, in place. rows: ``k``, ``v`` [L, N, n_kv, head_dim] (+ ``k_scale``,
    ``v_scale`` [L, N, n_kv] for an int8 pool); blk, off: [N] int32."""
    bs, nkv = pools["k"].shape[2:4]
    out = {"k": pools["k"].at[:, blk, off].set(rows["k"]),
           "v": pools["v"].at[:, blk, off].set(rows["v"])}
    for name in sorted(set(rows) - {"k", "v"}):     # int8: the scale planes
        # one scatter per head: N scalars into the head's own lanes. ONE
        # scatter of all N * n_kv scalars is moved through a transposed
        # copy of the whole plane once N reaches a few hundred (a span)
        plane = pools[name]
        for h in range(nkv):
            plane = plane.at[:, blk, h * bs + off].set(rows[name][:, :, h])
        out[name] = plane
    return out


def decode_step_paged(params: Params, tokens, cfg: TransformerConfig,
                      pools: Params, block_tables, seq_lens, active=None,
                      backend: str = "xla", lora=None
                      ) -> Tuple[jnp.ndarray, Params]:
    """One decode step for every slot of a paged serving batch.

    tokens: [S] int32 (one in-flight token per slot); block_tables:
    [S, MB] int32, or a ``BlockList`` — the flat list of the blocks the
    slots hold, which sizes the XLA backend's read by their sum; seq_lens:
    [S] = tokens already in each slot's cache (the fresh row is written AT
    seq_lens); active: [S] bool (None = all). Returns (logits [S, V],
    pools). The program is shaped by the POOL and table (or list) dims
    only — admitting/evicting sequences changes their contents, never the
    compiled program.

    ``lora``: optional ``(adapter_pool, aidx)`` — ``adapter_pool`` maps
    projection name -> {"a": [L, NS, In, r], "b": [L, NS, r, Out]} device
    slot tables, ``aidx`` [S] int32 the adapter SLOT each serving slot
    reads (0 = the all-zero null adapter). Like the block pool, the
    compiled program is shaped by the slot-pool dims only — which
    adapters are resident changes table contents, never the program.

    Inactive slots still compute (lockstep SPMD) but their K/V rows land in
    the reserved trash block 0 and their logits are discarded host-side.
    """
    S = tokens.shape[0]
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    if active is None:
        active = jnp.ones((S,), jnp.bool_)
    # named scopes: the train forward's words (embed / layers / attn /
    # mlp | moe / lm_head), plus attn/kv_gather and attn/kv_write for the
    # pool traffic — a kernel's instruction name follows its scope
    with jax.named_scope("embed"):
        x = params["tok_embed"][tokens[:, None]].astype(cfg.dtype)  # [S,1,H]
        if cfg.position_type == "learned":
            x = x + params["pos_embed"][seq_lens][:, None].astype(cfg.dtype)
        if cfg.embed_norm:
            x = _norm(x, params["embed_norm_scale"],
                      params.get("embed_norm_bias"), cfg)
    positions = seq_lens[:, None]                                # [S, 1]
    int8_kv = cfg.kv_cache_bits == 8
    bs = pools["k"].shape[2]

    def at_layer(tree, i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree)

    wins = (jnp.asarray(cfg.attn_windows, jnp.int32)
            if cfg.attn_windows else None)

    _, held = _hold_expert_stacks(params["layers"], cfg)

    def body(x_c, i, t):
        layer_p = at_layer(params["layers"], i)
        # the expert stacks go WHOLE beside their slices: a step whose rows
        # are expected to reach few experts sorts (``_moe._sorts``) and its
        # kernel reads the layer's experts in place; one that keeps the
        # one-hot form takes the slices, as it always did
        layer_p = {**layer_p, **{k: _moe.LayerOf(v, i, layer_p[k])
                                 for k, v in held.items()}}
        # the WHOLE pools: the plane — the layer, and the pass of a looped
        # stack — is a coordinate of the read's gather
        sc = (pools["k_scale"], pools["v_scale"]) if int8_kv else None
        c = (pools["k"], pools["v"], seq_lens, None, sc)
        if cfg.offload_params:
            layer_p = _fetch_layer(layer_p, cfg)
        lora_i = None
        if lora is not None:
            apool, aidx = lora
            lora_i = ({k: (v["a"], v["b"])
                       for k, v in at_layer(apool, i).items()}, aidx)
        with _moe.layer_load_tap() as tap:
            y, _, (k_row, v_row) = transformer_layer(
                x_c, layer_p, cfg, positions=positions, deterministic=True,
                cache=c, return_kv=False,
                paged=(block_tables, backend,
                       _looped.plane(i, t, cfg.num_layers)),
                attn_window=None if wins is None else wins[i], lora=lora_i)
        return y, (k_row, v_row, tap and tap.stacked())

    # an expert-load tap and an exit-gate tap count the active slots: the
    # others compute in lockstep
    with _moe.counted_tokens(active), _looped.counted_tokens(active):
        x, (k_rows, v_rows, loads) = _walk_layers(body, x, params, cfg)
    _moe.record_expert_load(loads)
    # one [planes, S, nkv, hd] scatter writes every plane's fresh row at
    # (block_tables[s, len // bs], len % bs), a whole minor tile of the
    # token-major pool; inactive slots hit the trash block (duplicate trash
    # writes are unordered and never read)
    with jax.named_scope("attn"), jax.named_scope("kv_write"):
        blk = jnp.where(active, _block_at(block_tables, seq_lens // bs), 0)
        off = jnp.where(active, seq_lens % bs, 0)
        k_rows, v_rows = k_rows[:, :, :, 0], v_rows[:, :, :, 0]
        if int8_kv:
            kq, ks_ = _quant_kv(k_rows)          # [L, S, nkv, hd] + [L,S,nkv]
            vq, vs_ = _quant_kv(v_rows)
            rows = {"k": kq, "v": vq, "k_scale": ks_, "v_scale": vs_}
        else:
            rows = {"k": k_rows.astype(pools["k"].dtype),
                    "v": v_rows.astype(pools["v"].dtype)}
        new_pools = _scatter_rows(pools, blk, off, rows)
    with jax.named_scope("lm_head"):
        x = _head_norm(x, params, cfg)
        logits = lm_head_logits(x, params)
    return logits[:, 0, :], new_pools


def decode_span_paged(params: Params, tokens, cfg: TransformerConfig,
                      pools: Params, block_tables, seq_lens, active=None,
                      n_rows=None, backend: str = "xla", lora=None
                      ) -> Tuple[jnp.ndarray, Params]:
    """T consecutive tokens per slot in ONE pass — the latency-frontier
    program (ISSUE 12): the speculation verify step scores K+1 proposed
    tokens with one weight read, and a prefill chunk appends a prompt
    slice behind rows already in the pool (a prefix-cache hit or an
    earlier chunk).

    tokens: [S, T] int32 occupying positions ``seq_lens .. seq_lens+T-1``;
    returns (logits [S, T, V], pools) with each written token's K/V row
    scattered at its position. ``n_rows``: [S] rows actually WRITTEN per
    slot (default T) — a bucketed chunk's pad tokens beyond ``n_rows``
    compute garbage but land in the trash block, so padding can never
    overwrite live rows or run off the block table. Inactive slots behave
    as in ``decode_step_paged`` (lockstep compute, trash writes, host
    discards), and ``lora`` carries the same ``(adapter_pool, aidx)``
    slot tables — multi-adapter prefill chunks and verify spans reuse
    the identical gathered-einsum path. The caller owns cursor roll-back: rows past an accepted
    speculation prefix stay in place, masked by ``seq_lens`` until
    overwritten — shared (refcounted) blocks are never touched because
    the scheduler's copy-on-write fork runs before any span dispatch.

    With T == 1 this is arithmetically ``decode_step_paged``; the engine
    still dispatches the single-token program for K=0 so "speculation
    off" is the identical compiled artifact, not merely equal math.
    """
    S, T = tokens.shape
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    if active is None:
        active = jnp.ones((S,), jnp.bool_)
    if n_rows is None:
        n_rows = jnp.full((S,), T, jnp.int32)
    positions = seq_lens[:, None] + jnp.arange(T)[None, :]       # [S, T]
    with jax.named_scope("embed"):
        x = params["tok_embed"][tokens].astype(cfg.dtype)        # [S, T, H]
        if cfg.position_type == "learned":
            x = x + params["pos_embed"][positions].astype(cfg.dtype)
        if cfg.embed_norm:
            x = _norm(x, params["embed_norm_scale"],
                      params.get("embed_norm_bias"), cfg)
    int8_kv = cfg.kv_cache_bits == 8
    bs = pools["k"].shape[2]
    MB = block_tables.shape[1]

    def at_layer(tree, i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree)

    wins = (jnp.asarray(cfg.attn_windows, jnp.int32)
            if cfg.attn_windows else None)

    sliced, held = _hold_expert_stacks(params["layers"], cfg)

    def body(x_c, i, t):
        layer_p = _held_layer(at_layer(sliced, i), held, i)
        # the WHOLE pools: the plane is a coordinate of the read's gather
        sc = (pools["k_scale"], pools["v_scale"]) if int8_kv else None
        c = (pools["k"], pools["v"], seq_lens, None, sc)
        if cfg.offload_params:
            layer_p = _fetch_layer(layer_p, cfg)
        lora_i = None
        if lora is not None:
            apool, aidx = lora
            lora_i = ({k: (v["a"], v["b"])
                       for k, v in at_layer(apool, i).items()}, aidx)
        with _moe.layer_load_tap() as tap:
            y, _, (k_row, v_row) = transformer_layer(
                x_c, layer_p, cfg, positions=positions, deterministic=True,
                cache=c, return_kv=False,
                paged=(block_tables, backend,
                       _looped.plane(i, t, cfg.num_layers)),
                attn_window=None if wins is None else wins[i], lora=lora_i)
        # rows: [S, nkv, T, hd]
        return y, (k_row, v_row, tap and tap.stacked())

    # an expert-load tap counts the rows that are written: no pad token of a
    # bucketed chunk, no inactive slot
    counted = active[:, None] & (jnp.arange(T)[None, :] < n_rows[:, None])
    with _moe.counted_tokens(counted), _looped.counted_tokens(counted):
        x, (k_rows, v_rows, loads) = _walk_layers(body, x, params, cfg)
    _moe.record_expert_load(loads)
    # one [S*T]-row scatter writes every (slot, position) pair's fresh row
    # across all layers; pad/inactive rows route to the trash block 0
    # (duplicate trash writes are unordered and never read). Positions at
    # or past the table's row capacity ALSO go to trash: a verify step
    # within K tokens of a request's context cap would otherwise wrap its
    # clipped block index back INTO the slot's last block and clobber
    # valid history (such tokens are never committed — the budget check
    # finishes the request first — but their rows must not land).
    write = active[:, None] & (jnp.arange(T)[None, :] < n_rows[:, None]) \
        & (positions < MB * bs)
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, MB - 1), axis=1)
    blk = jnp.where(write, blk, 0).reshape(-1)
    off = jnp.where(write, positions % bs, 0).reshape(-1)

    def flat(a):           # [L, S, nkv, T(, hd)] -> [L, S*T, nkv(, hd)]
        a = jnp.swapaxes(a, 2, 3)
        return a.reshape((a.shape[0], S * T) + a.shape[3:])

    with jax.named_scope("attn"), jax.named_scope("kv_write"):
        if int8_kv:
            kq, ks_ = _quant_kv(k_rows)          # scales [L, S, nkv, T]
            vq, vs_ = _quant_kv(v_rows)
            rows = {"k": kq, "v": vq, "k_scale": ks_, "v_scale": vs_}
        else:
            rows = {"k": k_rows.astype(pools["k"].dtype),
                    "v": v_rows.astype(pools["v"].dtype)}
        new_pools = _scatter_rows(pools, blk, off, jax.tree.map(flat, rows))
    with jax.named_scope("lm_head"):
        return lm_head_logits(_head_norm(x, params, cfg), params), new_pools


def prefill_paged(params: Params, input_ids, cfg: TransformerConfig,
                  pools: Params, block_ids, length: Optional[int] = None,
                  segments=None) -> Tuple[jnp.ndarray, Params]:
    """Prefill ONE request and scatter its K/V into the slot's blocks.

    input_ids: [1, P] with P a multiple of the block size (shape-bucketed:
    one compile per bucket); block_ids: [P // bs] int32 pool blocks the
    scheduler allocated; length: true prompt length (pad rows land in the
    last blocks but are masked by seq_len and overwritten as decode
    appends). Returns (last_logits [1, V], pools). The contiguous prefill
    cache is a jit-local temporary — it never leaves the program.

    segments=(starts [K], lengths [K]) in place of ``length``: SEVERAL
    requests in the row (``prefill``), every start on a block's edge, so
    ``block_ids`` is their blocks one request after the other (the trash
    block behind the last) and the scatter is the same. Returns
    (last_logits [K, V], pools)."""
    B, P = input_ids.shape
    cache = init_cache(cfg, B, P)
    last, cache = prefill(params, input_ids, cfg, cache, length=length,
                          segments=segments)
    return last, _write_prefill_blocks(pools, block_ids, cache,
                                       cfg.kv_cache_bits == 8)


def _write_prefill_blocks(pools: Params, block_ids, cache: Params,
                          int8: bool) -> Params:
    """One request's contiguous prefill cache into its blocks of the pool:
    cache ``k``, ``v`` [L, 1, nkv, P, hd] (+ ``k_scale``, ``v_scale``
    [L, 1, nkv, P] for an int8 pool), P = len(block_ids) blocks. Returns the
    pool's K/V leaves."""
    bs = pools["k"].shape[2]
    nblk = cache["k"].shape[3] // bs

    def to_blocks(a):          # [L, 1, nkv, P, hd] -> [L, nblk, bs, nkv, hd]
        L_, _, nkv, _, hd = a.shape
        return jnp.swapaxes(a[:, 0], 1, 2).reshape(L_, nblk, bs, nkv, hd)

    def to_blocks_s(a):        # [L, 1, nkv, P] -> [L, nblk, nkv * bs]
        L_, _, nkv, _ = a.shape
        return (a[:, 0].reshape(L_, nkv, nblk, bs).swapaxes(1, 2)
                .reshape(L_, nblk, nkv * bs))

    # forward() carries embed / layers / attn / mlp | moe / lm_head
    with jax.named_scope("attn"), jax.named_scope("kv_write"):
        new_pools = {
            "k": pools["k"].at[:, block_ids].set(to_blocks(cache["k"])),
            "v": pools["v"].at[:, block_ids].set(to_blocks(cache["v"]))}
        if int8:
            new_pools["k_scale"] = pools["k_scale"].at[:, block_ids].set(
                to_blocks_s(cache["k_scale"]))
            new_pools["v_scale"] = pools["v_scale"].at[:, block_ids].set(
                to_blocks_s(cache["v_scale"]))
    return new_pools


def chunked_cross_entropy(x, head, labels, chunk: int,
                          ignore_index: int = -100,
                          tied_embed: bool = False):
    """CE over sequence chunks: the fp32 logits exist only chunk-at-a-time
    (the head matmul re-runs in backward via jax.checkpoint). x: [B,S,H]
    final hidden (already normed); head: [H,V] — or, with
    ``tied_embed=True``, the UNtransposed [V,H] embedding table contracted
    on its embed dim (see lm_head_logits: the explicit transpose forces an
    involuntary SPMD rematerialization on fsdp x tensor meshes)."""
    B, S, H = x.shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    n = S // c

    def proj(xc):
        if tied_embed:
            return tied_head_logits(xc, head)
        return (xc @ head.astype(xc.dtype)).astype(jnp.float32)

    def body(carry, i):
        tot, cnt = carry
        xc = lax.dynamic_slice_in_dim(x, i * c, c, axis=1)
        lc = lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
        logits = proj(xc)
        valid = lc != ignore_index
        safe = jnp.where(valid, lc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = _gold_logit(logits, safe)
        nll = (logz - gold) * valid
        return (tot + nll.sum(), cnt + valid.sum()), None

    body = jax.checkpoint(body, prevent_cse=False)
    (tot, cnt), _ = lax.scan(body, (jnp.float32(0.0), jnp.int32(0)),
                             jnp.arange(n))
    return tot / jnp.maximum(cnt, 1)


def lm_loss(params, batch, cfg: TransformerConfig, dropout_rng=None,
            deterministic: bool = True):
    """Standard causal-LM loss: predict token t+1 from prefix ≤ t."""
    ids = batch["input_ids"]
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((ids.shape[0], 1), -100, ids.dtype)], axis=1)
    mask = batch.get("attention_mask")
    pld_theta = batch.get("_pld_theta")
    if cfg.loss_chunk and cfg.loss_chunk > 0:
        x, aux = forward(params, ids, cfg, attention_mask=mask,
                         dropout_rng=dropout_rng,
                         deterministic=deterministic, return_hidden=True,
                         pld_theta=pld_theta)
        head = params.get("lm_head")
        tied = head is None
        if tied:
            head = params["tok_embed"]
        with jax.named_scope("loss"):
            loss = chunked_cross_entropy(x, head, labels, cfg.loss_chunk,
                                         tied_embed=tied)
    else:
        logits, aux = forward(params, ids, cfg, attention_mask=mask,
                              dropout_rng=dropout_rng,
                              deterministic=deterministic, return_aux=True,
                              pld_theta=pld_theta)
        with jax.named_scope("loss"):
            loss = cross_entropy_loss(logits, labels)
    if cfg.num_experts > 1:
        loss = loss + cfg.moe_aux_loss_weight * aux
    return loss


# --------------------------------------------------------------------------
# ModelSpec — what the engine consumes
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ModelSpec:
    """Bundle of pure functions + metadata; any model exposing this plugs into
    the engine (the reference's nn.Module contract equivalent)."""
    init: Callable[[Any], Params]
    loss_fn: Callable[..., jnp.ndarray]       # (params, batch, rng, deterministic)
    apply: Callable[..., jnp.ndarray]         # (params, input_ids, ...) -> logits
    logical_axes: Params
    config: Any = None
    name: str = "model"
    # KV-cache decode protocol (None -> InferenceEngine falls back to
    # full-recompute). init_cache(batch, max_len) -> cache;
    # prefill(params, ids, cache) -> (last_logits, cache);
    # decode_step(params, token, cache) -> (logits, cache).
    init_cache: Optional[Callable[..., Params]] = None
    prefill: Optional[Callable[..., Tuple[jnp.ndarray, Params]]] = None
    decode_step: Optional[Callable[..., Tuple[jnp.ndarray, Params]]] = None
    cache_axes: Optional[Callable[[], Params]] = None
    # two-level decode (frozen prefix + per-segment suffix carry); the
    # decode loop prefers these when present — carrying the full ring
    # buffer through the token scan copies O(T) bytes per token
    init_suffix: Optional[Callable[..., Params]] = None
    decode_step_suffix: Optional[Callable[..., Tuple[jnp.ndarray,
                                                     Params]]] = None
    merge_suffix: Optional[Callable[..., Params]] = None
    # paged serving protocol (block pool + block tables; the ServingEngine
    # consumes these): init_paged_cache(num_blocks, block_size, dtype=,
    # max_seqs=) -> pools;
    # prefill_paged(params, ids, pools, block_ids, length) ->
    # (last_logits, pools); decode_step_paged(params, tokens, pools,
    # block_tables, seq_lens, active, backend) -> (logits, pools).
    init_paged_cache: Optional[Callable[..., Params]] = None
    prefill_paged: Optional[Callable[..., Tuple[jnp.ndarray,
                                                Params]]] = None
    decode_step_paged: Optional[Callable[..., Tuple[jnp.ndarray,
                                                    Params]]] = None
    # latency-frontier span protocol (ISSUE 12): decode_span_paged(params,
    # tokens [S, T], pools, block_tables, seq_lens, active, n_rows,
    # backend) -> (logits [S, T, V], pools) — one pass over T consecutive
    # tokens per slot (speculation verify / chunked prefill). None ->
    # ServingEngine refuses spec decoding, chunked prefill and prefix
    # caching at config time.
    decode_span_paged: Optional[Callable[..., Tuple[jnp.ndarray,
                                                    Params]]] = None
    paged_cache_axes: Optional[Callable[[], Params]] = None
    # what the model keeps PER SERVING SLOT beside its K/V blocks: the names
    # of those leaves of init_paged_cache's tree, sized by its ``max_seqs``
    # (a recurrent kind's state before its convolution tail). Empty: a
    # request's state is its blocks alone, and only then may it be shared,
    # rolled back, resumed or shipped by them. Everything else about the
    # cache (shapes, dtypes, bytes) is ``jax.eval_shape`` of
    # init_paged_cache: ``inference/kv_cache.abstract_cache``.
    slot_leaves: Tuple[str, ...] = ()
    # rows of a window ring (0: none). The ring leaves are the per-slot
    # leaves the tree holds as a tuple, one array a window block.
    ring_rows: int = 0

    def flops_per_token(self) -> float:
        """Approximate train FLOPs/token (6N rule + attention)."""
        cfg = self.config
        if cfg is None:
            return 0.0
        n_params = (cfg.vocab_size * cfg.hidden_size * (1 if cfg.tie_embeddings else 2)
                    + cfg.num_layers * (
                        cfg.hidden_size * (cfg.num_heads + 2 * cfg.kv_heads) * cfg.dim_per_head
                        + cfg.num_heads * cfg.dim_per_head * cfg.hidden_size
                        + cfg.hidden_size * cfg.ffn_dim * (3 if "glu" in cfg.activation else 2)))
        attn = 6 * cfg.num_layers * cfg.hidden_size * cfg.max_seq_len  # rough
        return 6.0 * n_params + attn


def _common_spec(cfg: TransformerConfig, name: str) -> dict:
    """What every ModelSpec of this module has, whatever its cache."""
    return dict(
        init=lambda key: init_params(key, cfg),
        loss_fn=lambda params, batch, rng=None, deterministic=True:
            lm_loss(params, batch, cfg, dropout_rng=rng, deterministic=deterministic),
        apply=lambda params, input_ids, **kw: forward(params, input_ids, cfg, **kw),
        logical_axes=logical_axes(cfg),
        config=cfg,
        name=name)


def _make_hybrid_model(cfg: TransformerConfig, name: str) -> ModelSpec:
    """A model with a ``block_pattern`` (models/hybrid.py): the same
    ModelSpec, with the paged serving protocol over two kinds of state and
    no contiguous-cache protocol (``generate`` recomputes). No span
    protocol: a span that is rolled back (a rejected draft) or resumed (a
    prompt chunk, a shared prefix) needs a snapshot of the recurrent state,
    which nothing keeps yet — the serving engine refuses what would call it."""
    from deepspeed_tpu.models import hybrid
    hybrid.blocks(cfg)                         # a bad pattern fails here
    return ModelSpec(
        **_common_spec(cfg, name),
        init_paged_cache=lambda num_blocks, block_size, dtype=None, **kw:
            hybrid.init_paged_cache(cfg, num_blocks, block_size, dtype=dtype,
                                    **kw),
        prefill_paged=lambda params, input_ids, pools, block_ids, **kw:
            hybrid.prefill_paged(params, input_ids, cfg, pools, block_ids,
                                 **kw),
        decode_step_paged=lambda params, tokens, pools, block_tables,
            seq_lens, **kw:
            hybrid.decode_step_paged(params, tokens, cfg, pools,
                                     block_tables, seq_lens, **kw),
        paged_cache_axes=lambda: hybrid.paged_cache_logical_axes(cfg),
        slot_leaves=tuple(hybrid.state_leaves(cfg, 1))
        + tuple(hybrid.ring_leaves(cfg, 1)),
        ring_rows=hybrid.window(cfg) if cfg.window_blocks else 0,
    )


def make_model(cfg: TransformerConfig, name: str = "transformer") -> ModelSpec:
    _looped.check(cfg)
    _remat_policy(cfg)          # an unknown name raises here, not at a trace
    if cfg.rope_tables is not None and not cfg.block_pattern:
        raise NotImplementedError(
            "rope_tables states a rotary table per KIND of attention block "
            "of a hybrid (block_pattern) stack; a homogeneous stack has the "
            "one rope_theta")
    if cfg.hc_mult != 1 and not cfg.block_pattern:
        raise NotImplementedError(
            f"hc_mult={cfg.hc_mult}: a residual stream of several rows is "
            "the hybrid (block_pattern) walker's (models/hybrid.py); a "
            "homogeneous stack keeps one row a token")
    if cfg.block_pattern:
        return _make_hybrid_model(cfg, name)
    # the two-level suffix decode unrolls its layers: a looped model is
    # decoded through decode_step, whose cache has a plane per pass
    suffix = {} if cfg.ut_steps > 1 else dict(
        init_suffix=lambda batch_size, seg_len, cache=None:
            init_suffix(cfg, batch_size, seg_len, cache=cache),
        decode_step_suffix=lambda params, token, cache, suffix, **kw:
            decode_step_suffix(params, token, cfg, cache, suffix, **kw),
        merge_suffix=lambda cache, suffix: merge_suffix(cfg, cache, suffix))
    return ModelSpec(
        **_common_spec(cfg, name), **suffix,
        init_cache=lambda batch_size, max_len, dtype=None:
            init_cache(cfg, batch_size, max_len, dtype=dtype),
        prefill=lambda params, input_ids, cache, **kw:
            prefill(params, input_ids, cfg, cache, **kw),
        decode_step=lambda params, token, cache, **kw:
            decode_step(params, token, cfg, cache, **kw),
        cache_axes=lambda: cache_logical_axes(cfg),
        # max_seqs: for what a model keeps per serving slot — nothing here
        init_paged_cache=lambda num_blocks, block_size, dtype=None,
            max_seqs=None:
            init_paged_cache(cfg, num_blocks, block_size, dtype=dtype),
        prefill_paged=lambda params, input_ids, pools, block_ids, **kw:
            prefill_paged(params, input_ids, cfg, pools, block_ids, **kw),
        decode_step_paged=lambda params, tokens, pools, block_tables,
            seq_lens, **kw:
            decode_step_paged(params, tokens, cfg, pools, block_tables,
                              seq_lens, **kw),
        decode_span_paged=lambda params, tokens, pools, block_tables,
            seq_lens, **kw:
            decode_span_paged(params, tokens, cfg, pools, block_tables,
                              seq_lens, **kw),
        paged_cache_axes=lambda: paged_cache_logical_axes(cfg),
    )
