"""The Mistral family: its plain reference, its cost model, its toy widths.

A model FAMILY is a file found by the configuration's published
``model_type`` (``harness/loadgen.load_family``), like a traffic kind or a
per-layer reader. This one also serves Mixtral (``mixtral.py`` re-exports
it): the expert layer is chosen by what the parameter tree holds, the
operation and byte counts by ``num_local_experts``.

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")`` (on a TPU a float32 matmul otherwise runs in bf16 passes): no
kernel, no cache, no batching, no capacity. One sequence at a time, one
layer per call and — for the expert layer — one expert per call, so the
float32 copy that lives beside the engine's state is one matrix group, not
the model.

Follows the published descriptions (Mistral 7B, arXiv:2310.06825; Mixtral of
Experts, arXiv:2401.04088) as the HF ``modeling_mistral`` / ``modeling_mixtral``
code computes them:

- pre-norm RMSNorm (eps from the config), no biases;
- grouped-query attention, rotary embedding over the whole head in the
  "rotate-half" pairing (dims d and d + hd/2), theta from the config; causal;
  a key further than ``sliding_window`` behind the query is masked (only
  matters past the window);
- SwiGLU feed-forward: ``w_out(silu(w_gate x) * w_in x)``;
- Mixtral: router logits -> softmax over ALL experts -> top-2 ->
  renormalise the two weights to sum 1 -> weighted sum of the two experts'
  SwiGLU outputs. Dropless: every token reaches both of its experts.

Departures: none in the arithmetic. The parameter tree is the program's
(stacked on a leading layer dim, ``wq/wk/wv`` or fused ``wqkv``, ``w_in/w_gate``
or fused ``w_in_gate`` = [up | gate], experts as ``moe_w_in/moe_w_gate/moe_w_out``
[L, E, ...], router ``wg``): the reference reads the SAME stored values the
engine serves or trains with and upcasts them to float32.

2. Operations (``train_flops_per_token``, ``flash_flops``)
----------------------------------------------------------
Computed from the published config's shapes. Counts matrix-multiplication
work only: 2 FLOPs per multiply-add. The input embedding is a row lookup and does no matmul, so its table is NOT in the
count (``bench._count_params`` includes it: at 2 layers that is 131 M of
698 M parameters, an MFU overstated by ~18 %). The output head is a matmul
whether tied or not. Recomputed operations (remat replays) never count.

Attention is causal: a query at position i needs keys 0..i, so the required
work is HALF the S x S square. Every function here counts that half once
and says so; the PaLM "12 L H S" term counts the full square.

3. Bytes of a decode step (``decode_step_bytes``)
------------------------------------------------
A decode step reads every weight the batch touches once and the live KV
rows of every running sequence. With a full batch every expert of an MoE
layer is hit (32 tokens x top-2 over 8 experts: P(an expert idle) =
(6/8)^32 ~ 1e-4), so all experts count. Writes (one KV row per sequence)
are four orders of magnitude smaller and are left out.
"""
import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = functools.partial(jax.default_matmul_precision, "highest")


# --rehearsal: the same head grouping at toy widths (head_dim 64); the layer
# pattern has period 1, and a configuration's own expert count stays
TOY = {"vocab_size": 512, "hidden_size": 512, "intermediate_size": 512,
       "num_attention_heads": 8, "num_key_value_heads": 2,
       "num_hidden_layers": 2}


def dims(hf: dict):
    """(hidden, query heads, kv heads, head size) of a published config."""
    H = hf["hidden_size"]
    nh = hf["num_attention_heads"]
    nkv = hf.get("num_key_value_heads") or nh
    hd = hf.get("head_dim") or H // nh
    return H, nh, nkv, hd


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x: [S, n, hd]; positions 0..S-1; rotate-half pairing."""
    S, _, hd = x.shape
    half = hd // 2
    inv = jnp.exp(-jnp.arange(half, dtype=F32) * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _at(layers, key, i):
    return layers[key][i].astype(F32)


class Reference:
    """``Reference(hf, params)`` — ``hf`` is the published config dict (as
    run: the cut depth), ``params`` the program's parameter tree."""

    def __init__(self, hf: dict, params):
        self.hf = hf
        self.params = params
        self.L = int(params["layers"]["ln1_scale"].shape[0])
        self.moe = "wg" in params["layers"]
        self._attn = jax.jit(self._attn_block)
        self._ffn = jax.jit(self._dense_ffn)
        self._route = jax.jit(self._router)
        self._head = jax.jit(self._final)
        self._embed = jax.jit(lambda p, ids: p["tok_embed"][ids].astype(F32))
        self._residual = jax.jit(lambda x, y: x + y)
        self._add_expert = jax.jit(
            lambda layers, i, e, h, w, y:
            y + jnp.take(w, e, axis=1)[:, None] * self._one_expert(layers, i, e, h))

    # ---- pieces (each one jitted program, layer / expert index traced) ----

    def _attn_block(self, layers, i, x):
        hf = self.hf
        H, nh, nkv, hd = dims(hf)
        S = x.shape[0]
        h = _rms(x, _at(layers, "ln1_scale", i), hf["rms_norm_eps"])
        if "wqkv" in layers:
            qkv = h @ _at(layers, "wqkv", i)
            q, k, v = (qkv[:, :nh * hd], qkv[:, nh * hd:(nh + nkv) * hd],
                       qkv[:, (nh + nkv) * hd:])
        else:
            q = h @ _at(layers, "wq", i)
            k = h @ _at(layers, "wk", i)
            v = h @ _at(layers, "wv", i)
        theta = float(hf.get("rope_theta", 10000.0))
        q = _rope(q.reshape(S, nh, hd), theta).reshape(S, nkv, nh // nkv, hd)
        k = _rope(k.reshape(S, nkv, hd), theta)
        v = v.reshape(S, nkv, hd)
        s = jnp.einsum("sngd,tnd->ngst", q, k) / math.sqrt(hd)
        qi = jnp.arange(S)[:, None]
        kj = jnp.arange(S)[None, :]
        ok = kj <= qi
        win = hf.get("sliding_window")
        if win:
            ok = ok & (qi - kj < win)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("ngst,tnd->sngd", p, v).reshape(S, nh * hd)
        x = x + o @ _at(layers, "wo", i)
        return x, _rms(x, _at(layers, "ln2_scale", i), hf["rms_norm_eps"])

    def _dense_ffn(self, layers, i, h):
        if "w_in_gate" in layers:
            ug = h @ _at(layers, "w_in_gate", i)
            half = ug.shape[-1] // 2
            up, gate = ug[:, :half], ug[:, half:]
        else:
            up = h @ _at(layers, "w_in", i)
            gate = h @ _at(layers, "w_gate", i)
        return (jax.nn.silu(gate) * up) @ _at(layers, "w_out", i)

    def _router(self, layers, i, h):
        """[S, E] combine weights: softmax over all experts, top-k kept and
        renormalised, zero elsewhere."""
        k = self.hf["num_experts_per_tok"]
        probs = jax.nn.softmax(h @ _at(layers, "wg", i), axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=F32)   # [S,k,E]
        return jnp.einsum("sk,ske->se", top, onehot)

    def _one_expert(self, layers, i, e, h):
        up = h @ layers["moe_w_in"][i, e].astype(F32)
        gate = h @ layers["moe_w_gate"][i, e].astype(F32)
        return (jax.nn.silu(gate) * up) @ layers["moe_w_out"][i, e].astype(F32)

    def _final(self, params, x):
        x = _rms(x, params["final_norm_scale"].astype(F32),
                 self.hf["rms_norm_eps"])
        head = params.get("lm_head")
        if head is None:                       # tied
            return x @ params["tok_embed"].astype(F32).T
        return x @ head.astype(F32)

    # ---- whole forward ----------------------------------------------------

    def logits(self, ids, pad_to: int = 512):
        """ids: [S] int -> float32 logits [S, vocab] as a NUMPY array. The
        sequence is padded at its END to a multiple of ``pad_to`` (attention
        is causal, so no real position sees a pad) so that few shapes are
        ever compiled; padding and the cut back to S happen on the host,
        where a new length costs no new program."""
        import numpy as np
        params, layers = self.params, self.params["layers"]
        n = len(ids)
        padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        with _HIGHEST():
            x = self._embed(params, jnp.asarray(padded))
            for i in range(self.L):
                x, h = self._attn(layers, i, x)
                if self.moe:
                    w = self._route(layers, i, h)
                    y = jnp.zeros_like(x)
                    for e in range(w.shape[-1]):
                        y = self._add_expert(layers, i, e, h, w, y)
                else:
                    y = self._ffn(layers, i, h)
                x = self._residual(x, y)
            return np.asarray(self._head(params, x))[:n]

    def loss(self, batch_ids):
        """Mean next-token cross-entropy over a [B, S] batch (the last
        position of each sequence has no label), as the engine's
        ``lm_loss`` defines it. Summed in float64 on the host."""
        import numpy as np
        tot, n = 0.0, 0
        for ids in batch_ids:
            ids = np.asarray(ids)
            lg = self.logits(ids)[:-1].astype(np.float64)
            m = lg.max(axis=-1)
            lse = m + np.log(np.exp(lg - m[:, None]).sum(axis=-1))
            gold = lg[np.arange(lg.shape[0]), ids[1:]]
            tot += float((lse - gold).sum())
            n += lg.shape[0]
        return tot / n


# ---- the cost model: operations ------------------------------------------

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


# ---- the cost model: bytes of a decode step -------------------------------

def weight_bytes(hf: dict, bytes_per_param: float = 2.0) -> float:
    """Layer stack + output head as served (bf16). The embedding table is
    read one row per token and is not counted."""
    return matmul_params(hf, active=False) * bytes_per_param


def kv_bytes_per_token(hf: dict, kv_bits: int) -> float:
    """K and V of one cached position across all layers. int8 pools carry
    one f32 scale per (position, kv head) for each of K and V."""
    _, _, nkv, hd = dims(hf)
    L = hf["num_hidden_layers"]
    if kv_bits == 8:
        per_head = hd * 1 + 4
    else:
        per_head = hd * 2
    return 2.0 * L * nkv * per_head


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step reads: weights once + the live cache
    (``counters``: what the serve job counted over the window — the pool's
    ``kv_cache_bits`` and the mean of the live rows sampled after each
    round)."""
    return (weight_bytes(hf) + kv_bytes_per_token(hf, counters["kv_cache_bits"])
            * counters["mean_live_tokens"])
