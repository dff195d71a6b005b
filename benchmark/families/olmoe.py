"""The OLMoE family (allenai/OLMoE-1B-7B): its plain reference, its cost
model, its toy widths. Found by the configuration's ``model_type`` "olmoe".

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no cache, no batching, no sort, no capacity. One
sequence at a time, one layer per call and one expert per call (a Python loop
over ALL experts, each applied to every position and weighted by that
position's combine weight, which is zero where the expert was not picked), so
the float32 copy beside the engine's state is one expert's three matrices.
It imports nothing from ``deepspeed_tpu``.

Follows the published block (OLMoE: Open Mixture-of-Experts Language Models,
arXiv:2409.02060) as the HF ``modeling_olmoe`` code computes it:

- pre-norm RMSNorm (eps from the config), no biases, ``clip_qkv`` null;
- multi-head attention (16 query = 16 key/value heads of 128) with an
  RMSNorm over the WHOLE q projection (``q_norm``, ``hidden_size`` wide) and
  the WHOLE k projection (``k_norm``, ``num_key_value_heads x head_dim``)
  BEFORE the split into heads and before the rotary embedding ("rotate-half"
  pairing, theta from the config); causal;
- every layer an expert layer, no shared expert: router logits -> softmax
  over ALL 64 experts in float32 -> top-8 -> the eight weights TAKEN AS THEY
  ARE (``norm_topk_prob`` false: they sum to less than 1; divided by their
  sum only if the config says true) -> weighted sum of the eight experts'
  SwiGLU outputs ``down(silu(gate x) * up x)``. Dropless;
- untied output head.

Departures: none in the arithmetic. The parameter tree is the program's
(stacked on a leading layer dim: ``wq/wk/wv/wo``, ``q_norm/k_norm``, router
``wg``, experts ``moe_w_in`` (up) / ``moe_w_gate`` / ``moe_w_out`` (down)
[L, E, ...]): the reference reads the SAME stored values the engine serves
with and upcasts them to float32.

2. The cost model
-----------------
From the published shapes; matrix-multiplication work only, 2 FLOPs per
multiply-add, the embedding lookup not counted, causal attention as the half
square (``mistral.py`` says why). One layer is 4 x 2048^2 (attention) +
2048 x 64 (router) + 64 x 3 x 2048 x 1024 (experts) = 419 561 472 matmul
parameters, of which a token uses 4 x 2048^2 + 2048 x 64 + 8 x 3 x 2048 x
1024 = 67 239 936.

A decode step reads the head, every layer's attention matrices and router,
the live KV rows, and the matrices of the experts the step's active slots
TOUCHED — ``moe_experts_touched_per_step`` of the engine's ``stats()``, not
all 64: 32 slots x top-8 leave about one expert in 64 idle on uniform
routing and more on a skewed router, and a step that skips idle experts must
not read over 100 % of a roofline that charges it for them.

The expert feed-forward's own ops (``sat_moe_ffn_roofline``,
``sat_moe_share_of_device``, and the same two of the sorted form alone,
``sat_moe_sorted_*``): ``moe_ffn_flops`` and ``moe_ffn_bytes`` of ONE
layer's three expert matmuls over ``rows`` = tokens x top-k assignments,
``expert_matmul``, which finds them in a device trace, and
``is_grouped_matmul``, which tells the sorted form's.
"""

import re

import jax
import jax.numpy as jnp

from benchmark.families.mistral import (  # noqa: F401 — the shared pieces
    F32, _HIGHEST, _at, _rms, _rope, attn_flops_per_token_fwd,
    attn_proj_params, dims, flash_flops, head_params, kv_bytes_per_token)

# --rehearsal: the published 64 experts x top-8 and the MHA grouping at toy
# widths (head_dim 64); every layer is an expert layer (period 1)
TOY = {"vocab_size": 512, "hidden_size": 256, "intermediate_size": 64,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "num_hidden_layers": 2}


class Reference:
    """``Reference(hf, params)`` — ``hf`` is the published config dict (as
    run: the cut depth), ``params`` the program's parameter tree."""

    def __init__(self, hf: dict, params):
        self.hf = hf
        self.params = params
        self.L = int(params["layers"]["ln1_scale"].shape[0])
        self._attn = jax.jit(self._attn_block)
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
        S, eps = x.shape[0], hf["rms_norm_eps"]
        h = _rms(x, _at(layers, "ln1_scale", i), eps)
        # the q/k norm: over the whole projection, all heads together
        q = _rms(h @ _at(layers, "wq", i), _at(layers, "q_norm", i), eps)
        k = _rms(h @ _at(layers, "wk", i), _at(layers, "k_norm", i), eps)
        v = h @ _at(layers, "wv", i)
        theta = float(hf.get("rope_theta", 10000.0))
        q = _rope(q.reshape(S, nh, hd), theta).reshape(S, nkv, nh // nkv, hd)
        k = _rope(k.reshape(S, nkv, hd), theta)
        v = v.reshape(S, nkv, hd)
        s = jnp.einsum("sngd,tnd->ngst", q, k) / (hd ** 0.5)
        ok = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("ngst,tnd->sngd", p, v).reshape(S, nh * hd)
        x = x + o @ _at(layers, "wo", i)
        return x, _rms(x, _at(layers, "ln2_scale", i), eps)

    def _router(self, layers, i, h):
        """[S, E] combine weights: softmax over all experts, top-k kept as
        they are (or renormalised if the config says so), zero elsewhere."""
        probs = jax.nn.softmax(h @ _at(layers, "wg", i), axis=-1)
        top, idx = jax.lax.top_k(probs, self.hf["num_experts_per_tok"])
        if self.hf.get("norm_topk_prob", False):
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
        return x @ params["lm_head"].astype(F32)            # untied

    # ---- whole forward ----------------------------------------------------

    def logits(self, ids, pad_to: int = 512):
        """ids: [S] int -> float32 logits [S, vocab] as a NUMPY array. The
        sequence is padded at its END to a multiple of ``pad_to`` (attention
        is causal, so no real position sees a pad) so that few shapes are
        ever compiled; padding and the cut back to S happen on the host."""
        import numpy as np
        params, layers = self.params, self.params["layers"]
        n = len(ids)
        padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        with _HIGHEST():
            x = self._embed(params, jnp.asarray(padded))
            for i in range(self.L):
                x, h = self._attn(layers, i, x)
                w = self._route(layers, i, h)
                y = jnp.zeros_like(x)
                for e in range(w.shape[-1]):
                    y = self._add_expert(layers, i, e, h, w, y)
                x = self._residual(x, y)
            return np.asarray(self._head(params, x))[:n]

    def loss_of(self, params, batch_ids):
        """The same forward and the same mean next-token cross-entropy as a
        DIFFERENTIABLE function of ``params`` (``jax.grad`` of it is the
        reference for the train forward's gradients): the pieces above,
        un-jitted, in a Python loop over layers and experts. For toy widths:
        it traces every expert of every layer."""
        layers, tot, n = params["layers"], 0.0, 0
        with _HIGHEST():
            for ids in batch_ids:
                ids = jnp.asarray(ids, jnp.int32)
                x = params["tok_embed"][ids].astype(F32)
                for i in range(self.L):
                    x, h = self._attn_block(layers, i, x)
                    w = self._router(layers, i, h)
                    for e in range(w.shape[-1]):
                        x = x + w[:, e, None] * self._one_expert(layers, i, e, h)
                lg = self._final(params, x)[:-1]
                gold = jnp.take_along_axis(lg, ids[1:, None], axis=-1)[:, 0]
                tot = tot + jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)
                n += lg.shape[0]
        return tot / n

    def loss(self, batch_ids):
        """Mean next-token cross-entropy over a [B, S] batch (the last
        position of each sequence has no label), as the engine's
        ``lm_loss`` defines it — the router's auxiliary loss is NOT in it.
        Summed in float64 on the host."""
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

def expert_params(hf: dict) -> int:
    """One expert's three matrices (up, gate, down); ``intermediate_size``
    is the width of ONE expert."""
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def router_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["num_experts"]


def layer_params(hf: dict, experts: float = None) -> float:
    """One layer's matmul parameters with ``experts`` expert matrices
    counted (default: all of them — what the chip holds)."""
    E = hf["num_experts"] if experts is None else experts
    return attn_proj_params(hf) + router_params(hf) + E * expert_params(hf)


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """Forward + backward (= 3x forward) for one token of a seq_len
    sequence: 6 FLOPs per matmul parameter the token USES (its top-k
    experts) plus causal attention."""
    L = hf["num_hidden_layers"]
    used = L * layer_params(hf, hf["num_experts_per_tok"]) + head_params(hf)
    return 6.0 * used + 3.0 * L * attn_flops_per_token_fwd(hf, seq_len)


def moe_ffn_flops(hf: dict, rows: float) -> float:
    """FLOPs of ONE layer's three grouped matmuls over ``rows`` sorted
    (token, expert) rows: 2 per multiply-add, 3 matrices of H x F."""
    return 2.0 * rows * expert_params(hf)


def moe_ffn_bytes(hf: dict, rows: float, touched: float,
                  bytes_per_value: float = 2.0) -> float:
    """Least bytes ONE layer's three grouped matmuls move: the matrices of
    the ``touched`` experts once, and the rows in (H wide) and out (H wide).
    The F-wide intermediates need not leave the chip's fast memory."""
    return bytes_per_value * (touched * expert_params(hf)
                              + 2.0 * rows * hf["hidden_size"])


# ---- the expert matmuls in a device trace ---------------------------------
#
# The dropless expert layer (deepspeed_tpu/moe/sharded_moe.py) runs each of
# its three projections as ONE op per layer and program execution, in one of
# two forms chosen by the call's token count (and, under a mesh or in
# training, always the second):
# - SORTED, a call of many tokens (a prompt past 256): a grouped matmul over
#   the tokens x top-k rows sorted by expert, the Pallas kernel `moe_gmm`
#   (deepspeed_tpu/ops/grouped_matmul.py): an `XLA Ops` event whose HLO text
#   starts `%moe_gmm.N = ` and is a custom call with result [rows, N] (JAX's
#   megablox kernel would be `%gmm.N`, XLA's own ragged_dot is
#   `%ragged-dot-none.N`, beside a `%ragged-dot-metadata` that is not one);
# - ONE-HOT, a call of few tokens (every decode step, a prompt to 256): every
#   expert over all T rows, a fusion with result [E, T, N] that reads the
#   stacked expert weights [L, E, K, N] in place.

_EXPERT_KERNEL = re.compile(
    r"^%(moe_gmm|gmm|ragged-dot-none)[.\d]* = [a-z0-9]+\[(\d+),\d+\]")


def is_grouped_matmul(event_name: str) -> bool:
    """This trace event is a grouped matmul of the SORTED form."""
    return bool(_EXPERT_KERNEL.match(event_name)) and "custom-call" in event_name


def expert_matmul(event_name: str, hf: dict):
    """``(tokens, matrices)`` if this trace event is (part of) an expert
    layer's matmuls, else None: the tokens T of the call and how many of
    the layer's three stacked matrices the op streams — one for a kernel
    call; one, two or three for a fusion, which XLA may build from several
    of the layer's matmuls (it fuses the down projection with the combine,
    so the result's shape says little: the operands do). A kernel's rows
    are padded to its row tile, so T is rounded up with them."""
    E, H, F = hf["num_experts"], hf["hidden_size"], hf["intermediate_size"]
    if is_grouped_matmul(event_name):
        rows = int(_EXPERT_KERNEL.match(event_name).group(2))
        return max(1, rows // hf["num_experts_per_tok"]), 1
    if " fusion(" not in event_name:
        return None
    stacks = re.findall(rf"\[\d+,{E},(?:{H},{F}|{F},{H})\]", event_name)
    # an [E, T, H or F] operand or result; [E, H, F] itself is one layer's
    # slice of a stack (the one-hot form never runs T = H or F tokens)
    rows = [int(t) for t, n in re.findall(rf"\[{E},(\d+),({H}|{F})\]", event_name)
            if {int(t), int(n)} != {H, F}]
    if not stacks or not rows:
        return None
    return rows[0], len(stacks)


# ---- the cost model: bytes of a decode step -------------------------------

def touched_experts(hf: dict, counters: dict) -> float:
    """Distinct experts a decode step read, mean per layer, from the
    engine's routing counter; every expert where the counter is absent."""
    stats = counters.get("stats") or {}
    return float(stats.get("moe_experts_touched_per_step", hf["num_experts"]))


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step reads: head + every layer's attention
    matrices and router + the TOUCHED experts' matrices, in bf16, + the live
    cache (``counters``: the pool's ``kv_cache_bits``, the mean of the live
    rows sampled after each round, and ``stats`` with the routing counter)."""
    L = hf["num_hidden_layers"]
    weights = 2.0 * (L * layer_params(hf, touched_experts(hf, counters))
                     + head_params(hf))
    return (weights + kv_bytes_per_token(hf, counters["kv_cache_bits"])
            * counters["mean_live_tokens"])
