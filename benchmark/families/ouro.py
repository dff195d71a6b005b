"""The Ouro family (ByteDance/Ouro-2.6B, a looped language model): its plain
reference, its cost model, its toy widths. Found by the configuration's
``model_type`` "ouro".

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no cache, no batching, no scan. One sequence at a
time, one layer per call, the passes and the layers Python loops. It imports
nothing from ``deepspeed_tpu``.

Follows the published model (Scaling Latent Reasoning via Looped Language
Models, the HF ``modeling_ouro`` code of the checkpoint) as I know it:

- ``h = E[ids]``; for pass ``t = 0 .. total_ut_steps - 1``, for layer
  ``i = 0 .. num_hidden_layers - 1`` — the SAME layers' weights in every
  pass — a block with a norm before AND after each sublayer:
  ``h += RMSNorm(Attn_i(RMSNorm(h; g1_i)); g2_i)``, then
  ``h += RMSNorm(W_down (silu(W_gate u) * W_up u); g4_i)`` with
  ``u = RMSNorm(h; g3_i)`` (HF ``input_layernorm``, ``input_layernorm_2``,
  ``post_attention_layernorm``, ``post_attention_layernorm_2``); eps from the
  config, no bias anywhere;
- at the end of EACH pass ``h = RMSNorm(h; g_final)``: that normed stream is
  pass ``t``'s output ``s_t`` and what pass ``t + 1`` starts from;
- attention: multi-head (as many K/V heads as query heads), rotary in the
  "rotate-half" pairing at the token's position (the same in every pass),
  theta from the config, causal, scale 1 / sqrt(head_dim). K and V of pass
  ``t``, layer ``i`` are their own: a pass never reads another pass's;
- the exit gate: ``lambda_t = sigmoid(w_g . s_t + b_g)``,
  ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the last pass takes the
  remainder. With the published ``early_exit_threshold`` 1 every pass runs
  and the logits are ``W_head s_last`` (untied head): what is served.

Departures: none in the arithmetic. The parameter tree is the program's
(stacked on a leading layer dim: ``wq/wk/wv`` or the engine's fused ``wqkv``,
``wo``, ``w_in`` (up) / ``w_gate`` or fused ``w_in_gate`` = [up | gate],
``w_out`` (down), the four scales ``ln1_scale``, ``ln1_post_scale``,
``ln2_scale``, ``ln2_post_scale``; ``final_norm_scale``, ``exit_gate_w``
[H, 1], ``exit_gate_b`` [1], ``lm_head``): the reference reads the SAME
stored values the engine serves with and upcasts them to float32.

``_after``, ``_handed_on``, ``_reads``, ``_stored`` and ``_mm`` are the
places ``tools/ouro_defects.py`` overrides (the norm after a sublayer; what
the next pass starts from; which pass's K/V a pass reads; what the cache
keeps of a K or V row; a product with a weight matrix); here each is what
the lines above say.

2. The cost model
-----------------
From the published shapes; matrix-multiplication work only, 2 FLOPs per
multiply-add, the embedding lookup not counted, causal attention as the half
square (``mistral.py`` says why). One layer is 4 x 2048^2 + 3 x 2048 x 5632
= 51 380 224 matmul parameters; the model holds 48 of them ONCE
(2 466 250 752; with the four norm scales a layer, embedding + untied head
2 x 49 152 x 2048, the final norm and the gate: 2 667 974 657, the published
"2.6B") and a token passes them ``total_ut_steps`` = 4 times.

A decode step therefore READS the layers four times (the passes are
sequential: pass t + 1 needs pass t's output for every layer, so nothing of
a layer stays on chip between its uses) and the head once, 19.93 GB in
bf16, plus the live K/V of every plane: a token keeps 4 x 48 = 192 planes,
192 x 2 x 16 heads x (128 + 4) B = 811 008 B in the int8 pool.
"""
import jax
import jax.numpy as jnp

from benchmark.families.mistral import (  # noqa: F401 — the shared pieces
    F32, _HIGHEST, _at, _rms, _rope, attn_flops_per_token_fwd,
    attn_proj_params, dims, flash_flops, head_params)

# --rehearsal: multi-head attention at toy widths (head_dim 64), two layers
# walked by the published four passes
TOY = {"vocab_size": 512, "hidden_size": 256, "intermediate_size": 512,
       "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 64,
       "num_hidden_layers": 2, "max_window_layers": 2,
       "layer_types": ["full_attention", "full_attention"]}


def ut_steps(hf: dict) -> int:
    return int(hf.get("total_ut_steps", 1))


class Reference:
    """``Reference(hf, params)`` — ``hf`` is the published config dict,
    ``params`` the program's parameter tree."""

    def __init__(self, hf: dict, params):
        self.hf = hf
        self.params = params
        self.L = int(params["layers"]["ln1_scale"].shape[0])
        self.T = ut_steps(hf)
        self._qkv = jax.jit(self._project)
        self._attn = jax.jit(self._attend)
        self._ffn = jax.jit(self._mlp)
        self._end = jax.jit(self._pass_end)
        self._embed = jax.jit(lambda p, ids: p["tok_embed"][ids].astype(F32))
        self._head = jax.jit(lambda p, s: self._mm(s, p["lm_head"].astype(F32)))

    # ---- pieces (each one jitted program, the layer index traced) ---------

    def _project(self, layers, i, x):
        """q [S, nh, hd], and the layer's K and V rows [S, nkv, hd] as the
        cache holds them."""
        hf = self.hf
        _, nh, nkv, hd = dims(hf)
        S = x.shape[0]
        h = _rms(x, _at(layers, "ln1_scale", i), hf["rms_norm_eps"])
        theta = float(hf.get("rope_theta", 10000.0))
        if "wqkv" in layers:              # the engine's fused [q | k | v]
            qkv = self._mm(h, _at(layers, "wqkv", i))
            q, k, v = (qkv[:, :nh * hd], qkv[:, nh * hd:(nh + nkv) * hd],
                       qkv[:, (nh + nkv) * hd:])
        else:
            q, k, v = (self._mm(h, _at(layers, n, i)) for n in ("wq", "wk", "wv"))
        q = _rope(q.reshape(S, nh, hd), theta)
        k = _rope(k.reshape(S, nkv, hd), theta)
        return q, self._stored(k), self._stored(v.reshape(S, nkv, hd))

    def _stored(self, rows):
        """What the cache keeps of a K or V row: the row."""
        return rows

    def _mm(self, a, w):
        """Every product with a weight matrix: float32, as it stands."""
        return a @ w

    def _attend(self, layers, i, x, q, k, v):
        """x + RMSNorm(W_o softmax(q k^T) v; g2)."""
        S, nh, hd = q.shape
        nkv = k.shape[1]
        s = jnp.einsum("sngd,tnd->ngst", q.reshape(S, nkv, nh // nkv, hd), k
                       ) / (hd ** 0.5)
        ok = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("ngst,tnd->sngd", p, v).reshape(S, nh * hd)
        return x + self._after(self._mm(o, _at(layers, "wo", i)),
                               _at(layers, "ln1_post_scale", i))

    def _mlp(self, layers, i, x):
        """x + RMSNorm(W_down (silu(W_gate u) * W_up u); g4)."""
        u = _rms(x, _at(layers, "ln2_scale", i), self.hf["rms_norm_eps"])
        if "w_in_gate" in layers:         # the engine's fused [up | gate]
            ug = self._mm(u, _at(layers, "w_in_gate", i))
            up, gate = ug[:, :ug.shape[-1] // 2], ug[:, ug.shape[-1] // 2:]
        else:
            up, gate = (self._mm(u, _at(layers, n, i)) for n in ("w_in", "w_gate"))
        m = self._mm(jax.nn.silu(gate) * up, _at(layers, "w_out", i))
        return x + self._after(m, _at(layers, "ln2_post_scale", i))

    def _after(self, y, scale):
        """The norm after a sublayer."""
        return _rms(y, scale, self.hf["rms_norm_eps"])

    def _pass_end(self, params, x):
        """(s_t, lambda_t): the pass's output and the exit gate on it."""
        s = _rms(x, params["final_norm_scale"].astype(F32),
                 self.hf["rms_norm_eps"])
        z = (s @ params["exit_gate_w"].astype(F32))[:, 0]
        return s, jax.nn.sigmoid(z + params["exit_gate_b"].astype(F32))

    def _handed_on(self, s, x):
        """What the next pass starts from, of a pass's output ``s`` and the
        stream ``x`` it is the norm of: the output."""
        return s

    def _reads(self, t: int, i: int):
        """The (pass, layer) whose K/V pass ``t``'s layer ``i`` reads: its
        own."""
        return t, i

    # ---- whole forward ------------------------------------------------------

    def passes(self, ids, pad_to: int = 128):
        """ids: [S] int -> (s [T, S, H], lambda [T, S]) as jax arrays over
        the PADDED length, and S. The sequence is padded at its END to a
        multiple of ``pad_to`` (attention is causal, so no real position
        sees a pad) so that few shapes are ever compiled."""
        import numpy as np
        params, layers = self.params, self.params["layers"]
        n = len(ids)
        padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        outs, lams = [], []
        # the planes another (pass, layer) reads: none, as the model is
        kept = {}
        for t in range(self.T):
            for i in range(self.L):
                if self._reads(t, i) != (t, i):
                    kept[self._reads(t, i)] = None
        with _HIGHEST():
            x = self._embed(params, jnp.asarray(padded))
            for t in range(self.T):
                for i in range(self.L):
                    q, k, v = self._qkv(layers, i, x)
                    if (t, i) in kept:
                        kept[t, i] = (k, v)
                    k, v = kept.get(self._reads(t, i), (k, v))
                    x = self._attn(layers, i, x, q, k, v)
                    x = self._ffn(layers, i, x)
                s, lam = self._end(params, x)
                outs.append(s)
                lams.append(lam)
                x = self._handed_on(s, x)
        return jnp.stack(outs), jnp.stack(lams), n

    def logits(self, ids, pad_to: int = 128):
        """ids: [S] int -> float32 logits [S, vocab] as a NUMPY array: the
        head on the LAST pass's output."""
        import numpy as np
        s, _, n = self.passes(ids, pad_to)
        with _HIGHEST():
            return np.asarray(self._head(self.params, s[-1]))[:n]

    def exit_distribution(self, ids, pad_to: int = 128):
        """[S, T] float32: per position the probability of leaving at each
        pass."""
        import numpy as np
        _, lam, n = self.passes(ids, pad_to)
        lam = np.asarray(lam, np.float64)[:, :n]                # [T, S]
        before = np.concatenate([np.ones_like(lam[:1]),
                                 np.cumprod(1.0 - lam[:-1], axis=0)])
        p = np.concatenate([lam[:-1] * before[:-1], before[-1:]])
        return p.T.astype(np.float32)


# ---- the cost model ---------------------------------------------------------

def layer_params(hf: dict) -> int:
    """One layer's matmul parameters: attention + the gated feed-forward."""
    return attn_proj_params(hf) + 3 * hf["hidden_size"] * hf["intermediate_size"]


def stored_params(hf: dict) -> int:
    """Every parameter the checkpoint holds: the layers ONCE with their four
    norm scales, embedding + head, the final norm, the exit gate."""
    H, V, L = hf["hidden_size"], hf["vocab_size"], hf["num_hidden_layers"]
    head = 1 if hf.get("tie_word_embeddings") else 2
    return L * (layer_params(hf) + 4 * H) + head * V * H + H + (H + 1)


def matmul_params(hf: dict) -> int:
    """Parameters that take part in a matmul for one token: every layer
    once PER PASS, the head once."""
    return (ut_steps(hf) * hf["num_hidden_layers"] * layer_params(hf)
            + head_params(hf))


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """Forward + backward for one token: 6 FLOPs per matmul parameter a
    pass, plus causal attention in every (pass, layer)."""
    planes = ut_steps(hf) * hf["num_hidden_layers"]
    return (6.0 * matmul_params(hf)
            + 3.0 * planes * attn_flops_per_token_fwd(hf, seq_len))


def weight_bytes(hf: dict, bytes_per_param: float = 2.0) -> float:
    """What one decode step reads of the weights as served (bf16): the
    layers once per pass and the head once."""
    return matmul_params(hf) * bytes_per_param


def loop_reread_bytes(hf: dict, bytes_per_param: float = 2.0) -> float:
    """What passes 2.. cost a step beyond what an unlooped model of these
    weights would read."""
    return ((ut_steps(hf) - 1) * hf["num_hidden_layers"] * layer_params(hf)
            * bytes_per_param)


def kv_planes(hf: dict) -> int:
    return ut_steps(hf) * hf["num_hidden_layers"]


def kv_bytes_per_token(hf: dict, kv_bits: int) -> float:
    """K and V of one cached position across all planes. int8 pools carry
    one f32 scale per (position, kv head) for each of K and V."""
    _, _, nkv, hd = dims(hf)
    per_head = hd + 4 if kv_bits == 8 else hd * 2
    return 2.0 * kv_planes(hf) * nkv * per_head


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step reads: the weights of every pass + the
    live cache of every plane."""
    return (weight_bytes(hf) + kv_bytes_per_token(hf, counters["kv_cache_bits"])
            * counters["mean_live_tokens"])
