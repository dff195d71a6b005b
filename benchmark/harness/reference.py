"""Plain references: the Mistral block and the Mixtral block.

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
"""
import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = functools.partial(jax.default_matmul_precision, "highest")


def _dims(hf):
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
        H, nh, nkv, hd = _dims(hf)
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
