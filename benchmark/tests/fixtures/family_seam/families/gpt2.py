"""The GPT-2 family — the worked example of adding a family (a TEST fixture:
``tests/test_family_seam.py`` copies it to ``families/gpt2.py`` of a scratch
copy of the benchmark; it is no cell of the benchmark).

The plain reference follows "Language Models are Unsupervised Multitask
Learners" (Radford et al., 2019) as HF ``modeling_gpt2`` computes it: learned
absolute positions added to the token embedding; pre-LayerNorm blocks (scale
AND bias, eps from the config); multi-head causal attention scaled by
1 / sqrt(head size), every projection with a bias; a 4x GELU MLP in the tanh
form (``gelu_new``) with biases; a final LayerNorm; the output head tied to
the token embedding. Float32 under "highest" matmul precision, one sequence
per call, no cache. Departures: none. It reads the program's own parameter
tree (stacked on a leading layer dim; ``wq/wk/wv`` + ``bq/bk/bv``, or the
serving engine's fused ``wqkv`` + ``bqkv``)."""
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# --rehearsal: head size 64, the layer pattern has period 1
TOY = {"vocab_size": 512, "n_embd": 256, "n_head": 4, "n_layer": 2}


def _ln(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


class Reference:
    def __init__(self, hf: dict, params):
        self.hf, self.params = hf, params
        self._forward = jax.jit(self._whole_forward)

    def _whole_forward(self, params, ids):
        nh, eps = self.hf["n_head"], self.hf["layer_norm_epsilon"]
        S = ids.shape[0]
        x = params["tok_embed"][ids].astype(F32) + params["pos_embed"][:S].astype(F32)
        hd = x.shape[-1] // nh
        causal = jnp.tril(jnp.ones((S, S), bool))
        layers = params["layers"]
        for i in range(layers["ln1_scale"].shape[0]):
            def at(key):
                return layers[key][i].astype(F32)
            h = _ln(x, at("ln1_scale"), at("ln1_bias"), eps)
            if "wqkv" in layers:
                q, k, v = jnp.split(h @ at("wqkv") + at("bqkv"), 3, axis=-1)
            else:
                q, k, v = (h @ at("w" + n) + at("b" + n) for n in "qkv")
            q, k, v = (t.reshape(S, nh, hd) for t in (q, k, v))
            s = jnp.einsum("snd,tnd->nst", q, k) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("nst,tnd->snd", p, v).reshape(S, nh * hd)
            x = x + o @ at("wo") + at("bo")
            h = _ln(x, at("ln2_scale"), at("ln2_bias"), eps)
            up = jax.nn.gelu(h @ at("w_in") + at("b_in"), approximate=True)
            x = x + up @ at("w_out") + at("b_out")
        x = _ln(x, params["final_norm_scale"].astype(F32),
                params["final_norm_bias"].astype(F32), eps)
        return x @ params["tok_embed"].astype(F32).T

    def logits(self, ids, pad_to: int = 64):
        """ids [S] -> float32 logits [S, vocab] (NumPy). Padded at the END
        (causal: no real position sees a pad) so that few shapes compile."""
        n = len(ids)
        rows = self.params["pos_embed"].shape[0]
        padded = np.zeros((min(-(-n // pad_to) * pad_to, rows),), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._forward(self.params, jnp.asarray(padded)))[:n]


# ---- the cost model: matmul work only, the causal half counted once -------

def _matmul_params(hf: dict) -> int:
    H, L = hf["n_embd"], hf["n_layer"]
    F = hf.get("n_inner") or 4 * H
    return L * (4 * H * H + 2 * H * F) + H * hf["vocab_size"]   # + the tied head


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    return (6.0 * _matmul_params(hf)
            + 3.0 * hf["n_layer"] * 2 * 2 * (seq_len / 2) * hf["n_embd"])


def flash_flops(hf: dict, batch: int, seq_len: int) -> dict:
    one = 2.0 * batch * hf["n_embd"] * seq_len * seq_len / 2.0   # ONE layer's
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """bf16 weights once + K and V of every live row (an int8 pool carries
    one f32 scale per row and head)."""
    hd = hf["n_embd"] // hf["n_head"]
    per_head = hd + 4 if counters["kv_cache_bits"] == 8 else 2 * hd
    return (2.0 * _matmul_params(hf)
            + 2.0 * hf["n_layer"] * hf["n_head"] * per_head * counters["mean_live_tokens"])
