"""The xing4_0 family (``model_type`` ``xing4_0``: XingChen Xing4.0-29B-A4B):
its plain reference, its cost model, its toy widths.

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no cache, no batching, no sort. One sequence at a
time, one block per call, one expert per call. It imports nothing from
``deepspeed_tpu`` (its YaRN frequencies are written here from
``transformers``' ``_compute_yarn_parameters``) and reads the program's stored
parameter tree (``params["layers"]["latent" | "dense" | "moe"]``, each stacked
on the blocks of its kind and carrying the block's ``hc_phi`` / ``hc_b`` /
``hc_a``; ``params["hc_out_*"]``), a block's slice cast to float32 inside its
own jitted call.

``N(x) = x / rms(x) . s``, eps ``rms_norm_eps``. The residual stream of a token
is ``X`` [n, H], n = ``hc_mult`` (manifold-constrained hyper-connections,
arXiv:2512.24880 over arXiv:2409.19606). ``X = [x0; ..; x0]``, ``x0`` the
embedding. Every BLOCK (a layer's attention and its feed-forward are two) owns
``phi`` [n H, 2n + n^2], ``b`` and three scalars ``a`` and does, in float32::

    u      = vec(X) / sqrt(mean(vec(X)^2) + eps)       # all n H values
    m      = u phi
    H_pre  = sigmoid(a_pre m[:n] + b[:n])
    H_post = 2 sigmoid(a_post m[n:2n] + b[n:2n])
    R      = clip(a_res mat(m[2n:]) + b[2n:], clamp)    # [n, n], row-major
    M      = exp(R - rowmax(R))
    20 x:    M = M / (rowsum(M) + hc_eps);  M = M / (colsum(M) + hc_eps)
    h      = H_pre X;   y = F(N_block(h));   X' = M X + H_post^T y

After the last block ``h = sigmoid(a_out (u phi_out) + b_out) X``, the final
norm, the untied head. ``F``:

- an ``L`` block, latent attention, here in the EXPANDED order only (the
  program serves a decode step in the ABSORBED one)::

      c_q = N(h W_qa);  q = c_q W_qb          -> heads x (nope | rope)
      [c_kv | k_r] = h W_kva;  c = N(c_kv)    # ONE k_r a token, every head's
      rotary on q's rope part and on k_r      # YaRN table, interleaved pairs
      [k_nope | v] = c W_kvb                  -> heads x (nope | v), v NARROWER
      P = softmax(q k^T (nope + rope)^-1/2 mscale^2), causal;  out = (P v) W_o

  ``mscale = 0.1 mscale_all_dim ln(factor) + 1``; cos and sin carry
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1 as published).
- ``D``: SwiGLU of ``intermediate_size``. ``E``: ``s = sigmoid(h W_r)``, the
  top ``num_experts_per_tok`` of ``s + b`` chosen, weights the chosen ``s``
  over their sum times ``routed_scaling_factor``, plus the shared expert.

``Reference(hf, params, defect=...)`` computes the same forward with ONE seeded
defect (``DEFECTS``). ``precision_below`` is the WHOLE forward one precision
below the stated one: the operands of every matrix product and the cached row
``[c | rope(k_r)]`` in ``float8_e5m2`` (bf16 stated), the stream's mappings in
bf16 (float32 stated); ``fp8_operands``, ``latent_fp8`` and ``bf16_mappings``
are its parts alone.

2. The cost model
-----------------
Matmul work, 2 FLOPs a multiply-add. Attention 3584 x 768 + 768 x 6144 + 3584
x 576 + 512 x 8192 + 4096 x 3584 = 28.41 M; a dense layer 28.41 + 3 x 3584 x
9216 = 127.50 M; an expert 11.01 M; an expert layer 28.41 + 64 x 11.01 + 11.01
+ 0.23 = 744.29 M; a block's mappings 14336 x 24 + 24 + 3 = 0.344 M; embedding
+ head 939.5 M. Whole: 2 x 127.5 + 38 x 744.29 + 939.5 + 80 x 0.344 = 29.5 B;
active a token 3.9 B.

``hc_bytes`` is the least ANY implementation of the stream moves: a token and
block ONE read and ONE write of the n H stream, the block's H-wide input
written and its H-wide output read, and ``phi`` once a program run.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import glm4_moe_lite as _glm
from benchmark.families.glm4_moe_lite import (  # noqa: F401  dims-generic:
    # used here, or what the shared readers and the family protocol ask for
    blocks, count, expert_matmul, flash_flops, head_params, is_grouped_matmul,
    latent_bytes_per_token, latent_dims, latent_op, latent_read_op,
    moe_ffn_bytes, moe_ffn_flops, touched_experts)
from benchmark.families.mistral import F32, _HIGHEST, _rms

Q_BLOCK = 256

# --rehearsal and the CPU tests: n = 4 rows kept; TWO leading dense layers, so
# that first_k_dense_replace 2 is walked, then two expert layers; V narrower
# than the keys (16 beside 16 + 8: the published 2 : 3); YaRN with a factor
# above 1 over a short original context, so that the toy's positions pass it
TOY = {"vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 4,
       "first_k_dense_replace": 2,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "intermediate_size": 256, "moe_intermediate_size": 64,
       "n_routed_experts": 8, "num_experts": 8,
       "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16,
       "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 16,
                        "type": "yarn"}}

# one seeded defect each: what `correct` and the CPU tests must tell apart
DEFECTS = ("precision_below", "fp8_operands", "latent_fp8", "bf16_mappings",
           "sinkhorn_one_round", "no_column_norm", "post_without_2",
           "no_h_res", "close_by_sum", "open_row0_only", "no_mscale",
           "plain_rope", "v_wrong_columns", "no_routed_scale")


def _eps(hf):
    return hf.get("rms_norm_eps", 1e-6)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(hf: dict, plain: bool = False):
    """(inverse frequencies float32 [rope / 2], what cos and sin are
    multiplied by): ``transformers``' ``_compute_yarn_parameters`` — pair i
    keeps ``theta^(-2i/d)`` below the pair that makes ``beta_fast`` turns over
    the original context, turns ``factor`` times slower above the pair that
    makes ``beta_slow``, a linear ramp between (the bounds rounded outward) —
    and DeepSeek-V3's attention factor ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)``. ``plain``: ``rope_theta`` alone."""
    d, theta = hf["qk_rope_head_dim"], float(hf.get("rope_theta", 10000.0))
    inv = theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    rs = hf.get("rope_scaling")
    if plain or not rs:
        return jnp.asarray(inv, F32), 1.0
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def pair(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))
    lo = max(math.floor(pair(float(rs.get("beta_fast") or 32))), 0)
    hi = min(math.ceil(pair(float(rs.get("beta_slow") or 1))), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    af = yarn_mscale(factor, float(rs.get("mscale") or 1.0)) \
        / yarn_mscale(factor, float(rs.get("mscale_all_dim") or 0.0))
    return jnp.asarray(inv, F32), af


def softmax_scale(hf: dict, mscale: bool = True) -> float:
    """``(nope + rope)^-1/2`` times ``mscale(factor, mscale_all_dim)^2``."""
    scale = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    rs = hf.get("rope_scaling")
    if mscale and rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(float(rs["factor"]),
                             float(rs["mscale_all_dim"])) ** 2
    return scale


def _rope_interleaved(x, inv, factor):
    """x [S, n, d], positions 0..S-1: rotary over all d dims, pairing dims
    (2i, 2i + 1), cos and sin times ``factor``."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :]
    cos, sin = (f(ang)[:, None, :] * factor for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


class Reference:
    """``Reference(hf, params)`` — ``hf`` the published config dict as run
    (the cut depth), ``params`` the program's parameter tree. ``defect``: one
    of ``DEFECTS``."""

    def __init__(self, hf: dict, params, defect: str = None):
        if defect is not None and defect not in DEFECTS:
            raise ValueError(f"defect {defect!r}: one of {DEFECTS}")
        self.hf, self.params, self.defect = hf, params, defect
        self._operand = jnp.float8_e5m2 \
            if defect in ("precision_below", "fp8_operands") else None
        self._latent_fp8 = defect in ("precision_below", "latent_fp8")
        # the dtype the stream's mappings are computed in
        self._map = jnp.bfloat16 \
            if defect in ("precision_below", "bf16_mappings") else F32
        self._read = jax.jit(self._hc_read)
        self._write = jax.jit(self._hc_write)
        self._close = jax.jit(self._hc_close)
        self._attn = jax.jit(self._latent_block)
        self._dense = jax.jit(self._dense_block)
        self._route = jax.jit(self._router)
        self._shared = jax.jit(self._shared_expert)
        self._head = jax.jit(self._final, static_argnames=("cols",))
        self._embed = jax.jit(lambda p, ids: p["tok_embed"][ids].astype(F32))
        self._add_expert = jax.jit(
            lambda st, j, e, h, w, y:
            y + w[:, None] * self._one_expert(st, j, e, h))

    # ---- the stream ------------------------------------------------------

    def _project(self, X, phi):
        """X [S, n, H] -> ``u phi`` [S, K], u the whole stream over its RMS."""
        v = X.reshape(X.shape[0], -1)
        u = v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                         + _eps(self.hf))
        return (u.astype(self._map) @ phi.astype(self._map)).astype(self._map)

    def _hc_read(self, st, j, X):
        """-> (the block's normed input [S, H], H_post [S, n], H_res [S, n,
        n])."""
        hf, dt = self.hf, self._map
        n = hf["hc_mult"]
        m = self._project(X, st["hc_phi"][j])
        b, a = st["hc_b"][j].astype(dt), st["hc_a"][j].astype(dt)
        pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
        post = jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
        if self.defect != "post_without_2":
            post = 2 * post
        R = jnp.clip(a[2] * m[:, 2 * n:] + b[2 * n:],
                     hf.get("mhc_h_res_clamp_min", -30),
                     hf.get("mhc_h_res_clamp_max", 30)).reshape(-1, n, n)
        M = jnp.exp(R - jnp.max(R, axis=-1, keepdims=True))
        eps = jnp.asarray(hf.get("hc_eps", 1e-6), dt)
        rounds = 1 if self.defect == "sinkhorn_one_round" \
            else hf.get("hc_sinkhorn_iters", 20)
        for _ in range(rounds):
            M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)       # rows
            if self.defect != "no_column_norm":
                M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)   # columns
        if self.defect == "no_h_res":
            M = jnp.broadcast_to(jnp.eye(n, dtype=dt), M.shape)
        h = jnp.einsum("sn,snh->sh", pre.astype(F32), X)
        return (_rms(h, st["ln_scale"][j].astype(F32), _eps(hf)),
                post.astype(F32), M.astype(F32))

    def _hc_write(self, X, y, post, M):
        return jnp.einsum("sij,sjh->sih", M, X) + post[:, :, None] * y[:, None]

    def _hc_close(self, params, X):
        if self.defect == "close_by_sum":
            return jnp.sum(X, axis=1)
        dt = self._map
        m = self._project(X, params["hc_out_phi"])
        w = jax.nn.sigmoid(params["hc_out_a"].astype(dt)[0] * m
                           + params["hc_out_b"].astype(dt))
        return jnp.einsum("sn,snh->sh", w.astype(F32), X)

    # ---- the mixers (each one jitted program; block / expert index traced)

    def _lo(self, a):
        a = a.astype(F32)
        return a if self._operand is None else \
            a.astype(self._operand).astype(F32)

    def _mm(self, a, w):
        return self._lo(a) @ self._lo(w)

    def _latent_block(self, st, j, h):
        """h [S, H] -> the latent-attention block's output, EXPANDED."""
        hf = self.hf
        nq, dn, dr, dv, _, rkv = latent_dims(hf)
        S = h.shape[0]
        inv, af = yarn_frequencies(hf, plain=self.defect == "plain_rope")
        c_q = _rms(self._mm(h, st["wq_a"][j]), st["q_a_norm"][j].astype(F32),
                   _eps(hf))
        q = self._mm(c_q, st["wq_b"][j]).reshape(S, nq, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], _rope_interleaved(q[..., dn:], inv, af)], axis=-1)
        kv = self._mm(h, st["wkv_a"][j])
        c = _rms(kv[:, :rkv], st["kv_a_norm"][j].astype(F32), _eps(hf))
        k_r = _rope_interleaved(kv[:, None, rkv:], inv, af)
        if self._latent_fp8:
            c, k_r = (a.astype(jnp.float8_e5m2).astype(F32) for a in (c, k_r))
        w = st["wkv_b"][j].reshape(rkv, nq, dn + dv)
        k_nope = jnp.einsum("sc,chn->shn", self._lo(c), self._lo(w[..., :dn]))
        # a head's columns of W_kvb are [k_nope | v]; the defect reads V from
        # the head's FIRST v columns (its keys')
        w_v = w[..., :dv] if self.defect == "v_wrong_columns" else w[..., dn:]
        v = jnp.einsum("sc,chv->shv", self._lo(c), self._lo(w_v))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (S, nq, dr))], -1)
        scale = softmax_scale(hf, mscale=self.defect != "no_mscale")
        qb = min(Q_BLOCK, S)
        q = self._lo(q).reshape(S // qb, qb, nq, dn + dr)
        k, keys = self._lo(k), jnp.arange(S)[None, :]

        def rows(xs):           # one block of queries against all the keys
            qs, i0 = xs
            s = jnp.einsum("shd,thd->hst", qs, k) * scale
            ok = keys <= i0 + jnp.arange(qb)[:, None]
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hst,thv->shv", self._lo(p), self._lo(v))

        o = jax.lax.map(rows, (q, jnp.arange(S // qb) * qb))
        return self._mm(o.reshape(S, nq * dv), st["wo"][j])

    def _dense_block(self, st, j, h):
        up, gate = self._mm(h, st["w_in"][j]), self._mm(h, st["w_gate"][j])
        return self._mm(jax.nn.silu(gate) * up, st["w_out"][j])

    def _router(self, st, j, h):
        """[S, E] combine weights, zero where an expert was not chosen."""
        hf = self.hf
        s = jax.nn.sigmoid(self._mm(h, st["wg"][j]))
        idx = jax.lax.top_k(s + st["e_bias"][j].astype(F32)[None],
                            hf["num_experts_per_tok"])[1]
        w = jnp.take_along_axis(s, idx, axis=-1)
        if hf.get("norm_topk_prob", True):
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        if self.defect != "no_routed_scale":
            w = w * float(hf.get("routed_scaling_factor", 1.0))
        return jnp.einsum("sk,ske->se", w, jax.nn.one_hot(
            idx, hf["n_routed_experts"], dtype=F32))

    def _one_expert(self, st, j, e, h):
        up = self._mm(h, st["moe_w_in_t"][j, e].T)
        gate = self._mm(h, st["moe_w_gate"][j, e])
        return self._mm(jax.nn.silu(gate) * up, st["moe_w_out"][j, e])

    def _shared_expert(self, st, j, h):
        up = self._mm(h, st["shared_w_in"][j])
        gate = self._mm(h, st["shared_w_gate"][j])
        return self._mm(jax.nn.silu(gate) * up, st["shared_w_out"][j])

    def _final(self, params, x, c0, cols: int):
        x = _rms(x, params["final_norm_scale"].astype(F32), _eps(self.hf))
        head = jax.lax.dynamic_slice_in_dim(params["lm_head"], c0, cols, axis=1)
        return self._mm(x, head)

    # ---- whole forward ----------------------------------------------------

    def logits(self, ids, pad_to: int = 1280):
        """ids [S] int -> float32 logits [S, vocab] as a NUMPY array. The ids
        are padded at the END to a multiple of ``pad_to`` (every block is
        causal and the stream's mappings are per token, so no real position
        sees a pad; a multiple of ``Q_BLOCK``)."""
        params, hf = self.params, self.hf
        n = len(ids)
        padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        with _HIGHEST():
            x0 = self._embed(params, jnp.asarray(padded))
            X = jnp.repeat(x0[:, None], hf["hc_mult"], axis=1)
            if self.defect == "open_row0_only":
                X = X.at[:, 1:].set(0.0)
            for kind, j in blocks(hf):
                st = params["layers"][kind]
                h, post, M = self._read(st, j, X)
                if kind == "latent":
                    y = self._attn(st, j, h)
                elif kind == "dense":
                    y = self._dense(st, j, h)
                else:
                    w = self._route(st, j, h)
                    y = self._shared(st, j, h)
                    for e in range(w.shape[-1]):
                        y = self._add_expert(st, j, e, h, w[:, e], y)
                X = self._write(X, y, post, M)
            V = hf["vocab_size"]
            cols = next(c for c in (8192, 4096, 512, V) if V % c == 0)
            x = self._close(params, X[:n])
            out = np.empty((n, V), np.float32)
            for c0 in range(0, V, cols):
                out[:, c0:c0 + cols] = np.asarray(
                    self._head(params, x, c0, cols=cols))
            return out


# ---- the cost model: parameters and operations ----------------------------

def hc_params(hf: dict, closing: bool = False) -> int:
    """One block's mappings (``phi``, ``b``, the three scalars), or the
    closing read's."""
    n = hf["hc_mult"]
    k = n if closing else 2 * n + n * n
    return n * hf["hidden_size"] * k + k + (1 if closing else 3)


def block_params(hf: dict, kind: str, experts: float = None) -> float:
    """Matmul parameters of one block of ``kind`` (``experts`` routed experts
    counted; default all), its stream mappings included."""
    return _glm.block_params(hf, kind, experts) + hc_params(hf)


def param_count(hf: dict) -> float:
    """Every stored parameter a matmul or the lookup uses: blocks (mappings
    included), the closing read, embedding + untied head."""
    return (sum(block_params(hf, kind) for kind, _ in blocks(hf))
            + hc_params(hf, closing=True) + 2 * head_params(hf))


def active_params(hf: dict) -> float:
    """Parameters ONE token's forward multiplies by: ``num_experts_per_tok``
    routed experts a layer, the head, not the embedding table."""
    return (sum(block_params(hf, kind, hf["num_experts_per_tok"])
                for kind, _ in blocks(hf))
            + hc_params(hf, closing=True) + head_params(hf))


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token USES + causal attention in the
    expanded order (the family protocol's; no cell trains this model)."""
    nq, dn, dr, dv, _, _ = latent_dims(hf)
    attn = 2 * (seq_len / 2) * nq * (dn + dr + dv)
    return 6.0 * active_params(hf) + 3.0 * count(hf, "latent") * attn


def weight_bytes(hf: dict, touched: float = None) -> float:
    """bf16 matrices a step reads: every block with ``touched`` routed
    experts per expert block, the mappings, the closing read, the head."""
    return 2.0 * (sum(block_params(hf, kind, touched) for kind, _ in blocks(hf))
                  + hc_params(hf, closing=True) + head_params(hf))


def stream_bytes_per_token(hf: dict, counters: dict = None) -> float:
    """The n H stream of one token in the stream's dtype: what the RUN says
    (``stats`` ``stream_bytes_per_token``), else bf16."""
    stats = (counters or {}).get("stats") or {}
    return float(stats.get("stream_bytes_per_token",
                           2 * hf["hc_mult"] * hf["hidden_size"]))


def hc_bytes(hf: dict, tokens: float, runs: float = 0.0,
             counters: dict = None) -> float:
    """Least bytes the stream's reads and writes move for ``tokens`` tokens
    through every block in ``runs`` program runs: a token and block ONE read
    and ONE write of the stream, the block's H-wide input written and its
    H-wide output read (a row each: 1 / n of the stream); the closing read
    once a token; and every block's mappings (bf16) once a run. Whatever an
    implementation moves beyond that (the stream read once for the norm and
    again for the mix, a float32 copy) is time, not need."""
    stream = stream_bytes_per_token(hf, counters)
    row = stream / hf["hc_mult"]
    per_token = len(blocks(hf)) * (2 * stream + 2 * row) + stream + row
    maps = 2.0 * (len(blocks(hf)) * hc_params(hf)
                  + hc_params(hf, closing=True))
    return tokens * per_token + runs * maps


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step moves: the weights of what it touched
    (mappings included), the live latent rows once, and the stream's reads
    and writes for the step's slots (``hc_bytes`` without the mappings, which
    ``weight_bytes`` counts)."""
    slots = float(counters.get("mean_occupancy", counters.get("max_seqs", 0)))
    return (weight_bytes(hf, touched_experts(hf, counters))
            + latent_bytes_per_token(hf, counters)
            * counters["mean_live_tokens"]
            + hc_bytes(hf, slots, 0.0, counters))


# ---- the stream in a device trace -----------------------------------------

_HC_KERNEL = re.compile(r"^%hc_(read|write)[.\d]* = ")


def hc_op(event_name: str, hf: dict) -> bool:
    """True if this trace event reads or writes the widened stream: a kernel
    named ``%hc_read.N`` / ``%hc_write.N`` (none today), or an op with an
    operand or result whose LAST dim is ``hc_mult x hidden_size`` (14336 at the
    published widths: the program carries the stream flat, and no other tensor
    of this model is that wide — the dense layer is 9216, the experts 1024,
    the head's rows 3584). Trailing dims ``[hc_mult, hidden_size]`` are NOT
    taken for it: with ``num_experts_per_tok`` = ``hc_mult`` = 4 the expert
    layer's combine ``[tokens, top-k, hidden]`` has exactly that shape (the
    first traced runs of the cell counted it: 0.72 ms a 4096-token block)."""
    if _HC_KERNEL.match(event_name):
        return True
    return bool(stream_tokens(event_name, hf))


def stream_tokens(event_name: str, hf: dict) -> int:
    """Tokens whose stream this op holds: the product of the dims in front of
    the largest ``[.., hc_mult x hidden_size]`` operand or result (0: none)."""
    width = hf["hc_mult"] * hf["hidden_size"]
    best = 0
    for lead in re.findall(rf"[a-z0-9]+\[((?:\d+,)*){width}\]", event_name):
        best = max(best, math.prod(int(d) for d in lead.split(",") if d))
    return best


def program_tokens(module_runs, ops, hf: dict):
    """(tokens, runs) of the programs that carry the stream: ``module_runs``
    [(name with its hash, start, duration)] executions of the step and
    prefill programs, ``ops`` [(name, start, duration)] the device's op
    events sorted by start. A program's tokens a run are read ONCE, from its
    own shapes — the largest stream any op inside one of its runs holds (the
    step program: its slots; a prefill program: its padded row) — and counted
    once a RUN, whatever number of ops an implementation spends on them."""
    import bisect
    starts = [s for _, s, _ in ops]
    per_program, tokens, runs = {}, 0, 0
    for name, s, d in module_runs:
        if name not in per_program:
            i, best = bisect.bisect_left(starts, s), 0
            while i < len(ops) and ops[i][1] < s + d:
                best = max(best, stream_tokens(ops[i][0], hf))
                i += 1
            per_program[name] = best
        if per_program[name]:
            tokens, runs = tokens + per_program[name], runs + 1
    return tokens, runs
