"""The glm4_moe_lite family (``model_type`` ``glm4_moe_lite``: Z.ai
GLM-4.7-Flash, 30B-A3B): its plain reference, its cost model, its toy widths.

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no cache, no batching, no sort. One sequence at a
time, one block per call, one expert per call. It imports nothing from
``deepspeed_tpu`` and reads the program's stored parameter tree
(``params["layers"]["latent" | "dense" | "moe"]``, each stacked on the blocks
of its kind, a block's slice cast to float32 inside its own jitted call).

The equations are DeepSeek-V2 / V3's (arXiv:2405.04434 section 2.1,
arXiv:2412.19437 section 2.1), which the published config's keys name one for
one. ``N(x) = x / rms(x) . s``, eps ``rms_norm_eps``; layer ``l`` is ``x = x +
Attn_l(N(x)); x = x + FFN_l(N(x))``.

- ``Attn_l``, latent attention, here in the EXPANDED order only (the program
  serves a prompt in this order and a decode step in the ABSORBED one, with
  ``W_kvb`` moved onto the query and the output: the reference is the
  independent one)::

      c_q = N(h W_qa);  q = c_q W_qb          -> heads x (nope | rope)
      [c_kv | k_r] = h W_kva;  c = N(c_kv)    # ONE k_r a token, every head's
      rotary on q's rope part and on k_r      # theta rope_theta, all rope dims
      [k_nope | v] = c W_kvb                  -> heads x (nope | v)
      k = [k_nope | k_r];  P = softmax(q k^T / sqrt(nope + rope)), causal
      out = concat_heads(P v) W_o

  The rotary pairing is the family's interleaved one, dims (2i, 2i + 1) (HF's
  ``apply_rotary_pos_emb_interleave`` for DeepSeek-V3, which this
  ``model_type`` inherits): ASSUMED, the catalog row does not say, and the
  same in the program (``TransformerConfig.rotary_interleaved``). The scores
  are taken a block of ``Q_BLOCK`` queries at a time against all the keys.
- ``FFN_l``, ``l < first_k_dense_replace``: SwiGLU of ``intermediate_size``.
  Otherwise (``topk_method`` ``noaux_tc``, one group) ``s = sigmoid(h W_r)``
  in float32; the ``num_experts_per_tok`` experts with the largest ``s + b``
  (``b`` the stored correction bias, for the CHOICE only); ``w = s[chosen] /
  (their sum + 1e-20)`` (``norm_topk_prob``) ``* routed_scaling_factor``; ``f
  = SwiGLU_shared(h) + sum_k w_k SwiGLU_{e_k}(h)``, every routed expert of
  ``moe_intermediate_size`` and the shared one of ``n_shared_experts`` times
  that.
- A final RMSNorm, then the untied head over the whole vocabulary.

No next-token-prediction module: ``num_nextn_predict_layers`` is 0 in the
configuration as run (the published forward does not run the module, and HF's
classes drop its tensors at load).

``Reference(hf, params, defect=...)`` computes the same forward with ONE
seeded defect (``DEFECTS``): what the configuration's ``correct`` limits and
the CPU tests are shown to tell apart. ``precision_below`` is the WHOLE
forward in the precision below the one the configuration states: both
operands of every matrix product rounded to ``float8_e5m2`` (bf16 stated) and
the cached row ``[c | rope(k_r)]`` to ``float8_e5m2`` (the bf16 latent pool
stated); ``fp8_operands`` and ``latent_fp8`` are its two halves alone.

2. The cost model
-----------------
From the published shapes; matmul work only, 2 FLOPs per multiply-add, the
embedding lookup not counted. Attention 2048 x 768 + 768 x 5120 + 2048 x 576 +
512 x 8960 + 5120 x 2048 = 21.76 M; a dense layer 21.76 + 3 x 2048 x 10240 =
84.67 M; an expert layer 21.76 + shared 9.44 + router 0.13 + 64 x 9.437 =
635.3 M; embedding + head 2 x 154 880 x 2048 = 634.4 M. Whole: 84.67 + 46 x
635.3 + 634.4 = 29.94 B (the published 30B); the cut (one dense + five expert
layers) 3.896 B.

A decode step reads the head, every attention and dense block's matrices, the
routers and shared experts, the matrices of the experts its active slots
TOUCHED (the engine's counter) and the live latent rows ONCE — one plane a
block is both K and V — at the row's bytes the RUN reports (``stats``
``latent_row_bytes``, ``latent_planes``).
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import qwen3_next as _experts
from benchmark.families.mistral import F32, _HIGHEST, _rms  # noqa: F401
from benchmark.families.qwen3_next import is_grouped_matmul  # noqa: F401

Q_BLOCK = 256

# --rehearsal and the CPU tests: the cut's own pattern (a leading dense layer,
# then expert layers), every mechanism at toy widths: 8 experts, the published
# top-4, a shared expert; 4 heads of 24 nope + 8 rope dims (the published 3 : 1)
# and 32 value dims (nope + rope, as published), ranks 48 / 32 (3 : 2)
TOY = {"vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "intermediate_size": 256, "moe_intermediate_size": 64,
       "n_routed_experts": 8, "num_experts": 8,
       "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 24,
       "qk_rope_head_dim": 8, "v_head_dim": 32}

# one seeded defect each: what `correct` and the CPU tests must tell apart
DEFECTS = ("precision_below", "fp8_operands", "latent_fp8", "no_kv_norm",
           "no_q_norm", "no_rope_on_k", "scale_nope_only", "bias_in_weights",
           "no_routed_scale", "no_shared_expert", "v_wrong_columns")


def blocks(hf: dict):
    """[(kind, index within its kind)] in block order: a layer is its
    attention block (``latent``) and then its feed-forward block (``dense``
    | ``moe``)."""
    seen, out = {}, []
    for i in range(hf["num_hidden_layers"]):
        for k in ("latent",
                  "dense" if i < hf.get("first_k_dense_replace", 0) else "moe"):
            out.append((k, seen.get(k, 0)))
            seen[k] = seen.get(k, 0) + 1
    return out


def count(hf: dict, kind: str) -> int:
    return sum(1 for k, _ in blocks(hf) if k == kind)


def latent_dims(hf: dict):
    """(heads, nope, rope, v, q rank, kv rank)."""
    return (hf["num_attention_heads"], hf["qk_nope_head_dim"],
            hf["qk_rope_head_dim"], hf["v_head_dim"], hf["q_lora_rank"],
            hf["kv_lora_rank"])


def _eps(hf):
    return hf.get("rms_norm_eps", 1e-5)


def _rope_interleaved(x, theta):
    """x [S, n, d], positions 0..S-1: rotary over all d dims, pairing dims
    (2i, 2i + 1)."""
    S, d = x.shape[0], x.shape[-1]
    inv = jnp.exp(-jnp.arange(d // 2, dtype=F32) * (math.log(theta) / (d // 2)))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
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
        # what a matrix product's operands are rounded to (None: float32) and
        # whether the cached row keeps 8 bits
        self._operand = jnp.float8_e5m2 \
            if defect in ("precision_below", "fp8_operands") else None
        self._latent_fp8 = defect in ("precision_below", "latent_fp8")
        self._attn = jax.jit(self._latent_block)
        self._dense = jax.jit(self._dense_block)
        self._route = jax.jit(self._router)
        self._shared = jax.jit(self._shared_expert)
        self._head = jax.jit(self._final, static_argnames=("cols",))
        self._embed = jax.jit(lambda p, ids: p["tok_embed"][ids].astype(F32))
        self._norm_in = jax.jit(
            lambda st, j, x: _rms(x, st["ln_scale"][j].astype(F32), _eps(hf)))
        self._add_expert = jax.jit(
            lambda st, j, e, h, w, y:
            y + w[:, None] * self._one_expert(st, j, e, h))

    # ---- pieces (each one jitted program; block / expert index traced) ----

    def _lo(self, a):
        """``a`` in float32, rounded to the precision of a matrix product's
        operands (a plain run: as it is)."""
        a = a.astype(F32)
        return a if self._operand is None else \
            a.astype(self._operand).astype(F32)

    def _mm(self, a, w):
        return self._lo(a) @ self._lo(w)

    def _latent_block(self, st, j, h):
        """h [S, H] -> the latent-attention block's output, EXPANDED."""
        hf = self.hf
        nq, dn, dr, dv, _, rkv = latent_dims(hf)
        S, theta = h.shape[0], float(hf.get("rope_theta", 10000.0))
        c_q = self._mm(h, st["wq_a"][j])
        if self.defect != "no_q_norm":
            c_q = _rms(c_q, st["q_a_norm"][j].astype(F32), _eps(hf))
        q = self._mm(c_q, st["wq_b"][j]).reshape(S, nq, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], _rope_interleaved(q[..., dn:], theta)], axis=-1)
        kv = self._mm(h, st["wkv_a"][j])
        c, k_r = kv[:, :rkv], kv[:, None, rkv:]
        if self.defect != "no_kv_norm":
            c = _rms(c, st["kv_a_norm"][j].astype(F32), _eps(hf))
        if self.defect != "no_rope_on_k":
            k_r = _rope_interleaved(k_r, theta)
        if self._latent_fp8:
            # the cached row in 8 bits: the nearest precision below the bf16
            # latent pool the configuration states
            c, k_r = (a.astype(jnp.float8_e5m2).astype(F32) for a in (c, k_r))
        w = st["wkv_b"][j].reshape(rkv, nq, dn + dv)
        k_nope = jnp.einsum("sc,chn->shn", self._lo(c), self._lo(w[..., :dn]))
        c_v = c
        if self.defect == "v_wrong_columns":
            # V from the row's LAST `rank` columns, [c | k_r][rope:], where the
            # row's first `rank` are the latent
            c_v = jnp.concatenate([c, k_r[:, 0]], axis=-1)[:, dr:]
        v = jnp.einsum("sc,chv->shv", self._lo(c_v), self._lo(w[..., dn:]))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (S, nq, dr))], -1)
        scale = math.sqrt(dn if self.defect == "scale_nope_only" else dn + dr)
        qb = min(Q_BLOCK, S)
        q = self._lo(q).reshape(S // qb, qb, nq, dn + dr)
        k, keys = self._lo(k), jnp.arange(S)[None, :]

        def rows(xs):           # one block of queries against all the keys
            qs, i0 = xs
            s = jnp.einsum("shd,thd->hst", qs, k) / scale
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
        E = hf["n_routed_experts"]
        s = jax.nn.sigmoid(self._mm(h, st["wg"][j]))
        biased = s + st["e_bias"][j].astype(F32)[None]
        idx = jax.lax.top_k(biased, hf["num_experts_per_tok"])[1]
        w = jnp.take_along_axis(
            biased if self.defect == "bias_in_weights" else s, idx, axis=-1)
        if hf.get("norm_topk_prob", True):
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        if self.defect != "no_routed_scale":
            w = w * float(hf.get("routed_scaling_factor", 1.0))
        return jnp.einsum("sk,ske->se", w, jax.nn.one_hot(idx, E, dtype=F32))

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
        causal, so no real position sees a pad; a multiple of ``Q_BLOCK``):
        four padded lengths cover the cell's 4 864 positions."""
        params, hf = self.params, self.hf
        n = len(ids)
        padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        with _HIGHEST():
            x = self._embed(params, jnp.asarray(padded))
            for kind, j in blocks(hf):
                st = params["layers"][kind]
                h = self._norm_in(st, j, x)
                if kind == "latent":
                    y = self._attn(st, j, h)
                elif kind == "dense":
                    y = self._dense(st, j, h)
                else:
                    w = self._route(st, j, h)
                    y = jnp.zeros_like(h) \
                        if self.defect == "no_shared_expert" \
                        else self._shared(st, j, h)
                    for e in range(w.shape[-1]):
                        y = self._add_expert(st, j, e, h, w[:, e], y)
                x = x + y
            V = hf["vocab_size"]
            cols = next(c for c in (7040, 4096, 512, V) if V % c == 0)
            x = x[:n]
            out = np.empty((n, V), np.float32)
            for c0 in range(0, V, cols):
                out[:, c0:c0 + cols] = np.asarray(
                    self._head(params, x, c0, cols=cols))
            return out


# ---- the cost model: parameters and operations ----------------------------

def _as_experts(hf: dict) -> dict:
    """``hf`` under the key the shared expert-layer arithmetic reads
    (``families/qwen3_next.py``: ``num_experts`` counts the experts held;
    every routed expert is held here)."""
    return dict(hf, num_experts=hf["n_routed_experts"])


def attn_params(hf: dict) -> int:
    """The five matrices of one latent-attention block."""
    H = hf["hidden_size"]
    nq, dn, dr, dv, rq, rkv = latent_dims(hf)
    return (H * rq + rq * nq * (dn + dr) + H * (rkv + dr)
            + rkv * nq * (dn + dv) + nq * dv * H)


def expert_params(hf: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def block_params(hf: dict, kind: str, experts: float = None) -> float:
    """Matmul parameters of one block of ``kind`` (``experts`` routed experts
    counted; default all of them). Norm scales and the correction bias
    (~0.001 %) are left out."""
    H = hf["hidden_size"]
    if kind == "latent":
        return attn_params(hf)
    if kind == "dense":
        return 3 * H * hf["intermediate_size"]
    E = hf["n_routed_experts"] if experts is None else experts
    return ((E + hf.get("n_shared_experts", 0)) * expert_params(hf)
            + H * hf["n_routed_experts"])


def head_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def param_count(hf: dict) -> float:
    """Every stored parameter a matmul or the lookup uses: blocks + embedding
    + untied head."""
    return (sum(block_params(hf, kind) for kind, _ in blocks(hf))
            + 2 * head_params(hf))


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token USES + causal attention in the
    expanded order (the family protocol's; no cell trains this model)."""
    nq, dn, dr, dv, _, _ = latent_dims(hf)
    used = sum(block_params(hf, kind, hf["num_experts_per_tok"])
               for kind, _ in blocks(hf)) + head_params(hf)
    attn = 2 * (seq_len / 2) * nq * (dn + dr + dv)
    return 6.0 * used + 3.0 * count(hf, "latent") * attn


def flash_flops(hf: dict, batch: int, seq_len: int) -> dict:
    """One latent block's EXPANDED attention for one step: every head at
    nope + rope dims in Q K^T and v dims in P V (mistral.py's accounting:
    causal half, backward 2.5 x forward)."""
    nq, dn, dr, dv, _, _ = latent_dims(hf)
    one = 2.0 * batch * nq * seq_len * seq_len * (dn + dr + dv) / 2.0 / 2.0
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


def expert_matmul(event_name: str, hf: dict):
    return _experts.expert_matmul(event_name, _as_experts(hf))


def moe_ffn_flops(hf: dict, rows: float) -> float:
    return _experts.moe_ffn_flops(_as_experts(hf), rows)


def moe_ffn_bytes(hf: dict, rows: float, touched: float,
                  bytes_per_value: float = 2.0) -> float:
    return _experts.moe_ffn_bytes(_as_experts(hf), rows, touched,
                                  bytes_per_value)


# ---- the cost model: bytes of a decode step -------------------------------

def touched_experts(hf: dict, counters: dict) -> float:
    """Distinct experts a decode step read, mean per expert block, from the
    engine's routing counter; every expert where it is absent."""
    stats = counters.get("stats") or {}
    return float(stats.get("moe_experts_touched_per_step",
                           hf["n_routed_experts"]))


def latent_row_bytes(hf: dict, counters: dict = None) -> float:
    """One cached row of ONE plane, [c | rope(k_r)]: what the RUN says its
    pool holds (``stats`` ``latent_row_bytes``), else the published width in
    bf16: (512 + 64) x 2 = 1 152 B."""
    stats = (counters or {}).get("stats") or {}
    return float(stats.get("latent_row_bytes",
                           2 * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"])))


def latent_planes(hf: dict, counters: dict = None) -> float:
    stats = (counters or {}).get("stats") or {}
    return float(stats.get("latent_planes", count(hf, "latent")))


def latent_bytes_per_token(hf: dict, counters: dict = None) -> float:
    """One cached position over every plane, read ONCE: a plane is both K
    and V."""
    return latent_planes(hf, counters) * latent_row_bytes(hf, counters)


def weight_bytes(hf: dict, touched: float = None) -> float:
    """bf16 matrices a step reads: every block with ``touched`` routed
    experts per expert block, and the head."""
    return 2.0 * (sum(block_params(hf, kind, touched) for kind, _ in blocks(hf))
                  + head_params(hf))


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step moves: the weights of what it touched
    (attention, dense and shared matrices, routers, the TOUCHED experts, the
    head) and the live latent rows once."""
    return (weight_bytes(hf, touched_experts(hf, counters))
            + latent_bytes_per_token(hf, counters)
            * counters["mean_live_tokens"])


# ---- latent attention in a device trace -----------------------------------

_LATENT_KERNEL = re.compile(r"^%latent_decode[.\d]* = ")
_FLASH = re.compile(r"^%flash_fwd[.\d]* = ")


def _pool_shape(counters: dict):
    """The latent pool leaf's shape as the run reports it, or None."""
    leaf = (counters.get("pool") or {}).get("latent")
    return tuple(leaf["shape"]) if leaf else None


def latent_read_op(event_name: str, counters: dict) -> bool:
    """True if this trace event is part of the DECODE read of the latent
    pool: a kernel of that name (``%latent_decode.N``, a custom call to
    Mosaic), or — the XLA read — an op with an operand or result of the
    gathered blocks' shape, ``[runs, whole blocks of positions, row width]``
    (the gather out of the pool, the scores' and the values' contractions over
    what it gathered; NOT ``[planes, slots, width]``, the step's stacked fresh
    rows), that does not produce the pool itself (a write does)."""
    shape = _pool_shape(counters)
    if shape is None:
        return False
    if _LATENT_KERNEL.match(event_name) and "custom-call" in event_name:
        return True
    planes, _, bs, width = shape
    listed = any(int(n) != planes and int(m) % bs == 0 for n, m in re.findall(
        rf"[a-z0-9]+\[(\d+),(\d+),{width}\]", event_name))
    return listed and not _writes_pool(event_name, shape)


def _writes_pool(event_name: str, shape) -> bool:
    dims = ",".join(str(d) for d in shape)
    return bool(re.match(rf"^%[\w.\-]+ = \(?[a-z0-9]+\[{dims}\]", event_name))


def latent_op(event_name: str, counters: dict) -> bool:
    """True if this trace event is latent attention's in EITHER path: the
    decode read (``latent_read_op``), a write of the pool (an op whose result
    is the pool leaf: the step's row scatter, the prefill's block scatter),
    or the expanded prefill's flash forward (``%flash_fwd.N``: this family
    has no other attention). The low-rank projections, the norms and rotary
    are small XLA fusions that touch no pool and are not in it."""
    shape = _pool_shape(counters)
    if shape is None:
        return False
    if _FLASH.match(event_name) and "custom-call" in event_name:
        return True
    return latent_read_op(event_name, counters) \
        or _writes_pool(event_name, shape)
