"""The Qwen3-Next family (``model_type`` ``qwen3_next``: Qwen3-Next-80B-A3B):
its plain reference, its cost model, its toy widths.

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no cache, no batching, no chunks, no sort, no
capacity. One sequence at a time, one block per call, one expert per call (a
Python loop over the experts HELD). It imports nothing from ``deepspeed_tpu``
and reads the program's stored parameter tree: ``params["layers"]["gdn" |
"attn" | "moe"]``, each stacked on the blocks of its kind, a block's slice
cast to float32 inside its own jitted call (so the reference fits beside the
engine).

The equations, as the issue that added it wrote them down from the published
config and HF's ``modeling_qwen3_next``. ``RMSNorm(x) = x / rms(x) . s``, eps
``rms_norm_eps`` (the checkpoint stores ``s - 1``; the program stores ``s``).
Layer ``i`` is *full attention* iff ``(i + 1) % full_attention_interval ==
0``, else *Gated DeltaNet*; ``h <- h + Mixer(RMSNorm(h)); h <- h + Experts
(RMSNorm(h))``; a final RMSNorm, then the untied head.

- *Gated attention*: ``[q | gate]`` per head from ``wq``, k, v; RMSNorm over
  each head's dims of q and k; rotary (half-split pairing, ``rope_theta``) on
  the first ``partial_rotary_factor x head_dim`` dims; causal softmax
  attention, scale ``head_dim^-1/2``, grouped-query; ``wo (attn .
  sigmoid(gate))``.
- *Gated DeltaNet* (``Hk`` key heads, ``Hv`` value heads): ``[q | k | v | z]``
  and ``[b | a]`` projected; ``[q | k | v]`` through a depthwise causal
  convolution (kernel ``linear_conv_kernel_dim``, no bias) and SiLU; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; ``q <- l2norm(q)
  dk^-1/2``, ``k <- l2norm(k)`` (eps 1e-6); key head ``j`` serves value heads
  ``j R .. j R + R - 1``; per value head a float32 state ``S`` [dk, dv] from
  0, ``S~ = exp(g_t) S; S <- S~ + k_t (beta_t (v_t - S~^T k_t))^T; o_t = S^T
  q_t`` — a sequential ``lax.scan`` over positions; ``y = out_proj(RMSNorm_dv
  (o) w_n . silu(z))`` (the norm BEFORE the gate).
- *Experts*: ``p = softmax(x W_r)`` over ALL ``num_experts_router`` experts;
  the top-k by ``p``; weights ``p_e`` over the sum of the k chosen; expert
  ``W_down (silu(W_gate x) . W_up x)``; the shared expert of the same form
  times ``sigmoid(w_s . x)``. THE CHIP'S SHARE: the stacks hold experts
  ``expert_first .. + num_experts - 1``; the layer returns the shared expert
  + the sum over the chosen experts THAT ARE HELD, with the weights
  normalised over all k — what this chip contributes before the deployment's
  combine — and that partial result goes on to the next block. Nothing
  stands in for the absent chips.

Departures: the multi-token-prediction module is not part of the next-token
forward and is left out; ``intermediate_size`` (a dense layer's width) is
unread because ``mlp_only_layers`` is empty. Storage: the experts' up
projection is kept as ``moe_w_in_t`` ``[blocks, E, F, H]``; the reference
multiplies by its transpose. ``in_qkvz`` keeps [q | k | v | z] as contiguous
column ranges (a checkpoint interleaves them per key head).

``Reference(hf, params, defect=...)`` computes the same forward with ONE
seeded defect (``DEFECTS``): what the configuration's ``correct`` limits and
the CPU tests are shown to tell apart. ``precision_below`` is the WHOLE
forward in the precision below the one the configuration states, every kind
of state at once: both operands of every matrix product and the convolved
``[q | k | v]`` rounded to ``float8_e5m2`` (bf16 stated), the recurrent state
to bf16 (float32 stated), K and V to 4 bits (the int8 pool stated).

2. The cost model
-----------------
From the published shapes; matmul work only, 2 FLOPs per multiply-add, the
embedding lookup not counted. ``block_params`` counts a block's matrices: a
Gated DeltaNet mixer 33.72 M, an attention mixer 27.26 M, an expert block
4.20 M outside its routed experts (router over 512, the shared expert and its
gate) + 3.146 M a routed expert held. 48 layers x 512 experts + embedding +
head = 79.67 B; the cut (12 layers, 128 experts, 37 984 rows) 5.423 B.

A decode step reads the head slice, every mixer's matrices, the routers and
shared experts, the matrices of the HELD experts its active slots TOUCHED (the
engine's counter), the live K/V rows of the attention blocks only, and — read
AND written — the recurrent state and convolution tail of the live slots.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.mistral import F32, _HIGHEST, _rms  # noqa: F401

L2_EPS = 1e-6

# --rehearsal and the CPU tests: ONE period (3 Gated DeltaNet layers + 1
# attention layer, an expert block after each), every mechanism at toy
# widths: 8 of 32 experts held (the published top-10 stays — the CPU tests
# take top-4 —); 2 key heads serving 4 value heads (the published 2 : 1 kept),
# dims 32; 4 query heads over 2 K/V heads of 64, rotary on the first 16
TOY = {"vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 4,
       "full_attention_interval": 4, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 64,
       "linear_num_key_heads": 2, "linear_num_value_heads": 4,
       "linear_key_head_dim": 32, "linear_value_head_dim": 32,
       "num_experts": 8, "num_experts_router": 32, "expert_first": 0,
       "moe_intermediate_size": 64,
       "shared_expert_intermediate_size": 64, "intermediate_size": 64}

# one seeded defect each: what `correct` and the CPU tests must tell apart
DEFECTS = ("precision_below", "renorm_over_held", "no_l2norm", "beta_one",
           "g_zero", "no_out_gate", "rotary_all_dims", "no_shared_gate",
           "state_not_zeroed", "bf16_state", "bf16_router", "kv_4bit")


def blocks(hf: dict):
    """[(kind, index within its kind)] in block order: a layer is its token
    mixer (``gdn`` | ``attn``) and then its expert block (``moe``)."""
    interval = hf.get("full_attention_interval", 4)
    seen, out = {"gdn": 0, "attn": 0}, []
    for i in range(hf["num_hidden_layers"]):
        kind = "attn" if (i + 1) % interval == 0 else "gdn"
        out += [(kind, seen[kind]), ("moe", i)]
        seen[kind] += 1
    return out


def count(hf: dict, kind: str) -> int:
    return sum(1 for k, _ in blocks(hf) if k == kind)


def gdn_dims(hf: dict):
    """(key heads, value heads, key dim, value dim, conv_dim, kernel)."""
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    return Hk, Hv, dk, dv, 2 * Hk * dk + Hv * dv, hf.get("linear_conv_kernel_dim", 4)


def router_width(hf: dict) -> int:
    """Experts the router scores (``num_experts`` counts the experts HELD)."""
    return hf.get("num_experts_router", hf["num_experts"])


def held_share(hf: dict) -> float:
    return hf["num_experts"] / router_width(hf)


def _eps(hf):
    return hf.get("rms_norm_eps", 1e-6)


def _rotary_dim(hf):
    return int(hf["head_dim"] * float(hf.get("partial_rotary_factor", 0.25)))


def _rope_first(x, theta, rd):
    """x [S, n, hd], positions 0..S-1: rotate-half pairing on the first
    ``rd`` dims, the rest passed through."""
    S = x.shape[0]
    half = rd // 2
    inv = jnp.exp(-jnp.arange(half, dtype=F32) * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rd:]], axis=-1)


class Reference:
    """``Reference(hf, params)`` — ``hf`` the published config dict as run
    (the cut depth, the experts held and the router's width), ``params`` the
    program's parameter tree. ``defect``: one of ``DEFECTS``."""

    def __init__(self, hf: dict, params, defect: str = None):
        if defect is not None and defect not in DEFECTS:
            raise ValueError(f"defect {defect!r}: one of {DEFECTS}")
        self.hf, self.params, self.defect = hf, params, defect
        # what a matrix product's operands are rounded to (None: float32),
        # whether the recurrent state is bf16, and the bits of K and V
        self._operand = jnp.float8_e5m2 if defect == "precision_below" \
            else None
        self._bf16_state = defect in ("bf16_state", "precision_below")
        self._kv_4bit = defect in ("kv_4bit", "precision_below")
        self._gdn = jax.jit(self._gdn_block)
        self._attn = jax.jit(self._attn_block)
        self._route = jax.jit(self._router)
        self._shared = jax.jit(self._shared_expert)
        self._head = jax.jit(self._final, static_argnames=("cols",))
        self._embed = jax.jit(lambda p, ids: p["tok_embed"][ids].astype(F32))
        self._norm_in = jax.jit(
            lambda st, j, x: _rms(x, st["ln_scale"][j].astype(F32), _eps(hf)))
        self._add = jax.jit(lambda x, y: x + y)
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

    def _gdn_block(self, st, j, h):
        """h [S, H] -> the mixer's output."""
        hf = self.hf
        Hk, Hv, dk, dv, conv_dim, K = gdn_dims(hf)
        S = h.shape[0]
        at = lambda n: st[n][j].astype(F32)                        # noqa: E731
        qkvz, ba = self._mm(h, at("in_qkvz")), self._mm(h, at("in_ba"))
        qkv, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
        b, a = ba[:, :Hv], ba[:, Hv:]
        # causal depthwise convolution: row t sees rows t-K+1 .. t (zeros
        # before the sequence). conv_w[k] multiplies the row K-1-k back.
        w = at("conv_w")
        src = jnp.concatenate([jnp.zeros((K - 1, conv_dim), F32), qkv], 0)
        qkv = self._lo(jax.nn.silu(sum(src[k:k + S] * w[k][None]
                                       for k in range(K))))
        q = qkv[:, :Hk * dk].reshape(S, Hk, dk)
        k = qkv[:, Hk * dk:2 * Hk * dk].reshape(S, Hk, dk)
        v = qkv[:, 2 * Hk * dk:].reshape(S, Hv, dv)
        if self.defect != "no_l2norm":
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        q = jnp.repeat(q * dk ** -0.5, Hv // Hk, axis=1)         # [S, Hv, dk]
        k = jnp.repeat(k, Hv // Hk, axis=1)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(at("A_log"))[None] * jax.nn.softplus(a + at("dt_bias")[None])
        if self.defect == "beta_one":
            beta = jnp.ones_like(beta)
        if self.defect == "g_zero":
            g = jnp.zeros_like(g)
        low = self._bf16_state

        def step(state, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            state = state * jnp.exp(g_t)[:, None, None]
            u = b_t[:, None] * (v_t - jnp.einsum("hkd,hk->hd", state, k_t))
            state = state + k_t[:, :, None] * u[:, None, :]
            if low:
                state = state.astype(jnp.bfloat16).astype(F32)
            return state, jnp.einsum("hkd,hk->hd", state, q_t)

        s0 = jnp.zeros((Hv, dk, dv), F32)
        if self.defect == "state_not_zeroed":
            # the slot's last request left its state: here, this sequence's own
            s0 = jax.lax.scan(step, s0, (q, k, v, g, beta))[0]
        _, o = jax.lax.scan(step, s0, (q, k, v, g, beta))        # [S, Hv, dv]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + _eps(hf)) \
            * at("gate_norm")[None, None]
        return self._mm(o.reshape(S, Hv * dv) * jax.nn.silu(z), at("out_proj"))

    def _attn_block(self, st, j, h):
        hf = self.hf
        nq, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                       hf["head_dim"])
        S = h.shape[0]
        qg = self._mm(h, st["wq"][j]).reshape(S, nq, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:].reshape(S, nq * hd)
        k = self._mm(h, st["wk"][j]).reshape(S, nkv, hd)
        v = self._mm(h, st["wv"][j]).reshape(S, nkv, hd)
        q = _rms(q, st["q_norm"][j].astype(F32), _eps(hf))
        k = _rms(k, st["k_norm"][j].astype(F32), _eps(hf))
        rd = hd if self.defect == "rotary_all_dims" else _rotary_dim(hf)
        theta = float(hf.get("rope_theta", 10000.0))
        q, k = _rope_first(q, theta, rd), _rope_first(k, theta, rd)
        if self._kv_4bit:
            # K and V rounded to 4 bits per (position, head): the nearest
            # precision below the int8 pool the configuration states
            def four_bits(a):
                scale = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 7.0
                return jnp.round(a / jnp.where(scale > 0, scale, 1.0)) * scale
            k, v = four_bits(k), four_bits(v)
        q = q.reshape(S, nkv, nq // nkv, hd)
        s = jnp.einsum("sngd,tnd->ngst", self._lo(q), k) / math.sqrt(hd)
        ok = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("ngst,tnd->sngd", self._lo(p), v).reshape(S, nq * hd)
        if self.defect != "no_out_gate":
            o = o * jax.nn.sigmoid(gate)
        return self._mm(o, st["wo"][j])

    def _router(self, st, j, h):
        """[S, held] combine weights of the experts HELD, zero where an
        expert was not chosen: the softmax and the top-k are over ALL the
        router's experts, the weights divided by the sum of all the k
        chosen."""
        hf = self.hf
        E, first = hf["num_experts"], hf.get("expert_first", 0)
        if self.defect == "bf16_router":
            logits = (h.astype(jnp.bfloat16).astype(F32)
                      @ st["wg"][j].astype(jnp.bfloat16).astype(F32))
            p = jax.nn.softmax(logits.astype(jnp.bfloat16).astype(F32), -1)
        else:
            p = jax.nn.softmax(self._mm(h, st["wg"][j]), axis=-1)
        w, idx = jax.lax.top_k(p, hf["num_experts_per_tok"])
        mine = (idx >= first) & (idx < first + E)
        if self.defect == "renorm_over_held":
            w = jnp.where(mine, w, 0.0)
        if hf.get("norm_topk_prob", True):
            w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-30)
        w = jnp.where(mine, w, 0.0)
        return jnp.einsum("sk,ske->se", w,
                          jax.nn.one_hot(idx - first, E, dtype=F32))

    def _one_expert(self, st, j, e, h):
        up = self._mm(h, st["moe_w_in_t"][j, e].T)
        gate = self._mm(h, st["moe_w_gate"][j, e])
        return self._mm(jax.nn.silu(gate) * up, st["moe_w_out"][j, e])

    def _shared_expert(self, st, j, h):
        up = self._mm(h, st["shared_w_in"][j])
        gate = self._mm(h, st["shared_w_gate"][j])
        out = self._mm(jax.nn.silu(gate) * up, st["shared_w_out"][j])
        if self.defect == "no_shared_gate":
            return out
        return out * jax.nn.sigmoid(
            self._mm(h, st["shared_gate"][j][:, None]))

    def _final(self, params, x, c0, cols: int):
        x = _rms(x, params["final_norm_scale"].astype(F32), _eps(self.hf))
        head = jax.lax.dynamic_slice_in_dim(params["lm_head"], c0, cols, axis=1)
        return self._mm(x, head)

    # ---- whole forward ----------------------------------------------------

    def logits(self, ids, pad_to: int = 1280):
        """ids [S] int -> float32 logits [S, vocab] as a NUMPY array. The ids
        are padded at the END to a multiple of ``pad_to`` (every block is
        causal, so no real position sees a pad): two padded lengths cover
        the cell's 2560 positions, and every new length is a dozen programs
        to compile, the sequential scan among them."""
        params, hf = self.params, self.hf
        n = len(ids)
        padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        with _HIGHEST():
            x = self._embed(params, jnp.asarray(padded))
            for kind, j in blocks(hf):
                st = params["layers"][kind]
                h = self._norm_in(st, j, x)
                if kind == "gdn":
                    y = self._gdn(st, j, h)
                elif kind == "attn":
                    y = self._attn(st, j, h)
                else:
                    w = self._route(st, j, h)
                    y = self._shared(st, j, h) if "shared_w_in" in st \
                        else jnp.zeros_like(x)
                    for e in range(w.shape[-1]):
                        y = self._add_expert(st, j, e, h, w[:, e], y)
                x = self._add(x, y)
            V = hf["vocab_size"]
            cols = next(c for c in (16384, 4096, 512, V) if V % c == 0)
            x = x[:n]
            out = np.empty((n, V), np.float32)
            for c0 in range(0, V, cols):
                out[:, c0:c0 + cols] = np.asarray(
                    self._head(params, x, c0, cols=cols))
            return out


# ---- the cost model: parameters and operations ----------------------------

def expert_params(hf: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def block_params(hf: dict, kind: str, experts: float = None) -> float:
    """Matmul parameters of one block of ``kind`` (``experts`` routed
    experts counted; default the experts HELD: what the chip holds). The
    convolution's taps are counted with the Gated DeltaNet mixer; norm
    scales and the per-head scalars (~0.001 %) are left out."""
    H = hf["hidden_size"]
    if kind == "gdn":
        Hk, Hv, dk, dv, conv_dim, K = gdn_dims(hf)
        return (H * (conv_dim + Hv * dv) + H * 2 * Hv + conv_dim * K
                + Hv * dv * H)
    if kind == "attn":
        nq, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                       hf["head_dim"])
        return H * nq * hd * 2 + 2 * H * nkv * hd + nq * hd * H
    E = hf["num_experts"] if experts is None else experts
    Fs = hf.get("shared_expert_intermediate_size", 0) or 0
    return (E * expert_params(hf) + 3 * H * Fs + (H if Fs else 0)
            + H * router_width(hf))


def head_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def param_count(hf: dict) -> float:
    """Every stored parameter a matmul, the convolution or the lookup uses:
    blocks + embedding + untied head."""
    return (sum(block_params(hf, kind) for kind, _ in blocks(hf))
            + 2 * head_params(hf))


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token USES on this chip + causal
    attention in the attention blocks + the recurrence (``gdn_chunk_flops``
    per position x 3)."""
    used = sum(block_params(hf, kind, hf["num_experts_per_tok"] * held_share(hf))
               for kind, _ in blocks(hf)) + head_params(hf)
    attn = 2 * 2 * (seq_len / 2) * hf["num_attention_heads"] * hf["head_dim"]
    return (6.0 * used + 3.0 * count(hf, "attn") * attn
            + 3.0 * count(hf, "gdn") * gdn_chunk_flops(hf, 1))


def flash_flops(hf: dict, batch: int, seq_len: int) -> dict:
    """One attention block's kernels for one step (mistral.py's accounting:
    causal half, backward 2.5 x forward)."""
    one = 2.0 * batch * hf["num_attention_heads"] * seq_len * seq_len \
        * hf["head_dim"] / 2.0
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


# ---- the recurrence -------------------------------------------------------

GDN_CHUNK = 64


def gdn_state_bytes(hf: dict) -> float:
    """One slot's recurrent state in ONE Gated DeltaNet block: float32
    [value heads, key dim, value dim]."""
    _, Hv, dk, dv, _, _ = gdn_dims(hf)
    return 4.0 * Hv * dk * dv


def conv_tail_bytes(hf: dict, itemsize: int = 2) -> float:
    _, _, _, _, conv_dim, K = gdn_dims(hf)
    return float(itemsize * (K - 1) * conv_dim)


def gdn_step_bytes(hf: dict, slots: float) -> float:
    """Least bytes the step of ONE Gated DeltaNet block moves for ``slots``
    live slots: their state read once and written once, and the convolution
    tail likewise (the tail is XLA's, beside the kernel: it is counted
    because the step cannot do without it)."""
    return 2.0 * slots * (gdn_state_bytes(hf) + conv_tail_bytes(hf))


def gdn_chunk_flops(hf: dict, tokens: float, chunk: int = GDN_CHUNK) -> float:
    """FLOPs the chunk form of ONE Gated DeltaNet block NEEDS over
    ``tokens`` positions, 2 per multiply-add. Per position and key head: k
    k^T and q k^T (2 Q dk). Per position and value head: the triangular
    solve by substitution (Q^2 / 3), ``T (beta v)``, ``T (beta k e^c)`` and
    ``tril(q k^T G) V'`` (Q (2 dv + dk)), and the three products with the
    carried state (3 dk dv). The doublings the kernel spends on the solve
    beyond Q^2 / 3 are time, not need."""
    Hk, Hv, dk, dv, _, _ = gdn_dims(hf)
    Q = chunk
    return 2.0 * tokens * (Hk * 2 * Q * dk
                           + Hv * (Q * Q / 3 + Q * (2 * dv + dk) + 3 * dk * dv))


def gdn_chunk_bytes(hf: dict, tokens: float, itemsize: int = 2) -> float:
    """Least bytes the chunk form of ONE block must move: q, k, v, g and
    beta in, o out, and the state in and out once."""
    Hk, Hv, dk, dv, _, _ = gdn_dims(hf)
    per_token = itemsize * (2 * Hk * dk + 2 * Hv * dv) + 2 * 4 * Hv
    return tokens * per_token + 2.0 * gdn_state_bytes(hf)


_GDN_KERNEL = re.compile(r"^%gdn_(chunk|step)[.\d]* = ")


def gdn_kernel(event_name: str):
    """``"chunk"`` / ``"step"`` if this trace event is one of the
    recurrence's Pallas kernels (``%gdn_chunk.N``, ``%gdn_step.N``: a custom
    call to Mosaic), else None."""
    m = _GDN_KERNEL.match(event_name)
    return m.group(1) if m and "custom-call" in event_name else None


# ---- the expert matmuls in a device trace ---------------------------------
#
# As families/olmoe.py tells them: a call of many tokens runs each projection
# as ONE grouped matmul over the sorted tokens x top-k rows (`%moe_gmm.N`, a
# custom call with result [rows, N]); a call of few tokens runs every HELD
# expert over all T rows in a fusion that reads a layer of the stacked
# weights [blocks, E, F, H] / [blocks, E, H, F] in place and has an [E, T, H
# or F] operand or result. E is the experts held.

_EXPERT_KERNEL = re.compile(
    r"^%(moe_gmm|gmm|ragged-dot-none)[.\d]* = [a-z0-9]+\[(\d+),\d+\]")


def is_grouped_matmul(event_name: str) -> bool:
    return bool(_EXPERT_KERNEL.match(event_name)) and "custom-call" in event_name


def expert_matmul(event_name: str, hf: dict):
    """``(tokens, matrices)`` if this trace event is (part of) an expert
    layer's matmuls, else None (families/olmoe.py's contract; an expert here
    has three matrices)."""
    E, H, F = (hf["num_experts"], hf["hidden_size"],
               hf["moe_intermediate_size"])
    if is_grouped_matmul(event_name):
        rows = int(_EXPERT_KERNEL.match(event_name).group(2))
        return max(1, rows // hf["num_experts_per_tok"]), 1
    if " fusion(" not in event_name:
        return None
    stacks = re.findall(rf"\[\d+,{E},(?:{H},{F}|{F},{H})\]", event_name)
    rows = [int(t) for t, n in re.findall(rf"\[{E},(\d+),({H}|{F})\]", event_name)
            if {int(t), int(n)} != {H, F}]
    if not stacks or not rows:
        return None
    return rows[0], len(stacks)


def moe_ffn_flops(hf: dict, rows: float) -> float:
    """FLOPs ONE layer's three matmuls NEED for ``rows`` (token, expert)
    pairs the ROUTER made: only the share that lands on the experts held is
    this chip's to multiply."""
    return 2.0 * rows * held_share(hf) * expert_params(hf)


def moe_ffn_bytes(hf: dict, rows: float, touched: float,
                  bytes_per_value: float = 2.0) -> float:
    """Least bytes ONE layer's three matmuls move: the matrices of the
    ``touched`` HELD experts once, and this chip's share of the rows in and
    out (H wide)."""
    return bytes_per_value * (touched * expert_params(hf)
                              + 2.0 * rows * held_share(hf) * hf["hidden_size"])


# ---- the cost model: bytes of a decode step -------------------------------

def touched_experts(hf: dict, counters: dict) -> float:
    """Distinct HELD experts a decode step read, mean per expert block, from
    the engine's routing counter; every held expert where it is absent."""
    stats = counters.get("stats") or {}
    return float(stats.get("moe_experts_touched_per_step", hf["num_experts"]))


def kv_bytes_per_token(hf: dict, kv_bits: int) -> float:
    """K and V of one cached position over the ATTENTION blocks only."""
    per_head = hf["head_dim"] + 4 if kv_bits == 8 else 2 * hf["head_dim"]
    return 2.0 * count(hf, "attn") * hf["num_key_value_heads"] * per_head


def weight_bytes(hf: dict, touched: float = None) -> float:
    """bf16 matrices a step reads: every block with ``touched`` routed
    experts per expert block, and the head slice."""
    return 2.0 * (sum(block_params(hf, kind, touched) for kind, _ in blocks(hf))
                  + head_params(hf))


def state_bytes_per_slot(hf: dict) -> float:
    """One slot's recurrent state over all Gated DeltaNet blocks (state +
    tail)."""
    return count(hf, "gdn") * (gdn_state_bytes(hf) + conv_tail_bytes(hf))


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step moves: the weights of what it touched
    (other weights + the TOUCHED held experts + the head slice), the live
    K/V of the attention blocks, and the recurrent state and tails of the
    live slots read and written (``mean_occupancy``; 0 live slots: weights
    alone)."""
    live = float(counters.get("mean_occupancy", 0.0))
    return (weight_bytes(hf, touched_experts(hf, counters))
            + kv_bytes_per_token(hf, counters["kv_cache_bits"])
            * counters["mean_live_tokens"]
            + 2.0 * live * state_bytes_per_slot(hf))
