"""The Falcon-H1 family (``model_type`` ``falcon_h1``: TII Falcon-H1-34B-Instruct):
its plain reference, its cost model, its toy widths.

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no cache, no batching, no chunks. One sequence at a
time, one branch of a layer per call, the feed-forward and the head in column
slices and attention in query blocks, every leaf upcast where it is used, so
that the float32 copies beside the engine's weights are a few hundred MB. It
imports nothing from ``deepspeed_tpu`` and reads the program's stored
parameter tree: ``params["layers"]["par"]`` (a layer's ONE input norm and both
its mixers) and ``params["layers"]["dense"]`` (the norm before the feed-forward
and its three matrices), each stacked on the layers. The muP multipliers are
read from the config dict, as the program reads them: the tree holds the
tensors as a checkpoint publishes them.

The layer, as the issue that added it wrote it down from the config's keys
(HF ``modeling_falcon_h1``), for layer input ``h``:

    n   = RMSNorm_1(h)
    zxd = in_proj(n * ssm_in_multiplier) * mup     # the five ssm_multipliers
    z, xBC, dt = split(zxd)                        # over z | x | B | C | dt
    x, B, C = split(silu(conv1d(xBC) + conv_b))    # depthwise, causal, K = 4
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    m   = out_proj(RMSNormGrouped(y * silu(z)))    # the gate BEFORE the norm
    u   = n * attention_in_multiplier
    q, k, v = q_proj(u), k_proj(u) * key_multiplier, v_proj(u)
    a   = o_proj(causal_softmax_attention(rope(q), rope(k), v))
    h'  = h + m * ssm_out_multiplier + a * attention_out_multiplier
    f   = RMSNorm_2(h')
    h'' = h' + down(up(f) * silu(gate(f) * g)) * d      # mlp_multipliers g, d

around it ``embed(ids) * embedding_multiplier``, a final RMSNorm and
``lm_head(h) * lm_head_multiplier``, untied. The recurrence is a SEQUENTIAL
``lax.scan`` over positions with a float32 state (not the chunk algebra the
program's kernel uses); head ``h`` reads group ``h // (heads / groups)``;
rotary over the whole head in the rotate-half pairing, theta ``rope_theta``.

Departures: none in the arithmetic.

``Reference(hf, params, defect=...)`` computes the same forward with ONE
seeded defect (``DEFECTS``): what the configuration's ``correct`` limits and
the CPU tests are shown to tell apart. ``precision_below`` is the WHOLE
forward in the precision below the one the configuration states, every kind
of state at once: both operands of every matrix product and the convolved
``xBC`` rounded to ``float8_e5m2`` (bf16 stated), the SSM state to bf16
(float32 stated), K and V to 4 bits (the int8 pool stated). A multiplier is
left out by handing the reference a config with 1 in its place.

``WITNESSES`` are no defects: the same forward in the precision the
configuration STATES, part by part, still as a sequential scan with no kernel
and no cache. ``bf16_operands``: both operands of every matrix product, the
convolved ``xBC`` and the residual stream rounded to bf16. ``int8_read``: K
and V rounded to int8 a (position, head), as the pool's rows are, the query
to int8 a row and the probabilities x V's scale to int8 a row, as the decode
read does (``models/transformer.py`` ``_quant_query`` / ``_quant_probs``; the
program's prefill attends in bf16, so this puts the decode read's rounding on
the prompt's rows too). ``stated_precision``: both. A run that mismatches the
float32 reference and agrees with ``stated_precision`` computes what the
configuration states; one that agrees with neither does not.

2. The cost model
-----------------
From the published shapes; matmul work only, 2 FLOPs per multiply-add, the
embedding lookup not counted. ``block_params``: a layer's attention 31.46 M,
its Mamba mixer 68.33 M (+ 0.03 M of convolution and vectors), its
feed-forward 330.30 M; 72 layers + embedding + head = 33.64 B.

A decode step reads the head and every layer's matrices, the live K/V rows of
EVERY layer, and — read AND written — the recurrent state of the live slots
in EVERY layer: both kinds of state, in all layers.

``blocks(hf)`` answers in the names the benchmark's readers ask for: a layer
is a ``"mamba"`` block (it owns a state layer and runs both SSM kernels), an
``"attn"`` block (it owns a K/V plane) and a ``"dense"`` block.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.mistral import F32, _HIGHEST, _rms, _rope
# the recurrence's two kernels are the Nemotron-H family's, by the same names
from benchmark.families.nemotron_h import _SSM_KERNEL, ssm_kernel  # noqa: F401

# --rehearsal and the CPU tests: every mechanism at toy widths — 5 query heads
# a K/V head and the published FOUR K/V heads (what decides how an int8 pool
# is stored: ``models/hybrid.py`` blocks_head_major), 8 Mamba heads of 16 in 2
# groups (4 a group), state 32, chunks of 16, two layers (PD PD). The multipliers stay the published ones, but
# `attention_in_multiplier`: 1 as published, 0.75 here so that leaving it out
# shows.
TOY = {"vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 2,
       "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 32,
       "intermediate_size": 256, "mamba_n_heads": 8, "mamba_d_head": 16,
       "mamba_d_ssm": 128, "mamba_n_groups": 2, "mamba_d_state": 32,
       "mamba_chunk_size": 16, "attention_in_multiplier": 0.75}

# one seeded defect each: what `correct` and the CPU tests must tell apart
DEFECTS = ("no_key_multiplier", "no_mup_vector", "no_ssm_branch",
           "no_attn_branch", "no_rotary", "gate_after_norm", "no_conv_bias",
           "no_D", "state_not_zeroed", "bf16_state", "kv_4bit",
           "precision_below")
# the forward in the STATED precision, part by part (no defects)
WITNESSES = ("bf16_operands", "int8_read", "stated_precision")

# the fourteen multipliers, as (config key, index in it or None)
MULTIPLIERS = (("embedding_multiplier", None), ("lm_head_multiplier", None),
               ("attention_in_multiplier", None),
               ("attention_out_multiplier", None), ("key_multiplier", None),
               ("ssm_in_multiplier", None), ("ssm_out_multiplier", None),
               *(("ssm_multipliers", i) for i in range(5)),
               ("mlp_multipliers", 0), ("mlp_multipliers", 1))


def without_multiplier(hf: dict, key: str, index=None) -> dict:
    """``hf`` with one multiplier left out (1 in its place)."""
    if index is None:
        return dict(hf, **{key: 1.0})
    values = list(hf[key])
    values[index] = 1.0
    return dict(hf, **{key: values})


def blocks(hf: dict):
    """[(kind, index within its kind)] in block order, in the names the
    benchmark's readers count by: every layer a "mamba", an "attn" and a
    "dense" block."""
    return [(kind, j) for j in range(hf["num_hidden_layers"])
            for kind in ("mamba", "attn", "dense")]


def mamba_dims(hf: dict):
    """(heads, head dim, groups, state, d_inner, conv_dim, kernel)."""
    nh, hd = hf["mamba_n_heads"], hf["mamba_d_head"]
    G, N = hf["mamba_n_groups"], hf["mamba_d_state"]
    return nh, hd, G, N, nh * hd, nh * hd + 2 * G * N, hf.get("mamba_d_conv", 4)


def attn_dims(hf: dict):
    """(query heads, K/V heads, head dim)."""
    nq = hf["num_attention_heads"]
    return nq, hf["num_key_value_heads"], hf.get("head_dim") or \
        hf["hidden_size"] // nq


def mup_vector(hf: dict):
    """The five ``ssm_multipliers`` over in_proj's columns z | x | B | C | dt."""
    nh, _, G, N, d_inner, _, _ = mamba_dims(hf)
    return np.repeat(np.asarray(hf["ssm_multipliers"], np.float32),
                     (d_inner, d_inner, G * N, G * N, nh))


def _rounded(a, levels: float):
    """``a`` rounded symmetrically to ``levels`` steps a side of 0, one scale
    for each row of the last axis (its largest magnitude)."""
    scale = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / levels
    return jnp.round(a / jnp.where(scale > 0, scale, 1.0)) * scale


class Reference:
    """``Reference(hf, params)`` — ``hf`` the published config dict as run
    (the cut depth), ``params`` the program's parameter tree. ``defect``: one
    of ``DEFECTS``."""

    QUERY_BLOCK = 640          # attention's query rows a call
    FFN_SLICES = 4             # the feed-forward's width in that many calls

    def __init__(self, hf: dict, params, defect: str = None):
        if defect is not None and defect not in DEFECTS + WITNESSES:
            raise ValueError(f"defect {defect!r}: one of {DEFECTS + WITNESSES}")
        if defect == "no_key_multiplier":
            hf = without_multiplier(hf, "key_multiplier")
        if defect == "no_mup_vector":
            hf = dict(hf, ssm_multipliers=[1.0] * 5)
        self.hf, self.params, self.defect = hf, params, defect
        # what a matrix product's operands are rounded to (None: float32),
        # whether the residual stream is bf16 and the SSM state is bf16, the
        # levels K and V are rounded to a side of 0 (4 bits: 7, int8: 127),
        # and whether the query and the probabilities are int8 too
        bf16 = defect in ("bf16_operands", "stated_precision")
        self._operand = jnp.float8_e5m2 if defect == "precision_below" \
            else jnp.bfloat16 if bf16 else None
        self._bf16_state = defect in ("bf16_state", "precision_below")
        self._int8_read = defect in ("int8_read", "stated_precision")
        self._kv_levels = 7.0 if defect in ("kv_4bit", "precision_below") \
            else 127.0 if self._int8_read else None
        eps = hf.get("rms_norm_eps", 1e-5)
        self._mamba = jax.jit(self._mamba_branch)
        self._qkv = jax.jit(self._attn_qkv)
        self._attend = jax.jit(self._attn_rows)
        self._attn_out = jax.jit(
            lambda st, j, o: self._mm(o, st["wo"][j])
            * hf["attention_out_multiplier"])
        self._ffn = jax.jit(self._ffn_slice, static_argnames=("cols",))
        self._head = jax.jit(self._final, static_argnames=("cols",))
        self._embed = jax.jit(lambda p, ids: p["tok_embed"][ids].astype(F32)
                              * hf["embedding_multiplier"])
        self._norm_in = jax.jit(
            lambda st, j, x: _rms(x, st["ln_scale"][j].astype(F32), eps))
        self._add = jax.jit(
            (lambda x, y: (x + y).astype(jnp.bfloat16).astype(F32)) if bf16
            else (lambda x, y: x + y))

    # ---- pieces (each one jitted program; the layer index traced) ---------

    def _lo(self, a):
        """``a`` in float32, rounded to the precision of a matrix product's
        operands (a plain run: as it is)."""
        a = a.astype(F32)
        return a if self._operand is None else \
            a.astype(self._operand).astype(F32)

    def _mm(self, a, w):
        return self._lo(a) @ self._lo(w)

    def _mamba_branch(self, st, j, n):
        """n [S, H], the layer's normed input -> m [S, H], the output
        multiplier applied."""
        hf = self.hf
        nh, hd, G, N, d_inner, conv_dim, K = mamba_dims(hf)
        S = n.shape[0]
        at = lambda name: st[name][j].astype(F32)                  # noqa: E731
        zxd = self._mm(n * hf["ssm_in_multiplier"], at("in_proj")) \
            * mup_vector(hf)[None]
        z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:d_inner + conv_dim],
                      zxd[:, d_inner + conv_dim:])
        # causal depthwise convolution: row t sees rows t-K+1 .. t (zeros
        # before the sequence). conv_w[k] multiplies the row K-1-k back.
        w = at("conv_w")
        ext = jnp.concatenate([jnp.zeros((K - 1, conv_dim), F32), xbc], 0)
        conv = sum(ext[k:k + S] * w[k][None] for k in range(K))
        if self.defect != "no_conv_bias":
            conv = conv + at("conv_b")[None]
        xbc = self._lo(jax.nn.silu(conv))
        x = xbc[:, :d_inner].reshape(S, nh, hd)
        B = xbc[:, d_inner:d_inner + G * N].reshape(S, G, N)
        C = xbc[:, d_inner + G * N:].reshape(S, G, N)
        dt = jax.nn.softplus(dt + at("dt_bias")[None])
        A = -jnp.exp(at("A_log"))
        Bh, Ch = jnp.repeat(B, nh // G, axis=1), jnp.repeat(C, nh // G, axis=1)
        low = self._bf16_state

        def step(state, xs):
            x_t, dt_t, B_t, C_t = xs
            state = (state * jnp.exp(dt_t * A)[:, None, None]
                     + (dt_t[:, None] * x_t)[..., None] * B_t[:, None, :])
            if low:
                state = state.astype(jnp.bfloat16).astype(F32)
            return state, jnp.einsum("hpn,hn->hp", state, C_t)

        s0 = jnp.zeros((nh, hd, N), F32)
        if self.defect == "state_not_zeroed":
            # the slot's last request left its state: here, this sequence's own
            s0 = jax.lax.scan(step, s0, (x, dt, Bh, Ch))[0]
        _, y = jax.lax.scan(step, s0, (x, dt, Bh, Ch))
        if self.defect != "no_D":
            y = y + at("D")[None, :, None] * x
        y = y.reshape(S, d_inner)

        def grouped_norm(y):
            g = y.reshape(S, G, d_inner // G)
            g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                                  + hf.get("rms_norm_eps", 1e-5))
            return g.reshape(S, d_inner) * at("gate_norm")[None]

        if self.defect == "gate_after_norm":
            y = grouped_norm(y) * jax.nn.silu(z)
        else:
            y = grouped_norm(y * jax.nn.silu(z))
        return self._mm(y, at("out_proj")) * hf["ssm_out_multiplier"]

    def _attn_qkv(self, st, j, n):
        """n [S, H] -> q [S, nkv, rep, hd], k, v [S, nkv, hd], rotary and the
        key multiplier applied."""
        hf = self.hf
        nq, nkv, hd = attn_dims(hf)
        S = n.shape[0]
        u = n * hf["attention_in_multiplier"]
        q = self._mm(u, st["wq"][j]).reshape(S, nq, hd)
        k = (self._mm(u, st["wk"][j]) * hf["key_multiplier"]
             ).reshape(S, nkv, hd)
        v = self._mm(u, st["wv"][j]).reshape(S, nkv, hd)
        if self.defect != "no_rotary":
            theta = float(hf.get("rope_theta", 10000.0))
            q, k = _rope(q, theta), _rope(k, theta)
        if self._kv_levels:
            # K and V rounded per (position, head): to the int8 pool the
            # configuration states, or to 4 bits, the nearest precision below
            k, v = _rounded(k, self._kv_levels), _rounded(v, self._kv_levels)
        if self._int8_read:
            q = _rounded(q, 127.0)
        return q.reshape(S, nkv, nq // nkv, hd), k, v

    def _attn_rows(self, q, k, v, r0):
        """Query rows r0 .. r0 + len(q) - 1 over ALL keys, causal -> [rows,
        nq hd]."""
        rows, nkv, rep, hd = q.shape
        s = jnp.einsum("sngd,tnd->ngst", self._lo(q), k) / math.sqrt(hd)
        ok = jnp.arange(k.shape[0])[None, :] <= (r0 + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        if self._int8_read:
            # probabilities x V's scale, requantised per row to 0 .. 127
            vs = (jnp.max(jnp.abs(v), axis=-1) / 127.0).T[:, None, None, :]
            vs = jnp.where(vs > 0, vs, 1.0)
            p = _rounded(p * vs, 127.0) / vs
        else:
            p = self._lo(p)
        return jnp.einsum("ngst,tnd->sngd", p, v
                          ).reshape(rows, nkv * rep * hd)

    def _attn_branch(self, st, j, n):
        q, k, v = self._qkv(st, j, n)
        S, qb = n.shape[0], min(self.QUERY_BLOCK, n.shape[0])
        o = jnp.concatenate([self._attend(q[r0:r0 + qb], k, v, r0)
                             for r0 in range(0, S, qb)], axis=0)
        return self._attn_out(st, j, o)

    def _ffn_slice(self, st, j, f, c0, cols: int):
        """Columns c0 .. c0 + cols - 1 of the feed-forward's width, through
        their rows of the down projection, the output multiplier applied."""
        g, d = self.hf["mlp_multipliers"]
        cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(      # noqa: E731
            w[j], c0, cols, axis=axis)
        act = self._mm(f, cut(st["w_in"], 1)) * jax.nn.silu(
            self._mm(f, cut(st["w_gate"], 1)) * g)
        return self._mm(act, cut(st["w_out"], 0)) * d

    def _final(self, params, x, c0, cols: int):
        x = _rms(x, params["final_norm_scale"].astype(F32),
                 self.hf.get("rms_norm_eps", 1e-5))
        head = jax.lax.dynamic_slice_in_dim(params["lm_head"], c0, cols, axis=1)
        return self._mm(x, head) * self.hf["lm_head_multiplier"]

    # ---- whole forward ----------------------------------------------------

    def logits(self, ids, pad_to: int = 1280):
        """ids [S] int -> float32 logits [S, vocab] as a NUMPY array. The ids
        are padded at the END to a multiple of ``pad_to`` (every block is
        causal, so no real position sees a pad): two padded lengths cover the
        cell's 2560 positions, and every new length is a dozen programs to
        compile, the sequential scan among them."""
        params, hf = self.params, self.hf
        n = len(ids)
        total = -(-n // pad_to) * pad_to
        padded = np.zeros((total,), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        par, dense = params["layers"]["par"], params["layers"]["dense"]
        F = hf["intermediate_size"]
        fcols = F // self.FFN_SLICES if F % self.FFN_SLICES == 0 else F
        with _HIGHEST():
            x = self._embed(params, jnp.asarray(padded))
            for j in range(hf["num_hidden_layers"]):
                h = self._norm_in(par, j, x)
                y = jnp.zeros_like(x)
                if self.defect != "no_ssm_branch":
                    y = self._add(y, self._mamba(par, j, h))
                if self.defect != "no_attn_branch":
                    y = self._add(y, self._attn_branch(par, j, h))
                x = self._add(x, y)
                f = self._norm_in(dense, j, x)
                for c0 in range(0, F, fcols):
                    x = self._add(x, self._ffn(dense, j, f, c0, cols=fcols))
            V = hf["vocab_size"]
            cols = next(c for c in (16320, 4096, 512, V) if V % c == 0)
            x = x[:n]
            out = np.empty((n, V), np.float32)
            for c0 in range(0, V, cols):
                out[:, c0:c0 + cols] = np.asarray(
                    self._head(params, x, c0, cols=cols))
            return out


# ---- the cost model: parameters and operations ----------------------------

def block_params(hf: dict, kind: str) -> float:
    """Matmul parameters of one layer's ``kind`` part (``blocks``' names)."""
    H = hf["hidden_size"]
    if kind == "mamba":
        nh, _, _, _, d_inner, conv_dim, _ = mamba_dims(hf)
        return H * (d_inner + conv_dim + nh) + d_inner * H
    if kind == "attn":
        nq, nkv, hd = attn_dims(hf)
        return 2 * H * nq * hd + 2 * H * nkv * hd
    return 3 * H * hf["intermediate_size"]


def small_params(hf: dict) -> float:
    """What a layer stores beside its matrices: the convolution and its
    bias, dt_bias, A_log, D, the gated norm's weight and the two norms."""
    nh, _, _, _, d_inner, conv_dim, K = mamba_dims(hf)
    return (K + 1) * conv_dim + 3 * nh + d_inner + 2 * hf["hidden_size"]


def head_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def param_count(hf: dict) -> float:
    """Every stored parameter: the layers' matrices and vectors, embedding,
    untied head and the final norm."""
    return (sum(block_params(hf, kind) for kind, _ in blocks(hf))
            + hf["num_hidden_layers"] * small_params(hf)
            + 2 * head_params(hf) + hf["hidden_size"])


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token uses + causal attention in every
    layer + the recurrence (``ssm_scan_flops`` per position x 3)."""
    L = hf["num_hidden_layers"]
    used = sum(block_params(hf, kind) for kind, _ in blocks(hf)) \
        + head_params(hf)
    nq, _, hd = attn_dims(hf)
    attn = 2 * 2 * (seq_len / 2) * nq * hd
    return 6.0 * used + 3.0 * L * attn + 3.0 * L * ssm_scan_flops(hf, 1)


def flash_flops(hf: dict, batch: int, seq_len: int) -> dict:
    """One layer's attention kernels for one step (mistral.py's accounting:
    causal half, backward 2.5 x forward)."""
    nq, _, hd = attn_dims(hf)
    one = 2.0 * batch * nq * seq_len * seq_len * hd / 2.0
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


# ---- the recurrence -------------------------------------------------------

def ssm_state_bytes(hf: dict) -> float:
    """One slot's recurrent state in ONE layer: float32 [heads, head dim,
    state]."""
    nh, hd, _, N, _, _, _ = mamba_dims(hf)
    return 4.0 * nh * hd * N


def conv_tail_bytes(hf: dict, itemsize: int = 2) -> float:
    _, _, _, _, _, conv_dim, K = mamba_dims(hf)
    return float(itemsize * (K - 1) * conv_dim)


def ssm_step_bytes(hf: dict, slots: float) -> float:
    """Least bytes the step kernel of ONE layer moves for ``slots`` live
    slots: their state read once and written once, and the convolution tail
    likewise (the tail is XLA's, beside the kernel: it is counted because the
    step cannot do without it)."""
    return 2.0 * slots * (ssm_state_bytes(hf) + conv_tail_bytes(hf))


def ssm_scan_flops(hf: dict, tokens: float, chunk: int = None) -> float:
    """FLOPs of the chunked scan of ONE layer over ``tokens`` positions: per
    position C B^T per group (Q N), the weighted product with x (Q P per
    head), and the two products with the carried state (2 P N per head); 2
    per multiply-add."""
    nh, hd, G, N, _, _, _ = mamba_dims(hf)
    Q = chunk or hf.get("mamba_chunk_size", 128)
    return 2.0 * tokens * (G * Q * N + nh * Q * hd + 2 * nh * hd * N)


def ssm_scan_bytes(hf: dict, tokens: float, itemsize: int = 2) -> float:
    """Least bytes the scan of ONE layer must move: x, B, C and dt in, y
    out, and the state in and out once."""
    nh, hd, G, N, d_inner, _, _ = mamba_dims(hf)
    per_token = itemsize * (2 * d_inner + 2 * G * N) + 4 * nh
    return tokens * per_token + 2.0 * ssm_state_bytes(hf)


# ---- the cost model: bytes of a decode step -------------------------------

def kv_bytes_per_token(hf: dict, kv_bits: int) -> float:
    """K and V of one cached position over EVERY layer (int8: a row's bytes
    and its float32 scale a head)."""
    _, nkv, hd = attn_dims(hf)
    per_head = hd + 4 if kv_bits == 8 else 2 * hd
    return 2.0 * hf["num_hidden_layers"] * nkv * per_head


def weight_bytes(hf: dict) -> float:
    """bf16 matrices a step reads: every layer's and the head."""
    return 2.0 * (sum(block_params(hf, kind) for kind, _ in blocks(hf))
                  + head_params(hf))


def state_bytes_per_slot(hf: dict, itemsize: int = 2) -> float:
    """One slot's recurrent state over all layers (state + tail, the tail in
    the pool's ``itemsize``)."""
    return hf["num_hidden_layers"] * (ssm_state_bytes(hf)
                                      + conv_tail_bytes(hf, itemsize))


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step moves: the weights, the live K/V rows of
    every layer, and the recurrent state of the live slots read and written
    (``mean_occupancy``; 0 live slots: weights alone)."""
    live = float(counters.get("mean_occupancy", 0.0))
    return (weight_bytes(hf)
            + kv_bytes_per_token(hf, counters["kv_cache_bits"])
            * counters["mean_live_tokens"]
            + 2.0 * live * state_bytes_per_slot(hf))


# ---- the block's two mixers and the head in a device trace ----------------

_NAMED = re.compile(r"^%(flash_fwd|paged_decode_int8|paged_decode)[.\d]* = ")


def _leaf_shapes(counters: dict, names) -> list:
    pool = counters.get("pool") or {}
    return [tuple(pool[n]["shape"]) for n in names if n in pool]


def _result_is(event_name: str, shape) -> bool:
    dims = ",".join(str(d) for d in shape)
    return bool(re.match(rf"^%[\w.\-]+ = \(?[a-z0-9]+\[{dims}\]", event_name))


def mixer_op(event_name: str, counters: dict) -> bool:
    """True if this trace event is one of the ops a layer's TWO mixers run on
    their state: the recurrence's kernels (``%ssm_scan``, ``%ssm_step``), the
    prompt's flash forward (``%flash_fwd``), the decode read of the K/V
    planes — the paged kernel by its name, or XLA's list read by the gathered
    blocks' shape ``[blocks or runs listed, whole blocks of positions, K/V
    heads, head dim]`` —, and the
    writes of either pool: an op whose result is a pool leaf (``k``, ``v``,
    their scale planes, ``ssm``, ``conv``). The projections, the convolution,
    rotary, the gated norm and the sum of the branches are XLA fusions that
    touch no pool and are NOT in it."""
    if "custom-call" in event_name and (
            _SSM_KERNEL.match(event_name) or _NAMED.match(event_name)):
        return True
    leaves = _leaf_shapes(counters, ("k", "v", "k_scale", "v_scale", "ssm",
                                     "conv"))
    for planes, nb, a, b, hd in _leaf_shapes(counters, ("k",)):
        # a pool of four int8 K/V heads is STORED [.., heads, block, hd] and
        # written through its [.., block, heads, hd] view (``models/hybrid.py``
        # blocks_head_major); declared token-major, the compiler's whole-leaf
        # relayout has the same two shapes: either is the leaf
        nkv, bs = sorted((a, b))
        leaves += [(planes, nb, b, a, hd)] * 2          # (k and v)
        # the list read: a gather out of one plane, and the contractions
        # over what it gathered, as blocks or as runs of whole blocks
        if any(int(m) % bs == 0 for m in re.findall(
                rf"s8\[\d+,(\d+),{nkv},{hd}\]", event_name)) \
                and not re.search(rf"s8\[{planes},", event_name):
            return True
    return any(_result_is(event_name, shape) for shape in leaves)


def head_op(event_name: str, hf: dict) -> bool:
    """True if this trace event is the head's: an op with a result or an
    operand whose LAST dimension is the vocabulary — the final projection
    (the head's matrix in, the logits out) and the greedy pick's passes over
    the logits. The embedding lookup reads a table whose FIRST dimension is
    the vocabulary and is not in it."""
    return bool(re.search(rf"\[(\d+,)*{hf['vocab_size']}\]", event_name))
