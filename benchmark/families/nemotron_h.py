"""The Nemotron-H family (``model_type`` ``nemotron_h``: NVIDIA Nemotron-3-Nano
30B-A3B): its plain reference, its cost model, its toy widths.

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no cache, no batching, no chunks, no sort, no
capacity. One sequence at a time, one block per call, one expert per call (a
Python loop over ALL experts). It imports nothing from ``deepspeed_tpu`` and
reads the program's stored parameter tree: ``params["layers"]["mamba" |
"moe" | "attn"]``, each stacked on the blocks of its kind.

Follows HF's ``modeling_nemotron_h`` as the issue that added it wrote it down:

- ``hybrid_override_pattern`` names every block's ONE mixer; block ``i`` is
  ``h <- h + mixer_i(RMSNorm_i(h))``, eps ``layer_norm_epsilon``; a final
  RMSNorm, then the untied head.
- ``M`` (Mamba-2): ``in_proj`` -> z (``mamba_num_heads x mamba_head_dim``) |
  xBC (``+ 2 n_groups ssm_state_size``) | dt (heads); ``xBC <- silu(conv1d
  (xBC))``, depthwise, causal, kernel ``conv_kernel``, with bias; x, B, C;
  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``
  — a sequential ``lax.scan`` over positions, float32 state; head ``h`` reads
  group ``h // (heads / groups)``; ``y <- RMSNorm_grouped(y * silu(z))`` (the
  gate BEFORE the norm, each group normalised by itself, with a weight);
  ``out_proj``.
- ``E``: ``s = sigmoid(x W_r)`` over all experts; the CHOICE is the top-k of
  ``s + e_score_correction_bias``; the WEIGHTS are ``s`` at the chosen
  experts, divided by their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``. Expert: ``W_down relu(W_up x)^2``, no gate, no
  bias; a shared expert of the same form at its own width, added unweighted.
- ``*``: grouped-query attention, causal, scale 1/sqrt(head_dim), NO
  positional embedding.

Departures: none in the arithmetic. Storage: the experts' up projection is
kept as ``moe_w_in_t`` ``[blocks, E, F, H]`` (each matrix transposed: the
program reads a width off the 128 grid in place only that way); the reference
multiplies by its transpose.

``Reference(hf, params, defect=...)`` computes the same forward with ONE
seeded defect (``DEFECTS``): what the configuration's ``correct`` limits and
the CPU tests are shown to tell apart. ``precision_below`` is the WHOLE
forward in the precision below the one the configuration states, every kind
of state at once: both operands of every matrix product and the convolved
``xBC`` rounded to ``float8_e5m2`` (bf16 stated), the SSM state to bf16
(float32 stated), K and V to 4 bits (the int8 pool stated).

2. The cost model
-----------------
From the published shapes; matmul work only, 2 FLOPs per multiply-add, the
embedding lookup not counted. ``block_params`` counts a block's matrices:
``M`` 38.7 M, ``E`` 1297.5 M (128 experts x 2 x 2688 x 1856 + shared + router),
``*`` 23.4 M at the published widths; 52 blocks + embedding + head = 31.58 B.

A decode step reads the head, every ``M`` and ``*`` block's matrices, the
routers and shared experts, the matrices of the experts its active slots
TOUCHED (the engine's counter), the live K/V rows of the attention blocks
only, and — read AND written — the recurrent state of the live slots.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.mistral import F32, _HIGHEST, _rms, _rope  # noqa: F401

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}

# --rehearsal and the CPU tests: the published pattern's first nine blocks
# and every mechanism at toy widths (8 experts, the published top-6 — the CPU
# tests take top-2 —; 8 Mamba heads of 16 in 2 groups, state 32, chunks of
# 16; 4 query heads over 2 K/V heads)
TOY = {"vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 9,
       "hybrid_override_pattern": "MEMEM*EME",
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
       "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
       "ssm_state_size": 32, "chunk_size": 16,
       "n_routed_experts": 8, "moe_intermediate_size": 64, "intermediate_size": 64,
       "moe_shared_expert_intermediate_size": 96}

# one seeded defect each: what `correct` and the CPU tests must tell apart
DEFECTS = ("softmax_router", "no_shared_expert", "no_routed_scale",
           "swiglu_experts", "relu_experts", "rotary", "pad_moves_state",
           "pad_in_conv_tail", "bf16_state", "state_not_zeroed", "kv_4bit",
           "precision_below")


def blocks(hf: dict):
    """[(kind, index within its kind)] in block order."""
    seen, out = {}, []
    for letter in hf["hybrid_override_pattern"]:
        kind = KINDS[letter]
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def mamba_dims(hf: dict):
    """(heads, head dim, groups, state, d_inner, conv_dim, kernel)."""
    nh, hd = hf["mamba_num_heads"], hf["mamba_head_dim"]
    G, N = hf["n_groups"], hf["ssm_state_size"]
    return nh, hd, G, N, nh * hd, nh * hd + 2 * G * N, hf.get("conv_kernel", 4)


def _eps(hf):
    return hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5))


class Reference:
    """``Reference(hf, params)`` — ``hf`` the published config dict as run
    (the cut pattern), ``params`` the program's parameter tree. ``defect``:
    one of ``DEFECTS``. The padding defects need to know where the prompt
    ends and what it was padded to: ``prompt_len`` / ``prompt_bucket`` (set
    by the caller per request)."""

    def __init__(self, hf: dict, params, defect: str = None):
        if defect is not None and defect not in DEFECTS:
            raise ValueError(f"defect {defect!r}: one of {DEFECTS}")
        self.hf, self.params, self.defect = hf, params, defect
        # what a matrix product's operands are rounded to (None: float32),
        # whether the SSM state is bf16, and the bits of K and V
        self._operand = jnp.float8_e5m2 if defect == "precision_below" \
            else None
        self._bf16_state = defect in ("bf16_state", "precision_below")
        self._kv_4bit = defect in ("kv_4bit", "precision_below")
        self.prompt_len, self.prompt_bucket = None, 64
        self._mamba = jax.jit(self._mamba_block)
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
            y + jnp.take(w, e, axis=1)[:, None] * self._one_expert(st, j, e, h))

    # ---- pieces (each one jitted program; block / expert index traced) ----

    def _lo(self, a):
        """``a`` in float32, rounded to the precision of a matrix product's
        operands (a plain run: as it is)."""
        a = a.astype(F32)
        return a if self._operand is None else \
            a.astype(self._operand).astype(F32)

    def _mm(self, a, w):
        return self._lo(a) @ self._lo(w)

    def _mamba_block(self, st, j, h, win, dt_on):
        """h [S, H] -> the mixer's output. ``win`` [S, K] int32: the rows
        each position's convolution reads, oldest first, S standing for a
        row of zeros (a plain run: t-K+1 .. t); ``dt_on`` [S] float32: 0
        where a position must not move the state (a plain run: all 1)."""
        hf = self.hf
        nh, hd, G, N, d_inner, conv_dim, K = mamba_dims(hf)
        S = h.shape[0]
        at = lambda n: st[n][j].astype(F32)                        # noqa: E731
        zxd = self._mm(h, at("in_proj"))
        z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:d_inner + conv_dim],
                      zxd[:, d_inner + conv_dim:])
        # causal depthwise convolution: row t sees rows t-K+1 .. t (zeros
        # before the sequence). conv_w[k] multiplies the row K-1-k back.
        w, b = at("conv_w"), at("conv_b")
        src = jnp.concatenate([xbc, jnp.zeros((1, conv_dim), F32)], 0)
        conv = b[None] + sum(src[win[:, k]] * w[k][None] for k in range(K))
        xbc = self._lo(jax.nn.silu(conv))
        x = xbc[:, :d_inner].reshape(S, nh, hd)
        B = xbc[:, d_inner:d_inner + G * N].reshape(S, G, N)
        C = xbc[:, d_inner + G * N:].reshape(S, G, N)
        dt = jax.nn.softplus(dt + at("dt_bias")[None]) * dt_on[:, None]
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
        y = y + at("D")[None, :, None] * x
        y = y.reshape(S, d_inner) * jax.nn.silu(z)
        g = y.reshape(S, G, d_inner // G)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + _eps(hf))
        return self._mm(g.reshape(S, d_inner) * at("gate_norm")[None],
                        at("out_proj"))

    def _attn_block(self, st, j, h, visible):
        """``visible`` [S] bool: keys a later query may see (a plain run:
        all)."""
        hf = self.hf
        nq, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                       hf["head_dim"])
        S = h.shape[0]
        q = self._mm(h, st["wq"][j]).reshape(S, nq, hd)
        k = self._mm(h, st["wk"][j]).reshape(S, nkv, hd)
        v = self._mm(h, st["wv"][j]).reshape(S, nkv, hd)
        if self.defect == "rotary":
            theta = float(hf.get("rope_theta", 10000.0))
            q, k = _rope(q, theta), _rope(k, theta)
        if self._kv_4bit:
            # K and V rounded to 4 bits per (position, head): the nearest
            # precision below the int8 pool the configuration states
            def four_bits(a):
                scale = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 7.0
                return jnp.round(a / jnp.where(scale > 0, scale, 1.0)) * scale
            k, v = four_bits(k), four_bits(v)
        q = q.reshape(S, nkv, nq // nkv, hd)
        s = jnp.einsum("sngd,tnd->ngst", self._lo(q), k) / math.sqrt(hd)
        ok = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]) & (
            visible[None, :] | jnp.eye(S, dtype=bool))
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("ngst,tnd->sngd", self._lo(p), v).reshape(S, nq * hd)
        return self._mm(o, st["wo"][j])

    def _router(self, st, j, h):
        """[S, E] combine weights, zero where an expert was not chosen."""
        hf = self.hf
        logits = self._mm(h, st["wg"][j])
        if self.defect == "softmax_router":
            s = jax.nn.softmax(logits, axis=-1)
        else:
            s = jax.nn.sigmoid(logits)
        idx = jax.lax.top_k(s + st["e_bias"][j].astype(F32)[None],
                            hf["num_experts_per_tok"])[1]
        w = jnp.take_along_axis(s, idx, axis=-1)
        if hf.get("norm_topk_prob", True):
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        if self.defect != "no_routed_scale":
            w = w * hf.get("routed_scaling_factor", 1.0)
        return jnp.einsum("sk,ske->se", w,
                          jax.nn.one_hot(idx, s.shape[-1], dtype=F32))

    def _act(self, u):
        if self.defect == "swiglu_experts":      # a gate where there is none
            return jax.nn.silu(u) * u
        if self.defect == "relu_experts":
            return jax.nn.relu(u)
        return jnp.square(jax.nn.relu(u))

    def _one_expert(self, st, j, e, h):
        up = self._mm(h, st["moe_w_in_t"][j, e].T)
        return self._mm(self._act(up), st["moe_w_out"][j, e])

    def _shared_expert(self, st, j, h):
        return self._mm(self._act(self._mm(h, st["shared_w_in"][j])),
                        st["shared_w_out"][j])

    def _final(self, params, x, c0, cols: int):
        x = _rms(x, params["final_norm_scale"].astype(F32), _eps(self.hf))
        head = jax.lax.dynamic_slice_in_dim(params["lm_head"], c0, cols, axis=1)
        return self._mm(x, head)

    # ---- whole forward ----------------------------------------------------

    def _layout(self, n: int, pad_to: int):
        """How the ids are laid out as computed: (row of each id, rows in
        all, which rows are real, ``win``, ``dt_on``). A plain run computes
        the ids as they are, padded at the END to a multiple of ``pad_to``
        (every block is causal, so no real position sees a pad). The two
        padding defects compute the prompt padded to its bucket as the
        engine's prefill sees it — pad tokens BETWEEN the prompt and the
        generated tokens, invisible to attention — and let them into the
        state or into the convolution's window."""
        K = mamba_dims(self.hf)[-1]
        gap = 0
        if self.defect in ("pad_moves_state", "pad_in_conv_tail"):
            gap = -self.prompt_len % self.prompt_bucket
        real = np.concatenate([np.arange(min(n, self.prompt_len or n)),
                               np.arange(self.prompt_len or n, n) + gap])
        total = -(-(n + gap) // pad_to) * pad_to
        is_real = np.zeros(total, bool)
        is_real[real] = True
        back = np.arange(K)[None, :] - (K - 1)                  # -K+1 .. 0
        win = np.arange(total)[:, None] + back                   # plain
        if gap and self.defect != "pad_in_conv_tail":
            # a real row reads the last K REAL rows up to itself
            order = np.flatnonzero(is_real)
            rank = np.full(total, -1)
            rank[order] = np.arange(order.size)
            r = rank[:, None] + back
            win = np.where(r >= 0, order[np.clip(r, 0, order.size - 1)], total)
            win[~is_real] = total
        win = np.where(win < 0, total, win).astype(np.int32)
        dt_on = np.ones(total, np.float32)
        if gap and self.defect != "pad_moves_state":
            dt_on[~is_real] = 0.0
        return real, total, is_real, win, dt_on

    def logits(self, ids, pad_to: int = 1280):
        """ids [S] int -> float32 logits [S, vocab] as a NUMPY array. Two
        padded lengths cover the cell's 2560 positions: every new length is
        nine programs to compile, the sequential scan among them."""
        params, hf = self.params, self.hf
        n = len(ids)
        real, total, is_real, win, dt_on = self._layout(n, pad_to)
        padded = np.zeros((total,), np.int32)
        padded[real] = np.asarray(ids, np.int32)
        win, dt_on = jnp.asarray(win), jnp.asarray(dt_on)
        with _HIGHEST():
            x = self._embed(params, jnp.asarray(padded))
            for kind, j in blocks(hf):
                st = params["layers"][kind]
                h = self._norm_in(st, j, x)
                if kind == "mamba":
                    y = self._mamba(st, j, h, win, dt_on)
                elif kind == "moe":
                    w = self._route(st, j, h)
                    y = jnp.zeros_like(x)
                    for e in range(w.shape[-1]):
                        y = self._add_expert(st, j, e, h, w, y)
                    if "shared_w_in" in st and self.defect != "no_shared_expert":
                        y = self._add(y, self._shared(st, j, h))
                else:
                    y = self._attn(st, j, h, jnp.asarray(is_real))
                x = self._add(x, y)
            V = hf["vocab_size"]
            cols = next(c for c in (16384, 4096, 512, V) if V % c == 0)
            x = x[jnp.asarray(real)]
            out = np.empty((n, V), np.float32)
            for c0 in range(0, V, cols):
                out[:, c0:c0 + cols] = np.asarray(
                    self._head(params, x, c0, cols=cols))
            return out


# ---- the cost model: parameters and operations ----------------------------

def block_params(hf: dict, kind: str, experts: float = None) -> float:
    """Matmul parameters of one block of ``kind`` (``experts`` routed
    experts counted; default all of them: what the chip holds)."""
    H = hf["hidden_size"]
    if kind == "mamba":
        nh, _, _, _, d_inner, conv_dim, _ = mamba_dims(hf)
        return H * (d_inner + conv_dim + nh) + d_inner * H
    if kind == "attn":
        nq, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                       hf["head_dim"])
        return 2 * H * nq * hd + 2 * H * nkv * hd
    E = hf["n_routed_experts"] if experts is None else experts
    return (E * expert_params(hf) + 2 * H * shared_width(hf)
            + H * hf["n_routed_experts"])


def expert_params(hf: dict) -> int:
    """One routed expert: up and down, no gate."""
    return 2 * hf["hidden_size"] * hf["moe_intermediate_size"]


def shared_width(hf: dict) -> int:
    return (hf.get("moe_shared_expert_intermediate_size", 0)
            if hf.get("n_shared_experts", 1) else 0)


def head_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def param_count(hf: dict) -> float:
    """Every stored parameter a matmul or the lookup uses: blocks +
    embedding + untied head (norm scales, biases and the per-head scalars
    are ~0.001 % and left out)."""
    return (sum(block_params(hf, kind) for kind, _ in blocks(hf))
            + 2 * head_params(hf))


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token USES + causal attention in the
    ``*`` blocks + the recurrence (``ssm_scan_flops`` per position x 3)."""
    k = hf["num_experts_per_tok"]
    used = sum(block_params(hf, kind, k) for kind, _ in blocks(hf)) \
        + head_params(hf)
    n_attn = sum(1 for kind, _ in blocks(hf) if kind == "attn")
    n_m = sum(1 for kind, _ in blocks(hf) if kind == "mamba")
    attn = 2 * 2 * (seq_len / 2) * hf["num_attention_heads"] * hf["head_dim"]
    return (6.0 * used + 3.0 * n_attn * attn
            + 3.0 * n_m * ssm_scan_flops(hf, 1))


def flash_flops(hf: dict, batch: int, seq_len: int) -> dict:
    """One ``*`` block's attention kernels for one step (mistral.py's
    accounting: causal half, backward 2.5 x forward)."""
    one = 2.0 * batch * hf["num_attention_heads"] * seq_len * seq_len \
        * hf["head_dim"] / 2.0
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


# ---- the recurrence -------------------------------------------------------

def ssm_state_bytes(hf: dict) -> float:
    """One slot's recurrent state in ONE ``M`` block: float32 [heads, head
    dim, state]."""
    nh, hd, _, N, _, _, _ = mamba_dims(hf)
    return 4.0 * nh * hd * N


def conv_tail_bytes(hf: dict, itemsize: int = 2) -> float:
    _, _, _, _, _, conv_dim, K = mamba_dims(hf)
    return float(itemsize * (K - 1) * conv_dim)


def ssm_step_bytes(hf: dict, slots: float) -> float:
    """Least bytes the step kernel of ONE ``M`` block moves for ``slots``
    live slots: their state read once and written once, and the convolution
    tail likewise (the tail is XLA's, beside the kernel: it is counted
    because the step cannot do without it)."""
    return 2.0 * slots * (ssm_state_bytes(hf) + conv_tail_bytes(hf))


def ssm_scan_flops(hf: dict, tokens: float, chunk: int = None) -> float:
    """FLOPs of the chunked scan of ONE ``M`` block over ``tokens``
    positions: per position C B^T per group (Q N), the weighted product with
    x (Q P per head), and the two products with the carried state (2 P N per
    head); 2 per multiply-add."""
    nh, hd, G, N, _, _, _ = mamba_dims(hf)
    Q = chunk or hf.get("chunk_size", 128)
    return 2.0 * tokens * (G * Q * N + nh * Q * hd + 2 * nh * hd * N)


def ssm_scan_bytes(hf: dict, tokens: float, itemsize: int = 2) -> float:
    """Least bytes the scan of ONE ``M`` block must move: x, B, C and dt in,
    y out, and the state in and out once."""
    nh, hd, G, N, d_inner, _, _ = mamba_dims(hf)
    per_token = itemsize * (2 * d_inner + 2 * G * N) + 4 * nh
    return tokens * per_token + 2.0 * ssm_state_bytes(hf)


_SSM_KERNEL = re.compile(r"^%ssm_(scan|step)[.\d]* = ")


def ssm_kernel(event_name: str):
    """``"scan"`` / ``"step"`` if this trace event is one of the
    recurrence's Pallas kernels (``%ssm_scan.N``, ``%ssm_step.N``: a custom
    call to Mosaic), else None."""
    m = _SSM_KERNEL.match(event_name)
    return m.group(1) if m and "custom-call" in event_name else None


# ---- the expert matmuls in a device trace ---------------------------------
#
# As families/olmoe.py tells them: a call of many tokens runs each
# projection as ONE grouped matmul over the sorted tokens x top-k rows
# (`%moe_gmm.N`, a custom call with result [rows, N]; XLA's own would be
# `%ragged-dot-none.N`); a call of few tokens runs every expert over all T
# rows in a fusion that reads a layer of the stacked weights
# [blocks, E, F, H] in place and has an [E, T, H or F] operand or result.

_EXPERT_KERNEL = re.compile(
    r"^%(moe_gmm|gmm|ragged-dot-none)[.\d]* = [a-z0-9]+\[(\d+),\d+\]")


def is_grouped_matmul(event_name: str) -> bool:
    return bool(_EXPERT_KERNEL.match(event_name)) and "custom-call" in event_name


def expert_matmul(event_name: str, hf: dict):
    """``(tokens, matrices)`` if this trace event is (part of) an expert
    layer's matmuls, else None (families/olmoe.py's contract; an expert here
    has two matrices)."""
    E, H, F = (hf["n_routed_experts"], hf["hidden_size"],
               hf["moe_intermediate_size"])
    if is_grouped_matmul(event_name):
        rows = int(_EXPERT_KERNEL.match(event_name).group(2))
        return max(1, rows // hf["num_experts_per_tok"]), 1
    if " fusion(" not in event_name:
        return None
    stacks = re.findall(rf"\[\d+,{E},{F},{H}\]", event_name)
    rows = [int(t) for t, n in re.findall(rf"\[{E},(\d+),({H}|{F})\]", event_name)
            if {int(t), int(n)} != {H, F}]
    if not stacks or not rows:
        return None
    return rows[0], len(stacks)


# ---- the cost model: bytes of a decode step -------------------------------

def touched_experts(hf: dict, counters: dict) -> float:
    stats = counters.get("stats") or {}
    return float(stats.get("moe_experts_touched_per_step",
                           hf["n_routed_experts"]))


def kv_bytes_per_token(hf: dict, kv_bits: int) -> float:
    """K and V of one cached position over the ATTENTION blocks only."""
    n_attn = sum(1 for kind, _ in blocks(hf) if kind == "attn")
    per_head = hf["head_dim"] + 4 if kv_bits == 8 else 2 * hf["head_dim"]
    return 2.0 * n_attn * hf["num_key_value_heads"] * per_head


def weight_bytes(hf: dict, touched: float = None) -> float:
    """bf16 matrices a step reads: every block with ``touched`` routed
    experts per ``E`` block, and the head."""
    return 2.0 * (sum(block_params(hf, kind, touched) for kind, _ in blocks(hf))
                  + head_params(hf))


def state_bytes_per_slot(hf: dict) -> float:
    """One slot's recurrent state over all ``M`` blocks (state + tail)."""
    n_m = sum(1 for kind, _ in blocks(hf) if kind == "mamba")
    return n_m * (ssm_state_bytes(hf) + conv_tail_bytes(hf))


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step moves: the weights of what it touched,
    the live K/V of the attention blocks, and the recurrent state of the
    live slots read and written (``mean_occupancy``; 0 live slots: weights
    alone)."""
    live = float(counters.get("mean_occupancy", 0.0))
    return (weight_bytes(hf, touched_experts(hf, counters))
            + kv_bytes_per_token(hf, counters["kv_cache_bits"])
            * counters["mean_live_tokens"]
            + 2.0 * live * state_bytes_per_slot(hf))
