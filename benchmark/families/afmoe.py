"""The afmoe family (``model_type`` ``afmoe``: Arcee Trinity-Large-Preview,
400B-A13B): its plain reference, its cost model, its toy widths.

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no cache, no ring, no batching, no sort, no
capacity. One sequence at a time, one block per call, one expert per call (a
Python loop over the experts HELD). It imports nothing from ``deepspeed_tpu``
and reads the program's stored parameter tree: ``params["layers"]["wattn" |
"attn" | "dense" | "moe"]``, each stacked on the blocks of its kind, a
block's slice cast to float32 inside its own jitted call (so the reference
fits beside the engine).

The equations, as ISSUE 44 wrote them down from the published config and HF's
``modeling_afmoe``. ``x0 = E[ids] * sqrt(hidden_size)`` (``mup_enabled``).
``N(x) = x / rms(x) . s``, eps ``rms_norm_eps``. Layer ``l`` has FOUR norms,
a sandwich on both sublayers::

    a = Attn_l(N1(x));  x = x + N2(a)     # input / post_attention layernorm
    f = FFN_l(N3(x));   x = x + N4(f)     # pre_mlp / post_mlp layernorm

- ``Attn_l``: q (48 heads x 128), k, v (8 heads x 128) and a gate (48 x 128)
  projected from the same input; RMSNorm over each head's dims of q and of
  k; rotary (theta ``rope_theta``, the whole head, half-split pairing) on q
  and k ONLY where ``layer_types[l] == "sliding_attention"`` — a
  ``full_attention`` layer applies NO positional embedding; causal softmax
  attention at scale head_dim^-1/2, on a sliding layer restricted to ``i - j
  < sliding_window``; ``out = Wo (attn . sigmoid(gate))``. The scores are
  taken a block of ``Q_BLOCK`` queries at a time against all the keys (a
  [48, S, S] float32 score at S = 11 264 is 24 GB), the band as a MASK.
- ``FFN_l``, ``l < num_dense_layers``: SwiGLU of ``intermediate_size``.
  Otherwise ``s = sigmoid(Wr h)`` in float32 over ALL ``num_experts_router``
  experts; the ``num_experts_per_tok`` experts with the largest ``s + b``
  (``b`` the stored ``expert_bias``, used for the choice only); ``w =
  s[chosen] / (their sum + 1e-20)`` (``route_norm``) ``* route_scale``; ``f =
  SwiGLU_shared(h) + sum_k w_k SwiGLU_{e_k}(h)``, every expert and the shared
  one of ``moe_intermediate_size``. THE CHIP'S SHARE: the stacks hold experts
  ``expert_first .. + num_experts - 1``; the layer returns the shared expert
  + the sum over the chosen experts THAT ARE HELD, with the weights
  normalised over all the chosen — what this chip contributes before the
  deployment's combine — and that partial result goes on to the next block.
  Nothing stands in for the absent chips.
- A final RMSNorm, then the untied head (the chip's slice of the vocabulary).

Departures: none in the arithmetic. Storage: the program keeps a head's
columns of ``q_proj`` and ``gate_proj`` side by side in ``wq`` ([q | gate]
per head); the experts' up projection is ``moe_w_in_t`` [blocks, E, F, H] and
the reference multiplies by its transpose. No multi-token-prediction module
has a key in ``config.json`` and none is built.

``Reference(hf, params, defect=...)`` computes the same forward with ONE
seeded defect (``DEFECTS``): what the configuration's ``correct`` limits and
the CPU tests are shown to tell apart. ``precision_below`` is the WHOLE
forward in the precision below the one the configuration states: both
operands of every matrix product rounded to ``float8_e5m2`` (bf16 stated) and
K and V to 4 bits (the int8 cache stated); ``fp8_operands`` and ``kv_4bit``
are its two halves alone. A ring of exactly ``sliding_window`` rows can show
ONE stale row, position ``i - window`` in the row the step is about to
write: that is ``band_off_by_one``.

2. The cost model
-----------------
From the published shapes; matmul work only, 2 FLOPs per multiply-add, the
embedding lookup not counted. One expert 3 x 3072 x 3072 = 28.31 M;
attention q + gate 3072 x 12288, k and v 2 x 3072 x 1024, o 6144 x 3072 =
62.91 M; a dense layer 62.91 + 3 x 3072 x 12288 = 176.2 M; an expert layer
outside its routed experts 62.91 + shared 28.31 + router 0.79 = 92.0 M. Whole:
6 x 176.2 M + 54 x (92.0 M + 256 x 28.31 M) + 2 x 200 192 x 3072 = 398.6 B.
The cut (one dense + four expert layers, 32 of 256 experts, 25 024 of 200 192
rows) 176.2 M + 4 x 998.0 M + 153.8 M = 4.322 B.

A decode step reads the head slice, every attention and dense block's
matrices, the routers and shared experts, the matrices of the HELD experts
its active slots TOUCHED (the engine's counter), the live rows of the FULL
planes and ``min(context, window)`` rows of every window plane of every live
slot. The banded prefill is counted by its VISIBLE (query, key) pairs, ``S W
- W (W - 1) / 2`` for ``S > W``: the same work whatever computes it.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.mistral import F32, _HIGHEST, _rms  # noqa: F401
from benchmark.families.qwen3_next import (  # noqa: F401
    _rope_first, expert_matmul, expert_params, held_share, is_grouped_matmul,
    moe_ffn_bytes, moe_ffn_flops, router_width, touched_experts)

Q_BLOCK = 256

# --rehearsal and the CPU tests: the cut's own pattern (a leading dense layer,
# then ONE whole period of 3 sliding + 1 full in the four expert layers), every
# mechanism at toy widths: 8 of 16 experts held, top-4, six query heads a K/V
# head (the published 48 : 8). The window stays the published 4096
# (benchmark/tests/test_benchmark_json.py: a toy replaces no `sliding_window`),
# past every rehearsal prompt: the CPU tests take 16 against 60+ positions and
# tests/unit/test_program_text.py 64 against a 128-token bucket
TOY = {"vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 5,
       "num_dense_layers": 1,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                                   "sliding_attention"],
       "num_attention_heads": 6, "num_key_value_heads": 1, "head_dim": 32,
       "intermediate_size": 256, "moe_intermediate_size": 64,
       "num_experts": 8, "num_experts_router": 16, "expert_first": 0}

# one seeded defect each: what `correct` and the CPU tests must tell apart
DEFECTS = ("precision_below", "fp8_operands", "kv_4bit", "band_off_by_one",
           "rotary_on_full", "no_rotary_on_sliding", "no_out_gate",
           "no_route_scale", "renorm_over_held", "no_mup", "no_post_norm")


def layer_types(hf: dict):
    every = hf.get("global_attn_every_n_layers", 4)
    return hf.get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else "sliding_attention"
        for i in range(hf["num_hidden_layers"])]


def blocks(hf: dict):
    """[(kind, index within its kind)] in block order: a layer is its
    attention block (``wattn`` sliding | ``attn`` full) and then its
    feed-forward block (``dense`` | ``moe``)."""
    seen, out = {}, []
    for i, kind in enumerate(layer_types(hf)[:hf["num_hidden_layers"]]):
        for k in ("wattn" if kind == "sliding_attention" else "attn",
                  "dense" if i < hf.get("num_dense_layers", 0) else "moe"):
            out.append((k, seen.get(k, 0)))
            seen[k] = seen.get(k, 0) + 1
    return out


def count(hf: dict, kind: str) -> int:
    return sum(1 for k, _ in blocks(hf) if k == kind)


def _eps(hf):
    return hf.get("rms_norm_eps", 1e-5)


class Reference:
    """``Reference(hf, params)`` — ``hf`` the published config dict as run
    (the cut depth, the experts held and the router's width), ``params`` the
    program's parameter tree. ``defect``: one of ``DEFECTS``."""

    def __init__(self, hf: dict, params, defect: str = None):
        if defect is not None and defect not in DEFECTS:
            raise ValueError(f"defect {defect!r}: one of {DEFECTS}")
        self.hf, self.params, self.defect = hf, params, defect
        # what a matrix product's operands are rounded to (None: float32)
        # and whether K and V keep 4 bits
        self._operand = jnp.float8_e5m2 \
            if defect in ("precision_below", "fp8_operands") else None
        self._kv_4bit = defect in ("precision_below", "kv_4bit")
        self._attn = jax.jit(self._attn_block, static_argnames=("local",))
        self._dense = jax.jit(self._dense_block)
        self._route = jax.jit(self._router)
        self._shared = jax.jit(self._shared_expert)
        self._head = jax.jit(self._final, static_argnames=("cols",))
        scale = 1.0 if defect == "no_mup" or not hf.get("mup_enabled", True) \
            else math.sqrt(hf["hidden_size"])
        self._embed = jax.jit(
            lambda p, ids: p["tok_embed"][ids].astype(F32) * scale)
        self._norm_in = jax.jit(
            lambda st, j, x: _rms(x, st["ln_scale"][j].astype(F32), _eps(hf)))
        self._join = jax.jit(self._residual)
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

    def _residual(self, st, j, x, y):
        """The block's output through its norm AFTER the sublayer."""
        if self.defect != "no_post_norm":
            y = _rms(y, st["post_ln_scale"][j].astype(F32), _eps(self.hf))
        return x + y

    def _attn_block(self, st, j, h, local: bool):
        """h [S, H] -> the attention block's output; ``local``: a sliding
        layer (rotary, banded), else a full one (no positional embedding)."""
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
        if (local and self.defect != "no_rotary_on_sliding") \
                or self.defect == "rotary_on_full":
            theta = float(hf.get("rope_theta", 10000.0))
            q, k = _rope_first(q, theta, hd), _rope_first(k, theta, hd)
        if self._kv_4bit:
            # K and V rounded to 4 bits per (position, head): the nearest
            # precision below the int8 cache the configuration states
            def four_bits(a):
                scale = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 7.0
                return jnp.round(a / jnp.where(scale > 0, scale, 1.0)) * scale
            k, v = four_bits(k), four_bits(v)
        W = hf["sliding_window"] + (self.defect == "band_off_by_one") \
            if local else S
        qb = min(Q_BLOCK, S)
        q = self._lo(q).reshape(S // qb, qb, nkv, nq // nkv, hd)
        keys = jnp.arange(S)[None, :]

        def rows(xs):           # one block of queries against all the keys
            qs, i0 = xs
            s = jnp.einsum("sngd,tnd->ngst", qs, k) / math.sqrt(hd)
            at = i0 + jnp.arange(qb)[:, None]
            ok = (keys <= at) & (at - keys < W)
            p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), -1)
            return jnp.einsum("ngst,tnd->sngd", self._lo(p), v)

        o = jax.lax.map(rows, (q, jnp.arange(S // qb) * qb))
        o = o.reshape(S, nq * hd)
        if self.defect != "no_out_gate":
            o = o * jax.nn.sigmoid(gate)
        return self._mm(o, st["wo"][j])

    def _dense_block(self, st, j, h):
        up, gate = self._mm(h, st["w_in"][j]), self._mm(h, st["w_gate"][j])
        return self._mm(jax.nn.silu(gate) * up, st["w_out"][j])

    def _router(self, st, j, h):
        """[S, held] combine weights of the experts HELD, zero where an
        expert was not chosen: the sigmoid and the top-k (of score + bias)
        are over ALL the router's experts, the weights the scores divided by
        the sum of all the k chosen, times ``route_scale``."""
        hf = self.hf
        E, first = hf["num_experts"], hf.get("expert_first", 0)
        s = jax.nn.sigmoid(self._mm(h, st["wg"][j]))
        idx = jax.lax.top_k(s + st["e_bias"][j].astype(F32)[None],
                            hf["num_experts_per_tok"])[1]
        w = jnp.take_along_axis(s, idx, axis=-1)
        mine = (idx >= first) & (idx < first + E)
        if self.defect == "renorm_over_held":
            w = jnp.where(mine, w, 0.0)
        if hf.get("route_norm", True):
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        if self.defect != "no_route_scale":
            w = w * float(hf.get("route_scale", 1.0))
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
        return self._mm(jax.nn.silu(gate) * up, st["shared_w_out"][j])

    def _final(self, params, x, c0, cols: int):
        x = _rms(x, params["final_norm_scale"].astype(F32), _eps(self.hf))
        head = jax.lax.dynamic_slice_in_dim(params["lm_head"], c0, cols, axis=1)
        return self._mm(x, head)

    # ---- whole forward ----------------------------------------------------

    def logits(self, ids, pad_to: int = 2816):
        """ids [S] int -> float32 logits [S, vocab] as a NUMPY array. The ids
        are padded at the END to a multiple of ``pad_to`` (every block is
        causal, so no real position sees a pad; a multiple of ``Q_BLOCK``):
        four padded lengths cover the cell's 11 264 positions, and every new
        length is a dozen programs to compile."""
        params, hf = self.params, self.hf
        n = len(ids)
        padded = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        with _HIGHEST():
            x = self._embed(params, jnp.asarray(padded))
            for kind, j in blocks(hf):
                st = params["layers"][kind]
                h = self._norm_in(st, j, x)
                if kind in ("attn", "wattn"):
                    y = self._attn(st, j, h, local=kind == "wattn")
                elif kind == "dense":
                    y = self._dense(st, j, h)
                else:
                    w = self._route(st, j, h)
                    y = self._shared(st, j, h)
                    for e in range(w.shape[-1]):
                        y = self._add_expert(st, j, e, h, w[:, e], y)
                x = self._join(st, j, x, y)
            V = hf["vocab_size"]
            cols = next(c for c in (16384, 4096, 512, V) if V % c == 0)
            x = x[:n]
            out = np.empty((n, V), np.float32)
            for c0 in range(0, V, cols):
                out[:, c0:c0 + cols] = np.asarray(
                    self._head(params, x, c0, cols=cols))
            return out


# ---- the cost model: parameters and operations ----------------------------

def attn_params(hf: dict) -> int:
    """q + gate, k, v and o of one attention block (either kind)."""
    H, nq, nkv, hd = (hf["hidden_size"], hf["num_attention_heads"],
                      hf["num_key_value_heads"], hf["head_dim"])
    return H * nq * hd * 2 + 2 * H * nkv * hd + nq * hd * H


def block_params(hf: dict, kind: str, experts: float = None) -> float:
    """Matmul parameters of one block of ``kind`` (``experts`` routed experts
    counted; default the experts HELD: what the chip holds). Norm scales and
    the selection bias (~0.001 %) are left out."""
    H = hf["hidden_size"]
    if kind in ("attn", "wattn"):
        return attn_params(hf)
    if kind == "dense":
        return 3 * H * hf["intermediate_size"]
    E = hf["num_experts"] if experts is None else experts
    return (E + hf.get("num_shared_experts", 1)) * expert_params(hf) \
        + H * router_width(hf)


def head_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def param_count(hf: dict) -> float:
    """Every stored parameter a matmul or the lookup uses: blocks + embedding
    + untied head."""
    return (sum(block_params(hf, kind) for kind, _ in blocks(hf))
            + 2 * head_params(hf))


def band_pairs(seq_len: int, window: int) -> float:
    """Visible (query, key) pairs of causal attention within a band: query i
    sees keys max(0, i - window + 1) .. i. ``S (S + 1) / 2`` up to the
    window, ``S W - W (W - 1) / 2`` past it."""
    S, W = seq_len, min(window, seq_len)
    return S * W - W * (W - 1) / 2.0


def flash_band_flops(hf: dict, seq_len: int) -> float:
    """FLOPs ONE sliding block's attention NEEDS over a prompt of
    ``seq_len`` positions: Q K^T and P V over the visible pairs only, 2 per
    multiply-add, every query head."""
    return 2.0 * 2.0 * band_pairs(seq_len, hf["sliding_window"]) \
        * hf["num_attention_heads"] * hf["head_dim"]


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token USES on this chip + attention
    over the visible pairs of each kind of block (the family protocol's; no
    cell trains this model)."""
    used = sum(block_params(hf, kind, hf["num_experts_per_tok"] * held_share(hf))
               for kind, _ in blocks(hf)) + head_params(hf)
    per_pair = 2 * 2 * hf["num_attention_heads"] * hf["head_dim"]
    pairs = (count(hf, "attn") * band_pairs(seq_len, seq_len)
             + count(hf, "wattn") * band_pairs(seq_len, hf["sliding_window"]))
    return 6.0 * used + 3.0 * per_pair * pairs / seq_len


def flash_flops(hf: dict, batch: int, seq_len: int) -> dict:
    """One FULL attention block's kernels for one step (mistral.py's
    accounting: causal half, backward 2.5 x forward)."""
    one = 2.0 * batch * hf["num_attention_heads"] * seq_len * seq_len \
        * hf["head_dim"] / 2.0
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


_BAND_KERNEL = re.compile(
    r"^%flash_fwd_band[.\d]* = \(?[a-z0-9]+\[\d+,\d+,\d+,(\d+),\d+\]")


def flash_band_kernel(event_name: str):
    """The positions S of the prompt bucket if this trace event is the banded
    flash forward (``%flash_fwd_band.N``: a custom call to Mosaic whose
    result is [batch, kv heads, group, S, head dim]), else None."""
    m = _BAND_KERNEL.match(event_name)
    return int(m.group(1)) if m and "custom-call" in event_name else None


# ---- the cost model: bytes of a decode step -------------------------------

def row_bytes(hf: dict, kv_bits: int) -> float:
    """K and V of one cached position in ONE plane: int8 rows + a float32
    scale a head, or bf16 rows. 2 x 8 x (128 + 4) = 2 112 B."""
    per_head = hf["head_dim"] + 4 if kv_bits == 8 else 2 * hf["head_dim"]
    return 2.0 * hf["num_key_value_heads"] * per_head


def kv_bytes_per_token(hf: dict, kv_bits: int) -> float:
    """K and V of one cached position over the FULL planes only: what grows
    with the context."""
    return count(hf, "attn") * row_bytes(hf, kv_bits)


def ring_bytes_per_slot(hf: dict, kv_bits: int) -> float:
    """One slot's window rings, whatever its context: every sliding block's
    ``sliding_window`` rows."""
    return count(hf, "wattn") * hf["sliding_window"] * row_bytes(hf, kv_bits)


def weight_bytes(hf: dict, touched: float = None) -> float:
    """bf16 matrices a step reads: every block with ``touched`` routed
    experts per expert block, and the head slice."""
    return 2.0 * (sum(block_params(hf, kind, touched) for kind, _ in blocks(hf))
                  + head_params(hf))


def window_rows_per_slot(hf: dict, counters: dict) -> float:
    """Rows of ONE window plane inside a live slot's band: min(context,
    window) at the mean live context."""
    live = float(counters.get("mean_occupancy", 0.0))
    if not live:
        return 0.0
    return min(counters["mean_live_tokens"] / live, hf["sliding_window"])


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """Least bytes one decode step moves, by layer kind: the weights of what
    it touched (other weights + the TOUCHED held experts + the head slice),
    the live rows of the full planes, and of every window plane the rows
    inside each live slot's band — min(context, window), not the context."""
    kv_bits = counters["kv_cache_bits"]
    live = float(counters.get("mean_occupancy", 0.0))
    return (weight_bytes(hf, touched_experts(hf, counters))
            + kv_bytes_per_token(hf, kv_bits) * counters["mean_live_tokens"]
            + live * count(hf, "wattn") * window_rows_per_slot(hf, counters)
            * row_bytes(hf, kv_bits))


# ---- the window blocks in a device trace ----------------------------------

def window_op(event_name: str, hf: dict) -> bool:
    """True if this trace event is the banded flash forward or an op that
    reads or writes a window ring in place: an instruction with an operand
    or result of a ring leaf's shape — one block's ``[slots, window, kv
    heads, head dim]`` or its scale plane ``[slots, kv heads x window]``. The
    scores' softmax between the two contractions touches no ring and is not
    in it."""
    if flash_band_kernel(event_name) is not None:
        return True
    W, nkv, hd = (hf["sliding_window"], hf["num_key_value_heads"],
                  hf["head_dim"])
    return bool(re.search(rf"\[\d+,{W},{nkv},{hd}\]|\[\d+,{nkv * W}\]",
                          event_name))
