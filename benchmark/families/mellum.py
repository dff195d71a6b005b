"""The mellum family (``model_type`` ``mellum``: JetBrains Mellum2-12B-A2.5B):
its plain reference, its cost model, its toy widths.

1. The plain reference
----------------------
Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernel, no sort, no capacity, no remat, no chunked loss.
One sequence at a time, one block per call, one expert per call (a Python loop
over the experts HELD). It imports nothing from ``deepspeed_tpu`` and reads
the program's stored parameter tree: ``params["layers"]["wattn" | "attn" |
"moe"]``, each stacked on the blocks of its kind, a block's slice cast to
float32 inside its own jitted call (so the reference fits beside the engine's
optimizer state).

The equations, as ISSUE 48 wrote them down from the published config.
``N(x) = x / rms(x) . s``, eps ``rms_norm_eps``; no bias anywhere. ``x =
E[ids]``; layer ``l``: ``x = x + Attn_l(N1(x))``; ``x = x + MoE_l(N2(x))``;
then ``Nf(x)`` and the untied head (the chip's slice of the vocabulary).

- ``Attn_l``: q (32 heads x 128), k, v (4 x 128); RMSNorm over the dims of
  each head of q and of k (one scale per dim, shared by the heads) BEFORE
  rotary; rotary over the whole head, half-split pairing, with the table of
  the layer's TYPE; causal softmax at scale head_dim^-1/2, on a
  ``sliding_attention`` layer key j visible to query i iff ``0 <= i - j <
  sliding_window``; ``Wo``. The scores are taken a block of ``Q_BLOCK``
  queries at a time against all the keys (a [32, S, S] float32 score at S =
  8192 is 8.6 GB), the band as a MASK.
- The tables (``rope_parameters``; d = head_dim, b = ``rope_theta``, pair i of
  d / 2): ``sliding_attention`` plain, ``f_i = b^(-2i/d)``. ``full_attention``
  YaRN as ``transformers`` computes it: ``c(r) = d ln(P / (2 pi r)) / (2 ln
  b)`` with P = ``original_max_position_embeddings``; ``lo = max(floor(c(
  beta_fast)), 0)``, ``hi = min(ceil(c(beta_slow)), d - 1)``; ``ramp_i =
  clip((i - lo) / (hi - lo), 0, 1)``; ``f_i = b^(-2i/d) ((1 - ramp_i) +
  ramp_i / factor)``; cos and sin of ``p f_i`` BOTH times
  ``attention_factor``. Published: lo 18, hi 35 of 64 pairs, factor 16.
- ``MoE_l``: ``p = softmax(Wr h)`` in float32 over ALL ``num_experts_router``
  experts; the ``num_experts_per_tok`` largest; ``w = p[chosen] / sum(p[
  chosen])`` (``norm_topk_prob``); ``sum_k w_k SwiGLU_{e_k}(h)``, every expert
  of ``moe_intermediate_size``; no shared expert. THE CHIP'S SHARE: the stacks
  hold experts ``expert_first .. + num_experts - 1``; the layer returns the
  sum over the chosen experts THAT ARE HELD, with the weights normalised over
  all the chosen — what this chip contributes before the deployment's
  combine — and that partial result goes on to the next block. Nothing stands
  in for the absent chips.
- Loss: mean next-token cross-entropy over the slice's logits. No auxiliary
  loss (``config.json`` keys no coefficient).

Departures: none in the arithmetic. Storage: the experts' up projection is
``moe_w_in_t`` [blocks, E, F, H] and the reference multiplies by its
transpose. No multi-token-prediction module has a key in ``config.json`` and
none is built; ``intermediate_size`` and ``max_window_layers`` are unread
(every ``mlp_layer_types`` entry is ``sparse``; ``layer_types`` is given).

``Reference(hf, params, defect=...)`` computes the same forward with ONE
seeded defect (``DEFECTS``): what the configuration's ``correct`` limit and
the CPU tests are shown to tell apart. ``precision_below`` is the WHOLE
forward in the precision below the one the configuration states: both
operands of every matrix product rounded to ``float8_e5m2`` (bf16 stated).

``loss_fn(params, batch)`` is the same loss as a pure function of the
parameter tree (``jax.grad`` of it is the gradient the CPU tests hold the
program's to, leaf by leaf); ``loss(batch)`` sums it on the host in float64,
a sequence at a time.

2. The cost model
-----------------
From the published shapes; matmul work only, 2 FLOPs per multiply-add, the
embedding lookup not counted. Attention 2304 x 4096 + 2 x 2304 x 512 + 4096 x
2304 = 21.23 M; router 2304 x 64 = 0.147 M; one expert 3 x 2304 x 896 = 6.193
M; a layer whole 21.38 M + 64 x 6.193 M = 417.7 M; the model 28 x 417.7 M + 2
x 98 304 x 2304 = 12.15 B (published 12B), 2.44 B active. The cut (4 layers,
16 of 64 experts, 24 576 of 98 304 rows): 4 x 120.5 M + 113.2 M = 595.1 M.

A train step's attention is counted by its VISIBLE (query, key) pairs — ``S
(S + 1) / 2`` on a full layer, ``S W - W (W - 1) / 2`` on a sliding one past
the window — the same work whatever computes it; the expert matmuls by the
rows the HELD experts are EXPECTED to get, ``tokens x num_experts_per_tok x
held / router width`` (``harness/train_job.py`` hands a reader no program
counter; the engine's own ``moe_held_rows`` metric is the count).
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.afmoe import band_pairs  # noqa: F401
from benchmark.families.mistral import F32, _HIGHEST, _rms
from benchmark.families.qwen3_next import (  # noqa: F401
    expert_params, held_share, router_width)

Q_BLOCK = 256

# --rehearsal and the CPU tests: ONE whole period (3 sliding + 1 full), every
# mechanism at toy widths: 8 of 32 experts held (the cell's quarter) behind
# the published top-8, eight query heads a K/V head (the published 32 : 4).
# The window stays the published 1024 (benchmark/tests/test_benchmark_json.py:
# a toy replaces no `sliding_window` and no `num_experts_per_tok`), the
# rehearsal's sequence length: the CPU tests set 16 against 48+ positions
TOY = {"vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 4,
       "num_attention_heads": 8, "num_key_value_heads": 1, "head_dim": 32,
       "intermediate_size": 256, "moe_intermediate_size": 64,
       "num_experts": 8, "num_experts_router": 32, "expert_first": 0}

# one seeded defect each: what `correct` and the CPU tests must tell apart
DEFECTS = ("precision_below", "no_qk_norm", "yarn_on_sliding",
           "plain_on_full", "no_attention_factor", "no_renorm",
           "renorm_over_held", "band_off_by_one")


def layer_types(hf: dict):
    return list(hf["layer_types"])[:hf["num_hidden_layers"]]


def blocks(hf: dict):
    """[(kind, index within its kind)] in block order: a layer is its
    attention block (``wattn`` sliding | ``attn`` full) and then its expert
    block (``moe``)."""
    seen, out = {}, []
    for kind in layer_types(hf):
        for k in ("wattn" if kind == "sliding_attention" else "attn", "moe"):
            out.append((k, seen.get(k, 0)))
            seen[k] = seen.get(k, 0) + 1
    return out


def count(hf: dict, kind: str) -> int:
    return sum(1 for k, _ in blocks(hf) if k == kind)


def _eps(hf):
    return hf.get("rms_norm_eps", 1e-6)


def yarn_band(group: dict, dim: int):
    """(lo, hi): the pairs between which YaRN's ramp runs."""
    def c(r):
        return dim * math.log(group["original_max_position_embeddings"]
                              / (2 * math.pi * r)) \
            / (2 * math.log(group["rope_theta"]))
    return (max(math.floor(c(group.get("beta_fast") or 32)), 0),
            min(math.ceil(c(group.get("beta_slow") or 1)), dim - 1))


def rope_table(group: dict, dim: int):
    """(float64 frequencies [dim / 2], attention factor) of one
    ``rope_parameters`` group: the closed form of the module docstring."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = float(group["rope_theta"]) ** (-2.0 * i / dim)
    if group.get("rope_type", "default") == "default":
        return f, 1.0
    lo, hi = yarn_band(group, dim)
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    factor = float(group["factor"])
    af = group.get("attention_factor")
    return (f * ((1.0 - ramp) + ramp / factor),
            float(af) if af is not None else 0.1 * math.log(factor) + 1.0)


def _rotate(x, freqs, factor):
    """x [S, n, hd], positions 0..S-1, half-split pairing over the whole
    head; cos and sin times ``factor``."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] \
        * jnp.asarray(freqs, F32)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Reference:
    """``Reference(hf, params)`` — ``hf`` the published config dict as run
    (the cut depth, the experts held and the router's width), ``params`` the
    program's parameter tree. ``defect``: one of ``DEFECTS``."""

    def __init__(self, hf: dict, params, defect: str = None):
        if defect is not None and defect not in DEFECTS:
            raise ValueError(f"defect {defect!r}: one of {DEFECTS}")
        self.hf, self.params, self.defect = hf, params, defect
        # what a matrix product's operands are rounded to (None: float32)
        self._operand = jnp.float8_e5m2 if defect == "precision_below" \
            else None
        self._attn = jax.jit(self._attn_block, static_argnames=("local",))
        self._route = jax.jit(self._router)
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

    def _table(self, local: bool):
        """The rotary table of a sliding (``local``) or a full layer."""
        groups, hd = self.hf["rope_parameters"], self.hf["head_dim"]
        kind = "sliding_attention" if local else "full_attention"
        if self.defect == "yarn_on_sliding":
            kind = "full_attention"
        if self.defect == "plain_on_full":
            kind = "sliding_attention"
        freqs, factor = rope_table(groups[kind], hd)
        return freqs, 1.0 if self.defect == "no_attention_factor" else factor

    def _attn_block(self, st, j, h, local: bool):
        """h [S, H] -> the attention block's output; ``local``: a sliding
        layer (plain table, banded), else a full one (YaRN table)."""
        hf = self.hf
        nq, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                       hf["head_dim"])
        S = h.shape[0]
        q = self._mm(h, st["wq"][j]).reshape(S, nq, hd)
        k = self._mm(h, st["wk"][j]).reshape(S, nkv, hd)
        v = self._mm(h, st["wv"][j]).reshape(S, nkv, hd)
        if self.defect != "no_qk_norm":
            q = _rms(q, st["q_norm"][j].astype(F32), _eps(hf))
            k = _rms(k, st["k_norm"][j].astype(F32), _eps(hf))
        freqs, factor = self._table(local)
        q, k = _rotate(q, freqs, factor), _rotate(k, freqs, factor)
        W = hf["sliding_window"] + (self.defect == "band_off_by_one") \
            if local else S
        qb = min(Q_BLOCK, S)
        q = self._lo(q).reshape(S // qb, qb, nkv, nq // nkv, hd)
        keys = jnp.arange(S)[None, :]

        def rows(xs):           # one block of queries against all the keys
            qs, i0 = xs
            s = jnp.einsum("sngd,tnd->ngst", qs, self._lo(k)) / math.sqrt(hd)
            at = i0 + jnp.arange(qb)[:, None]
            ok = (keys <= at) & (at - keys < W)
            p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), -1)
            return jnp.einsum("ngst,tnd->sngd", self._lo(p), self._lo(v))

        o = jax.lax.map(rows, (q, jnp.arange(S // qb) * qb))
        return self._mm(o.reshape(S, nq * hd), st["wo"][j])

    def _router(self, st, j, h):
        """[S, held] combine weights of the experts HELD, zero where an
        expert was not chosen: the softmax and the top-k are over ALL the
        router's experts, the weights divided by the sum of all the k
        chosen."""
        hf = self.hf
        E, first = hf["num_experts"], hf.get("expert_first", 0)
        p = jax.nn.softmax(self._mm(h, st["wg"][j]), axis=-1)
        w, idx = jax.lax.top_k(p, hf["num_experts_per_tok"])
        mine = (idx >= first) & (idx < first + E)
        if self.defect == "renorm_over_held":
            w = jnp.where(mine, w, 0.0)
        if hf.get("norm_topk_prob", True) and self.defect != "no_renorm":
            w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-30)
        w = jnp.where(mine, w, 0.0)
        return jnp.einsum("sk,ske->se", w,
                          jax.nn.one_hot(idx - first, E, dtype=F32))

    def _one_expert(self, st, j, e, h):
        up = self._mm(h, st["moe_w_in_t"][j, e].T)
        gate = self._mm(h, st["moe_w_gate"][j, e])
        return self._mm(jax.nn.silu(gate) * up, st["moe_w_out"][j, e])

    def _final(self, params, x, c0, cols: int):
        x = _rms(x, params["final_norm_scale"].astype(F32), _eps(self.hf))
        head = jax.lax.dynamic_slice_in_dim(params["lm_head"], c0, cols, axis=1)
        return self._mm(x, head)

    # ---- whole forward ----------------------------------------------------

    def hidden(self, params, ids):
        """ids [S] (S a multiple of ``Q_BLOCK`` or below it) -> the stream
        after the last block [S, H], before the final norm."""
        x = self._embed(params, ids)
        for kind, j in blocks(self.hf):
            st = params["layers"][kind]
            h = self._norm_in(st, j, x)
            if kind == "moe":
                w = self._route(st, j, h)
                y = jnp.zeros_like(x)
                for e in range(w.shape[-1]):
                    y = self._add_expert(st, j, e, h, w[:, e], y)
            else:
                y = self._attn(st, j, h, local=kind == "wattn")
            x = x + y
        return x

    def _padded(self, ids):
        n = len(ids)
        padded = np.zeros((-(-n // Q_BLOCK) * Q_BLOCK if n > Q_BLOCK else n,),
                          np.int32)
        padded[:n] = np.asarray(ids, np.int32)
        return jnp.asarray(padded), n

    def logits(self, ids):
        """ids [S] int -> float32 logits [S, vocab] as a NUMPY array. The ids
        are padded at the END to a multiple of ``Q_BLOCK`` (every block is
        causal, so no real position sees a pad)."""
        params, V = self.params, self.hf["vocab_size"]
        padded, n = self._padded(ids)
        with _HIGHEST():
            x = self.hidden(params, padded)[:n]
            cols = next(c for c in (8192, 4096, 512, V) if V % c == 0)
            out = np.empty((n, V), np.float32)
            for c0 in range(0, V, cols):
                out[:, c0:c0 + cols] = np.asarray(
                    self._head(params, x, c0, cols=cols))
            return out

    def loss_fn(self, params, batch_ids):
        """Mean next-token cross-entropy of a [B, S] batch as a function of
        the parameter tree: differentiable, float32, for toy sizes (the
        whole [S, vocab] logits of a sequence at once)."""
        V = self.hf["vocab_size"]
        tot, n = 0.0, 0
        with _HIGHEST():
            for ids in np.asarray(batch_ids):
                x = self.hidden(params, jnp.asarray(ids, jnp.int32))
                lg = self._head(params, x, 0, cols=V)[:-1]
                gold = jnp.take_along_axis(lg, jnp.asarray(ids[1:])[:, None], 1)
                tot = tot + jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold[:, 0])
                n += lg.shape[0]
        return tot / n

    def loss(self, batch_ids):
        """Mean next-token cross-entropy over a [B, S] batch (the last
        position of each sequence has no label), as the engine's ``lm_loss``
        defines it. Summed in float64 on the host."""
        tot, n = 0.0, 0
        for ids in batch_ids:
            ids = np.asarray(ids)
            lg = self.logits(ids)[:-1].astype(np.float64)
            m = lg.max(axis=-1)
            lse = m + np.log(np.exp(lg - m[:, None]).sum(axis=-1))
            tot += float((lse - lg[np.arange(lg.shape[0]), ids[1:]]).sum())
            n += lg.shape[0]
        return tot / n


# ---- the cost model: parameters and operations ----------------------------

def attn_params(hf: dict) -> int:
    """q, k, v and o of one attention block (either kind)."""
    H, nq, nkv, hd = (hf["hidden_size"], hf["num_attention_heads"],
                      hf["num_key_value_heads"], hf["head_dim"])
    return H * nq * hd + 2 * H * nkv * hd + nq * hd * H


def block_params(hf: dict, kind: str, experts: float = None) -> float:
    """Matmul parameters of one block of ``kind`` (``experts`` routed experts
    counted; default the experts HELD: what the chip holds). Norm scales are
    left out."""
    if kind in ("attn", "wattn"):
        return attn_params(hf)
    E = hf["num_experts"] if experts is None else experts
    return E * expert_params(hf) + hf["hidden_size"] * router_width(hf)


def head_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def param_count(hf: dict) -> float:
    """Every stored parameter a matmul or the lookup uses: blocks + embedding
    + untied head."""
    return (sum(block_params(hf, kind) for kind, _ in blocks(hf))
            + 2 * head_params(hf))


def attention_pairs(hf: dict, seq_len: int) -> dict:
    """Visible (query, key) pairs of ONE sequence of ``seq_len`` positions,
    summed over the blocks of each kind."""
    return {"attn": count(hf, "attn") * band_pairs(seq_len, seq_len),
            "wattn": count(hf, "wattn") * band_pairs(seq_len,
                                                     hf["sliding_window"])}


def pair_flops(hf: dict) -> float:
    """FLOPs of ONE matmul of attention over one visible pair, every query
    head: Q K^T, or P V, or one of the backward's five."""
    return 2.0 * hf["num_attention_heads"] * hf["head_dim"]


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token USES on this chip (its expected
    ``num_experts_per_tok x held share`` experts) + attention over the
    visible pairs of each kind of block, forward and backward (= 3 x the
    forward's two matmuls; the backward's recompute of the scores is not
    required work)."""
    used = sum(block_params(hf, kind,
                            hf["num_experts_per_tok"] * held_share(hf))
               for kind, _ in blocks(hf)) + head_params(hf)
    pairs = sum(attention_pairs(hf, seq_len).values())
    return 6.0 * used + 3.0 * 2.0 * pair_flops(hf) * pairs / seq_len


def flash_flops(hf: dict, batch: int, seq_len: int) -> dict:
    """One FULL attention block's kernels for one step (mistral.py's
    accounting: causal half, the backward's five matmuls 2.5 x the forward's
    two)."""
    one = batch * pair_flops(hf) * seq_len * seq_len / 2.0
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


def flash_band_flops(hf: dict, batch: int, seq_len: int) -> dict:
    """One SLIDING block's kernels for one step, by the visible pairs of the
    band (the same accounting: forward two matmuls, backward five)."""
    one = batch * pair_flops(hf) * band_pairs(seq_len, hf["sliding_window"])
    return {"fwd": 2 * one, "bwd": 5 * one, "total": 7 * one}


def expected_held_rows(hf: dict, tokens: int) -> float:
    """(token, expert) pairs that land on the experts HELD, of one expert
    block and step, under an even router."""
    return tokens * hf["num_experts_per_tok"] * held_share(hf)


def moe_gmm_train_flops(hf: dict, tokens: int) -> float:
    """FLOPs ONE expert block's grouped matmuls NEED for a train step of
    ``tokens`` tokens: three matrices, each multiplied forward, for the rows'
    gradient and for its own (3 x 2 x rows x expert parameters)."""
    return 3.0 * 2.0 * expected_held_rows(hf, tokens) * expert_params(hf)


def moe_gmm_train_bytes(hf: dict, tokens: int,
                        bytes_per_value: float = 2.0) -> float:
    """Least bytes those kernels move: the held experts' matrices read twice
    (forward, the rows' gradient) and their gradient written once, the rows
    of width H in and out of each of the three passes."""
    return bytes_per_value * (
        3.0 * hf["num_experts"] * expert_params(hf)
        + 3.0 * 2.0 * expected_held_rows(hf, tokens) * hf["hidden_size"])


def decode_step_bytes(hf: dict, counters: dict) -> float:
    """The family protocol's; no cell serves this model. The weights of what
    a step touches (every held expert) and the live rows of every plane,
    window planes to ``sliding_window``."""
    kv_bits = counters["kv_cache_bits"]
    per_head = hf["head_dim"] + 4 if kv_bits == 8 else 2 * hf["head_dim"]
    row = 2.0 * hf["num_key_value_heads"] * per_head
    live = float(counters.get("mean_occupancy", 0.0))
    ctx = counters["mean_live_tokens"] / live if live else 0.0
    weights = 2.0 * (sum(block_params(hf, kind) for kind, _ in blocks(hf))
                     + head_params(hf))
    return weights + live * row * (
        count(hf, "attn") * ctx
        + count(hf, "wattn") * min(ctx, hf["sliding_window"]))


# ---- this family's kernels in a device trace ------------------------------

_KERNEL = re.compile(r"^%([\w.\-]+) = ")


def kernel(event_name: str):
    """Which of this PR's kernels a trace event is — ``moe_gmm`` (forward, or
    the rows' gradient), ``moe_gmm_dw``, ``flash_fwd_band``,
    ``flash_bwd_band_dq``, ``flash_bwd_band_dkv`` — or None. A Mosaic custom
    call whose instruction name carries the kernel's (differentiation wraps
    it: ``%transpose_jvp_moe_gmm_dw__.3``)."""
    m = _KERNEL.match(event_name)
    if not m or 'custom_call_target="tpu_custom_call"' not in event_name:
        return None
    for name in ("moe_gmm_dw", "moe_gmm", "flash_fwd_band",
                 "flash_bwd_band_dq", "flash_bwd_band_dkv"):
        if name in m.group(1):
            return name
    return None
