"""Hybrid stacks: one MIXER per block — two side by side in a ``P`` block —,
its kind from a static pattern.

``TransformerConfig.block_pattern`` is one letter a block (``nemotron_h``'s
``hybrid_override_pattern``; ``qwen3_next``'s and ``afmoe``'s layers are TWO
blocks each, a token mixer then a feed-forward): ``M`` a Mamba-2 mixer
(``models/mamba.py``), ``G`` a Gated DeltaNet mixer
(``models/gated_deltanet.py``), ``E`` an expert feed-forward
(``moe/sharded_moe.py``), ``D`` a dense feed-forward, ``*`` attention over
the whole history, ``W`` attention over the last ``window(cfg)`` positions,
``L`` multi-head latent attention (``models/latent_attention.py``), ``P``
(``falcon_h1``) a Mamba-2 mixer AND rotary attention over the whole history on
ONE normed input, ``h <- h + ssm_out_multiplier Mamba2(n) +
attention_out_multiplier Attn(n attention_in_multiplier)``, ``n =
RMSNorm(h)``: one norm, one residual, a K/V plane of the block pool and a
layer of the ``ssm`` / ``conv`` state pool (``plane_of``, ``state_layer``).
Every other block is ``h <- h + mixer(RMSNorm(h))`` — through a second RMSNorm
after the mixer where the stack has one (``sandwich_norm``) —: the walker
puts no feed-forward after attention and no attention before an expert layer,
the pattern does. The rotary rule is per KIND (``rope_table``): a ``*`` block
carries no positional embedding (``position_type="none"``, ``nemotron_h``,
``afmoe``) or rotary over the first ``rotary_dim`` dims of a head
(``"rotary"``), a ``W`` block is always rotary, and a config that states a
table a kind (``rope_tables``: ``mellum``'s plain table on the ``W`` blocks,
YaRN on the ``*`` blocks) has said it all; both take a per-head RMSNorm of q and k
(``qk_norm_per_head``) and a sigmoid gate on their output, projected beside q
(``attn_out_gate``), as the config says. ``embed_scale`` multiplies the
embeddings; the other muP multipliers of ``falcon_h1`` (``lm_head_multiplier``
on the logits, ``key_multiplier`` on k, ``mlp_multipliers`` on a ``D`` block's
gate and output, the Mamba mixer's in ``models/mamba.py``) are static scalars
of the forward too, and a config that leaves one at 1 has no multiply for it
in its programs. ``models/transformer.py``'s ``init_params``, ``logical_axes``,
``forward``, ``init_paged_cache``, ``prefill_paged`` and
``decode_step_paged`` hand a config with a pattern to the functions here, so
a hybrid model is a ``make_model`` like any other and serves through the same
engine.

THE STREAM'S SHAPE RULE. What the walkers carry from block to block is ``[..,
H]`` — or, where ``cfg.hc_mult`` = n > 1 (``xing4_0``: manifold-constrained
hyper-connections), ``[.., n H]``: n rows of H a token, carried flat, row i the
columns i H .. (i + 1) H (never ``[.., n, H]``: a second-minor extent of 4 is
padded to a sublane tile in every buffer). Four functions touch it and nothing
else does: ``_embed`` OPENS it (every row the token's embedding), ``_norm_in``
is a block's READ (``RMSNorm(x)``; with n rows the block's three mappings from
the RMS-normed whole stream — sigmoid ``H_pre`` [n], ``2 sigmoid`` ``H_post``
[n], ``H_res`` [n, n] made doubly stochastic by ``hc_sinkhorn_iters`` unrolled
Sinkhorn rounds, all float32 — and ``RMSNorm(H_pre X)``, handing ``(H_post,
H_res)`` on), ``_residual`` is the block's WRITE (``x + y``; with n rows
``H_res X + H_post^T y``) and ``_final_norm`` CLOSES it (one more sigmoid read
with leaves of its own, ``hc_out_*``) before the head. A mixer sees ``[.., H]``
either way. The mappings are per token, so ``forward``, ``prefill_paged`` (pad
rows included) and ``decode_step_paged`` (idle slots included) share the one
implementation; a block's ``hc_phi`` / ``hc_b`` / ``hc_a`` are leaves of its
kind's stack. With ``hc_mult`` 1 none of it is traced: every other family's
programs lower to the text they had.

Parameters are stacked PER KIND (``params["layers"]["mamba" | "gdn" | "moe"
| "dense" | "attn" | "wattn" | "latent" | "par"]``, leading dim = blocks of
that kind; a ``P`` block's stack holds the leaves of both its mixers under
the one ``ln_scale``). The walk
over a pattern that does not repeat is unrolled: a block's index within its
kind is a Python int, its slice of a stack a static one. A pattern that
repeats (three periods of ``GEGEGE*E``) is a ``lax.scan`` over its repeats
with one unit unrolled in the body and the indices traced (``_walk``). Either
way a program is shaped by the pool and table dims only.

The cache is three kinds of state side by side in one tree (the serving
engine's ``srv.pools``): the block pool — ``k`` / ``v``, whose layer dim
counts the ``*`` and ``P`` blocks only (absent without one), and ``latent``
[planes, NB, block, stored width], ONE row a token and ``L`` block that is
both K and V
(``latent_leaf``), paged by the same tables and blocks —; a per-slot state
pool for the recurrent blocks — ``ssm`` float32
``[Lm, slots, heads, P, N]`` and ``conv`` ``[Lm, slots, K - 1, conv_dim]``
(the last K - 1 rows of ``xBC`` before the convolution) for the ``M`` and
``P`` blocks,
``gdn`` float32 ``[Lg, slots, value heads, dk, dv]`` and ``gdn_conv`` ``[Lg,
slots, K - 1, conv_dim]`` for the ``G`` blocks —; and per slot and ``W``
block a RING of the last ``window`` positions' K/V (``ring_leaves``), its
bytes fixed whatever the context. A prefill is a whole prompt from a zero
state and overwrites the slot's rows with the state after the last TRUE
position (so a slot given again carries nothing of the last request) and the
ring's rows with the last ``window`` true positions; a step advances the
active slots' state in place, writes their row into their rings, and leaves
the others' alone.
"""
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models import gated_deltanet as gdn
from deepspeed_tpu.models import latent_attention as latent
from deepspeed_tpu.models import mamba
from deepspeed_tpu.moe import sharded_moe as _moe

KINDS = {"M": "mamba", "G": "gdn", "E": "moe", "*": "attn", "W": "wattn",
         "D": "dense", "L": "latent", "P": "par"}
# the kinds whose blocks are softmax attention: "attn" keeps every position in
# the K/V block pool, "wattn" the last ``window(cfg)`` in a ring per slot
ATTN_KINDS = ("attn", "wattn")
# the kinds that own a plane of ``k`` / ``v`` in the block pool, and those
# that own a layer of ``ssm`` / ``conv`` in the state pool, each in the order
# the planes / layers are stacked: a kind's blocks follow the kind before it
PLANE_KINDS = ("attn", "par")
SSM_KINDS = ("mamba", "par")


def blocks(cfg):
    """[(kind, index within its kind)] in block order."""
    if cfg.position_type not in ("none", "rotary") \
            or cfg.norm_type != "rmsnorm":
        raise NotImplementedError(
            "a hybrid (block_pattern) stack is RMSNorm blocks whose attention "
            "carries no positional embedding or a rotary one; got "
            f"position_type={cfg.position_type!r}, "
            f"norm_type={cfg.norm_type!r}")
    if len(cfg.block_pattern) != cfg.num_layers:
        raise ValueError(f"block_pattern {cfg.block_pattern!r} has "
                         f"{len(cfg.block_pattern)} letters for "
                         f"{cfg.num_layers} layers")
    seen, out = {}, []
    for letter in cfg.block_pattern:
        if letter not in KINDS:
            raise ValueError(
                f"block_pattern letter {letter!r}: one of {sorted(KINDS)} "
                "(M Mamba-2, G Gated DeltaNet, E experts, D dense "
                "feed-forward, * attention, W sliding-window attention, L "
                "latent attention, P Mamba-2 and attention side by side)")
        kind = KINDS[letter]
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def count(cfg, kind: str) -> int:
    return sum(1 for k, _ in blocks(cfg) if k == kind)


def _stacked_at(cfg, kinds, kind: str, j):
    """Where block ``j`` of ``kind`` lies in a stack over the blocks of
    ``kinds``, one kind after the other: ``j`` (a Python int, or traced in a
    scanned walk) past the blocks of the kinds before it."""
    return sum(count(cfg, k) for k in kinds[:kinds.index(kind)]) + j


def plane_of(cfg, kind: str, j):
    """The K/V plane of the block pool that block ``j`` of ``kind`` owns."""
    return _stacked_at(cfg, PLANE_KINDS, kind, j)


def state_layer(cfg, kind: str, j):
    """The layer of ``ssm`` / ``conv`` that block ``j`` of ``kind`` owns."""
    return _stacked_at(cfg, SSM_KINDS, kind, j)


def window(cfg) -> int:
    """Positions a "W" block sees (key j visible to query i iff 0 <= i - j <
    window): ``attn_windows`` holds it at every "W" block of the pattern and
    0 at every other block, so the letter and the length cannot disagree."""
    want = tuple(letter == "W" for letter in cfg.block_pattern)
    wins = tuple(cfg.attn_windows or (0,) * len(want))
    sizes = {w for w in wins if w}
    if tuple(bool(w) for w in wins) != want or len(sizes) > 1:
        raise ValueError(
            f"attn_windows {wins} against block_pattern "
            f"{cfg.block_pattern!r}: ONE window length, at the W blocks and "
            "nowhere else")
    return sizes.pop() if sizes else 0


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def init_params(key, cfg):
    """Seeded weights. What a checkpoint would hold away from 0 / 1 is drawn
    away from them here too, so that leaving any of it out shows: ``A_log``
    (A in [-16, -1]), ``dt_bias`` (the inverse softplus of a step in
    [time_step_min, time_step_max]), ``D``, the convolution and its bias,
    and the router's correction bias (std 0.02 against a 6th-to-7th score
    gap of ~0.01: it moves choices without starving experts; at 0.1 the
    fullest expert of a step took 7 x the mean load). The ``G`` blocks take
    Mamba-2's draw of ``A_log`` and ``dt_bias`` (decays of 0.001 to 1.6 a
    position before the data-dependent part), a convolution without bias and
    an output-norm scale ``w_n`` in U(0.5, 1.5); a gated shared expert's
    gate vector is drawn like a router column.

    A stack with muP multipliers (``falcon_h1``: the ``P`` blocks, and the
    ``D`` blocks and the head where ``mlp_multipliers`` /
    ``lm_head_multiplier`` are stated) draws each projection so that WITH its
    multipliers its output is of the order of what it joins (``_mup_std``):
    at a flat 0.02 the keys are ``key_multiplier`` x 1.4 and every score is 0,
    so neither rotary nor the mask would move a token. A ``P`` block's
    Mamba leaves are drawn as an ``M`` block's, its ``gate_norm`` in
    U(0.5, 1.5)."""
    H, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype
    std = 0.02
    out_scale = std / math.sqrt(2 * cfg.num_layers)
    Lm, Lg, Le, La = (count(cfg, k) for k in ("mamba", "gdn", "moe", "attn"))
    keys = iter(jax.random.split(key, 32))
    # the norm scales' own stream, drawn from only where the configuration
    # asks for a start away from 1 (`norm_init_jitter`, `post_norm_init`:
    # transformer.init_params' rule): every other draw keeps its key
    nkeys = iter(jax.random.split(jax.random.fold_in(key, 1), 32))

    def norm_scale(shape, start=1.0):
        j = cfg.norm_init_jitter
        if not j:
            return jnp.full(shape, start, dt)
        return jax.random.uniform(next(nkeys), shape, jnp.float32,
                                  start * (1 - j), start * (1 + j)).astype(dt)

    def normal(shape, scale=std):
        return (jax.random.normal(next(keys), shape) * scale).astype(dt)

    # what an output projection joins: the embeddings as the stream has them
    stream = std * cfg.embed_init_scale * cfg.embed_scale

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def dt_bias(shape):
        """The inverse softplus of a step drawn log-uniformly in
        [time_step_min, time_step_max]."""
        step = jnp.exp(uniform(shape, math.log(cfg.time_step_min),
                               math.log(cfg.time_step_max)))
        step = jnp.maximum(step, cfg.time_step_floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)

    layers = {}
    if Lm:
        nh, hd, G, N, d_inner, conv_dim, K = mamba.dims(cfg)
        bias = dt_bias((Lm, nh))              # drawn first, as since PR 32
        layers["mamba"] = {
            "ln_scale": jnp.ones((Lm, H), dt),
            "in_proj": normal((Lm, H, d_inner + conv_dim + nh)),
            "conv_w": uniform((Lm, K, conv_dim), -0.5, 0.5).astype(dt),
            "conv_b": uniform((Lm, conv_dim), -0.5, 0.5).astype(dt),
            "dt_bias": bias,
            "A_log": jnp.log(uniform((Lm, nh), 1.0, 16.0)).astype(dt),
            "D": uniform((Lm, nh), 0.5, 1.5).astype(dt),
            "gate_norm": jnp.ones((Lm, d_inner), dt),
            "out_proj": normal((Lm, d_inner, H), out_scale),
        }
    if Lg:
        Hk, Hv, dk, dv, conv_dim, K = gdn.dims(cfg)
        layers["gdn"] = {
            "ln_scale": jnp.ones((Lg, H), dt),
            "in_qkvz": normal((Lg, H, conv_dim + Hv * dv)),
            "in_ba": normal((Lg, H, 2 * Hv)),
            "conv_w": uniform((Lg, K, conv_dim), -0.5, 0.5).astype(dt),
            "dt_bias": dt_bias((Lg, Hv)),
            "A_log": jnp.log(uniform((Lg, Hv), 1.0, 16.0)).astype(dt),
            "gate_norm": uniform((Lg, dv), 0.5, 1.5).astype(dt),
            "out_proj": normal((Lg, Hv * dv, H), out_scale),
        }
    if Le:
        E, F, Fs = cfg.num_experts, cfg.ffn_dim, cfg.moe_shared_size

        def experts(shape, scale=std):
            # one layer's experts at a time, written into the stack in
            # place (a `lax.map` over per-layer keys): a draw of the whole
            # stack passes 2^31 elements at the published widths, and a
            # `jnp.stack` of per-layer draws holds them all twice
            return lax.map(
                lambda k: (jax.random.normal(k, shape) * scale).astype(dt),
                jax.random.split(next(keys), Le))

        layers["moe"] = {
            "ln_scale": jnp.ones((Le, H), dt),
            # the router scores ALL the model's experts; the stacks hold
            # the E this chip holds (`moe_router_width` = E by default)
            "wg": normal((Le, H, cfg.moe_router_width)),
            # the up projection, each matrix stored [F, H]: a width off
            # the 128 grid (1856) is read in place only with H last
            # (ops/grouped_matmul.grouped_matmul, `transposed`)
            "moe_w_in_t": experts((E, F, H)),
            # a quarter of the other output projections' scale: relu^2
            # experts under weights that sum to 2.5 otherwise put several
            # times the residual stream's norm on it, and a router near-tie
            # that bf16 rounding flips (2-4 % of tokens a block at 128
            # experts) then moves that token's stream by a quarter
            # (PERF.md section 6, PR 32)
            "moe_w_out": experts((E, F, H), out_scale / 4),
        }
        if cfg.moe_scoring == "sigmoid":
            # a bias per expert the router SCORES (the choice is over all)
            layers["moe"]["e_bias"] = normal((Le, cfg.moe_router_width), 0.02)
        if "glu" in cfg.activation:
            layers["moe"]["moe_w_gate"] = experts((E, H, F))
        if Fs:
            layers["moe"]["shared_w_in"] = normal((Le, H, Fs))
            layers["moe"]["shared_w_out"] = normal((Le, Fs, H), out_scale)
            if "glu" in cfg.activation:
                layers["moe"]["shared_w_gate"] = normal((Le, H, Fs))
            if cfg.moe_shared_gate:
                layers["moe"]["shared_gate"] = normal((Le, H))
    for kind in ATTN_KINDS:
        La = count(cfg, kind)
        if not La:
            continue
        nq, nkv, ahd = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
        layers[kind] = {
            "ln_scale": jnp.ones((La, H), dt),
            # with an output gate, a head's columns are [q | gate]
            "wq": normal((La, H, nq * ahd * (2 if cfg.attn_out_gate else 1))),
            "wk": normal((La, H, nkv * ahd)),
            "wv": normal((La, H, nkv * ahd)),
            "wo": normal((La, nq * ahd, H), out_scale),
        }
        if cfg.qk_norm_per_head:
            layers[kind]["q_norm"] = norm_scale((La, ahd))
            layers[kind]["k_norm"] = norm_scale((La, ahd))
    Ld = count(cfg, "dense")
    if Ld:
        F = cfg.dense_ffn_size or cfg.ffn_dim
        scales = (std, out_scale, std)
        if cfg.mlp_multipliers is not None:
            # up and the scaled gate of order 1; up x silu(gate) then has an
            # rms of 0.6 (E silu(g)^2 = 0.356 for a standard normal g)
            g, d = cfg.mlp_multipliers
            scales = (_mup_std(H), _mup_std(F, d, to=stream / 0.6),
                      _mup_std(H, g))
        layers["dense"] = {"ln_scale": jnp.ones((Ld, H), dt),
                           "w_in": normal((Ld, H, F), scales[0]),
                           "w_out": normal((Ld, F, H), scales[1])}
        if "glu" in cfg.activation:
            layers["dense"]["w_gate"] = normal((Ld, H, F), scales[2])
    Ll = count(cfg, "latent")
    if Ll:
        # drawn last: every other kind keeps the keys it had
        layers["latent"] = {"ln_scale": jnp.ones((Ll, H), dt)}
        for name, shape in latent.leaf_shapes(cfg).items():
            layers["latent"][name] = (
                norm_scale((Ll,) + shape) if name.endswith("_norm")
                else normal((Ll,) + shape, out_scale if name == "wo" else std))
    Lp = count(cfg, "par")
    if Lp:
        # drawn after every other kind: each keeps the keys it had
        nh, hd, G, N, d_inner, conv_dim, K = mamba.dims(cfg)
        nq, nkv, ahd = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
        # q and k of std sqrt(2) each: scores of std 2, a softmax that a few
        # keys share (its output's rms ~0.25 over some hundred positions of
        # unit-variance v); z, x, B, C and dt of order 1 under the ONE std
        # in_proj has (the five multipliers' geometric mean: 0.18 at the
        # published ones, which gives 1.15, 0.81, 0.57, 1.62, 1.15); the
        # gated norm hands out_proj rows of rms 1
        mup = cfg.ssm_multipliers or (1.0,) * 5
        layers["par"] = {
            "ln_scale": jnp.ones((Lp, H), dt),
            "dt_bias": dt_bias((Lp, nh)),
            "in_proj": normal((Lp, H, d_inner + conv_dim + nh), _mup_std(
                H, cfg.ssm_in_multiplier,
                math.prod(mup) ** (1 / len(mup)))),
            "conv_w": uniform((Lp, K, conv_dim), -0.5, 0.5).astype(dt),
            "conv_b": uniform((Lp, conv_dim), -0.5, 0.5).astype(dt),
            "A_log": jnp.log(uniform((Lp, nh), 1.0, 16.0)).astype(dt),
            "D": uniform((Lp, nh), 0.5, 1.5).astype(dt),
            "gate_norm": uniform((Lp, d_inner), 0.5, 1.5).astype(dt),
            "out_proj": normal((Lp, d_inner, H), _mup_std(
                d_inner, cfg.ssm_out_multiplier, to=stream)),
            "wq": normal((Lp, H, nq * ahd), _mup_std(
                H, cfg.attention_in_multiplier, to=math.sqrt(2))),
            "wk": normal((Lp, H, nkv * ahd), _mup_std(
                H, cfg.attention_in_multiplier, cfg.key_multiplier,
                to=math.sqrt(2))),
            "wv": normal((Lp, H, nkv * ahd), _mup_std(
                H, cfg.attention_in_multiplier)),
            "wo": normal((Lp, nq * ahd, H), _mup_std(
                nq * ahd, cfg.attention_out_multiplier, to=stream / 0.25)),
        }
    for kind, stacks in layers.items():
        n = stacks["ln_scale"].shape[0]
        if cfg.norm_init_jitter:
            stacks["ln_scale"] = norm_scale((n, H))
        if cfg.sandwich_norm:
            stacks["post_ln_scale"] = norm_scale((n, H), cfg.post_norm_init)
    params = {"tok_embed": normal((V, H), std * cfg.embed_init_scale),
              "layers": layers,
              "final_norm_scale": norm_scale((H,))}
    if cfg.hc_mult > 1:
        # a stream of several rows: every block's mappings, leaves of its
        # kind's stack, and the closing read's; their own stream of keys
        hkeys = iter(jax.random.split(jax.random.fold_in(key, 2),
                                      3 * (len(layers) + 1)))
        for stacks in layers.values():
            stacks.update(_hc_init(hkeys, cfg, stacks["ln_scale"].shape[0]))
        out = _hc_init(hkeys, cfg, 1, closing=True)
        params.update({"hc_out_" + k[3:]: a[0] for k, a in out.items()})
    if not cfg.tie_embeddings:
        # a stated logit multiplier: logits of order 1 all the same
        params["lm_head"] = normal((H, V), std if cfg.lm_head_multiplier == 1.0
                                   else _mup_std(H, cfg.lm_head_multiplier))
    return params


def _hc_init(keys, cfg, blocks: int, closing: bool = False) -> dict:
    """``hc_phi`` [blocks, n H, K], ``hc_b`` [blocks, K], ``hc_a`` [blocks,
    3] (K = 2n + n^2: pre | post | res row-major) of ``blocks`` blocks, or
    the closing read's (K = n, ONE scalar). ``hc_init_std`` 0: the start a
    trained-from-scratch stack has — phi 0 and a 0.01, so the mappings are
    their biases: H_pre = 1 / n each (the block reads the rows' mean), H_post
    = 1, H_res within e^-8 of the identity: the plain residual on n equal
    rows. s > 0: phi normal of std s / sqrt(n H) (the projection of a unit-RMS
    stream is then of order s), b normal of std s, a in U(0.5, 1.5): every
    mapping moves token by token, which is what a comparison needs to see a
    round of Sinkhorn, the factor 2 or the closing read left out."""
    n, dt, s = cfg.hc_mult, cfg.param_dtype, cfg.hc_init_std
    nH = n * cfg.hidden_size
    K, A = (n, 1) if closing else (2 * n + n * n, 3)
    if not s:
        pre = jnp.full((n,), -math.log(n - 1.0))
        b = pre if closing else jnp.concatenate(
            [pre, jnp.zeros((n,)), (8.0 * (jnp.eye(n) - 1.0)).reshape(-1)])
        return {"hc_phi": jnp.zeros((blocks, nH, K), dt),
                "hc_b": jnp.broadcast_to(b, (blocks, K)).astype(dt),
                "hc_a": jnp.full((blocks, A), 0.01, dt)}
    return {
        "hc_phi": (jax.random.normal(next(keys), (blocks, nH, K))
                   * (s / math.sqrt(nH))).astype(dt),
        "hc_b": (jax.random.normal(next(keys), (blocks, K)) * s).astype(dt),
        "hc_a": jax.random.uniform(next(keys), (blocks, A), jnp.float32,
                                   0.5, 1.5).astype(dt)}


def _mup_std(fan_in: int, *multipliers, to: float = 1.0) -> float:
    """The std of a projection whose output, the multipliers on its input
    and output applied, has std ``to`` for an input of rms 1."""
    return to / (math.sqrt(fan_in) * math.prod(multipliers))


def logical_axes(cfg):
    """Same tree as ``init_params``. The recurrent mixers' leaves carry no
    model-parallel axis: the recurrent state is not split over ``tensor``
    (the serving engine refuses that degree for a model with ``M`` or ``G``
    blocks)."""
    layers = {}
    if count(cfg, "gdn"):
        layers["gdn"] = {
            "ln_scale": ("layers", "unmodeled"),
            "in_qkvz": ("layers", "embed", None),
            "in_ba": ("layers", "embed", None),
            "conv_w": ("layers", None, None), "dt_bias": ("layers", None),
            "A_log": ("layers", None), "gate_norm": ("layers", None),
            "out_proj": ("layers", None, "embed")}
    if count(cfg, "mamba"):
        layers["mamba"] = {
            "ln_scale": ("layers", "unmodeled"),
            "in_proj": ("layers", "embed", None),
            "conv_w": ("layers", None, None), "conv_b": ("layers", None),
            "dt_bias": ("layers", None), "A_log": ("layers", None),
            "D": ("layers", None), "gate_norm": ("layers", None),
            "out_proj": ("layers", None, "embed")}
    if count(cfg, "moe"):
        layers["moe"] = {
            "ln_scale": ("layers", "unmodeled"),
            "wg": ("layers", "embed", None),
            "moe_w_in_t": ("layers", "expert", "mlp", "embed"),
            "moe_w_out": ("layers", "expert", "mlp", "embed")}
        if cfg.moe_scoring == "sigmoid":
            layers["moe"]["e_bias"] = ("layers", None)
        if "glu" in cfg.activation:
            layers["moe"]["moe_w_gate"] = ("layers", "expert", "embed", "mlp")
        if cfg.moe_shared_size:
            layers["moe"]["shared_w_in"] = ("layers", "embed", "mlp")
            layers["moe"]["shared_w_out"] = ("layers", "mlp", "embed")
            if "glu" in cfg.activation:
                layers["moe"]["shared_w_gate"] = ("layers", "embed", "mlp")
            if cfg.moe_shared_gate:
                layers["moe"]["shared_gate"] = ("layers", "embed")
    for kind in ATTN_KINDS:
        if not count(cfg, kind):
            continue
        layers[kind] = {
            "ln_scale": ("layers", "unmodeled"),
            "wq": ("layers", "embed", "qkv"), "wk": ("layers", "embed", "qkv"),
            "wv": ("layers", "embed", "qkv"), "wo": ("layers", "heads", "embed")}
        if cfg.qk_norm_per_head:
            layers[kind]["q_norm"] = ("layers", None)
            layers[kind]["k_norm"] = ("layers", None)
    if count(cfg, "par"):
        # both mixers' leaves; the recurrent state is not split over
        # ``tensor``, and the serving engine refuses that degree with it
        layers["par"] = {
            "ln_scale": ("layers", "unmodeled"),
            "in_proj": ("layers", "embed", None),
            "conv_w": ("layers", None, None), "conv_b": ("layers", None),
            "dt_bias": ("layers", None), "A_log": ("layers", None),
            "D": ("layers", None), "gate_norm": ("layers", None),
            "out_proj": ("layers", None, "embed"),
            "wq": ("layers", "embed", "qkv"), "wk": ("layers", "embed", "qkv"),
            "wv": ("layers", "embed", "qkv"), "wo": ("layers", "heads", "embed")}
    if count(cfg, "dense"):
        layers["dense"] = {"ln_scale": ("layers", "unmodeled"),
                           "w_in": ("layers", "embed", "mlp"),
                           "w_out": ("layers", "mlp", "embed")}
        if "glu" in cfg.activation:
            layers["dense"]["w_gate"] = ("layers", "embed", "mlp")
    if count(cfg, "latent"):
        # no model-parallel axis: every head reads the one latent row, and
        # the serving engine refuses a tensor mesh over it
        layers["latent"] = {"ln_scale": ("layers", "unmodeled")}
        for name, shape in latent.leaf_shapes(cfg).items():
            layers["latent"][name] = ("layers",) + (None,) * len(shape)
    if cfg.sandwich_norm:
        for stacks in layers.values():
            stacks["post_ln_scale"] = ("layers", "unmodeled")
    axes = {"tok_embed": ("vocab", "embed"), "layers": layers,
            "final_norm_scale": ("unmodeled",)}
    if cfg.hc_mult > 1:       # the stream is whole on every chip: no split
        for stacks in layers.values():
            stacks.update(hc_phi=("layers", None, None),
                          hc_b=("layers", None), hc_a=("layers", None))
        axes.update(hc_out_phi=(None, None), hc_out_b=(None,),
                    hc_out_a=(None,))
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# --------------------------------------------------------------------------
# the mixers that are not Mamba
# --------------------------------------------------------------------------

def _block(params, kind: str, j: int):
    """Block ``j`` of its kind: a static slice of every stack, except the
    expert stacks, which stay WHOLE with the block's index beside them
    (``_moe.LayerOf``): the grouped-matmul kernel reads a layer's experts
    out of the whole stack, and a slice handed to it is a copy of the
    layer's experts first (1.2 GB a stack at the published widths)."""
    return {k: (_moe.LayerOf(a, j) if k.startswith("moe_w_") else a[j])
            for k, a in params["layers"][kind].items()}


def _moe_mixer(p, h, cfg, train: bool = False, rng=None):
    """h [B, T, H] -> (out, aux). The stacks keep the names ``moe_ffn``
    takes them under."""
    moe_params = {"wg": p["wg"], "w_in_t": p["moe_w_in_t"],
                  "w_out": p["moe_w_out"]}
    for ours, theirs in (("moe_w_gate", "w_gate"), ("e_bias", "e_bias"),
                         ("shared_w_in", "shared_w_in"),
                         ("shared_w_out", "shared_w_out"),
                         ("shared_w_gate", "shared_w_gate"),
                         ("shared_gate", "shared_gate")):
        if ours in p:
            moe_params[theirs] = p[ours]
    with jax.named_scope("moe"):
        return _moe.moe_ffn(moe_params, h, cfg, rng=rng, train=train)


def rope_table(cfg, kind: str):
    """The rotary table of an attention block of ``kind``, or None where it
    carries no positional embedding: the table the config states for the kind
    (``rope_tables``), else plain ``rope_theta`` — on every "wattn" block, and
    on an "attn" or "par" block where ``position_type`` says rotary."""
    from deepspeed_tpu.models.transformer import RopeTable
    if cfg.rope_tables is not None:
        return dict(cfg.rope_tables)[kind]
    if kind == "wattn" or cfg.position_type == "rotary":
        return RopeTable(cfg.rope_theta)
    return None


def _qkv(p, h, cfg, positions, kind: str):
    """h [B, T, H] of a block of ``kind`` -> (q [B, T, nq, hd], k, v [B, T,
    nkv, hd], gate [B, T, nq hd] or None). What the config names is applied
    in HF's order: the output gate's columns split off q (``attn_out_gate``:
    a head's columns are [q | gate]), ``key_multiplier`` on k, the per-head
    RMSNorm of q and k
    (``q_norm`` / ``k_norm`` [hd]), rotary at ``positions`` [B, T] over the
    first ``rotary_dim`` dims by the kind's table (``rope_table``)."""
    from deepspeed_tpu.models.transformer import (_rms_whole, _wmat,
                                                  rotary_embed)
    table = rope_table(cfg, kind)
    B, T, _ = h.shape
    hd, gate = cfg.dim_per_head, None
    q = _wmat(h, p["wq"])
    if cfg.attn_out_gate:
        q = q.reshape(B, T, cfg.num_heads, 2 * hd)
        q, gate = q[..., :hd], q[..., hd:].reshape(B, T, cfg.num_heads * hd)
    q = q.reshape(B, T, cfg.num_heads, hd)
    k = _wmat(h, p["wk"]).reshape(B, T, cfg.kv_heads, hd)
    if cfg.key_multiplier != 1.0:
        k = k * cfg.key_multiplier
    v = _wmat(h, p["wv"]).reshape(B, T, cfg.kv_heads, hd)
    if "q_norm" in p:
        q = _rms_whole(q, p["q_norm"], cfg.norm_eps)
        k = _rms_whole(k, p["k_norm"], cfg.norm_eps)
    if table is not None:
        q, k = (rotary_embed(a, positions, table.theta, cfg.rotary_dim,
                             cfg.rotary_interleaved, table) for a in (q, k))
    return q, k, v, gate


def _out(p, o, gate):
    """The attention block's output projection of o [B, T, nq hd], through
    the sigmoid gate where the block has one."""
    from deepspeed_tpu.models.transformer import _wrow
    if gate is not None:
        with jax.named_scope("attn"), jax.named_scope("out_gate"):
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    return _wrow(o, p["wo"])


def _attn_mixer(p, h, cfg, kind: str = "attn"):
    """Causal attention over whole sequences h [B, T, H] -> (out, k, v); a
    "wattn" block's is rotary and banded to the last ``window(cfg)``
    positions (a STATIC length: the banded flash kernel where the flash
    kernel would run)."""
    from deepspeed_tpu.models.transformer import attention
    B, T, _ = h.shape
    local = kind == "wattn"
    q, k, v, gate = _qkv(p, h, cfg,
                         jnp.broadcast_to(jnp.arange(T)[None], (B, T)), kind)
    if local:
        with jax.named_scope("attn"), jax.named_scope("window"):
            o = attention(q, k, v, causal=True, cfg=cfg, window=window(cfg))
    else:
        with jax.named_scope("attn"):
            o = attention(q, k, v, causal=True, cfg=cfg)
    return _out(p, o.reshape(B, T, -1), gate), k, v


def _dense_mixer(p, h, cfg):
    """A dense feed-forward block h [B, T, H] -> [B, T, H]; with
    ``mlp_multipliers`` (g, d): ``down(up(h) act(gate(h) g)) d``."""
    from deepspeed_tpu.models.transformer import _activation, _wmat, _wrow
    g, d = cfg.mlp_multipliers or (1.0, 1.0)
    with jax.named_scope("mlp"):
        gate = _wmat(h, p["w_gate"]) if "w_gate" in p else None
        if gate is not None and g != 1.0:
            gate = gate * g
        y = _wrow(_activation(_wmat(h, p["w_in"]), gate, cfg), p["w_out"])
        return y if d == 1.0 else y * d


def _attn_in(h, cfg):
    """The normed rows as a "P" block's attention branch takes them."""
    m = cfg.attention_in_multiplier
    return h if m == 1.0 else h * m


def _par_join(m, a, cfg):
    """A "P" block's two branches, each times its output multiplier, as the
    ONE update its residual adds."""
    return m * cfg.ssm_out_multiplier + a * cfg.attention_out_multiplier


def _residual(p, x, y, cfg, hc=None):
    """A block WRITES the stream: ``x + y``, through the block's RMSNorm
    AFTER the mixer where the stack has one (``sandwich_norm``); with a
    stream of several rows (``hc``: what the block's read handed on)
    ``H_res X + H_post^T y`` (``_hc_write``)."""
    from deepspeed_tpu.models.transformer import _norm
    if cfg.sandwich_norm:
        y = _norm(y, p["post_ln_scale"], None, cfg)
    if hc is None:
        return x + y
    with jax.named_scope("hc/write"):
        return _hc_write(x, y, hc, cfg)


def _norm_in(p, x, cfg):
    """A block READS the stream -> (its mixer's input ``RMSNorm(x)`` [..,
    H], None); with a stream of several rows ``RMSNorm(H_pre X)`` and
    ``(H_post, H_res)`` for the block's write (``_hc_read``)."""
    from deepspeed_tpu.models.transformer import _norm
    if cfg.hc_mult == 1:
        return _norm(x, p["ln_scale"], None, cfg), None
    with jax.named_scope("hc/read"):
        h, hc = _hc_read(x, p["hc_phi"], p["hc_b"], p["hc_a"], cfg)
        return _norm(h, p["ln_scale"], None, cfg).astype(x.dtype), hc


def _final_norm(params, x, cfg):
    """The stream CLOSES — several rows through one more sigmoid read of
    its own (``hc_out_*``) — into the final norm."""
    from deepspeed_tpu.models.transformer import _norm
    if cfg.hc_mult > 1:
        with jax.named_scope("hc/close"):
            m = _hc_project(x, params["hc_out_phi"], cfg)          # [n, N]
            w = jax.nn.sigmoid(
                params["hc_out_a"].astype(jnp.float32)[0] * m
                + params["hc_out_b"].astype(jnp.float32)[:, None])
            x = _hc_mix(w, _hc_rows(x, cfg), x.shape).astype(x.dtype)
    return _norm(x, params["final_norm_scale"], None, cfg)


# --------------------------------------------------------------------------
# a residual stream of several rows (manifold-constrained hyper-connections)
# --------------------------------------------------------------------------
# The stream of a token is n = ``hc_mult`` rows of H, carried FLAT, [.., n H],
# row i the columns i H .. (i + 1) H: a second-minor extent of 4 would be
# padded to a whole sublane tile by the TPU (8 rows of float32, 16 of bf16)
# in every buffer that holds the stream, and the rows are lane-aligned slices
# of the flat form (H a multiple of 128) that cost nothing. Everything here is
# per token (no state across tokens): T prompt tokens or one a slot alike, pad
# rows and idle slots included. The coefficients are float32 with the TOKENS
# minor ([n, N], [n, n, N]): 4 x 4 matrices a token as trailing dims would
# fill a sixty-fourth of a vector register each.

def _hc_rows(x, cfg):
    """The stream x [.., n H] as its n rows [.., H], float32. A row is
    sliced BEFORE it is widened: widened whole, the stream has three readers
    a block (the RMS, ``H_pre X``, the write) and the TPU compiler keeps ONE
    float32 copy of it for them — a ``convert`` of its own, 235 MB written
    and read three times a block of a 4096-token prompt — where each reader
    of a slice widens it inside its own fusion and reads the bf16 stream:
    25.7 -> 16.7 ms of a 4096-token row's 88.8 (PERF.md section 6, PR 59)."""
    H = cfg.hidden_size
    return [x[..., j * H:(j + 1) * H].astype(jnp.float32)
            for j in range(cfg.hc_mult)]


def _hc_project(x, phi, cfg):
    """``(x / rms(x)) phi`` over the WHOLE stream of each token (all n H
    values, no learned scale), x [.., n H], phi [n H, K] -> float32 [K, N],
    N the tokens. The RMS is a scalar a token, so it multiplies the
    projection's K results and not the stream's n H values; a stream and a
    ``phi`` both stored in bf16 are multiplied as they are (their products
    are exact in the float32 accumulator), anything wider in float32 at the
    highest precision."""
    x2 = x.reshape(-1, x.shape[-1])
    x32 = x2.astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + cfg.norm_eps)      # [N]
    dt = jnp.promote_types(x.dtype, phi.dtype)
    m = jnp.dot(x2.astype(dt), phi.astype(dt),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)                  # [N, K]
    return (m * r[:, None]).T


def _hc_mix(w, rows, shape):
    """``sum_j w[j] rows[j]``: w [n, N] float32, one weight a token and row,
    rows n x [.., H] -> [.., H] float32 (``shape``: the stream's)."""
    lead = shape[:-1] + (1,)
    out = w[0].reshape(lead) * rows[0]
    for j in range(1, len(rows)):
        out = out + w[j].reshape(lead) * rows[j]
    return out


def _sinkhorn(R, cfg):
    """R [n, n, N] (row, column, token) -> ``hc_sinkhorn_iters`` rounds of
    Sinkhorn-Knopp on ``exp(R - rowmax)``, a round the rows then the columns,
    ``hc_eps`` in every denominator: doubly stochastic. Unrolled: a loop
    would pay a launch a round for sixteen numbers a token."""
    M = jnp.exp(R - jnp.max(R, axis=1, keepdims=True))
    for _ in range(cfg.hc_sinkhorn_iters):
        M = M / (jnp.sum(M, axis=1, keepdims=True) + cfg.hc_eps)
        M = M / (jnp.sum(M, axis=0, keepdims=True) + cfg.hc_eps)
    return M


def _hc_read(x, phi, b, a, cfg):
    """A block's three mappings from the whole stream x [.., n H] and the
    block's input: -> (``H_pre X`` [.., H] float32, (H_post [n, N], H_res [n,
    n, N])). ``m = u phi`` [2n + n^2]: ``H_pre = sigmoid(a_pre m[:n] + b)``,
    ``H_post = 2 sigmoid(a_post m[n:2n] + b)``, ``H_res = Sinkhorn(clip(a_res
    mat(m[2n:]) + b, hc_res_clamp))``, row-major."""
    n = cfg.hc_mult
    m = _hc_project(x, phi, cfg)
    b, a = b.astype(jnp.float32)[:, None], a.astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * m[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[n:2 * n] + b[n:2 * n])
    with jax.named_scope("hc/sinkhorn"):
        lo, hi = cfg.hc_res_clamp
        res = _sinkhorn(jnp.clip(a[2] * m[2 * n:] + b[2 * n:], lo, hi
                                 ).reshape(n, n, -1), cfg)
    return _hc_mix(pre, _hc_rows(x, cfg), x.shape), (post, res)


def _hc_write(x, y, hc, cfg):
    """``H_res X + H_post^T y``: row i of the new stream is ``sum_j H_res[i,
    j] X[j] + H_post[i] y``, in float32, STORED in the stream's dtype: the
    barrier makes the rounded stream the array the next block reads. Without
    it the TPU compiler hands the next block the float32 values (every reader
    of the stream widens it first, so the conversion is hoisted into this
    fusion): a 235 MB float32 stream a block of a 4096-token prompt, read
    three times, where the stated bf16 one is 117 MB — 37.3 ms against 20.8
    for twelve blocks' reads and writes alone (PERF.md section 6, PR 59)."""
    post, res = hc
    rows = _hc_rows(x, cfg) + [y.astype(jnp.float32)]
    return lax.optimization_barrier(jnp.concatenate(
        [_hc_mix(jnp.concatenate([res[i], post[i:i + 1]]), rows, x.shape)
         for i in range(cfg.hc_mult)], axis=-1).astype(x.dtype))


def _head(params, x, cfg):
    from deepspeed_tpu.models.transformer import lm_head_logits
    with jax.named_scope("lm_head"):
        logits = lm_head_logits(_final_norm(params, x, cfg), params)
        return (logits if cfg.lm_head_multiplier == 1.0
                else logits * cfg.lm_head_multiplier)


def _embed(params, ids, cfg):
    with jax.named_scope("embed"):
        x = params["tok_embed"][ids].astype(cfg.dtype)
        x = x if cfg.embed_scale == 1.0 else x * cfg.embed_scale
        if cfg.hc_mult > 1:         # the stream OPENS: every row the embedding
            with jax.named_scope("hc/open"):
                x = jnp.tile(x, cfg.hc_mult)
        return x


# --------------------------------------------------------------------------
# the walk over the pattern
# --------------------------------------------------------------------------

def period(cfg):
    """(unit, n): the pattern as ``n`` repeats of its shortest unit; a
    pattern that does not repeat is one unit. DEFERRED: any pattern with
    Mamba-2 blocks ("M", or "P" with its two mixers) is one unit too, because
    ``%ssm_step`` takes its block's
    index as a Python int (``ops/ssm.py``: a literal in the block index, where
    ``%gdn_step``'s is a prefetched scalar). No pattern the benchmark runs
    has both Mamba-2 blocks and a repeat, and the traced index would change
    the accepted hybrid cell's step text (``tests/unit/test_program_text.
    py``): the PR that serves a repeating Mamba-2 pattern gives the kernel
    the scalar and drops the test on ``"M"``. A pattern with "W" blocks is
    one unit as well: their rings are one array a block (``ring_leaves``
    says why), which a traced index cannot choose among. A stack whose
    stream is several rows wide (``hc_mult`` > 1) is one unit too: no test
    holds the scanned walk to the unrolled one with that carry yet."""
    pattern = cfg.block_pattern
    if not set("MPW") & set(pattern) and cfg.hc_mult == 1:
        for size in range(1, len(pattern) // 2 + 1):
            if pattern == pattern[:size] * (len(pattern) // size):
                return pattern[:size], len(pattern) // size
    return pattern, 1


def _walk(params, cfg, carry, block):
    """``block(i, kind, j, p, carry) -> (carry, out)`` over the pattern's
    blocks in order (``j`` the block's index within its kind, ``p`` its
    slice of the kind's stacks, ``out`` None or what an attention block
    hands on) -> (carry, outs): {kind: its blocks' outs}, a list where the
    walk is unrolled and stacked arrays where it is a scan (``_stacked``
    makes either the latter), without the kinds that hand nothing on.

    A pattern that repeats (``period``) is walked as a ``lax.scan`` over its
    repeats with ONE unit unrolled in the body — ``j`` is then traced,
    ``repeat x blocks of the kind a unit + index in the unit`` — so a
    program holds one unit's blocks whatever the depth (three periods of
    eight blocks: a third of 24 blocks' compile time and program text). A
    pattern that does not repeat is unrolled, its indices Python ints."""
    unit, n = period(cfg)
    per_unit, steps = {}, []
    for letter in unit:
        kind = KINDS[letter]
        steps.append((kind, per_unit.get(kind, 0)))
        per_unit[kind] = per_unit.get(kind, 0) + 1

    def one_unit(carry, r):
        outs = {}
        for i, (kind, j) in enumerate(steps):
            j = r * per_unit[kind] + j
            carry, out = block(i, kind, j, _block(params, kind, j), carry)
            if out is not None:
                outs.setdefault(kind, []).append(out)
        return carry, outs

    if n == 1:
        return one_unit(carry, 0)

    def body(carry, r):
        with _moe.layer_load_tap() as tap:
            carry, outs = one_unit(carry, r)
        return carry, ({k: _stacked(v) for k, v in outs.items()} or None,
                       tap.stacked() if tap is not None else None)

    carry, (outs, load) = lax.scan(body, carry, jnp.arange(n))
    _moe.record_expert_load(load)
    # [repeats, blocks of the kind a unit, ...] -> [blocks of the kind, ...]
    return carry, ({} if outs is None else jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), outs))


def _planes(outs):
    """What the blocks that own a K/V plane handed on, stacked in the planes'
    order (``PLANE_KINDS``: a kind's blocks after the kind before it)."""
    parts = [_stacked(outs[k]) for k in PLANE_KINDS if k in outs]
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a), *parts)


def _stacked(outs):
    """A list of per-block pytrees -> one pytree of arrays stacked on a new
    leading dim; what is stacked already as it is."""
    if isinstance(outs, list):
        return jax.tree.map(lambda *a: jnp.stack(a), *outs)
    return outs


# --------------------------------------------------------------------------
# forward, no cache
# --------------------------------------------------------------------------

def forward(params, input_ids, cfg, *, deterministic: bool = True,
            dropout_rng=None, return_aux: bool = False,
            return_hidden: bool = False, **unsupported):
    """input_ids [B, T] -> float32 logits [B, T, V]."""
    extra = sorted(k for k, v in unsupported.items() if v not in (None, False))
    if extra:
        raise NotImplementedError(
            f"a hybrid (block_pattern) model's forward takes no {extra}")

    from deepspeed_tpu.models.transformer import _remat_policy
    remat = cfg.remat or cfg.remat_policy not in ("none", None)

    def block(i, kind, j, p, carry):
        def run(x, aux_total):
            # the expert load leaves a rematerialised block as an output of
            # it, as it leaves a scan body (`_walk`)
            with jax.named_scope(f"layer{i}"), _moe.layer_load_tap() as tap:
                h, hc = _norm_in(p, x, cfg)
                if kind == "mamba":
                    y = mamba.mixer_forward(p, h, cfg)
                elif kind == "gdn":
                    y = gdn.mixer_forward(p, h, cfg)
                elif kind == "moe":
                    y, aux = _moe_mixer(p, h, cfg, train=not deterministic,
                                        rng=dropout_rng)
                    aux_total = aux_total + aux
                elif kind == "dense":
                    y = _dense_mixer(p, h, cfg)
                elif kind == "latent":
                    y = latent.mixer_forward(p, h, cfg)[0]
                elif kind == "par":
                    with jax.named_scope("mix/ssm"):
                        m = mamba.mixer_forward(p, h, cfg)
                    with jax.named_scope("mix/attn"):
                        a = _attn_mixer(p, _attn_in(h, cfg), cfg, kind)[0]
                    y = _par_join(m, a, cfg)
                else:
                    y = _attn_mixer(p, h, cfg, kind)[0]
                return (_residual(p, x, y, cfg, hc), aux_total), (
                    tap.stacked() if tap is not None else None)

        if remat:       # the block's slices are closed over: residuals
            run = jax.checkpoint(run, policy=_remat_policy(cfg))
        carry, load = run(*carry)
        _moe.record_expert_load(load)
        return carry, None

    (x, aux_total), _ = _walk(
        params, cfg, (_embed(params, input_ids, cfg), jnp.float32(0.0)), block)
    if return_hidden:
        return _final_norm(params, x, cfg), aux_total
    logits = _head(params, x, cfg)
    return (logits, aux_total) if return_aux else logits


# --------------------------------------------------------------------------
# the paged cache: K/V blocks for the attention blocks, a state per slot for
# the recurrent blocks
# --------------------------------------------------------------------------

def init_paged_cache(cfg, num_blocks: int, block_size: int, dtype=None,
                     max_seqs: Optional[int] = None):
    """``k``, ``v`` (+ int8 scale planes) exactly as ``transformer.
    init_paged_cache`` lays them out, over the "*" and "P" blocks only
    (``cfg.kv_planes``; none: no such leaves; stored head-major where
    ``blocks_head_major`` says so), ``latent`` over the "L" blocks
    (``latent_leaf``),
    and the per-slot leaves of ``state_leaves`` for ``max_seqs`` slots:
    ``ssm`` / ``conv`` (the ``M`` blocks), ``gdn`` / ``gdn_conv`` (the ``G``
    blocks), ``wk`` / ``wv`` (+ scales: the ``W`` blocks' rings), each only
    where the pattern has such blocks."""
    import dataclasses
    from deepspeed_tpu.models import transformer as tf
    if max_seqs is None and (cfg.recurrent_blocks or cfg.window_blocks):
        raise ValueError("a model with recurrent or window blocks keeps a "
                         "state per serving slot: init_paged_cache needs "
                         "max_seqs")
    dtype = dtype or cfg.dtype
    pools = {}
    if cfg.kv_planes:
        pools = tf.init_paged_cache(
            dataclasses.replace(cfg, block_pattern=None, attn_windows=None,
                                num_layers=cfg.kv_planes),
            num_blocks, block_size, dtype=dtype)
    if cfg.latent_planes:
        pools["latent"] = jnp.zeros(*latent_leaf(cfg, num_blocks, block_size,
                                                 dtype))
    pools = _token_major(pools, cfg)            # (its own inverse)
    for name, (shape, leaf_dtype) in state_leaves(cfg, max_seqs,
                                                  dtype).items():
        pools[name] = jnp.zeros(shape, leaf_dtype)
    for name, (shape, leaf_dtype) in ring_leaves(cfg, max_seqs,
                                                 dtype).items():
        pools[name] = tuple(jnp.zeros(shape, leaf_dtype)
                            for _ in range(count(cfg, "wattn")))
    return pools


def state_leaves(cfg, max_seqs: int, dtype=None) -> dict:
    """{leaf: (shape, dtype)} of the recurrent blocks' per-slot state: the
    recurrent state (float32) and the convolution tail (the pool dtype) of
    each recurrent kind the pattern has, stacked on the blocks of the kind."""
    dtype = dtype or cfg.dtype
    out = {}
    Lm, Lg = sum(count(cfg, k) for k in SSM_KINDS), count(cfg, "gdn")
    if Lm:
        nh, hd, _, N, _, conv_dim, K = mamba.dims(cfg)
        out["ssm"] = ((Lm, max_seqs, nh, hd, N), jnp.float32)
        out["conv"] = ((Lm, max_seqs, K - 1, conv_dim), dtype)
    if Lg:
        _, Hv, dk, dv, conv_dim, K = gdn.dims(cfg)
        out["gdn"] = ((Lg, max_seqs, Hv, dk, dv), jnp.float32)
        out["gdn_conv"] = ((Lg, max_seqs, K - 1, conv_dim), dtype)
    return out


def blocks_head_major(cfg) -> bool:
    """Whether ``k`` / ``v`` are STORED [planes, NB, n_kv, block, head dim],
    a block's rows next to the head dim, and read and written through the
    token-major view of them (``_token_major``, a bitcast). Decided from
    what the pool's leaves are — their dtype and their heads — and from
    whether anything outside this module ever reads a block's bytes.

    FOUR rows of int8 pack into ONE 32-bit sublane word, so an int8 pool of
    exactly four K/V heads is dense token-major by the compiler's default
    (``T(4,128)(4,1)``), while every op of the TPU's that reads or writes
    whole blocks of it (the list read's gather, a prefill's block write)
    works on the block's rows next to the head dim (``{4,2,3,1,0:T(8,128)
    (4,1)}``): declared token-major, the compiler relayouts each whole leaf
    once a quantum call and around every prefill's block write. A pool of 2
    heads is stored head-major by the compiler's own default (2 rows would
    pad the word), 8 and 16 heads fill whole words and tiles either way, and
    a float pool packs no rows: none of them is relayouted, and they keep
    the token-major declaration (``tests/unit/test_pool_layout.py`` holds
    int8 pools of 8 and 16 heads free of whole-leaf copies on that side of
    the rule, compiled for a described v5e, and one of four on both). Both
    sides at the published sizes of
    the one benchmark stack with four int8 heads (PERF.md section 6, PR 57,
    the step compiled for a described v5e and both run on the chip): 1.54
    GiB of step temporaries token-major against 0.17, a step 24.3 ms against
    20.6, the two whole-leaf copies 10.7 % of the device, 2 321 tokens/s
    against 2 797, and one run in six with a round stalled for 3-4 s.

    Only where the blocks never leave the pool by themselves: a stack that
    keeps a state a slot beside them (``cfg.slot_state_blocks``) is refused
    the prefix cache, the K/V export and the swap-out (``inference/serving.
    py`` ``_by_blocks_alone``), whose readers and writers take a block's
    bytes token-major; there the order of the bytes is this module's own
    business. A stack of attention planes alone keeps the declaration those
    readers know."""
    return cfg.kv_cache_bits == 8 and cfg.kv_heads == 4 \
        and bool(cfg.slot_state_blocks)


def _token_major(pools, cfg):
    """The cache tree with ``k`` / ``v`` as every reader and writer here
    takes them, [planes, NB, block, n_kv, head dim]: the leaves themselves,
    or — where they are stored head-major (``blocks_head_major``) — their
    transposed view, which the compiler lays out as the stored bytes. Its
    own inverse."""
    if not blocks_head_major(cfg):
        return pools
    return {n: (jnp.swapaxes(a, 2, 3) if n in ("k", "v") else a)
            for n, a in pools.items()}


def latent_leaf(cfg, num_blocks: int, block_size: int, dtype=None):
    """(shape, dtype) of the "L" blocks' pool leaf: ``latent`` [planes, NB,
    block, stored width], ONE row a token and block — the normed latent and
    the rotary key, both K and V of every head, in whole lane tiles
    (``latent_attention.stored_width``) — token-major like ``k`` / ``v``
    (block 0 the trash block), so the row a step writes is scattered in
    place. It is kept in the POOL dtype: nothing here quantises a normalised
    latent beside a rotary key (``kv_cache_bits`` 8 is refused)."""
    if cfg.kv_cache_bits:
        raise NotImplementedError(
            f"kv_cache_bits={cfg.kv_cache_bits} on a model with latent "
            "attention: its cache row is a normalised latent beside a rotary "
            "key, kept in the pool's float dtype; no int8 recipe for it "
            "exists here")
    return ((cfg.latent_planes, num_blocks, block_size,
             latent.stored_width(cfg)), dtype or cfg.dtype)


def ring_leaves(cfg, max_seqs: int, dtype=None) -> dict:
    """{leaf: (shape, dtype)} of ONE "W" block's rings (none without such
    blocks): per slot the K/V of the last ``window(cfg)`` positions, position
    ``p`` in row ``p mod window`` — ``wk``, ``wv`` [slots, window, n_kv, head
    dim] and, for an int8 cache, ``wk_scale``, ``wv_scale`` [slots, n_kv x
    window]: a slot is laid out as a block of the pool is (token-major rows,
    head-major scales), so a ring row is quantised, written and read as a
    pool row. The cache tree holds each as a TUPLE of one array a "W" block:
    a block's step reads its own buffer and writes its row into it in place
    (stacked on the blocks, the TPU compiler split the stack into its planes
    and put them together again around every step: 4.3 GB of copies a step
    at the published sizes, PERF.md section 6, PR 44). A slot's ring bytes
    are fixed whatever its context; nothing of it is allocated or freed."""
    if not count(cfg, "wattn"):
        return {}
    dtype = dtype or cfg.dtype
    W, nkv, hd = window(cfg), cfg.kv_heads, cfg.dim_per_head
    int8 = cfg.kv_cache_bits == 8
    out = {}
    for name in ("wk", "wv"):
        out[name] = ((max_seqs, W, nkv, hd), jnp.int8 if int8 else dtype)
        if int8:
            out[name + "_scale"] = ((max_seqs, nkv * W), jnp.float32)
    return out


def paged_cache_logical_axes(cfg):
    from deepspeed_tpu.models import transformer as tf
    import dataclasses
    out = {}
    if cfg.kv_planes:
        out = tf.paged_cache_logical_axes(
            dataclasses.replace(cfg, block_pattern=None, attn_windows=None))
        if blocks_head_major(cfg):
            for name in ("k", "v"):
                a = out[name]
                out[name] = a[:2] + (a[3], a[2]) + a[4:]
    if cfg.latent_planes:         # one row for every head: nothing to split
        out["latent"] = (None,) * 4
    for name, (shape, _) in state_leaves(cfg, 1).items():
        out[name] = (None,) * len(shape)
    for name, (shape, _) in ring_leaves(cfg, 1).items():
        out[name] = ((None,) * len(shape),) * count(cfg, "wattn")
    return out


RING_LEAVES = ("wk", "wv", "wk_scale", "wv_scale")
STATE_LEAVES = ("ssm", "conv", "gdn", "gdn_conv") + RING_LEAVES


def _ring_rows(k, v, int8: bool, dtype):
    """K/V rows [..., n_kv, T, hd] (head-major, as the projections give
    them) as ring rows: {leaf: rows} token-major [..., T, n_kv, hd] and, for
    an int8 cache, the scales [..., n_kv, T] (``_quant_kv``: a pool row's
    quantisation)."""
    from deepspeed_tpu.models.transformer import _quant_kv
    out = {}
    for name, a in (("wk", k), ("wv", v)):
        if int8:
            a, out[name + "_scale"] = _quant_kv(a)
        out[name] = jnp.swapaxes(a, -3, -2).astype(jnp.int8 if int8 else dtype)
    return out


def _write_ring_prefill(ring, slot, k, v, true_len, cfg):
    """The last ``window`` positions of a prompt's K/V, k / v [1, n_kv, P,
    hd] of ONE "W" block, into ``slot``'s ring (``ring``: the block's {leaf:
    array}): position ``p`` to row ``p mod window``. A bucket no longer than
    the window is rows 0 .. P - 1 as they are (what lies behind them is an
    earlier request's and is masked by position until a step overwrites it);
    a longer one keeps the ``window`` positions that end at the last true
    one."""
    W = ring["wk"].shape[1]
    P = k.shape[2]
    if P > W:
        start = jnp.clip(true_len - W, 0, P - W)
        k, v = (lax.dynamic_slice_in_dim(a, start, W, axis=2) for a in (k, v))
    out = {}
    for name, a in _ring_rows(k[0], v[0], cfg.kv_cache_bits == 8,
                              ring["wk"].dtype).items():
        scale = name.endswith("_scale")
        if P > W:            # position start + t -> row (start + t) mod W
            a = jnp.roll(a, start % W, axis=1 if scale else 0)
        leaf = ring[name]
        if scale:                                       # [n_kv, T] -> lanes
            for h in range(a.shape[0]):
                leaf = lax.dynamic_update_slice(leaf, a[None, h],
                                                (slot, h * W))
        else:
            leaf = lax.dynamic_update_slice(leaf, a[None], (slot, 0, 0, 0))
        out[name] = leaf
    return out


def _ring_attention(q, ring, seq_lens, kv_row, cfg):
    """One token per slot against ONE "W" block's rings (``ring``: the
    block's {leaf: array}): q [S, 1, Nq, D], the token at position ``seq_lens[s]``; kv_row its fresh (k, v) [S,
    Nkv, 1, D], folded into the same softmax and written afterwards.

    Row ``r`` of a slot's ring holds the LAST position written there, ``p =
    n - 1 - ((n - 1 - r) mod W)`` with ``n`` the slot's length; it is seen
    iff ``p >= 0`` (the row was written by this request) and ``n - p < W``
    (the band). What is read is the ring — W rows a slot and plane whatever
    the context. The arithmetic is ``_paged_list_attention``'s recipe on one
    run a slot: the query quantised per row, int8 x int8 -> int32 laid out
    block-diagonally over the kv heads so that the rows are contracted
    token-major as stored, probabilities x V scale requantised per row."""
    from deepspeed_tpu.models.transformer import _quant_probs, _quant_query
    S, _, Nq, D = q.shape
    rk, rv = ring["wk"], ring["wv"]                      # [S, W, Nkv, D]
    W, Nkv = rk.shape[1:3]
    rep = Nq // Nkv
    sm = cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(S, Nkv, rep, D)
    k_row, v_row = kv_row
    int8 = "wk_scale" in ring
    if int8:
        ks, vs = (ring[n].reshape(S, Nkv, W)
                  for n in ("wk_scale", "wv_scale"))
        eye = jnp.eye(Nkv, dtype=jnp.int8)
        qi, qs = _quant_query(qg.astype(jnp.float32))
        qd = jnp.einsum("sgrd,gh->sgdhr", qi, eye)   # zeros off the diagonal
        scores = jnp.einsum("stgd,sgdhr->shrt", rk, qd,
                            preferred_element_type=jnp.int32
                            ).astype(jnp.float32)
        scores = scores * qs[..., None] * ks[:, :, None, :]
    else:
        scores = jnp.einsum("sgrd,stgd->sgrt", qg, rk).astype(jnp.float32)
    scores = scores * sm
    n = jnp.asarray(seq_lens, jnp.int32)[:, None]
    pos = n - 1 - (n - 1 - jnp.arange(W)[None, :]) % W            # [S, W]
    keep = (pos >= 0) & (n - pos < W)
    scores = jnp.where(keep[:, None, None, :], scores, -1e30)
    s_self = jnp.einsum("sgrd,sgtd->sgrt", qg,
                        k_row.astype(qg.dtype)).astype(jnp.float32) * sm
    probs = jax.nn.softmax(jnp.concatenate([scores, s_self], axis=-1),
                           axis=-1)
    pp = probs[..., :W]
    if int8:
        pvi, ps = _quant_probs(pp * vs[:, :, None, :])
        pd = jnp.einsum("shrt,hg->shrtg", pvi, eye)
        acc = jnp.einsum("shrtg,stgd->shrd", pd, rv,
                         preferred_element_type=jnp.int32)
        out = (acc.astype(jnp.float32) * ps[..., None]).astype(q.dtype)
    else:
        out = jnp.einsum("sgrt,stgd->sgrd", pp.astype(q.dtype), rv,
                         preferred_element_type=jnp.float32).astype(q.dtype)
    out = out + probs[..., W:].astype(q.dtype) * v_row.astype(q.dtype)
    return out.reshape(S, 1, Nq, D)


def _write_ring_rows(ring, seq_lens, active, k_row, v_row, cfg):
    """A step's fresh rows, k_row / v_row [S, n_kv, hd], into every ACTIVE
    slot's ring of ONE "W" block at row ``seq_lens mod window``, one scatter
    per head whose window is a head's row alone (``_write_rows`` says why).
    An inactive slot's index is out of range and its update dropped: a ring
    has no trash row, and a slot between two requests keeps what it holds."""
    S, nkv = k_row.shape[:2]
    W = ring["wk"].shape[1]
    slot = jnp.where(active, jnp.arange(S), S)
    row = jnp.asarray(seq_lens, jnp.int32) % W
    out = {}
    for name, r in _ring_rows(k_row[:, :, None], v_row[:, :, None],
                              cfg.kv_cache_bits == 8,
                              ring["wk"].dtype).items():
        leaf = ring[name]
        for h in range(nkv):
            if name.endswith("_scale"):              # r [S, n_kv, 1]
                leaf = leaf.at[slot, h * W + row].set(r[:, h, 0], mode="drop")
            else:                                    # r [S, 1, n_kv, hd]
                leaf = leaf.at[slot, row, h].set(r[:, 0, h], mode="drop")
        out[name] = leaf
    return out


def _rings(state):
    """The cache tree's ring leaves, {leaf: tuple a block} -> one {leaf:
    array} a "W" block."""
    names = [n for n in RING_LEAVES if n in state]
    return [dict(zip(names, leaves))
            for leaves in zip(*(state[n] for n in names))]


def _set_rings(state, rings):
    for name in rings[0]:
        state[name] = tuple(r[name] for r in rings)
    return state


def prefill_paged(params, input_ids, cfg, pools, block_ids,
                  length: Optional[int] = None, slot=None, segments=None):
    """Prefill ONE request into ``slot``: K/V of the attention blocks into
    the slot's blocks, the recurrent blocks' state after the last true
    position into the slot's rows of the state pool. input_ids [1, P], P a
    multiple of the block size. Returns (last logits [1, V], pools).

    A stack that keeps nothing per slot (its attention all "L" blocks) takes
    no ``slot``, and ``segments=(starts [K], lengths [K])`` in place of
    ``length`` as ``transformer.prefill_paged`` does: SEVERAL requests in the
    row, each from a block's edge, attending to itself alone and counting its
    positions from its start; ``block_ids`` their blocks one request after
    the other. Returns (last logits [K, V], pools)."""
    from deepspeed_tpu.models.transformer import (_packed_row, _quant_kv,
                                                  _write_prefill_blocks)
    slotted = bool(cfg.slot_state_blocks)
    if slotted and slot is None:
        raise ValueError("a model with recurrent blocks prefills INTO a "
                         "slot: prefill_paged needs slot=")
    B, P = input_ids.shape
    assert B == 1, "prefill_paged serves one request"
    packed = {}
    if segments is None:
        true_len = jnp.asarray(P if length is None else length, jnp.int32)
    else:
        if slotted or cfg.kv_planes or length is not None:
            raise NotImplementedError(
                "segments share ONE row and take the place of length; a "
                "stack with recurrent, window or per-head attention blocks "
                "prefills one prompt a row")
        starts, lengths = (jnp.asarray(a, jnp.int32) for a in segments)
        seg, pos, real, _, last_rows = _packed_row(starts, lengths, P)
        packed = {"segment_ids": seg, "positions": pos}
    pools = dict(_token_major(pools, cfg))

    def ssm_prefill(p, h, layer, state):
        """The Mamba-2 mixer over the prompt from a zero state; the state
        after the last true position and the convolution tail into ``slot``'s
        rows of ``layer`` of the state pool."""
        y, s, tail = mamba.mixer_prefill(p, h[0], cfg, true_len)
        y = y[None]
        with jax.named_scope("ssm"), jax.named_scope("state_write"):
            state["ssm"] = state["ssm"].at[layer, slot].set(s)
            state["conv"] = state["conv"].at[layer, slot].set(
                tail.astype(state["conv"].dtype))
        return y, state

    def block(i, kind, j, p, carry):
        x, state = carry
        state, out = dict(state), None
        with jax.named_scope(f"layer{i}"):
            h, hc = _norm_in(p, x, cfg)
            if kind == "mamba":
                y, state = ssm_prefill(p, h, j, state)
            elif kind == "par":
                with jax.named_scope("mix/ssm"):
                    m, state = ssm_prefill(p, h, state_layer(cfg, kind, j),
                                           state)
                with jax.named_scope("mix/attn"):
                    a, k, v = _attn_mixer(p, _attn_in(h, cfg), cfg, kind)
                y = _par_join(m, a, cfg)
                out = (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
            elif kind == "gdn":
                y, s, tail = gdn.mixer_prefill(p, h[0], cfg, true_len)
                y = y[None]
                with jax.named_scope("gdn"), jax.named_scope("state_write"):
                    state["gdn"] = state["gdn"].at[j, slot].set(s)
                    state["gdn_conv"] = state["gdn_conv"].at[j, slot].set(
                        tail.astype(state["gdn_conv"].dtype))
            elif kind == "moe":
                y, _ = _moe_mixer(p, h, cfg)
            elif kind == "dense":
                y = _dense_mixer(p, h, cfg)
            elif kind == "latent":
                y, out = latent.mixer_forward(p, h, cfg, **packed)
            else:
                y, k, v = _attn_mixer(p, h, cfg, kind)
                out = (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
            return (_residual(p, x, y, cfg, hc), state), out
            # out: [1, nkv, P, hd]

    x = _embed(params, input_ids, cfg)                            # [1, P, H]
    if segments is None:
        real = jnp.arange(P)[None] < true_len
    with _moe.counted_tokens(real):
        (x, state), kv = _walk(
            params, cfg,
            (x, {n: pools[n] for n in STATE_LEAVES if n in pools}), block)
    if "wattn" in kv:
        with jax.named_scope("attn"), jax.named_scope("window"), \
                jax.named_scope("kv_write"):
            state = _set_rings(state, [
                _write_ring_prefill(ring, slot, k, v, true_len, cfg)
                for ring, (k, v) in zip(_rings(state), kv["wattn"])])
    pools.update(state)
    if cfg.kv_planes:
        # the K/V of the blocks that own a plane as transformer.
        # prefill_paged's contiguous cache holds them, [La, 1, nkv, P, hd]:
        # one writer
        cache = dict(zip(("k", "v"), _planes(kv)))
        if cfg.kv_cache_bits == 8:
            (cache["k"], cache["k_scale"]), (cache["v"], cache["v_scale"]) = \
                _quant_kv(cache["k"]), _quant_kv(cache["v"])
        pools.update(_write_prefill_blocks(pools, block_ids, cache,
                                           cfg.kv_cache_bits == 8))
    if "latent" in kv:
        # the rows of every "L" block, [planes, P, width], as whole blocks
        with jax.named_scope("attn"), jax.named_scope("kv_write"):
            pool = pools["latent"]
            rows = latent.as_stored(
                _stacked(kv["latent"])[:, 0].astype(pool.dtype), cfg)
            pools["latent"] = pool.at[:, block_ids].set(
                rows.reshape(rows.shape[0], -1, *pool.shape[2:]))
    pools = _token_major(pools, cfg)
    if segments is not None:
        return _head(params, x[:, last_rows], cfg)[0], pools
    last = lax.dynamic_index_in_dim(x, true_len - 1, axis=1, keepdims=True)
    return _head(params, last, cfg)[:, 0], pools


def _write_rows(pools, blk, off, rows, head_major: bool = False):
    """One K/V row per slot into every plane of the pool — ``k`` / ``v`` [La,
    NB, block, n_kv, hd], or [La, NB, n_kv, block, hd] with ``head_major`` —
    (``transformer.
    _scatter_rows``'s contract), as one scatter per (plane, head), whose
    window is a head's row alone: the pool of a model with FEW K/V heads is
    stored by the TPU with the block's rows next to the head dim (2 heads
    would pad to a tile of 8 otherwise), and a scatter whose window spans
    planes or heads is answered with a relayout of the whole pool, in and
    out, EVERY step — four copies of 0.5 GB, a fifth of this family's step
    (PERF.md section 6, PR 40).

    DEFERRED, and the selector below is not the cause: a stack with ONE
    attention block keeps ``_scatter_rows``. One plane of 2 heads is copied
    just the same (1.3 % of the accepted hybrid cell's device time, PERF.md
    section 7); the branch is there because PR 40 had to leave that cell's
    step program the parent's text (``tests/unit/test_program_text.py``) and
    its stack is the one in the benchmark with a single attention block. The
    PR that hands it the per-head writes measures that cell, prints the
    golden text again and deletes the branch."""
    from deepspeed_tpu.models.transformer import _scatter_rows
    La, _, nkv = rows["k"].shape[:3]
    if La == 1 and not head_major:
        return _scatter_rows(pools, blk, off, rows)
    bs, out = pools["k"].shape[3 if head_major else 2], {}
    for name, r in rows.items():
        pool = pools[name]
        for j in range(La):
            for h in range(nkv):
                if r.ndim != 4:
                    where = (j, blk, h * bs + off)
                else:     # ``k`` / ``v`` as STORED (``blocks_head_major``)
                    where = ((j, blk, h, off) if head_major
                             else (j, blk, off, h))
                pool = pool.at[where].set(r[j, :, h])
        out[name] = pool
    return out


def decode_step_paged(params, tokens, cfg, pools, block_tables, seq_lens,
                      active=None, backend: str = "xla", lora=None):
    """One decode step for every slot (``transformer.decode_step_paged``'s
    contract): tokens [S] -> (logits [S, V], pools). Inactive slots compute
    in lockstep; their K/V rows land in the trash block and their recurrent
    state stays as it is."""
    from deepspeed_tpu.models.transformer import (
        _block_at, _paged_attention, _quant_kv)
    if lora is not None:
        raise NotImplementedError("LoRA adapters on a hybrid model")
    S = tokens.shape[0]
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    if active is None:
        active = jnp.ones((S,), jnp.bool_)
    int8_kv = cfg.kv_cache_bits == 8
    pools = dict(_token_major(pools, cfg))
    bs = pools["latent" if "latent" in pools else "k"].shape[2]
    sc = (pools["k_scale"], pools["v_scale"]) if int8_kv else None

    def attend(p, h, kind, j, state):
        """One token a slot through an attention block's projections and its
        read — the paged planes (``plane_of``) or the block's ring — ->
        (out [S, 1, H], the fresh K/V rows the block hands on)."""
        local = kind == "wattn"
        q, k, v, gate = _qkv(p, h, cfg, seq_lens[:, None], kind)
        row_dtype = cfg.dtype if int8_kv else pools["k"].dtype
        k_row = jnp.swapaxes(k, 1, 2).astype(row_dtype)
        v_row = jnp.swapaxes(v, 1, 2).astype(row_dtype)
        if local:
            with jax.named_scope("attn"), jax.named_scope("window"):
                o = _ring_attention(q, _rings(state)[j], seq_lens,
                                    (k_row, v_row), cfg)
        else:
            with jax.named_scope("attn"):
                o = _paged_attention(
                    q, pools["k"], pools["v"], block_tables,
                    seq_lens, cfg, kv_row=(k_row, v_row),
                    kv_scale=sc, backend=backend, window=None,
                    layer=plane_of(cfg, kind, j))
        return (_out(p, o.reshape(S, 1, -1), gate),
                (k_row[:, :, 0], v_row[:, :, 0]))

    def block(i, kind, j, p, carry):
        x, state = carry
        state, out = dict(state), None
        with jax.named_scope(f"layer{i}"):
            h, hc = _norm_in(p, x, cfg)
            if kind == "mamba":
                y, state["ssm"], state["conv"] = mamba.mixer_step(
                    p, h[:, 0], cfg, state["ssm"], state["conv"], j, active)
                y = y[:, None]
            elif kind == "par":
                with jax.named_scope("mix/ssm"):
                    m, state["ssm"], state["conv"] = mamba.mixer_step(
                        p, h[:, 0], cfg, state["ssm"], state["conv"],
                        state_layer(cfg, kind, j), active)
                with jax.named_scope("mix/attn"):
                    a, out = attend(p, _attn_in(h, cfg), kind, j, state)
                y = _par_join(m[:, None], a, cfg)
            elif kind == "gdn":
                y, state["gdn"], state["gdn_conv"] = gdn.mixer_step(
                    p, h[:, 0], cfg, state["gdn"], state["gdn_conv"], j,
                    active)
                y = y[:, None]
            elif kind == "moe":
                y, _ = _moe_mixer(p, h, cfg)
            elif kind == "dense":
                y = _dense_mixer(p, h, cfg)
            elif kind == "latent":
                y, out = latent.mixer_step(p, h, cfg, pools["latent"],
                                           block_tables, seq_lens, j, backend)
            else:
                y, out = attend(p, h, kind, j, state)
            return (_residual(p, x, y, cfg, hc), state), out

    with _moe.counted_tokens(active):
        (x, state), rows = _walk(
            params, cfg,
            (_embed(params, tokens[:, None], cfg),                # [S, 1, H]
             {n: pools[n] for n in STATE_LEAVES if n in pools}), block)
    if "wattn" in rows:
        with jax.named_scope("attn"), jax.named_scope("window"), \
                jax.named_scope("kv_write"):
            state = _set_rings(state, [
                _write_ring_rows(ring, seq_lens, active, k, v, cfg)
                for ring, (k, v) in zip(_rings(state), rows["wattn"])])
    pools.update(state)
    if "latent" in rows:
        # every plane's fresh row [S, width] at (block, offset): a whole minor
        # tile of the token-major leaf, written in place — one scatter a
        # plane, whose window is a row alone (a window that spans the planes
        # is answered with a relayout of the whole leaf, in and out, every
        # step: ``_write_rows``)
        with jax.named_scope("attn"), jax.named_scope("kv_write"):
            blk = jnp.where(active, _block_at(block_tables, seq_lens // bs),
                            0)
            off = jnp.where(active, seq_lens % bs, 0)
            pool = pools["latent"]
            for j, row in enumerate(rows["latent"]):
                pool = pool.at[j, blk, off].set(row)
            pools["latent"] = pool
    if cfg.kv_planes:
        with jax.named_scope("attn"), jax.named_scope("kv_write"):
            blk = jnp.where(active, _block_at(block_tables, seq_lens // bs),
                            0)
            off = jnp.where(active, seq_lens % bs, 0)
            kr, vr = _planes(rows)                         # [La, S, nkv, hd]
            if int8_kv:
                (kq, ks), (vq, vs) = _quant_kv(kr), _quant_kv(vr)
                rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                rows = {"k": kr.astype(pools["k"].dtype),
                        "v": vr.astype(pools["v"].dtype)}
            # the rows go into the leaves AS STORED: scattered through the
            # token-major view of a head-major pool, they cost a relayout of
            # the whole leaf there and back
            pools = _token_major(pools, cfg)
            pools.update(_write_rows(
                {n: pools[n] for n in rows}, blk, off, rows,
                head_major=blocks_head_major(cfg)))
    return _head(params, x, cfg)[:, 0], pools
