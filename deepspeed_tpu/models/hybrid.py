"""Hybrid stacks: one MIXER per block, its kind from a static pattern.

``TransformerConfig.block_pattern`` is one letter a block (``nemotron_h``'s
``hybrid_override_pattern``): ``M`` a Mamba-2 mixer (``models/mamba.py``),
``E`` an expert feed-forward (``moe/sharded_moe.py``), ``*`` attention. Every
block is ``h <- h + mixer(RMSNorm(h))``: no feed-forward after attention, no
attention before an expert layer. ``models/transformer.py``'s ``init_params``,
``logical_axes``, ``forward``, ``init_paged_cache``, ``prefill_paged`` and
``decode_step_paged`` hand a config with a pattern to the functions here, so
a hybrid model is a ``make_model`` like any other and serves through the same
engine.

Parameters are stacked PER KIND (``params["layers"]["mamba" | "moe" |
"attn"]``, leading dim = blocks of that kind) and the walk over the pattern is
unrolled: a block's index within its kind is a Python int, its slice of a
stack a static one, and a program is still shaped by the pool and table
dims only.

The cache is two kinds of state side by side in one tree (the serving
engine's ``srv.pools``): the K/V block pool, whose layer dim counts the
ATTENTION blocks only, and a per-slot state pool for the ``M`` blocks —
``ssm`` float32 ``[Lm, slots, heads, P, N]`` and ``conv`` ``[Lm, slots,
K - 1, conv_dim]`` (the last K - 1 rows of ``xBC`` before the convolution).
A prefill is a whole prompt from a zero state and overwrites the slot's rows
with the state after the last TRUE position (so a slot given again carries
nothing of the last request); a step advances the active slots' state in
place and leaves the others' alone.
"""
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models import mamba
from deepspeed_tpu.moe import sharded_moe as _moe

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def blocks(cfg):
    """[(kind, index within its kind)] in block order."""
    if cfg.position_type != "none" or cfg.norm_type != "rmsnorm":
        raise NotImplementedError(
            "a hybrid (block_pattern) stack is RMSNorm blocks whose attention "
            f"carries no positional embedding; got position_type="
            f"{cfg.position_type!r}, norm_type={cfg.norm_type!r}")
    if len(cfg.block_pattern) != cfg.num_layers:
        raise ValueError(f"block_pattern {cfg.block_pattern!r} has "
                         f"{len(cfg.block_pattern)} letters for "
                         f"{cfg.num_layers} layers")
    seen, out = {}, []
    for letter in cfg.block_pattern:
        if letter not in KINDS:
            raise ValueError(
                f"block_pattern letter {letter!r}: one of {sorted(KINDS)} "
                "(M Mamba-2, E experts, * attention)")
        kind = KINDS[letter]
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def count(cfg, kind: str) -> int:
    return sum(1 for k, _ in blocks(cfg) if k == kind)


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
    fullest expert of a step took 7 x the mean load)."""
    H, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype
    std = 0.02
    out_scale = std / math.sqrt(2 * cfg.num_layers)
    nh, hd, G, N, d_inner, conv_dim, K = mamba.dims(cfg)
    Lm, Le, La = (count(cfg, k) for k in ("mamba", "moe", "attn"))
    keys = iter(jax.random.split(key, 32))

    def normal(shape, scale=std):
        return (jax.random.normal(next(keys), shape) * scale).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    layers = {}
    if Lm:
        step = jnp.exp(uniform((Lm, nh), math.log(cfg.time_step_min),
                               math.log(cfg.time_step_max)))
        step = jnp.maximum(step, cfg.time_step_floor)
        layers["mamba"] = {
            "ln_scale": jnp.ones((Lm, H), dt),
            "in_proj": normal((Lm, H, d_inner + conv_dim + nh)),
            "conv_w": uniform((Lm, K, conv_dim), -0.5, 0.5).astype(dt),
            "conv_b": uniform((Lm, conv_dim), -0.5, 0.5).astype(dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "A_log": jnp.log(uniform((Lm, nh), 1.0, 16.0)).astype(dt),
            "D": uniform((Lm, nh), 0.5, 1.5).astype(dt),
            "gate_norm": jnp.ones((Lm, d_inner), dt),
            "out_proj": normal((Lm, d_inner, H), out_scale),
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
            "wg": normal((Le, H, E)),
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
            layers["moe"]["e_bias"] = normal((Le, E), 0.02)
        if "glu" in cfg.activation:
            layers["moe"]["moe_w_gate"] = experts((E, H, F))
        if Fs:
            layers["moe"]["shared_w_in"] = normal((Le, H, Fs))
            layers["moe"]["shared_w_out"] = normal((Le, Fs, H), out_scale)
    if La:
        nq, nkv, ahd = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
        layers["attn"] = {
            "ln_scale": jnp.ones((La, H), dt),
            "wq": normal((La, H, nq * ahd)),
            "wk": normal((La, H, nkv * ahd)),
            "wv": normal((La, H, nkv * ahd)),
            "wo": normal((La, nq * ahd, H), out_scale),
        }
    params = {"tok_embed": normal((V, H)), "layers": layers,
              "final_norm_scale": jnp.ones((H,), dt)}
    if not cfg.tie_embeddings:
        params["lm_head"] = normal((H, V))
    return params


def logical_axes(cfg):
    """Same tree as ``init_params``. The Mamba leaves carry no model-parallel
    axis: the recurrent state is not split over ``tensor`` (the serving
    engine refuses that degree for a model with ``M`` blocks)."""
    layers = {}
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
    if count(cfg, "attn"):
        layers["attn"] = {
            "ln_scale": ("layers", "unmodeled"),
            "wq": ("layers", "embed", "qkv"), "wk": ("layers", "embed", "qkv"),
            "wv": ("layers", "embed", "qkv"), "wo": ("layers", "heads", "embed")}
    axes = {"tok_embed": ("vocab", "embed"), "layers": layers,
            "final_norm_scale": ("unmodeled",)}
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
                         ("shared_w_out", "shared_w_out")):
        if ours in p:
            moe_params[theirs] = p[ours]
    with jax.named_scope("moe"):
        return _moe.moe_ffn(moe_params, h, cfg, rng=rng, train=train)


def _qkv(p, h, cfg):
    """h [B, T, H] -> q [B, T, nq, hd], k, v [B, T, nkv, hd]. The attention
    blocks of a hybrid stack carry no positional embedding (``blocks``
    refuses a config that names one)."""
    from deepspeed_tpu.models.transformer import _wmat
    B, T, _ = h.shape
    hd = cfg.dim_per_head
    return (_wmat(h, p["wq"]).reshape(B, T, cfg.num_heads, hd),
            _wmat(h, p["wk"]).reshape(B, T, cfg.kv_heads, hd),
            _wmat(h, p["wv"]).reshape(B, T, cfg.kv_heads, hd))


def _attn_mixer(p, h, cfg):
    """Causal attention over whole sequences h [B, T, H] -> (out, k, v)."""
    from deepspeed_tpu.models.transformer import _wrow, attention
    B, T, _ = h.shape
    q, k, v = _qkv(p, h, cfg)
    with jax.named_scope("attn"):
        o = attention(q, k, v, causal=True, cfg=cfg)
    return _wrow(o.reshape(B, T, -1), p["wo"]), k, v


def _norm_in(p, x, cfg):
    from deepspeed_tpu.models.transformer import _norm
    return _norm(x, p["ln_scale"], None, cfg)


def _final_norm(params, x, cfg):
    from deepspeed_tpu.models.transformer import _norm
    return _norm(x, params["final_norm_scale"], None, cfg)


def _head(params, x, cfg):
    from deepspeed_tpu.models.transformer import lm_head_logits
    with jax.named_scope("lm_head"):
        return lm_head_logits(_final_norm(params, x, cfg), params)


def _embed(params, ids, cfg):
    with jax.named_scope("embed"):
        return params["tok_embed"][ids].astype(cfg.dtype)


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
    x = _embed(params, input_ids, cfg)
    aux_total = jnp.float32(0.0)
    for i, (kind, j) in enumerate(blocks(cfg)):
        p = _block(params, kind, j)
        with jax.named_scope(f"layer{i}"):
            h = _norm_in(p, x, cfg)
            if kind == "mamba":
                y = mamba.mixer_forward(p, h, cfg)
            elif kind == "moe":
                y, aux = _moe_mixer(p, h, cfg, train=not deterministic,
                                    rng=dropout_rng)
                aux_total = aux_total + aux
            else:
                y = _attn_mixer(p, h, cfg)[0]
            x = x + y
    if return_hidden:
        return _final_norm(params, x, cfg), aux_total
    logits = _head(params, x, cfg)
    return (logits, aux_total) if return_aux else logits


# --------------------------------------------------------------------------
# the paged cache: K/V blocks for the attention blocks, a state per slot for
# the Mamba blocks
# --------------------------------------------------------------------------

def init_paged_cache(cfg, num_blocks: int, block_size: int, dtype=None,
                     max_seqs: Optional[int] = None):
    """``k``, ``v`` (+ int8 scale planes) exactly as ``transformer.
    init_paged_cache`` lays them out, over the ATTENTION blocks only, and
    ``ssm`` / ``conv`` for ``max_seqs`` slots."""
    import dataclasses
    from deepspeed_tpu.models import transformer as tf
    if max_seqs is None:
        raise ValueError("a model with recurrent blocks keeps a state per "
                         "serving slot: init_paged_cache needs max_seqs")
    dtype = dtype or cfg.dtype
    pools = tf.init_paged_cache(
        dataclasses.replace(cfg, block_pattern=None,
                            num_layers=count(cfg, "attn")),
        num_blocks, block_size, dtype=dtype)
    nh, hd, _, N, _, conv_dim, K = mamba.dims(cfg)
    Lm = count(cfg, "mamba")
    pools["ssm"] = jnp.zeros((Lm, max_seqs, nh, hd, N), jnp.float32)
    pools["conv"] = jnp.zeros((Lm, max_seqs, K - 1, conv_dim), dtype)
    return pools


def paged_cache_logical_axes(cfg):
    from deepspeed_tpu.models import transformer as tf
    import dataclasses
    out = tf.paged_cache_logical_axes(
        dataclasses.replace(cfg, block_pattern=None))
    out["ssm"] = (None,) * 5
    out["conv"] = (None,) * 4
    return out


STATE_LEAVES = ("ssm", "conv")


def prefill_paged(params, input_ids, cfg, pools, block_ids,
                  length: Optional[int] = None, slot=None):
    """Prefill ONE request into ``slot``: K/V of the attention blocks into
    the slot's blocks, the Mamba blocks' state after the last true position
    into the slot's rows of the state pool. input_ids [1, P], P a multiple
    of the block size. Returns (last logits [1, V], pools)."""
    from deepspeed_tpu.models.transformer import (_quant_kv,
                                                  _write_prefill_blocks)
    if slot is None:
        raise ValueError("a model with recurrent blocks prefills INTO a "
                         "slot: prefill_paged needs slot=")
    B, P = input_ids.shape
    assert B == 1, "prefill_paged serves one request"
    true_len = jnp.asarray(P if length is None else length, jnp.int32)
    x = _embed(params, input_ids, cfg)                            # [1, P, H]
    pools = dict(pools)
    k_seqs, v_seqs = [], []
    real = jnp.arange(P)[None] < true_len
    with _moe.counted_tokens(real):
        for i, (kind, j) in enumerate(blocks(cfg)):
            p = _block(params, kind, j)
            with jax.named_scope(f"layer{i}"):
                h = _norm_in(p, x, cfg)
                if kind == "mamba":
                    y, state, tail = mamba.mixer_prefill(
                        p, h[0], cfg, true_len)
                    y = y[None]
                    with jax.named_scope("ssm"), jax.named_scope("state_write"):
                        pools["ssm"] = pools["ssm"].at[j, slot].set(state)
                        pools["conv"] = pools["conv"].at[j, slot].set(
                            tail.astype(pools["conv"].dtype))
                elif kind == "moe":
                    y, _ = _moe_mixer(p, h, cfg)
                else:
                    y, k, v = _attn_mixer(p, h, cfg)
                    k_seqs.append(jnp.swapaxes(k, 1, 2))   # [1, nkv, P, hd]
                    v_seqs.append(jnp.swapaxes(v, 1, 2))
                x = x + y
    if k_seqs:
        # the attention blocks' K/V as transformer.prefill_paged's
        # contiguous cache holds them, [La, 1, nkv, P, hd]: one writer
        cache = {"k": jnp.stack(k_seqs), "v": jnp.stack(v_seqs)}
        if cfg.kv_cache_bits == 8:
            (cache["k"], cache["k_scale"]), (cache["v"], cache["v_scale"]) = \
                _quant_kv(cache["k"]), _quant_kv(cache["v"])
        pools.update(_write_prefill_blocks(pools, block_ids, cache,
                                           cfg.kv_cache_bits == 8))
    last = lax.dynamic_index_in_dim(x, true_len - 1, axis=1, keepdims=True)
    return _head(params, last, cfg)[:, 0], pools


def decode_step_paged(params, tokens, cfg, pools, block_tables, seq_lens,
                      active=None, backend: str = "xla", lora=None):
    """One decode step for every slot (``transformer.decode_step_paged``'s
    contract): tokens [S] -> (logits [S, V], pools). Inactive slots compute
    in lockstep; their K/V rows land in the trash block and their recurrent
    state stays as it is."""
    from deepspeed_tpu.models.transformer import (
        _block_at, _paged_attention, _quant_kv, _scatter_rows, _wrow)
    if lora is not None:
        raise NotImplementedError("LoRA adapters on a hybrid model")
    S = tokens.shape[0]
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    if active is None:
        active = jnp.ones((S,), jnp.bool_)
    x = _embed(params, tokens[:, None], cfg)                      # [S, 1, H]
    int8_kv = cfg.kv_cache_bits == 8
    bs = pools["k"].shape[2]
    pools = dict(pools)
    k_rows, v_rows = [], []
    with _moe.counted_tokens(active):
        for i, (kind, j) in enumerate(blocks(cfg)):
            p = _block(params, kind, j)
            with jax.named_scope(f"layer{i}"):
                h = _norm_in(p, x, cfg)
                if kind == "mamba":
                    y, pools["ssm"], pools["conv"] = mamba.mixer_step(
                        p, h[:, 0], cfg, pools["ssm"], pools["conv"], j,
                        active)
                    y = y[:, None]
                elif kind == "moe":
                    y, _ = _moe_mixer(p, h, cfg)
                else:
                    q, k, v = _qkv(p, h, cfg)
                    row_dtype = cfg.dtype if int8_kv else pools["k"].dtype
                    k_row = jnp.swapaxes(k, 1, 2).astype(row_dtype)
                    v_row = jnp.swapaxes(v, 1, 2).astype(row_dtype)
                    sc = (pools["k_scale"], pools["v_scale"]) if int8_kv \
                        else None
                    with jax.named_scope("attn"):
                        o = _paged_attention(
                            q, pools["k"], pools["v"], block_tables, seq_lens,
                            cfg, kv_row=(k_row, v_row), kv_scale=sc,
                            backend=backend, window=None, layer=j)
                    y = _wrow(o.reshape(S, 1, -1), p["wo"])
                    k_rows.append(k_row[:, :, 0])
                    v_rows.append(v_row[:, :, 0])
                x = x + y
    if k_rows:
        with jax.named_scope("attn"), jax.named_scope("kv_write"):
            blk = jnp.where(active, _block_at(block_tables, seq_lens // bs),
                            0)
            off = jnp.where(active, seq_lens % bs, 0)
            kr, vr = jnp.stack(k_rows), jnp.stack(v_rows)  # [La, S, nkv, hd]
            if int8_kv:
                (kq, ks), (vq, vs) = _quant_kv(kr), _quant_kv(vr)
                rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                rows = {"k": kr.astype(pools["k"].dtype),
                        "v": vr.astype(pools["v"].dtype)}
            pools.update(_scatter_rows(
                {n: pools[n] for n in rows}, blk, off, rows))
    return _head(params, x, cfg)[:, 0], pools
