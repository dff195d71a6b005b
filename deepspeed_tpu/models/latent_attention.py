"""Multi-head LATENT attention (DeepSeek-V2/V3; ``glm4_moe_lite``,
``xing4_0``): the ``L`` mixer of a hybrid stack (``models/hybrid.py``).

One block, ``h`` its normed input [.., H]::

    c_q = RMSNorm(h W_qa)                      # q_lora_rank
    q   = c_q W_qb  -> heads x (nope | rope)   # qk_nope_head_dim | qk_rope_head_dim
    [c_kv | k_r] = h W_kva                     # kv_lora_rank | qk_rope_head_dim
    c   = RMSNorm(c_kv);  k_r: ONE rotary key a token, shared by every head
    rotary (all qk_rope_head_dim dims) on q's rope part and k_r

The rotary TABLE is the kind's (``hybrid.rope_table(cfg, "latent")``): plain
``rope_theta``, or the table the config states (``rope_tables``: YaRN's
stretched frequencies, cos and sin times its ``attention_factor``). The softmax
scale is ``cfg.attn_scale`` where stated — the importer of a YaRN model puts
``(nope + rope)^-1/2 x mscale(factor, mscale_all_dim)^2`` there, DeepSeek-V3's
rule — else ``(nope + rope)^-1/2``; BOTH orders below read the same one.
``v_head_dim`` is V's OWN width: equal to nope + rope (``glm4_moe_lite``, 256)
or narrower (DeepSeek-V3's and ``xing4_0``'s 128 beside keys of 192).

What a token keeps is the ROW ``[c | rope(k_r)]`` (``cfg.latent_row_width``
values, stored in whole lane tiles: ``stored_width``): one plane a block, both
K and V, in the block pool (``latent`` [planes, NB, block, stored width]: it
is paged like K/V and belongs to no slot). Two
orders of the same arithmetic, ``W_kvb`` [kv_lora_rank, heads x (nope | v)]:

- EXPANDED (``mixer_forward``: whole sequences — the forward, a prompt's
  prefill): ``[k_nope | v] = c W_kvb`` per head, ``k = [k_nope | k_r]``,
  ordinary causal attention at that scale over heads x (nope + rope) in q.k
  and heads x v in P V — the flash kernel where it would run: a shared
  prefill row's forward keeps V at its own width, the differentiable call
  pads it (``ops/flash_attention.py``) — then ``W_o`` [heads x v, H].
- ABSORBED (``mixer_step``: one token a slot against the pool): with ``W_kvb``
  split per head into ``W_uk`` [nope, rank] and ``W_uv`` [rank, v], ``score =
  (q_nope W_uk) . c + q_rope . k_r`` and ``o = (P c) W_uv``: every head reads
  the SAME row, once, and nothing a head wide is ever stored or expanded.

``latent_read`` is the read of the pool: the kernel of ``ops/latent_decode.py``
where the engine's price chose it, else its XLA form, which works on the flat list
of a round's live blocks as ``transformer._paged_list_attention`` does (the
gather sized by what the slots hold, one softmax a slot through the per-slot
view), with ONE "kv head", every query head a row of the same contraction,
and V no array of its own: P is contracted with the whole gathered row and
the latent's columns are taken from the RESULT (an eighth more products on a
read that is bound by its bytes, and no second copy of what was gathered).
"""
import math

import jax
import jax.numpy as jnp

SCOPE_Q, SCOPE_READ, SCOPE_UP = "latent_q", "latent_read", "latent_up"
LANES = 128


def stored_width(cfg) -> int:
    """Lanes a row takes in the pool: ``cfg.latent_row_width`` rounded up to
    whole 128-lane tiles (576 -> 640), the rest zeros. The chip's tiled
    layout pads a 576-wide minor dim to 640 lanes anyway — and, left to
    itself, stores a leaf whose LAST extent is off the 128 grid with another
    dim innermost (the block index: a block's rows scattered over the leaf,
    and the whole leaf relayouted around every read and write; found by a
    compile for the described v5e). A leaf that states the 640 is stored
    token-major, as written."""
    return -(-cfg.latent_row_width // LANES) * LANES


def as_stored(x, cfg):
    """x [..., row width] -> [..., stored width], zeros in the pad lanes."""
    pad = stored_width(cfg) - x.shape[-1]
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def dims(cfg):
    """(heads, nope, rope, v, q rank, kv rank) of an "L" block."""
    out = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
           cfg.v_head_dim, cfg.q_lora_rank, cfg.kv_lora_rank)
    if not all(out):
        raise ValueError(
            "a latent-attention (L) block needs q_lora_rank, kv_lora_rank, "
            f"qk_nope_head_dim, qk_rope_head_dim and v_head_dim; got {out[1:]}")
    return out


def leaf_shapes(cfg) -> dict:
    """{leaf: shape} of ONE block's matrices and norm scales."""
    H = cfg.hidden_size
    nq, dn, dr, dv, rq, rkv = dims(cfg)
    return {"wq_a": (H, rq), "q_a_norm": (rq,), "wq_b": (rq, nq * (dn + dr)),
            "wkv_a": (H, rkv + dr), "kv_a_norm": (rkv,),
            "wkv_b": (rkv, nq * (dn + dv)), "wo": (nq * dv, H)}


def _rotary(x, positions, cfg):
    from deepspeed_tpu.models.hybrid import rope_table
    from deepspeed_tpu.models.transformer import rotary_embed
    table = rope_table(cfg, "latent")
    return rotary_embed(x, positions, table.theta, None,
                        cfg.rotary_interleaved, table)


def _project(p, h, cfg, positions):
    """h [B, T, H] at ``positions`` [B, T] -> (q_nope [B, T, heads, nope],
    q_rope [B, T, heads, rope], the cache row [B, T, rank + rope])."""
    from deepspeed_tpu.models.transformer import _rms_whole, _wmat
    nq, dn, dr, _, _, rkv = dims(cfg)
    B, T, _ = h.shape
    with jax.named_scope("attn"), jax.named_scope(SCOPE_Q):
        c_q = _rms_whole(_wmat(h, p["wq_a"]), p["q_a_norm"], cfg.norm_eps)
        q = _wmat(c_q, p["wq_b"]).reshape(B, T, nq, dn + dr)
        q_rope = _rotary(q[..., dn:], positions, cfg)
        kv = _wmat(h, p["wkv_a"])
        c = _rms_whole(kv[..., :rkv], p["kv_a_norm"], cfg.norm_eps)
        k_r = _rotary(kv[..., None, rkv:], positions, cfg)[:, :, 0]
    return q[..., :dn], q_rope, jnp.concatenate([c, k_r], axis=-1)


def mixer_forward(p, h, cfg, positions=None, segment_ids=None):
    """EXPANDED: causal attention over whole sequences h [B, T, H] -> (out
    [B, T, H], the cache rows [B, T, rank + rope]). ``positions`` [B, T]
    (default 0..T-1) and ``segment_ids`` [B, T] are a packed row's."""
    from deepspeed_tpu.models.transformer import _wmat, _wrow, attention
    nq, dn, dr, dv, _, rkv = dims(cfg)
    B, T, _ = h.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    q_nope, q_rope, row = _project(p, h, cfg, positions)
    with jax.named_scope("attn"):
        with jax.named_scope(SCOPE_UP):
            kv = _wmat(row[..., :rkv], p["wkv_b"]).reshape(B, T, nq, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(row[:, :, None, rkv:], (B, T, nq, dr))],
                axis=-1)
        o = attention(jnp.concatenate([q_nope, q_rope], axis=-1), k,
                      kv[..., dn:], causal=True, cfg=cfg,
                      segment_ids=segment_ids)
    return _wrow(o.reshape(B, T, nq * dv), p["wo"]), row


def mixer_step(p, h, cfg, pool, tables, seq_lens, layer, backend="xla"):
    """ABSORBED: one token a slot, h [S, 1, H] at position ``seq_lens[s]``,
    against plane ``layer`` of the WHOLE latent pool leaf [planes, NB, block,
    stored width] through ``tables`` (a ``BlockList`` or [S, MB]) -> (out [S,
    1, H], the token's row as stored [S, stored width], folded into the same
    softmax and written by the caller afterwards)."""
    from deepspeed_tpu.models.transformer import _wrow
    nq, dn, dr, dv, _, rkv = dims(cfg)
    S = h.shape[0]
    q_nope, q_rope, row = _project(p, h, cfg, seq_lens[:, None])
    w = p["wkv_b"].reshape(rkv, nq, dn + dv).astype(h.dtype)
    sm = cfg.attn_scale if cfg.attn_scale is not None \
        else 1.0 / math.sqrt(dn + dr)
    with jax.named_scope("attn"):
        with jax.named_scope(SCOPE_Q):      # q_nope W_uk: the keys' side of W_kvb
            q_lat = jnp.einsum("shn,chn->shc", q_nope[:, 0], w[..., :dn])
            q_lat = as_stored(jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1),
                              cfg)
        with jax.named_scope(SCOPE_READ):
            row = as_stored(row[:, 0].astype(pool.dtype), cfg)
            o_lat = latent_read(q_lat, pool, tables, seq_lens, row, layer,
                                sm, rkv, backend)
        with jax.named_scope(SCOPE_UP):     # (P c) W_uv: the values' side
            o = jnp.einsum("shc,chv->shv", o_lat, w[..., dn:])
    return _wrow(o.reshape(S, 1, nq * dv), p["wo"]), row


def latent_read(q, pool, tables, index, row, layer, sm: float, rank: int,
                backend: str = "xla"):
    """Softmax(q . rows) x the rows' first ``rank`` columns (the latent),
    every head against the same rows: q [S, heads, width] -> [S, heads, rank].
    pool: the whole leaf [planes, NB, block, width], ``layer`` the plane;
    index [S] the rows of each slot in the pool; row [S, width] the fresh
    row, not in the pool yet, folded into the softmax. backend="pallas"
    (rectangular tables [S, MB], a 16-bit pool): the kernel
    ``ops/latent_decode.latent_decode``; else the XLA list read below."""
    from deepspeed_tpu.models.transformer import (BlockList, _as_block_list,
                                                  _gather_blocks)
    if backend == "pallas" and not isinstance(tables, BlockList) \
            and pool.dtype.itemsize == 2:
        from deepspeed_tpu.ops.latent_decode import latent_decode
        return latent_decode(q, pool, tables, index, layer, row, rank=rank,
                             sm_scale=sm)
    blocks = _as_block_list(tables)
    S, Nq, Wd = q.shape
    R, W = blocks.where.shape[0], blocks.inv.shape[1]    # runs; a slot's
    with jax.named_scope("kv_gather"):
        g = _gather_blocks(pool, blocks.ids, layer)      # [R * c, block, Wd]
    bs = blocks.run * g.shape[1]                         # positions of a run
    g = g.reshape(R, bs, Wd)
    T = W * bs
    # a padding run has no slot: it reads the last slot's query and nothing
    # looks at what it gives (no place of `inv` names it)
    slot = jnp.minimum(blocks.where // W, S - 1)
    held = (blocks.inv < R)[:, :, None, None]
    inv = jnp.minimum(blocks.inv, R - 1)
    scores = jnp.einsum("nhd,ntd->nht", q[slot], g,
                        preferred_element_type=jnp.float32)
    scores = jnp.take(scores, inv, axis=0)               # [S, W, heads, bs]
    scores = scores.transpose(0, 2, 1, 3).reshape(S, Nq, T) * sm
    index = jnp.asarray(index, jnp.int32)[:, None]
    # rows at >= index are stale, another request's, the trash block's or no
    # block's; the token's own logit comes from the fresh row
    keep = jnp.arange(T)[None, :] < index
    scores = jnp.where(keep[:, None, :], scores, -1e30)
    s_self = jnp.einsum("shd,sd->sh", q, row.astype(q.dtype),
                        preferred_element_type=jnp.float32) * sm
    probs = jax.nn.softmax(
        jnp.concatenate([scores, s_self[..., None]], axis=-1), axis=-1)
    pp = probs[..., :T].astype(q.dtype).reshape(S, Nq, W, bs)
    pp = jnp.take(pp.transpose(0, 2, 1, 3).reshape(S * W, Nq, bs),
                  blocks.where, axis=0, mode="clip")     # [R, heads, bs]
    acc = jnp.einsum("nht,ntd->nhd", pp, g,
                     preferred_element_type=jnp.float32)
    out = jnp.sum(jnp.where(held, jnp.take(acc, inv, axis=0), 0), axis=1)
    out = out + probs[..., T:] * row.astype(jnp.float32)[:, None, :]
    return out[..., :rank].astype(q.dtype)
