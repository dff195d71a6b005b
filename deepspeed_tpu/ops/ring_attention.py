"""Ring attention: sequence/context parallelism over the `seq` mesh axis.

The reference has NO sequence parallelism at this snapshot (SURVEY §2.7 —
long sequences are handled only by block-sparse kernels + activation
partitioning), but it is a first-class target for the TPU build: activations
are sharded along the sequence dim, and attention exchanges K/V shards around
the ring with `lax.ppermute` while accumulating online-softmax partials —
K/V transfer overlaps with the current block's compute (XLA schedules the
collective-permute concurrently), so attention scales to sequences that
don't fit one chip's HBM.

Causality across shards is handled at block granularity: a K/V shard wholly
in the future contributes nothing (its contribution is masked), the diagonal
shard applies the intra-block triangular mask, and wholly-past shards are
unmasked.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_off, k_off, causal, sm_scale):
    """One q-shard vs one k/v-shard with global-position causal masking.
    q: [B, Sq, N, D], k/v: [B, Sk, N, D]. Returns (scores_max m [B,N,Sq,1],
    exp-sum l [B,N,Sq,1], weighted acc [B,Sq,N,D]) partials."""
    B, Sq, N, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bsnd,btnd->bnst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                      # [B,N,Sq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bnst,btnd->bsnd", p, v.astype(jnp.float32))
    return m, l, acc


def ring_attention_local(q, k, v, *, axis_name: str = "seq",
                         causal: bool = True,
                         sm_scale: Optional[float] = None,
                         axis_size: Optional[int] = None):
    """Call INSIDE shard_map: q/k/v are the local sequence shards
    [B, S_local, N, D]; returns the local output shard. ``axis_size``
    is the static ring size — pass it on jax versions without
    ``lax.axis_size`` (the ppermute table must be built from a Python
    int either way)."""
    B, Sl, N, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    sp = axis_size if axis_size is not None else lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    q_off = my * Sl
    # send k/v to the NEXT rank each step => at step t we hold shard (my - t)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    m = jnp.full((B, N, Sl, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, N, Sl, 1), jnp.float32)
    acc = jnp.zeros((B, Sl, N, D), jnp.float32)

    def step(t, carry):
        m, l, acc, k_cur, v_cur = carry
        kv_idx = (my - t) % sp
        k_off = kv_idx * Sl
        bm, bl, bacc = _block_attend(q, k_cur, v_cur, q_off, k_off, causal,
                                     sm_scale)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)          # rescale old
        beta = jnp.exp(bm - m_new)          # rescale incoming block
        l_new = l * alpha + bl * beta
        acc_new = acc * jnp.moveaxis(alpha, 1, 2) + \
            bacc * jnp.moveaxis(beta, 1, 2)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return m_new, l_new, acc_new, k_nxt, v_nxt

    m, l, acc, _, _ = lax.fori_loop(0, sp, step, (m, l, acc, k, v))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / jnp.moveaxis(l_safe, 1, 2)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = "seq",
                   causal: bool = True, sm_scale: Optional[float] = None,
                   batch_axes=("data", "fsdp", "expert"),
                   heads_axis: str = "tensor"):
    """SPMD entry: q/k/v are GLOBAL [B, S, N, D] arrays; full-manual
    shard_map (this jax version's partial-auto mode rejects sharded auto
    axes): batch over dp axes, sequence over `axis_name`, heads over
    `tensor` (TP attention layout), head_dim replicated. Requires pipe=1
    (ring attention inside a pipelined stage would need nested manual
    meshes)."""
    spec = P(batch_axes, axis_name, heads_axis, None)
    local = functools.partial(ring_attention_local, axis_name=axis_name,
                              causal=causal, sm_scale=sm_scale,
                              axis_size=int(mesh.shape[axis_name]))
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
