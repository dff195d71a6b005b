"""The Gated DeltaNet mixer of a hybrid model's ``G`` blocks (``qwen3_next``).

One block is ``h + mixer(RMSNorm(h))`` (the residual and the norm are the
walker's, ``models/hybrid.py``); the mixer, as HF's ``modeling_qwen3_next``
computes it (``Hk`` key heads of ``dk``, ``Hv`` value heads of ``dv``):

    [q | k | v | z] = in_qkvz(u)            # Hk dk | Hk dk | Hv dv | Hv dv
    [b | a] = in_ba(u)                      # Hv | Hv
    [q | k | v] = silu(conv1d([q | k | v])) # depthwise, causal, kernel K, no bias
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)      (float32)
    q = l2norm(q) dk^-1/2;  k = l2norm(k)   # per head, eps 1e-6
    S~ = exp(g_t) S_{t-1};  S_t = S~ + k_t (beta_t (v_t - S~^T k_t))^T
    o_t = S_t^T q_t                         # ops/gated_delta.py
    out = out_proj(RMSNorm_dv(o) w_n . silu(z))   # the norm BEFORE the gate

Key head ``j`` serves value heads ``j R .. j R + R - 1`` (``R = Hv / Hk``).
The stored ``in_qkvz`` keeps the four parts as contiguous column ranges (a
checkpoint interleaves them per key head; an importer would reorder).

Three entry points share ``_project`` / ``_finish``, as ``models/mamba.py``'s
do: ``mixer_forward`` (a whole sequence, no cache), ``mixer_prefill`` (one
padded prompt, from a zero state: positions past the true length take ``g =
0, beta = 0`` and the convolution tail handed on is the last K-1 TRUE rows)
and ``mixer_step`` (one position for every slot over the state pool, in
place). What a slot carries between calls: the float32 state ``[Hv, dk, dv]``
and the last ``K - 1`` rows of ``[q | k | v]`` before the convolution.
"""
import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.mamba import _conv
from deepspeed_tpu.ops.gated_delta import gdn_chunk, gdn_step

L2_EPS = 1e-6


def dims(cfg):
    """(key heads, value heads, key dim, value dim, conv_dim, kernel)."""
    Hk, Hv = cfg.gdn_num_k_heads, cfg.gdn_num_v_heads
    dk, dv = cfg.gdn_head_k_dim, cfg.gdn_head_v_dim
    return Hk, Hv, dk, dv, 2 * Hk * dk + Hv * dv, cfg.conv_kernel


def _project(p, u, cfg):
    """u [..., H] -> (qkv [..., conv_dim], z [..., Hv dv], b, a [..., Hv])."""
    _, Hv, _, _, conv_dim, _ = dims(cfg)
    qkvz = u @ p["in_qkvz"].astype(u.dtype)
    ba = u @ p["in_ba"].astype(u.dtype)
    return qkvz[..., :conv_dim], qkvz[..., conv_dim:], ba[..., :Hv], ba[..., Hv:]


def _gates(p, b, a):
    """(beta, g) float32 [..., Hv]."""
    f32 = jnp.float32
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        a.astype(f32) + p["dt_bias"].astype(f32))
    return jax.nn.sigmoid(b.astype(f32)), g


def _l2norm(x):
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True)
                           + L2_EPS)


def _split(qkv, cfg):
    """The convolved [q | k | v] [..., conv_dim] -> q, k [..., Hk, dk]
    (l2-normed, q scaled), v [..., Hv, dv], in qkv's dtype."""
    Hk, Hv, dk, dv, _, _ = dims(cfg)
    lead, kd = qkv.shape[:-1], Hk * dk
    q = _l2norm(qkv[..., :kd].reshape(lead + (Hk, dk))) * dk ** -0.5
    k = _l2norm(qkv[..., kd:2 * kd].reshape(lead + (Hk, dk)))
    return (q.astype(qkv.dtype), k.astype(qkv.dtype),
            qkv[..., 2 * kd:].reshape(lead + (Hv, dv)))


def _finish(p, o, z, cfg):
    """o [..., Hv, dv] float32, z [..., Hv dv] -> the mixer's output
    [..., H]."""
    with jax.named_scope("gdn"), jax.named_scope("gate_norm"):
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + cfg.norm_eps) * p["gate_norm"].astype(jnp.float32)
        y = o.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(z.dtype) @ p["out_proj"].astype(z.dtype)


def mixer_prefill(p, u, cfg, length):
    """One whole sequence, from a zero state.

    u [T, H] (the block's normed input, padded past ``length``) -> (out
    [T, H], state, tail): the state [Hv, dk, dv] float32 after position
    ``length - 1`` and the K-1 rows of [q | k | v] that precede position
    ``length``."""
    _, Hv, dk, dv, conv_dim, K = dims(cfg)
    T = u.shape[0]
    qkv, z, b, a = _project(p, u, cfg)
    with jax.named_scope("gdn"), jax.named_scope("conv"):
        ext = jnp.concatenate([jnp.zeros((K - 1, conv_dim), qkv.dtype), qkv],
                              axis=0)
        tail = lax.dynamic_slice_in_dim(ext, length, K - 1, axis=0)
        q, k, v = _split(_conv(p, ext, cfg), cfg)
    # a pad position must not move the state
    real = (jnp.arange(T) < length)[:, None]
    beta, g = _gates(p, b, a)
    beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
    with jax.named_scope("gdn"), jax.named_scope("chunk"):
        o, state = gdn_chunk(q, k, v, g, beta,
                             jnp.zeros((Hv, dk, dv), jnp.float32),
                             chunk=cfg.gdn_chunk)
    return _finish(p, o, z, cfg), state, tail


def mixer_forward(p, u, cfg):
    """u [B, T, H] -> [B, T, H], nothing kept."""
    return jax.vmap(lambda ub: mixer_prefill(
        p, ub, cfg, ub.shape[0])[0])(u)


def mixer_step(p, u, cfg, state_pool, conv_pool, layer, active):
    """One position for every slot.

    u [S, H], state_pool [Lg, S, Hv, dk, dv] float32, conv_pool [Lg, S,
    K-1, conv_dim], ``layer`` this block's index among the ``G`` blocks (an
    int, or traced where the walk scans the pattern's repeats), active [S]
    bool -> (out [S, H], state_pool, conv_pool). An inactive slot keeps its
    state and its tail."""
    qkv, z, b, a = _project(p, u, cfg)
    with jax.named_scope("gdn"), jax.named_scope("conv"):
        tail = conv_pool[layer]                              # [S, K-1, C]
        ext = jnp.concatenate([tail, qkv[:, None].astype(tail.dtype)], axis=1)
        conv = jax.vmap(lambda e: _conv(p, e, cfg)[0])(ext.astype(qkv.dtype))
        q, k, v = _split(conv, cfg)
        conv_pool = conv_pool.at[layer].set(
            jnp.where(active[:, None, None], ext[:, 1:], tail))
    beta, g = _gates(p, b, a)
    beta = jnp.where(active[:, None], beta, 0.0)
    g = jnp.where(active[:, None], g, 0.0)
    with jax.named_scope("gdn"), jax.named_scope("step"):
        o, state_pool = gdn_step(state_pool, layer, q, k, v, g, beta)
    return _finish(p, o, z, cfg), state_pool, conv_pool
