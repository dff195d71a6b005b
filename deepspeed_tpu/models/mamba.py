"""The Mamba-2 mixer of a hybrid model's ``M`` blocks (``nemotron_h``) and of
the ``P`` blocks' recurrent branch (``falcon_h1``, beside attention on the same
normed input).

One block is ``h + mixer(RMSNorm(h))`` (the residual and the norm are the
walker's, ``models/hybrid.py``); the mixer, as HF's ``modeling_nemotron_h``
computes it:

    z, xBC, dt = split(in_proj(u))          # d_inner | conv_dim | heads
    xBC = silu(conv1d(xBC))                 # depthwise, causal, kernel K, bias
    x, B, C = split(xBC)                    # [heads, P] | [groups, N] x 2
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    out = out_proj(RMSNorm_grouped(y * silu(z)))   # the gate BEFORE the norm

``d_inner = heads x head dim`` (not ``expand x hidden``); head ``h`` reads
group ``h // (heads / groups)``; the gated norm normalises each of the
``groups`` slices of ``d_inner`` by itself and has a weight.

``falcon_h1``'s muP multipliers are static scalars of the projection, not of
the stored tensors: ``in_proj(u * ssm_in_multiplier) * mup``, ``mup`` the five
``ssm_multipliers`` laid over the columns z | x | B | C | dt (``mup_vector``).
A config that states neither (``nemotron_h``) has no multiply for them in its
programs. The mixer's OUTPUT multiplier is the block's (``models/hybrid.py``).

Three entry points share ``_project`` / ``_finish``: ``mixer_forward`` (a
whole sequence, no cache: training-shaped callers and the tests),
``mixer_prefill`` (one padded prompt, from a zero state: positions past the
true length take ``dt = 0`` and the convolution tail handed on is the last
K-1 TRUE rows) and ``mixer_step`` (one position for every slot over the
state pool, in place). What a slot carries between calls: the float32 state
``[heads, P, N]`` and the last ``K - 1`` rows of ``xBC`` before the
convolution.
"""
import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.ops.ssm import ssm_scan, ssm_step


def dims(cfg):
    """(heads, head dim, groups, state size, d_inner, conv_dim, kernel)."""
    nh, hd = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N = cfg.mamba_n_groups, cfg.ssm_state_size
    return nh, hd, G, N, nh * hd, nh * hd + 2 * G * N, cfg.conv_kernel


def mup_vector(cfg):
    """float32 numpy [d_inner + conv_dim + heads]: ``ssm_multipliers`` (five)
    over the input projection's columns z | x | B | C | dt; None where the
    config states none."""
    import numpy as np
    if cfg.ssm_multipliers is None:
        return None
    nh, _, G, N, d_inner, _, _ = dims(cfg)
    if len(cfg.ssm_multipliers) != 5:
        raise ValueError(f"ssm_multipliers {cfg.ssm_multipliers!r}: five, "
                         "for z | x | B | C | dt")
    return np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                     (d_inner, d_inner, G * N, G * N, nh))


def _project(p, u, cfg):
    """u [..., H] -> (z [..., d_inner], xBC [..., conv_dim], dt [..., heads])
    of the input projection."""
    _, _, _, _, d_inner, conv_dim, _ = dims(cfg)
    if cfg.ssm_in_multiplier != 1.0:
        u = u * cfg.ssm_in_multiplier
    zxd = u @ p["in_proj"].astype(u.dtype)
    mup = mup_vector(cfg)
    if mup is not None:
        zxd = zxd * mup.astype(zxd.dtype)
    return (zxd[..., :d_inner], zxd[..., d_inner:d_inner + conv_dim],
            zxd[..., d_inner + conv_dim:])


def _split(xbc, cfg):
    nh, hd, G, N, d_inner, _, _ = dims(cfg)
    lead = xbc.shape[:-1]
    return (xbc[..., :d_inner].reshape(lead + (nh, hd)),
            xbc[..., d_inner:d_inner + G * N].reshape(lead + (G, N)),
            xbc[..., d_inner + G * N:].reshape(lead + (G, N)))


def _dt(p, dt_raw):
    return jax.nn.softplus(dt_raw.astype(jnp.float32)
                           + p["dt_bias"].astype(jnp.float32))


def _finish(p, y, x, z, cfg):
    """y [..., heads, P] float32 (no ``D x`` yet), x the same shape, z
    [..., d_inner] -> the mixer's output [..., H]."""
    _, _, G, _, d_inner, _, _ = dims(cfg)
    y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(y.shape[:-2] + (d_inner,)) * jax.nn.silu(
        z.astype(jnp.float32))
    with jax.named_scope("gate_norm"):
        g = y.reshape(y.shape[:-1] + (G, d_inner // G))
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + cfg.norm_eps)
        y = g.reshape(y.shape) * p["gate_norm"].astype(jnp.float32)
    return y.astype(z.dtype) @ p["out_proj"].astype(z.dtype)


def _conv(p, ext, cfg):
    """ext [K - 1 + T, conv_dim]: the sequence behind its K - 1 earlier rows
    -> silu(conv) [T, conv_dim]. ``conv_w[k]`` multiplies the row K-1-k
    positions back; ``conv_b`` where the mixer has a bias (the Gated DeltaNet
    mixer, ``models/gated_deltanet.py``, has none)."""
    K = cfg.conv_kernel
    T = ext.shape[0] - (K - 1)
    w = p["conv_w"].astype(jnp.float32)
    acc = p["conv_b"].astype(jnp.float32)[None, :] if "conv_b" in p else 0.0
    for k in range(K):
        acc = acc + ext[k:k + T].astype(jnp.float32) * w[k][None, :]
    return jax.nn.silu(acc).astype(ext.dtype)


def mixer_prefill(p, u, cfg, length):
    """One whole sequence, from a zero state (every prefill is a whole
    prompt: chunked prefill and prefix reuse are refused on a model with
    recurrent blocks).

    u [T, H] (the block's normed input, padded past ``length``) -> (out
    [T, H], state, tail): the state [heads, P, N] float32 after position
    ``length - 1`` and the K-1 rows of xBC that precede position
    ``length``."""
    nh, hd, _, N, _, conv_dim, K = dims(cfg)
    T = u.shape[0]
    z, xbc, dt_raw = _project(p, u, cfg)
    with jax.named_scope("ssm"), jax.named_scope("conv"):
        ext = jnp.concatenate([jnp.zeros((K - 1, conv_dim), xbc.dtype), xbc],
                              axis=0)
        tail = lax.dynamic_slice_in_dim(ext, length, K - 1, axis=0)
        x, B, C = _split(_conv(p, ext, cfg), cfg)
    # a pad position must not move the state
    dt = jnp.where((jnp.arange(T) < length)[:, None], _dt(p, dt_raw), 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope("ssm"), jax.named_scope("scan"):
        y, state = ssm_scan(x, dt, A, B, C,
                            jnp.zeros((nh, hd, N), jnp.float32),
                            chunk=cfg.mamba_chunk)
    return _finish(p, y, x, z, cfg), state, tail


def mixer_forward(p, u, cfg):
    """u [B, T, H] -> [B, T, H], nothing kept."""
    return jax.vmap(lambda ub: mixer_prefill(
        p, ub, cfg, ub.shape[0])[0])(u)


def mixer_step(p, u, cfg, ssm_pool, conv_pool, layer: int, active):
    """One position for every slot.

    u [S, H], ssm_pool [Lm, S, heads, P, N] float32, conv_pool
    [Lm, S, K-1, conv_dim], ``layer`` this block's (static) index among the
    ``M`` blocks, active [S] bool -> (out [S, H], ssm_pool, conv_pool). An
    inactive slot keeps its state and its tail."""
    z, xbc, dt_raw = _project(p, u, cfg)
    with jax.named_scope("ssm"), jax.named_scope("conv"):
        tail = conv_pool[layer]                              # [S, K-1, C]
        ext = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], axis=1)
        conv = jax.vmap(lambda e: _conv(p, e, cfg)[0])(ext.astype(xbc.dtype))
        x, B, C = _split(conv, cfg)
        conv_pool = conv_pool.at[layer].set(
            jnp.where(active[:, None, None], ext[:, 1:], tail))
    dt = jnp.where(active[:, None], _dt(p, dt_raw), 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope("ssm"), jax.named_scope("step"):
        y, ssm_pool = ssm_step(ssm_pool, layer, x, dt, A, B, C)
    return _finish(p, y, x, z, cfg), ssm_pool, conv_pool
