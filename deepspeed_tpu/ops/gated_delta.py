"""The gated delta rule: a chunked form over a prompt and a one-step update
for decode, each a Pallas TPU kernel with the plain ``jax.numpy`` form beside
it (the CPU runs that one; the tests run both and hold them to a sequential
scan).

Per value head (``dk`` = key dim, ``dv`` = value dim), with ``g_t <= 0`` and
``0 <= beta_t <= 1``, the state ``S in R^{dk x dv}`` float32:

    S~  = exp(g_t) S_{t-1}
    S_t = S~ + k_t (beta_t (v_t - S~^T k_t))^T
    o_t = S_t^T q_t

``ops/ssm.py``'s recurrence ADDS ``dt x (x) B`` to the decayed state; this one
first subtracts what the state already predicts for ``k_t``. Everything
around it (projections, convolution, the l2 norm of q and k, gates, the output
norm) is the caller's (``models/gated_deltanet.py``). Key head ``j`` serves
value heads ``j R .. j R + R - 1`` (``R`` = value heads / key heads). A
position with ``g = 0`` and ``beta = 0`` leaves the state exactly as it is
(``1 S + k 0``): that is how a prompt bucket's pad positions and a decode
step's inactive slots are kept out of the state.

**The chunk form** (``gdn_chunk``, kernel ``%gdn_chunk``): the sequence is cut
into chunks of ``Q`` positions. With ``c_t`` the inclusive cumulative sum of
``g`` inside a chunk and ``G[t, s] = exp(c_t - c_s)``:

    A  = -tril_strict((beta k) k^T . G)        T = (I - A)^-1
    U  = T (beta v)                             W = T (beta k . exp(c))
    V' = U - W S_in
    O  = (q . exp(c)) S_in + tril(q k^T . G) V'
    S_out = exp(c_Q) S_in + (k . exp(c_Q - c))^T V'

Inside a chunk the correction is a triangular SOLVE, not a product. ``T`` is
computed in float32 by forward substitution in blocks
(``unit_lower_inverse``): the inverse of a 2b x 2b unit lower-triangular
block from those of its two b x b diagonal blocks, ``T21 = T22 A21 T11`` —
six doublings for a chunk of 64, every product at the highest precision, on
whole ``Q x Q`` tiles with ``A`` masked to the corners a doubling joins (a
Neumann product ``(I + A)(I + A^2)...`` is the same matrix on paper and
cancels terms of 1e4 against each other when neighbouring keys are alike;
the doublings as XLA products on the small blocks themselves cost 5-15 s of
the TPU compiler's time a layer, so the kernel does them).
XLA takes the cumulative sum of ``g`` inside each chunk and lays the heads
out first; the kernel forms the decay factors from differences of those sums
(BEFORE any rounding), ``A``, the solve and the six matrix products of a
(head, chunk), and carries the state from chunk to chunk in float32. The
``jax.numpy`` form computes the same operands outside (``chunk_operands``).

**The step** (``gdn_step``, kernel ``%gdn_step``): one position for every slot
of a serving batch over the state pool ``[layers, slots, heads, dk, dv]``
float32, read and written IN PLACE (``input_output_aliases``; the layer is a
coordinate of the block index, a prefetched scalar, so no layer's slice is
ever copied). A step is
bound by the state's bytes: read once, written once.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# value heads a grid step updates: 16 x 128 x 128 float32 = 1 MiB of state in
# and as much out (the step), 8 heads' rows of a chunk (the chunk form)
STEP_HEADS = 16
CHUNK_HEADS = 8

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# the chunk form's operands in jax.numpy (float32)
# --------------------------------------------------------------------------

def unit_lower_inverse(A):
    """``(I - A)^-1`` for ``A`` [..., Q, Q] strictly lower-triangular, Q a
    power of two, float32: forward substitution by blocks, on whole ``Q x
    Q`` tiles. ``T`` starts as the identity; the doubling that joins blocks
    of size b adds ``T A_b T`` with ``A_b`` the entries of ``A`` in the
    lower-left b x b corner of each 2b x 2b diagonal block (``T`` is block
    diagonal until then, so the product lands in those corners and nowhere
    else: ``T21 = T22 A21 T11``). The kernel calls it on one tile, the
    ``jax.numpy`` form on all of them."""
    Q = A.shape[-1]
    if Q & (Q - 1):
        raise ValueError(f"chunk {Q}: a power of two")
    row = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mm = functools.partial(jnp.matmul, preferred_element_type=_F32,
                           precision=_HIGHEST)
    T = jnp.broadcast_to((row == col).astype(_F32), A.shape)
    b = 1
    while b < Q:
        corner = ((row // (2 * b) == col // (2 * b))
                  & ((row // b) % 2 == 1) & ((col // b) % 2 == 0))
        T = T + mm(mm(T, jnp.where(corner, A, 0.0)), T)
        b *= 2
    return T


def chunk_operands(q, k, v, g, beta, Q: int):
    """The chunk form's operands from one sequence, T a multiple of Q.

    q, k [T, Hk, dk] (compute dtype; q scaled, both l2-normed), v [T, Hv,
    dv], g, beta [T, Hv] float32 -> (A [Hv, T/Q, Q, Q] float32, then in the
    compute dtype QK [Hv, T/Q, Q, Q], bv [Hv, T/Q, Q, dv], bkc, qc [Hv, T/Q,
    Q, dk], kdT [Hv, T/Q, dk, Q], and d_all [Hv, T/Q] float32): ``A``,
    ``tril(q k^T . G)``, ``beta v``, ``beta k . exp(c)``, ``q . exp(c)``,
    ``(k . exp(c_Q - c))^T`` and ``exp(c_Q)`` of the module docstring."""
    T, Hk, dk = k.shape
    Hv, dt = v.shape[1], v.dtype
    R, nc = Hv // Hk, T // Q

    def heads_first(a, H):                                # [T, H, d] -> [H, nc, Q, d]
        return jnp.swapaxes(a, 0, 1).reshape(H, nc, Q, a.shape[-1])

    qh, kh, vh = heads_first(q, Hk), heads_first(k, Hk), heads_first(v, Hv)
    gh = g.astype(_F32).T.reshape(Hv, nc, Q)
    bh = beta.astype(_F32).T.reshape(Hv, nc, Q)
    c = jnp.cumsum(gh, axis=-1)
    seg = c[..., :, None] - c[..., None, :]               # [Hv, nc, t, s]
    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    G = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    # products of the compute dtype's own values, accumulated in float32
    kk = jnp.einsum("hctd,hcsd->hcts", kh, kh, preferred_element_type=_F32)
    qk = jnp.einsum("hctd,hcsd->hcts", qh, kh, preferred_element_type=_F32)
    kk, qk = jnp.repeat(kk, R, axis=0), jnp.repeat(qk, R, axis=0)
    strict = jnp.arange(Q)[:, None] > jnp.arange(Q)[None, :]
    A = jnp.where(strict, -(bh[..., :, None] * kk * G), 0.0)
    ec = jnp.exp(c)
    kv = jnp.repeat(kh, R, axis=0).astype(_F32)           # [Hv, nc, Q, dk]
    qv = jnp.repeat(qh, R, axis=0).astype(_F32)
    bv = vh.astype(_F32) * bh[..., None]
    bkc = kv * (bh * ec)[..., None]
    qc = qv * ec[..., None]
    kd = kv * jnp.exp(c[..., -1:] - c)[..., None]
    return (A, (qk * G).astype(dt), bv.astype(dt), bkc.astype(dt),
            qc.astype(dt), jnp.swapaxes(kd, -1, -2).astype(dt),
            jnp.exp(c[..., -1]))


# --------------------------------------------------------------------------
# the chunk form
# --------------------------------------------------------------------------

def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, cb_ref, crow_ref, s0_ref,
                  o_ref, sf_ref, st_ref, *, heads: int):
    """One (block of heads, chunk): q, k [heads, Q, dk], k^T [heads, dk, Q],
    v [heads, Q, dv], (c, beta) as columns [heads, Q, 2], c as a row [heads,
    1, Q], S0 [heads, dk, dv] -> o [heads, Q, dv] and the state after the
    last chunk. ``st_ref``: the carried state. Everything of the module
    docstring's chunk form but the cumulative sum is computed here."""
    c_i = pl.program_id(1)

    @pl.when(c_i == 0)
    def _load():
        st_ref[...] = s0_ref[...].astype(_F32)

    dt = v_ref.dtype
    Q = q_ref.shape[1]
    dot = functools.partial(jnp.dot, preferred_element_type=_F32)
    nt = (((1,), (1,)), ((), ()))                      # contract last dims
    row = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)

    def head(i, carry):
        q, k = q_ref[i], k_ref[i]
        c, beta = cb_ref[i][:, 0:1], cb_ref[i][:, 1:2]             # [Q, 1]
        crow = crow_ref[i]                                         # [1, Q]
        c_all = crow[:, Q - 1:Q]                                   # [1, 1]
        G = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, c - crow,
                                                    0.0)), 0.0)
        kk = lax.dot_general(k, k, nt, preferred_element_type=_F32)
        qk = lax.dot_general(q, k, nt, preferred_element_type=_F32)
        t = unit_lower_inverse(
            jnp.where(row > col, -(beta * kk * G), 0.0)).astype(dt)
        ec = jnp.exp(c)
        kf = k.astype(_F32)
        u = dot(t, (v_ref[i].astype(_F32) * beta).astype(dt))      # [Q, dv]
        w = dot(t, (kf * (beta * ec)).astype(dt))                  # [Q, dk]
        s_in = st_ref[i]                                           # [dk, dv]
        s_lo = s_in.astype(dt)
        vp = (u - dot(w.astype(dt), s_lo)).astype(dt)
        o_ref[i] = (dot((q.astype(_F32) * ec).astype(dt), s_lo)
                    + dot((qk * G).astype(dt), vp)).astype(o_ref.dtype)
        kdt = (kt_ref[i].astype(_F32) * jnp.exp(c_all - crow)).astype(dt)
        st_ref[i] = s_in * jnp.exp(c_all) + dot(kdt, vp)
        return carry

    lax.fori_loop(0, heads, head, None)

    @pl.when(c_i == pl.num_programs(1) - 1)
    def _store():
        sf_ref[...] = st_ref[...]


def _chunk_pallas(q, k, v, g, beta, S0, Q: int, interpret: bool):
    """The kernel over one sequence (T a multiple of Q). XLA lays the
    operands out heads-first, gives each value head its key head's q and k,
    and takes the cumulative sum of ``g`` inside each chunk."""
    T, Hk, dk = k.shape
    Hv, dv = v.shape[1:]
    R, nc = Hv // Hk, T // Q
    hb = CHUNK_HEADS if Hv % CHUNK_HEADS == 0 else Hv

    def heads_first(a):                           # [T, H, d] -> [Hv, nc, Q, d]
        a = jnp.swapaxes(a, 0, 1).reshape(a.shape[1], nc, Q, a.shape[-1])
        return a if a.shape[0] == Hv else jnp.repeat(a, R, axis=0)

    qh, kh, vh = heads_first(q), heads_first(k), heads_first(v)
    c = jnp.cumsum(g.astype(_F32).T.reshape(Hv, nc, Q), axis=-1)
    cb = jnp.stack([c, beta.astype(_F32).T.reshape(Hv, nc, Q)], axis=-1)

    def per_chunk(rows, cols):
        return pl.BlockSpec((hb, None, rows, cols), lambda h, c: (h, c, 0, 0))

    state = pl.BlockSpec((hb, dk, dv), lambda h, c: (h, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb),
        grid=(Hv // hb, nc),
        in_specs=[per_chunk(Q, dk), per_chunk(Q, dk), per_chunk(dk, Q),
                  per_chunk(Q, dv), per_chunk(Q, 2), per_chunk(1, Q), state],
        out_specs=[per_chunk(Q, dv), state],
        out_shape=[jax.ShapeDtypeStruct((Hv, nc, Q, dv), _F32),
                   jax.ShapeDtypeStruct((Hv, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_chunk",
    )(qh, kh, jnp.swapaxes(kh, -1, -2), vh, cb, c[:, :, None, :], S0)


def _chunk_jnp(A, QK, bv, bkc, qc, kdT, d_all, S0):
    """The kernel's arithmetic in ``jax.numpy``: the solve, the same six
    products per (head, chunk), a ``lax.scan`` over chunks for the carry."""
    dt = bv.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=_F32)
    Tm = unit_lower_inverse(A).astype(dt)
    U = mm("hcts,hcsd->chtd", Tm, bv)
    W = mm("hcts,hcsk->chtk", Tm, bkc)

    def carry(s, xs):
        u, w, q_c, qk_c, kdt_c, d = xs
        s_lo = s.astype(dt)
        vp = (u - mm("htk,hkd->htd", w.astype(dt), s_lo)).astype(dt)
        o = mm("htk,hkd->htd", q_c, s_lo) + mm("hts,hsd->htd", qk_c, vp)
        return s * d[:, None, None] + mm("hkt,htd->hkd", kdt_c, vp), o

    chunk_first = lambda a: jnp.swapaxes(a, 0, 1)          # noqa: E731
    s_fin, o = lax.scan(carry, S0.astype(_F32),
                        (U, W, chunk_first(qc), chunk_first(QK),
                         chunk_first(kdT), d_all.T))
    return jnp.swapaxes(o, 0, 1), s_fin


def gdn_chunk(q, k, v, g, beta, S0, chunk: int = 64, kernel=None):
    """The recurrence over one sequence.

    q, k [T, Hk, dk] (compute dtype; l2-normed, q scaled), v [T, Hv, dv],
    g [T, Hv] float32 (<= 0; 0 where a position must not move the state),
    beta [T, Hv] float32 (0 there too), S0 [Hv, dk, dv] float32 -> (o [T,
    Hv, dv] float32, final state [Hv, dk, dv] float32). T is padded to a
    multiple of ``chunk`` here (g = beta = 0 there).

    ``kernel``: None picks the Pallas kernel on a TPU and the ``jax.numpy``
    form elsewhere; True forces the kernel (interpret mode off the TPU)."""
    T = q.shape[0]
    Hv, dv = v.shape[1:]
    pad = -T % chunk
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, pad), (0, 0))) for a in (g, beta))
    use_kernel = not _interpret() if kernel is None else kernel
    if use_kernel:
        o, s_fin = _chunk_pallas(q, k, v, g, beta, S0, chunk, _interpret())
    else:
        with jax.named_scope("decays"):
            ops = chunk_operands(q, k, v, g, beta, chunk)
        o, s_fin = _chunk_jnp(*ops, S0)
    o = jnp.swapaxes(o.reshape(Hv, T + pad, dv), 0, 1)               # [T,Hv,dv]
    return (o[:T] if pad else o), s_fin


# --------------------------------------------------------------------------
# the one-step update over the state pool
# --------------------------------------------------------------------------

def _step_kernel(layer_ref, q_ref, k_ref, bk_ref, dec_ref, bv_ref, s_ref,
                 o_ref, so_ref, *, heads: int):
    """One (slot, block of heads): q, k, ``beta k`` and ``exp(g)`` [dk,
    heads] (the head in the LANES, so that a head's column broadcasts over
    the state's lanes), ``beta v`` [heads, 1, dv], state [heads, dk, dv] ->
    o [heads, 1, dv], state."""
    del layer_ref                                  # used by the index maps
    for h in range(heads):
        col = slice(h, h + 1)
        s = s_ref[h] * dec_ref[:, col]                             # [dk, dv]
        u = bv_ref[h] - jnp.sum(s * bk_ref[:, col], axis=0, keepdims=True)
        s = s + k_ref[:, col] * u
        so_ref[h] = s
        o_ref[h] = jnp.sum(s * q_ref[:, col], axis=0, keepdims=True)


def _step_pallas(pool, layer, qv, kv, bk, dec, bv, interpret: bool):
    _, S, H, dk, dv = pool.shape
    hb = STEP_HEADS if H % STEP_HEADS == 0 else H
    nb = H // hb

    def lanes(a):                     # [S, H, dk] -> [S, nb, dk, hb]
        return a.reshape(S, nb, hb, dk).swapaxes(2, 3)

    # the layer rides as a prefetched scalar: a walk that scans the pattern's
    # repeats (models/hybrid.py) hands a traced index
    state = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda s, b, ly: (ly[0], s, b, 0, 0))
    vec = pl.BlockSpec((None, None, dk, hb), lambda s, b, ly: (s, b, 0, 0))
    row = pl.BlockSpec((None, hb, 1, dv), lambda s, b, ly: (s, b, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, nb),
            in_specs=[vec, vec, vec, vec, row, state],
            out_specs=[row, state]),
        out_shape=[jax.ShapeDtypeStruct((S, H, 1, dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gdn_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), lanes(qv), lanes(kv),
      lanes(bk), lanes(dec), bv[:, :, None, :], pool)
    return o[:, :, 0], pool


def _step_jnp(pool, layer, qv, kv, bk, dec, bv):
    s = pool[layer] * dec[..., None]                         # [S, H, dk, dv]
    u = bv - jnp.sum(s * bk[..., None], axis=-2)
    s = s + kv[..., None] * u[..., None, :]
    return (jnp.sum(s * qv[..., None], axis=-2),
            pool.at[layer].set(s.astype(pool.dtype)))


def gdn_step(pool, layer, q, k, v, g, beta, kernel=None):
    """One position for every slot, the state pool updated in place.

    pool [layers, S, Hv, dk, dv] (float32 as served), ``layer`` an int or a
    traced int32 scalar, q, k [S, Hk, dk], v [S, Hv, dv], g, beta [S, Hv] float32 (both 0
    for a slot that must keep its state) -> (o [S, Hv, dv] float32, pool)."""
    Hv, dk = pool.shape[2], pool.shape[3]
    R = Hv // q.shape[1]
    with jax.named_scope("decays"):
        qv = jnp.repeat(q.astype(_F32), R, axis=1)               # [S, Hv, dk]
        kv = jnp.repeat(k.astype(_F32), R, axis=1)
        beta = beta.astype(_F32)[..., None]
        bk, bv = kv * beta, v.astype(_F32) * beta
        dec = jnp.broadcast_to(jnp.exp(g.astype(_F32))[..., None],
                               kv.shape[:2] + (dk,))
    use_kernel = not _interpret() if kernel is None else kernel
    if use_kernel:
        return _step_pallas(pool, layer, qv, kv, bk, dec, bv, _interpret())
    return _step_jnp(pool, layer, qv, kv, bk, dec, bv)


def gdn_sequential(q, k, v, g, beta, S0):
    """The recurrence as written, one position at a time in float32: what
    the tests hold both forms to. Same arguments as ``gdn_chunk``."""
    R = v.shape[1] // q.shape[1]
    qv = jnp.repeat(q.astype(_F32), R, axis=1)
    kv = jnp.repeat(k.astype(_F32), R, axis=1)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkd,hk->hd", s, k_t,
                                              precision=_HIGHEST))
        s = s + k_t[..., None] * u[:, None, :]
        return s, jnp.einsum("hkd,hk->hd", s, q_t, precision=_HIGHEST)

    s_fin, o = lax.scan(step, S0.astype(_F32),
                        (qv, kv, v.astype(_F32), g.astype(_F32),
                         beta.astype(_F32)))
    return o, s_fin
