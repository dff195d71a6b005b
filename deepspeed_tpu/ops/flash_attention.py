"""Flash attention for TPU in Pallas (fwd + bwd, custom_vjp, GQA-native).

Capability-equivalent of the reference's fused attention kernels
(``csrc/transformer/inference/csrc/softmax.cu`` + context kernels and the
training softmax in ``csrc/transformer/softmax_kernels.cu``), re-designed as a
single online-softmax kernel (the CUDA code materializes the S×S score matrix;
on TPU we never leave VMEM).

Layout: inputs [B, S, N, D] (seq-major like the models), internally
[B, N, S, D]. fp32 accumulation, bf16-friendly.

Blocked-KV grid: the grid has a KV-block dimension (innermost), so only one
[block_k, D] tile of K and V is VMEM-resident at a time and Pallas
double-buffers the next tile's DMA behind the current tile's compute. The
online-softmax state (m, l, acc) is carried across KV steps in VMEM scratch.
Sequence length is therefore bounded by HBM, not VMEM (the previous design
kept the whole [S, D] K/V — and in the backward a [rep, S, D] fp32 block —
resident, capping S at ~8-16k).

Causal masking skips invisible blocks two ways: `pl.when` predication skips
the compute, and the K/V index maps clamp invisible steps to the last visible
block so the pipeline emitter elides their DMAs (same-index fetches are
skipped). Causal attention therefore does ~half the FLOPs and ~half the HBM
traffic of full attention.

GQA is native: when n_q_heads > n_kv_heads the grid runs over KV heads and
each program processes the whole query-head GROUP against one K/V stream —
K/V are never repeated in HBM and their VMEM loads amortize over the group
(the naive path repeats K/V n_q/n_kv times).

A row that holds several sequences (a serving prefill's packed row) has a
forward of its own, ``flash_attention_packed``: ONE call whose walk the
segments bound — per query tile the key tiles from its first query's segment
start to the diagonal, as prefetched scalars — so a shared row costs less
than one causal pass. Forward only.

Backward uses the standard flash decomposition (dQ kernel + joint dK/dV
kernel) with the forward's log-sum-exp residuals; both are blocked the same
way (dQ: KV innermost with dQ in scratch; dK/dV: Q innermost with dK/dV in
scratch).

``fused_backward=True`` folds the delta epilogue (``rowsum(dO * O)``) into
both backward grids: the kernels read O directly and compute delta on-chip
(dQ grid: once per Q block at the first KV step, held in VMEM scratch;
dK/dV grid: recomputed per step — a [rows, D] elementwise-rowsum, noise
next to the step's five matmuls). This removes the separate XLA delta pass
— a full extra read of dO and O plus the [B, N, S, 1] delta tensor's HBM
round-trip per layer per step — so the whole attention backward is two
Pallas grids with no XLA prologue between forward and backward. The
forward also tags its outputs with ``checkpoint_name`` ("flash_out" /
"flash_lse"): every remat policy of models/transformer._remat_policy keeps
them across the fwd/bwd boundary, so no backward replays the full
online-softmax forward kernel under layer-level ``jax.checkpoint``.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
# rep * block_q rows of fp32 state live in VMEM scratch; past ~1024 rows the
# m/l/acc scratch plus the double-buffered Q/KV tiles exceed scoped VMEM
# (measured: rows=2048 fails to compile on v5e at D=64).
MAX_ROWS = 1024
NEG_INF = -1e30
# Floor for the running row-max: keeps exp(s - m) == 0 for fully-masked rows
# (otherwise m == s == NEG_INF makes exp(0) == 1 and a dead row attends
# uniformly to its masked keys). Real scores never get near -1e20.
M_FLOOR = -1e20


def _interpret() -> bool:
    """Pallas interpreter on non-TPU backends (CPU tests)."""
    return jax.default_backend() != "tpu"


def _compiler_params(n_parallel: int):
    """Grid semantics: all dims parallel except the innermost (carries
    scratch state / revisits the output block)."""
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",))


def _pow2_floor(x: int) -> int:
    return 1 << (max(x, 1).bit_length() - 1)


def _pick_blocks(s: int, block_q: int, block_k: int, rep: int = 1):
    # power-of-two blocks: halving then always terminates at a divisor of
    # any s with a pow2 factor (e.g. s % 128 == 0 keeps bk >= 128), instead
    # of degenerating to 1 for non-pow2 requests
    bq = _pow2_floor(min(block_q, s))
    bk = _pow2_floor(min(block_k, s))
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    while rep * bq > MAX_ROWS and bq > 8:
        bq //= 2
    return max(bq, 1), max(bk, 1)


def _causal_mask(s, q_start, k_start, rows, block_k, block_q):
    """rows = rep*block_q stacked row-major by head; row r is query position
    q_start + (r % block_q)."""
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_k), 0) % block_q
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _block_visible(qi, kj, block_q, block_k):
    """True iff KV block kj intersects the causal triangle of Q block qi
    (i.e. last query row >= first key col)."""
    return (qi + 1) * block_q > kj * block_k


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, sm_scale, causal, rep, block_q, block_k):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    num_kv = pl.num_programs(3)
    d = q_ref.shape[-1]
    rows = rep * block_q

    @pl.when(kj == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    visible = _block_visible(qi, kj, block_q, block_k) if causal else True

    @pl.when(visible)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d) * sm_scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi * block_q, kj * block_k, rows, block_k,
                             block_q)
        if m_ref is not None:
            kv_ok = m_ref[0, 0:1, :] > 0
            s = jnp.where(kv_ok, s, NEG_INF)   # [1,bk] broadcasts over rows
        m = m_s[:, 0:1]
        l = l_s[:, 0:1]
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True)),
                            M_FLOOR)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(kj == num_kv - 1)
    def _finalize():
        l = l_s[:, 0:1]
        m = m_s[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[:] / l_safe).reshape(rep, block_q, d).astype(
            o_ref.dtype)
        lse_ref[0, 0] = (m + jnp.log(l_safe)).reshape(rep, block_q, 1)


def _fwd_kernel_nomask(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, **kw):
    _fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref, *scratch, **kw)


def _clamp_kv(i, j, causal, bq, bk):
    """Clamp invisible KV steps to the last visible block: the pipeline
    emitter skips DMAs whose block index equals the previous step's."""
    if causal:
        last_visible = jax.lax.div((i + 1) * bq - 1, bk)
        j = jnp.minimum(j, last_visible)
    return j


def _kv_index_map(causal, bq, bk):
    return lambda b, g, i, j: (b, g, _clamp_kv(i, j, causal, bq, bk), 0)


# The [B, 8, S] key-padding mask is blocked like K/V (Mosaic's lane rule
# requires bk % 128 == 0 for this spec — guaranteed by the wrapper's masked-
# path guard: S % 128 == 0 and block_k >= 128 make _pick_blocks land on a
# multiple of 128).
def _mask_kv_index_map(causal, bq, bk):
    return lambda b, g, i, j: (b, 0, _clamp_kv(i, j, causal, bq, bk))


def _fwd(q, k, v, kv_mask, sm_scale, causal, block_q, block_k):
    B, N, S, D = q.shape
    Nkv = k.shape[1]
    rep = N // Nkv
    bq, bk = _pick_blocks(S, block_q, block_k, rep)
    grid = (B, Nkv, S // bq, S // bk)
    rows = rep * bq

    kv_spec = pl.BlockSpec((1, 1, bk, D), _kv_index_map(causal, bq, bk),
                           memory_space=pltpu.VMEM)
    kern = _fwd_kernel if kv_mask is not None else _fwd_kernel_nomask
    kernel = functools.partial(kern, sm_scale=sm_scale, causal=causal,
                               rep=rep, block_q=bq, block_k=bk)
    # q viewed as [B, Nkv, rep, S, D]: one program owns the whole head group
    qg = q.reshape(B, Nkv, rep, S, D)
    mask_spec = pl.BlockSpec((1, 8, bk), _mask_kv_index_map(causal, bq, bk),
                             memory_space=pltpu.VMEM)
    extra = () if kv_mask is None else (kv_mask,)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rep, bq, D),
                         lambda b, g, i, j: (b, g, 0, i, 0),
                         memory_space=pltpu.VMEM),
            kv_spec, kv_spec,
        ] + ([mask_spec] if kv_mask is not None else []),
        out_specs=[
            pl.BlockSpec((1, 1, rep, bq, D),
                         lambda b, g, i, j: (b, g, 0, i, 0),
                         memory_space=pltpu.VMEM),
            # trailing singleton keeps the (sublane, lane) tile legal
            pl.BlockSpec((1, 1, rep, bq, 1),
                         lambda b, g, i, j: (b, g, 0, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Nkv, rep, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, Nkv, rep, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),   # m (lane-padded)
            pltpu.VMEM((rows, 128), jnp.float32),   # l
            pltpu.VMEM((rows, D), jnp.float32),     # acc
        ],
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
        name="flash_fwd",
    )(qg, k, v, *extra)
    return o.reshape(B, N, S, D), lse.reshape(B, N, S, 1)


# --------------------------------------------------------------------------
# forward, banded: key j visible to query i iff 0 <= i - j < window
# --------------------------------------------------------------------------

def _band_tiles(window: int, bq: int, bk: int, s: int) -> int:
    """Key tiles the band of ONE query tile can touch: its keys span ``bq +
    window - 1`` positions, which start anywhere in a tile."""
    return min(s // bk, (bq + window - 2) // bk + 2)


def _band_first(i, window, bq, bk):
    """The first key tile that query tile ``i``'s band touches."""
    return jax.lax.div(jnp.maximum(i * bq - (window - 1), 0), bk)


def _band_inside(q_lo, k_lo, window, block_q, block_k):
    """Every (query, key) pair of the two tiles is visible: the tile's last
    key at or before the first query, its first key inside the LAST query's
    band: such a tile needs no mask."""
    return ((k_lo + block_k - 1 <= q_lo)
            & (q_lo + block_q - 1 - k_lo < window))


def _band_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                 sm_scale, window, rep, block_q, block_k):
    """``_fwd_kernel`` over the key tiles of a query tile's band alone: step
    ``t`` of the innermost grid dim is key tile ``first + t``. A tile that
    lies wholly inside the band takes the body without a mask, one that the
    band's or the diagonal's edge crosses is masked, one past the diagonal
    is skipped (its DMA too: the index map clamps it to the diagonal's)."""
    qi = pl.program_id(2)
    t = pl.program_id(3)
    d = q_ref.shape[-1]
    rows = rep * block_q
    kj = _band_first(qi, window, block_q, block_k) + t
    q_lo, k_lo = qi * block_q, kj * block_k

    @pl.when(t == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def step(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d) * sm_scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            q_pos = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 0) % block_q
            k_pos = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 1)
            s = jnp.where((q_pos >= k_pos) & (q_pos - k_pos < window), s,
                          NEG_INF)
        m = m_s[:, 0:1]
        l = l_s[:, 0:1]
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True)),
                            M_FLOOR)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    inside = _band_inside(q_lo, k_lo, window, block_q, block_k)
    visible = k_lo <= q_lo + block_q - 1        # not past the diagonal
    pl.when(inside)(lambda: step(False))
    pl.when(visible & jnp.logical_not(inside))(lambda: step(True))

    @pl.when(t == pl.num_programs(3) - 1)
    def _finalize():
        l = l_s[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[:] / l_safe).reshape(rep, block_q, d).astype(
            o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = (m_s[:, 0:1] + jnp.log(l_safe)).reshape(
                rep, block_q, 1)


def _band_kernel_no_lse(q_ref, k_ref, v_ref, o_ref, *scratch, **kw):
    _band_kernel(q_ref, k_ref, v_ref, o_ref, None, *scratch, **kw)


def _fwd_band(q, k, v, sm_scale, window, block_q, block_k,
              with_lse: bool = False):
    """q [B, N, S, D], k / v [B, Nkv, S, D] -> o, causal within the band;
    ``with_lse``: (o, log-sum-exp [B, N, S, 1] float32), what the backward
    kernels take the probabilities from."""
    B, N, S, D = q.shape
    Nkv = k.shape[1]
    rep = N // Nkv
    bq, bk = _pick_blocks(S, block_q, block_k, rep)
    rows = rep * bq

    def kv_index(b, g, i, t):
        last = jax.lax.div((i + 1) * bq - 1, bk)         # the diagonal's tile
        return (b, g, jnp.minimum(_band_first(i, window, bq, bk) + t, last), 0)

    kv_spec = pl.BlockSpec((1, 1, bk, D), kv_index, memory_space=pltpu.VMEM)
    q_spec = pl.BlockSpec((1, 1, rep, bq, D),
                          lambda b, g, i, t: (b, g, 0, i, 0),
                          memory_space=pltpu.VMEM)
    o_shape = jax.ShapeDtypeStruct((B, Nkv, rep, S, D), q.dtype)
    lse_spec = pl.BlockSpec((1, 1, rep, bq, 1),
                            lambda b, g, i, t: (b, g, 0, i, 0),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_band_kernel if with_lse else _band_kernel_no_lse,
                          sm_scale=sm_scale, window=window,
                          rep=rep, block_q=bq, block_k=bk),
        grid=(B, Nkv, S // bq, _band_tiles(window, bq, bk, S)),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec] if with_lse else q_spec,
        out_shape=[o_shape, jax.ShapeDtypeStruct((B, Nkv, rep, S, 1),
                                                 jnp.float32)]
        if with_lse else o_shape,
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),   # m (lane-padded)
            pltpu.VMEM((rows, 128), jnp.float32),   # l
            pltpu.VMEM((rows, D), jnp.float32),     # acc
        ],
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
        name="flash_fwd_band",
    )(q.reshape(B, Nkv, rep, S, D), k, v)
    if with_lse:
        return out[0].reshape(B, N, S, D), out[1].reshape(B, N, S, 1)
    return out.reshape(B, N, S, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_band(q, k, v, sm_scale, window, block_q, block_k):
    return _fwd_band(q, k, v, sm_scale, window, block_q, block_k)


def _flash_band_fwd(q, k, v, sm_scale, window, block_q, block_k):
    o, lse = _fwd_band(q, k, v, sm_scale, window, block_q, block_k,
                       with_lse=True)
    # named as the causal kernel's are: every remat policy keeps them
    # across the forward / backward boundary (`_flash_fwd`)
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _band_step(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_lo, k_lo,
               masked: bool, *, sm_scale, window, rep, block_q, block_k):
    """What both banded backward kernels compute for one (query tile, key
    tile) pair -> (q, k, do, p, ds): the probabilities from the forward's
    log-sum-exp, under the band mask where the tile crosses an edge of it."""
    d = q_ref.shape[-1]
    rows = rep * block_q
    q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d)
    do = do_ref[0, 0].astype(jnp.float32).reshape(rows, d)
    lse = lse_ref[0, 0].reshape(rows, 1)
    delta = delta_ref[0, 0].reshape(rows, 1)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if masked:
        q_pos = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0) % block_q
        k_pos = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        s = jnp.where((q_pos >= k_pos) & (q_pos - k_pos < window), s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return q, k, do, p, p * (dp - delta) * sm_scale


def _band_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                    dq_s, *, window, rep, block_q, block_k, **kw):
    """dQ of one query tile over the key tiles of its band alone: the
    forward's walk (step ``t`` is key tile ``first + t``)."""
    qi, t = pl.program_id(2), pl.program_id(3)
    kj = _band_first(qi, window, block_q, block_k) + t
    q_lo, k_lo = qi * block_q, kj * block_k

    @pl.when(t == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    def step(masked: bool):
        _, k, _, _, ds = _band_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_lo, k_lo,
            masked, window=window, rep=rep, block_q=block_q, block_k=block_k,
            **kw)
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    inside = _band_inside(q_lo, k_lo, window, block_q, block_k)
    visible = k_lo <= q_lo + block_q - 1        # not past the diagonal
    pl.when(inside)(lambda: step(False))
    pl.when(visible & jnp.logical_not(inside))(lambda: step(True))

    @pl.when(t == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = dq_s[:].reshape(rep, block_q, -1).astype(dq_ref.dtype)


def _band_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                     dv_ref, dk_s, dv_s, *, window, rep, block_q, block_k,
                     num_q, **kw):
    """dK and dV of one key tile over the query tiles whose band reaches it
    alone: step ``t`` is query tile ``first + t``, from the tile that holds
    the key tile's first position (the diagonal) to the one that holds its
    last position + window - 1."""
    kj, t = pl.program_id(2), pl.program_id(3)
    qi = jax.lax.div(kj * block_k, block_q) + t
    q_lo, k_lo = qi * block_q, kj * block_k

    @pl.when(t == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def step(masked: bool):
        q, _, do, p, ds = _band_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_lo, k_lo,
            masked, window=window, rep=rep, block_q=block_q, block_k=block_k,
            **kw)
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # a query tile past the sequence's end or wholly past the band of the
    # tile's LAST key sees none of it
    reached = (qi < num_q) & (q_lo - (k_lo + block_k - 1) < window)
    inside = _band_inside(q_lo, k_lo, window, block_q, block_k)
    pl.when(reached & inside)(lambda: step(False))
    pl.when(reached & jnp.logical_not(inside))(lambda: step(True))

    @pl.when(t == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_band_blocks(s: int, window: int, block_q: int, block_k: int,
                     rep: int):
    """Tiles of the banded backward: the forward's query tile, and key tiles
    of at most a quarter of the window (128 at the least) — a tile the band's
    edge crosses is computed whole and masked, and at the forward's 1024 keys
    a window of 1024 would compute two tiles for 1.1 of band."""
    return _pick_blocks(s, block_q, min(block_k, max(128, window // 4)), rep)


def _flash_band_bwd(sm_scale, window, block_q, block_k, residuals, g):
    """Two banded kernels, each over the tiles the band touches alone:
    ``flash_bwd_band_dq`` (a query tile against its key tiles, the forward's
    walk) and ``flash_bwd_band_dkv`` (a key tile against the query tiles that
    see it). The probabilities come from the forward's log-sum-exp; no
    ``[heads, S, S]`` score exists."""
    q, k, v, o, lse = residuals
    B, N, S, D = q.shape
    Nkv = k.shape[1]
    rep = N // Nkv
    bq, bk = _bwd_band_blocks(S, window, block_q, block_k, rep)
    rows = rep * bq
    kw = dict(sm_scale=sm_scale, window=window, rep=rep, block_q=bq,
              block_k=bk)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    qg, dog = (a.reshape(B, Nkv, rep, S, D) for a in (q, g))
    lseg, deltag = (a.reshape(B, Nkv, rep, S, 1) for a in (lse, delta))

    # ---- dQ: (B, Nkv, query tiles, key tiles of the band) ----
    def kv_index(b, g_, i, t):
        last = jax.lax.div((i + 1) * bq - 1, bk)         # the diagonal's tile
        return (b, g_, jnp.minimum(_band_first(i, window, bq, bk) + t, last),
                0)

    def q_index(b, g_, i, t):
        return (b, g_, 0, i, 0)

    kv_blk = pl.BlockSpec((1, 1, bk, D), kv_index, memory_space=pltpu.VMEM)
    grp_blk = pl.BlockSpec((1, 1, rep, bq, D), q_index,
                           memory_space=pltpu.VMEM)
    grp_vec = pl.BlockSpec((1, 1, rep, bq, 1), q_index,
                           memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_band_dq_kernel, **kw),
        grid=(B, Nkv, S // bq, _band_tiles(window, bq, bk, S)),
        in_specs=[grp_blk, kv_blk, kv_blk, grp_blk, grp_vec, grp_vec],
        out_specs=grp_blk,
        out_shape=jax.ShapeDtypeStruct((B, Nkv, rep, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)],
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
        name="flash_bwd_band_dq",
    )(qg, k, v, dog, lseg, deltag)

    # ---- dK/dV: (B, Nkv, key tiles, query tiles that see the key tile) ----
    num_q = S // bq

    def q_of_k(b, g_, j, t):
        # past the last query tile that sees the key tile: stay on it, so
        # that the step's DMA is elided as its compute is skipped
        last = jnp.minimum(jax.lax.div((j + 1) * bk + window - 2, bq),
                           num_q - 1)
        return (b, g_, 0, jnp.minimum(jax.lax.div(j * bk, bq) + t, last), 0)

    grp_q = pl.BlockSpec((1, 1, rep, bq, D), q_of_k, memory_space=pltpu.VMEM)
    grp_q_vec = pl.BlockSpec((1, 1, rep, bq, 1), q_of_k,
                             memory_space=pltpu.VMEM)
    kv_out = pl.BlockSpec((1, 1, bk, D), lambda b, g_, j, t: (b, g_, j, 0),
                          memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_band_dkv_kernel, num_q=num_q, **kw),
        grid=(B, Nkv, S // bk, min(num_q, (bk + window - 2) // bq + 2)),
        in_specs=[grp_q, kv_out, kv_out, grp_q, grp_q_vec, grp_q_vec],
        out_specs=[kv_out, kv_out],
        out_shape=[jax.ShapeDtypeStruct((B, Nkv, S, D), q.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32)] * 2,
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
        name="flash_bwd_band_dkv",
    )(qg, k, v, dog, lseg, deltag)
    return dq.reshape(B, N, S, D), dk, dv


_flash_band.defvjp(_flash_band_fwd, _flash_band_bwd)


# --------------------------------------------------------------------------
# forward, packed: several sequences share the row, each attends to itself.
# Forward only (no custom_vjp, no log-sum-exp) and a body of its own: a
# serving prefill's, whose count of segments is data
# --------------------------------------------------------------------------

def _packed_blocks(s: int, rep: int = 1):
    """The (query, key) tile of the packed forward at a row of ``s``: as
    many query rows as the scratch holds (``MAX_ROWS`` over the stacked
    heads) against 1024 keys. Measured on the v5e at 20 heads x 256 and
    rows of 2048-4096 (PERF.md, PR 53): 1024 x 1024 walks the pairs of 512
    x 1024 in half the steps, 5-11 % faster alone and shared; 512-key tiles
    walk fewer empty pairs at the diagonal and lose 7-20 % to their steps."""
    return _pick_blocks(s, MAX_ROWS, DEFAULT_BLOCK_K, rep)


def _diagonal_tile(i, bq: int, bk: int):
    """The key tile that holds the last position of query tile ``i``."""
    return ((i + 1) * bq - 1) // bk


def _packed_bounds(seg_start, bq: int, bk: int):
    """What bounds the packed walk, a query tile at a time. ``seg_start``
    [..., S]: for every position the first position of its segment (so it
    never falls along the row) — a numpy array on the host, a jax array in
    the program: operators only. Returns ``(lo, full)`` [..., S // bq]: the
    first key tile any query of the tile sees, the tile of its FIRST
    query's segment start (the last is the diagonal's), and the first key
    position from which every query of the tile sees a key, its LAST
    query's segment start: a key tile from there on and wholly under the
    diagonal needs no mask."""
    return seg_start[..., ::bq] // bk, seg_start[..., bq - 1::bq]


def packed_walk(starts, lengths, s: int, rep: int = 1):
    """Plain integers on the host: (the key tiles the packed forward walks
    for ONE kv head of a row of ``s`` tokens that holds the segments
    ``[starts[k], starts[k] + lengths[k])``, the tiles of one causal pass
    over the whole row). A segment of length 0 is not there, and the rows
    behind a segment up to the next start are its pad rows
    (``transformer._packed_row``); a row of one segment walks the causal
    pass."""
    import numpy as np
    bq, bk = _packed_blocks(s, rep)
    lo, hi = _packed_tiles(starts, lengths, s, bq, bk)
    return int(np.sum(hi - lo + 1)), int(np.sum(hi + 1))


def _packed_tiles(starts, lengths, s: int, bq: int, bk: int):
    """(first, last) key tile of every query tile of that row, numpy [s //
    bq] each: what the call's prefetched bounds and its diagonal let
    through."""
    import numpy as np
    seg_start = np.zeros(s, np.int64)
    for start, n in zip(starts, lengths):
        if n > 0:
            seg_start[int(start):] = int(start)
    return (_packed_bounds(seg_start, bq, bk)[0],
            _diagonal_tile(np.arange(s // bq), bq, bk))


def _packed_kernel(lo_ref, full_ref, q_ref, k_ref, v_ref, end_ref, o_ref,
                   m_s, l_s, acc_s, *, sm_scale, rep, block_q, block_k):
    """The online softmax over the key tiles ``lo[qi] .. diagonal`` of query
    tile ``qi`` alone (the others are skipped, their DMAs too: the index
    maps clamp them into that range). Key ``k`` is visible to the queries
    ``k <= q < end[k]``, its segment's rows from itself on: a tile under
    the diagonal that starts at or behind ``full[qi]`` is wholly visible
    and takes the body without a mask; one that the diagonal or a segment's
    edge crosses is masked pair by pair."""
    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    d = q_ref.shape[-1]
    rows = rep * block_q
    q_lo, k_lo = qi * block_q, kj * block_k
    tile = b * pl.num_programs(2) + qi

    @pl.when(kj == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def step(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d) * sm_scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            q_pos = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 0) % block_q
            k_pos = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 1)
            s = jnp.where((q_pos >= k_pos) & (q_pos < end_ref[0, 0:1, :]), s,
                          NEG_INF)
        m = m_s[:, 0:1]
        l = l_s[:, 0:1]
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True)),
                            M_FLOOR)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    # wholly under the diagonal and inside the segment of every query
    inside = (k_lo + block_k - 1 <= q_lo) & (k_lo >= full_ref[tile])
    visible = (kj >= lo_ref[tile]) & (k_lo <= q_lo + block_q - 1)
    pl.when(inside)(lambda: step(False))
    pl.when(visible & jnp.logical_not(inside))(lambda: step(True))

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finalize():
        l = l_s[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[:] / l_safe).reshape(
            rep, block_q, o_ref.shape[-1]).astype(o_ref.dtype)


def _fwd_packed(q, k, v, segment_ids, kv_mask, sm_scale):
    """q [B, N, S, D], k [B, Nkv, S, D], v [B, Nkv, S, Dv], segment_ids int32
    [B, S] -> o [B, N, S, Dv],
    causal within each segment (ids that never fall along a row: a segment
    is one run of its id), in ONE call whose walk the segments
    bound (``_packed_bounds``). ``kv_mask`` bool [B, S]: a masked key is
    visible to nobody, and every tile then takes the masked body. V may be
    narrower than the keys (latent attention whose ``v_head_dim`` is under
    nope + rope): its tile, the accumulator and the output are ``Dv`` wide,
    so P V costs what V holds."""
    B, N, S, D = q.shape
    Dv = v.shape[-1]
    Nkv = k.shape[1]
    rep = N // Nkv
    bq, bk = _packed_blocks(S, rep)
    nq = S // bq
    rows = rep * bq

    pos = jnp.arange(S, dtype=jnp.int32)[None]
    edge = segment_ids[:, 1:] != segment_ids[:, :-1]
    first = jnp.pad(edge, ((0, 0), (1, 0)), constant_values=True)
    last = jnp.pad(edge, ((0, 0), (0, 1)), constant_values=True)
    seg_start = jax.lax.cummax(jnp.where(first, pos, 0), axis=1)
    seg_end = jax.lax.cummin(jnp.where(last, pos + 1, S), axis=1,
                             reverse=True)
    lo, full = _packed_bounds(seg_start, bq, bk)
    if kv_mask is not None:
        seg_end = jnp.where(kv_mask, seg_end, 0)
        full = jnp.full_like(full, S)

    def kv_tile(b, i, j, lo_ref):
        return jnp.clip(j, lo_ref[b * nq + i], _diagonal_tile(i, bq, bk))

    def kv_spec(width):
        return pl.BlockSpec(
            (1, 1, bk, width),
            lambda b, g, i, j, lo_ref, _: (b, g, kv_tile(b, i, j, lo_ref), 0),
            memory_space=pltpu.VMEM)

    def q_spec(width):
        return pl.BlockSpec((1, 1, rep, bq, width),
                            lambda b, g, i, j, *_: (b, g, 0, i, 0),
                            memory_space=pltpu.VMEM)
    # the segments' ends [B, 8, S]: a sublane-broadcast copy blocked along
    # the lanes with K and V, as the key-padding mask of `_fwd` is
    end_spec = pl.BlockSpec(
        (1, 8, bk),
        lambda b, g, i, j, lo_ref, _: (b, 0, kv_tile(b, i, j, lo_ref)),
        memory_space=pltpu.VMEM)
    o = pl.pallas_call(
        functools.partial(_packed_kernel, sm_scale=sm_scale, rep=rep,
                          block_q=bq, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Nkv, nq, S // bk),
            in_specs=[q_spec(D), kv_spec(D), kv_spec(Dv), end_spec],
            out_specs=q_spec(Dv),
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),   # m (lane-padded)
                pltpu.VMEM((rows, 128), jnp.float32),   # l
                pltpu.VMEM((rows, Dv), jnp.float32),    # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Nkv, rep, S, Dv), q.dtype),
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
        name="flash_fwd",
    )(lo.reshape(-1), full.reshape(-1), q.reshape(B, Nkv, rep, S, D), k, v,
      jnp.broadcast_to(seg_end[:, None, :], (B, 8, S)))
    return o.reshape(B, N, S, Dv)


def flash_attention_packed(q, k, v, segment_ids, *,
                           sm_scale: Optional[float] = None, kv_mask=None):
    """Causal attention over rows that hold several sequences each, q [B, S,
    Nq, D], k [B, S, Nkv, D], v [B, S, Nkv, Dv] -> [B, S, Nq, Dv] (Dv = D,
    or a V narrower than the keys: ``_fwd_packed``): key j is visible to query
    i iff j <= i and both carry the same id. ``segment_ids`` int [B, S],
    traced, must NEVER FALL along a row (``transformer.attention``'s
    precondition; ``_packed_row``'s do not): a segment is then one run of
    its id, which is how the walk finds its edges. ONE call of the flash
    forward, walked over the key tiles that a query tile's segments reach;
    forward only. ``kv_mask`` [B, S]: a key-padding mask on top."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"n_q_heads {q.shape[2]} not divisible by "
                         f"n_kv_heads {k.shape[2]}")
    if not _interpret() and q.shape[1] % 128:
        raise ValueError("segment_ids on TPU require seq_len % 128 == 0 "
                         f"(got {q.shape[1]}): the segments' ends are "
                         "blocked along the lanes like K and V")
    o = _fwd_packed(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
                    jnp.asarray(segment_ids, jnp.int32),
                    None if kv_mask is None else jnp.asarray(kv_mask, bool),
                    float(sm_scale))
    return jnp.swapaxes(o, 1, 2)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, aux_ref, m_ref,
                   dq_ref, dq_s, *scratch, sm_scale, causal, rep, block_q,
                   block_k, fused=False):
    """aux_ref carries the precomputed delta ([..., 1], unfused) or the
    forward O block ([..., D], fused): the fused grid computes delta =
    rowsum(dO * O) ONCE per Q block at the first KV step and holds it in
    VMEM scratch across the KV sweep — no XLA delta pass, no [B,N,S,1]
    HBM round-trip."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    num_kv = pl.num_programs(3)
    d = q_ref.shape[-1]
    rows = rep * block_q

    @pl.when(kj == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)
        if fused:
            do = do_ref[0, 0].astype(jnp.float32).reshape(rows, d)
            o = aux_ref[0, 0].astype(jnp.float32).reshape(rows, d)
            scratch[0][:] = jnp.broadcast_to(
                jnp.sum(do * o, axis=-1, keepdims=True), scratch[0].shape)

    visible = _block_visible(qi, kj, block_q, block_k) if causal else True

    @pl.when(visible)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d)
        do = do_ref[0, 0].astype(jnp.float32).reshape(rows, d)
        lse = lse_ref[0, 0].reshape(rows, 1)
        delta = (scratch[0][:, 0:1] if fused
                 else aux_ref[0, 0].reshape(rows, 1))
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi * block_q, kj * block_k, rows, block_k,
                             block_q)
        if m_ref is not None:
            kv_ok = m_ref[0, 0:1, :] > 0
            s = jnp.where(kv_ok, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == num_kv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_s[:].reshape(rep, block_q, d).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, aux_ref, m_ref,
                    dk_ref, dv_ref, dk_s, dv_s, *, sm_scale, causal, rep,
                    block_q, block_k, fused=False):
    """aux_ref: precomputed delta (unfused) or the forward O block (fused —
    delta recomputed per (kj, qi) step; a [rows, D] rowsum is noise next to
    the step's five matmuls and saves the separate delta pass)."""
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    num_q = pl.num_programs(3)
    d = k_ref.shape[-1]
    rows = rep * block_q
    k_start = kj * block_k

    @pl.when(qi == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    visible = _block_visible(qi, kj, block_q, block_k) if causal else True

    @pl.when(visible)
    def _step():
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d)
        do = do_ref[0, 0].astype(jnp.float32).reshape(rows, d)
        lse = lse_ref[0, 0].reshape(rows, 1)
        if fused:
            o = aux_ref[0, 0].astype(jnp.float32).reshape(rows, d)
            delta = jnp.sum(do * o, axis=-1, keepdims=True)
        else:
            delta = aux_ref[0, 0].reshape(rows, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi * block_q, k_start, rows, block_k, block_q)
        if m_ref is not None:
            kv_ok = m_ref[0, 0:1, :] > 0
            s = jnp.where(kv_ok, s, NEG_INF)
        p = jnp.exp(s - lse)                        # [rows, bk]
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_dq_kernel_nomask(q_ref, k_ref, v_ref, do_ref, lse_ref, aux_ref,
                          dq_ref, *scratch, **kw):
    _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, aux_ref, None,
                   dq_ref, *scratch, **kw)


def _bwd_dkv_kernel_nomask(q_ref, k_ref, v_ref, do_ref, lse_ref, aux_ref,
                           dk_ref, dv_ref, *scratch, **kw):
    _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, aux_ref, None,
                    dk_ref, dv_ref, *scratch, **kw)


def _q_index_map(causal, bq, bk):
    """dK/dV kernel (Q innermost): clamp pre-diagonal Q steps up to the first
    visible block so their DMAs are elided."""
    def index(b, g, j, i):
        if causal:
            first_visible = jax.lax.div(j * bk, bq)
            i = jnp.maximum(i, first_visible)
        return (b, g, 0, i, 0)
    return index


def _bwd(sm_scale, causal, block_q, block_k, fused, residuals, g):
    q, k, v, kv_mask, o, lse = residuals
    do = g
    B, N, S, D = q.shape
    Nkv = k.shape[1]
    rep = N // Nkv
    bq, bk = _pick_blocks(S, block_q, block_k, rep)
    rows = rep * bq

    qg = q.reshape(B, Nkv, rep, S, D)
    dog = do.reshape(B, Nkv, rep, S, D)
    lseg = lse.reshape(B, Nkv, rep, S, 1)
    if fused:
        # delta computed inside both grids from O directly — no XLA pass
        auxg = o.reshape(B, Nkv, rep, S, D)
    else:
        # delta = rowsum(dO * O) — a separate XLA pass over dO and O
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)  # [B,N,S,1]
        auxg = delta.reshape(B, Nkv, rep, S, 1)

    # ---- dQ: grid (B, Nkv, num_q, num_kv), KV innermost ----
    kv_blk = pl.BlockSpec((1, 1, bk, D), _kv_index_map(causal, bq, bk),
                          memory_space=pltpu.VMEM)
    grp_blk = pl.BlockSpec((1, 1, rep, bq, D),
                           lambda b, g, i, j: (b, g, 0, i, 0),
                           memory_space=pltpu.VMEM)
    grp_vec = pl.BlockSpec((1, 1, rep, bq, 1),
                           lambda b, g, i, j: (b, g, 0, i, 0),
                           memory_space=pltpu.VMEM)
    mask_kv = pl.BlockSpec((1, 8, bk), _mask_kv_index_map(causal, bq, bk),
                           memory_space=pltpu.VMEM)
    extra = () if kv_mask is None else (kv_mask,)
    aux_blk = grp_blk if fused else grp_vec
    dq_kern = _bwd_dq_kernel if kv_mask is not None else _bwd_dq_kernel_nomask
    dq = pl.pallas_call(
        functools.partial(dq_kern, sm_scale=sm_scale, causal=causal,
                          rep=rep, block_q=bq, block_k=bk, fused=fused),
        grid=(B, Nkv, S // bq, S // bk),
        in_specs=[grp_blk, kv_blk, kv_blk, grp_blk, grp_vec, aux_blk]
        + ([mask_kv] if kv_mask is not None else []),
        out_specs=grp_blk,
        out_shape=jax.ShapeDtypeStruct((B, Nkv, rep, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)]
        + ([pltpu.VMEM((rows, 128), jnp.float32)] if fused else []),
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
        name="flash_dq",
    )(qg, k, v, dog, lseg, auxg, *extra)

    # ---- dK/dV: grid (B, Nkv, num_kv, num_q), Q innermost ----
    qmap = _q_index_map(causal, bq, bk)
    grp_q = pl.BlockSpec((1, 1, rep, bq, D), qmap, memory_space=pltpu.VMEM)
    grp_q_vec = pl.BlockSpec((1, 1, rep, bq, 1), qmap,
                             memory_space=pltpu.VMEM)
    kv_out = pl.BlockSpec((1, 1, bk, D), lambda b, g, j, i: (b, g, j, 0),
                          memory_space=pltpu.VMEM)
    mask_out = pl.BlockSpec((1, 8, bk), lambda b, g, j, i: (b, 0, j),
                            memory_space=pltpu.VMEM)
    dkv_kern = (_bwd_dkv_kernel if kv_mask is not None
                else _bwd_dkv_kernel_nomask)
    aux_q = grp_q if fused else grp_q_vec
    dk, dv = pl.pallas_call(
        functools.partial(dkv_kern, sm_scale=sm_scale, causal=causal,
                          rep=rep, block_q=bq, block_k=bk, fused=fused),
        grid=(B, Nkv, S // bk, S // bq),
        in_specs=[grp_q, kv_out, kv_out, grp_q, grp_q_vec, aux_q]
        + ([mask_out] if kv_mask is not None else []),
        out_specs=[kv_out, kv_out],
        out_shape=[
            jax.ShapeDtypeStruct((B, Nkv, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, Nkv, S, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
        name="flash_dkv",
    )(qg, k, v, dog, lseg, auxg, *extra)
    return dq.reshape(B, N, S, D), dk, dv


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kv_mask, sm_scale, causal, block_q, block_k, fused):
    o, _ = _fwd(q, k, v, kv_mask, sm_scale, causal, block_q, block_k)
    return o


def _flash_fwd(q, k, v, kv_mask, sm_scale, causal, block_q, block_k, fused):
    o, lse = _fwd(q, k, v, kv_mask, sm_scale, causal, block_q, block_k)
    # named residuals: when this call sits inside a jax.checkpoint region
    # (the layer scan body), every remat policy (transformer._remat_policy)
    # saves O and the log-sum-exp across the fwd/bwd boundary — the backward
    # then runs straight into the two backward grids instead of replaying
    # the full online-softmax forward kernel first
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, kv_mask, o, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, fused, residuals, g):
    dq, dk, dv = _bwd(sm_scale, causal, block_q, block_k, fused, residuals,
                      g)
    kv_mask = residuals[3]
    import numpy as _np
    dmask = (None if kv_mask is None
             else _np.zeros(kv_mask.shape, jax.dtypes.float0))
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    kv_mask=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    fused_backward: bool = False,
                    window: Optional[int] = None):
    """q: [B, S, Nq, D]; k, v: [B, S, Nkv, D] (Nkv may divide Nq: GQA runs
    natively without repeating K/V) -> [B, S, Nq, D].

    A V narrower than the keys (``v.shape[-1] < D``) is padded with zero
    columns to D and the output's first ``v.shape[-1]`` columns are returned:
    the forward and the two backward kernels share ONE width, and no serving
    program takes this call for such a model (a shared prefill row is
    ``flash_attention_packed``'s, whose V keeps its own width).

    window: a static length W — key j is visible to query i iff 0 <= i - j <
    W (causal within a band). The forward visits only the key tiles a query
    tile's band touches (``flash_fwd_band``), and so do the two backward
    kernels (``flash_bwd_band_dq``, ``flash_bwd_band_dkv``). Causal, no
    ``kv_mask``.

    kv_mask: optional [B, S] bool/int padding mask over keys — masked
    positions are excluded inside the kernel (no O(S^2) fallback).
    fused_backward: fold the delta epilogue into the backward grids (the
    kernels read O directly; no separate XLA delta pass)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"n_q_heads {q.shape[2]} not divisible by "
                         f"n_kv_heads {k.shape[2]}")
    dv = v.shape[-1]
    if dv < q.shape[-1]:
        v = jnp.pad(v, [(0, 0)] * 3 + [(0, q.shape[-1] - dv)])
        return flash_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, kv_mask=kv_mask,
            block_q=block_q, block_k=block_k, fused_backward=fused_backward,
            window=window)[..., :dv]
    if window is not None:
        if not causal or kv_mask is not None or window < 1:
            raise ValueError(
                "flash_attention(window=...) is causal attention within a "
                f"band of >= 1 positions and takes no kv_mask (causal="
                f"{causal}, window={window}, kv_mask "
                f"{'given' if kv_mask is not None else 'None'})")
        o = _flash_band(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
                        float(sm_scale), int(window), block_q, block_k)
        return jnp.swapaxes(o, 1, 2)
    if kv_mask is not None and not _interpret():
        # the blocked mask spec needs block_k % 128 == 0 on TPU; _pick_blocks
        # halves from a power-of-two >= 128, so any S % 128 == 0 lands there
        if q.shape[1] % 128:
            raise ValueError("kv_mask on TPU requires seq_len % 128 == 0 "
                             f"(got {q.shape[1]})")
        block_k = max(block_k, 128)
    qt = jnp.swapaxes(q, 1, 2)  # [B, N, S, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if kv_mask is not None:
        kv_mask = jnp.asarray(kv_mask).astype(jnp.float32)
        # (B, 8, S): the sublane-broadcast copy satisfies Mosaic's dynamic
        # sublane-index alignment rule (int8 [B,S] rows can't be dynamically
        # indexed); 8x a [B,S] int8 is negligible
        kv_mask = jnp.broadcast_to(kv_mask[:, None, :],
                                   (kv_mask.shape[0], 8, kv_mask.shape[1]))
    o = _flash(qt, kt, vt, kv_mask, float(sm_scale), bool(causal), block_q,
               block_k, bool(fused_backward))
    return jnp.swapaxes(o, 1, 2)


def reference_attention(q, k, v, *, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None, segment_ids=None):
    """XLA reference for parity tests (handles GQA by repeat); ``window``:
    key j visible to query i only where i - j < window; ``segment_ids`` [B,
    S]: only where the two ids are equal."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    B, S, N, D = q.shape
    if k.shape[2] != N:
        rep = N // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bsnd,btnd->bnst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
        s = jnp.where(mask[None, None], s, NEG_INF)
    if window is not None:
        pos = jnp.arange(S)
        s = jnp.where((pos[:, None] - pos[None, :] < window)[None, None], s,
                      NEG_INF)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        s = jnp.where(same[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnst,btnd->bsnd", p, v.astype(jnp.float32)).astype(q.dtype)
