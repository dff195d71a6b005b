"""Block-sparse attention: sparsity layouts + Pallas kernel.

Reference: ``deepspeed/ops/sparse_attention/sparse_self_attention.py:11``
(SparseSelfAttention over Triton block-sparse matmul/softmax) and
``sparsity_config.py:94-545`` (Dense/Fixed/BigBird/BSLongformer/Variable
layout builders).

TPU-native re-design: the Triton path multiplies against a block mask; here
each q-block carries an explicit index list of its active k-blocks (built
host-side from the layout, padded to the max row degree), and the Pallas
kernel loops ONLY over that list with online softmax — compute and HBM
traffic scale with the layout's density, not S^2. Backward reuses the flash
decomposition with the transposed adjacency for dK/dV.

Layouts are per-head-shared (the reference's `different_layout_per_head`
defaults off for these modes); causal masking composes with any layout.
"""

import dataclasses
import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# --------------------------------------------------------------------------
# sparsity configs (reference: sparsity_config.py)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparsityConfig:
    """Base: dense layout (reference: DenseSparsityConfig)."""
    block: int = 128

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = seq_len // self.block
        return np.ones((n, n), bool)


@dataclasses.dataclass(frozen=True)
class DenseSparsityConfig(SparsityConfig):
    pass


@dataclasses.dataclass(frozen=True)
class FixedSparsityConfig(SparsityConfig):
    """Local blocks + periodic global columns (reference:
    FixedSparsityConfig — num_local_blocks window, num_global_blocks stride
    summaries, 'unidirectional'/'bidirectional' attention)."""
    num_local_blocks: int = 4
    num_global_blocks: int = 1

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = seq_len // self.block
        L = np.zeros((n, n), bool)
        nl = self.num_local_blocks
        for i in range(n):
            w0 = (i // nl) * nl
            L[i, w0:min(w0 + nl, n)] = True          # local window
        for w0 in range(0, n, nl):                    # global columns: the
            g = min(self.num_global_blocks, n - w0)   # first blocks of each
            L[:, w0:w0 + g] = True                    # local window
        return L


@dataclasses.dataclass(frozen=True)
class BigBirdSparsityConfig(SparsityConfig):
    """Random + sliding window + global blocks (reference:
    BigBirdSparsityConfig)."""
    num_random_blocks: int = 1
    num_sliding_window_blocks: int = 3
    num_global_blocks: int = 1
    seed: int = 0

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = seq_len // self.block
        L = np.zeros((n, n), bool)
        w = self.num_sliding_window_blocks // 2
        for i in range(n):
            L[i, max(0, i - w):min(n, i + w + 1)] = True
        g = min(self.num_global_blocks, n)
        L[:, :g] = True
        L[:g, :] = True
        rng = np.random.default_rng(self.seed)
        for i in range(n):
            pick = rng.choice(n, size=min(self.num_random_blocks, n),
                              replace=False)
            L[i, pick] = True
        return L


@dataclasses.dataclass(frozen=True)
class BSLongformerSparsityConfig(SparsityConfig):
    """Sliding window + designated global block indices (reference:
    BSLongformerSparsityConfig)."""
    num_sliding_window_blocks: int = 3
    global_block_indices: Tuple[int, ...] = (0,)

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = seq_len // self.block
        L = np.zeros((n, n), bool)
        w = self.num_sliding_window_blocks // 2
        for i in range(n):
            L[i, max(0, i - w):min(n, i + w + 1)] = True
        for g in self.global_block_indices:
            if g < n:
                L[:, g] = True
                L[g, :] = True
        return L


@dataclasses.dataclass(frozen=True)
class VariableSparsityConfig(SparsityConfig):
    """Variable local window sizes + global blocks (reference:
    VariableSparsityConfig, simplified: per-row window grows with distance
    from the start)."""
    num_global_blocks: int = 1
    local_window_blocks: Tuple[int, ...] = (4,)

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = seq_len // self.block
        L = np.zeros((n, n), bool)
        windows = list(self.local_window_blocks)
        start = 0
        wi = 0
        while start < n:
            w = windows[min(wi, len(windows) - 1)]
            end = min(start + w, n)
            L[start:end, start:end] = True
            start, wi = end, wi + 1
        L[:, :min(self.num_global_blocks, n)] = True
        return L


_MODES = {
    "dense": DenseSparsityConfig,
    "fixed": FixedSparsityConfig,
    "bigbird": BigBirdSparsityConfig,
    "bslongformer": BSLongformerSparsityConfig,
    "variable": VariableSparsityConfig,
}


def get_sparsity_config(mode: str, **kw) -> SparsityConfig:
    if mode not in _MODES:
        raise ValueError(f"unknown sparse attention mode {mode!r}; "
                         f"have {sorted(_MODES)}")
    return _MODES[mode](**kw)


def _adjacency(layout: np.ndarray, causal: bool):
    """layout [Qb, Kb] -> (idx [Qb, max_deg] int32 padded -1, count [Qb]),
    plus the transpose for the dK/dV pass."""
    n = layout.shape[0]
    if causal:
        layout = layout & np.tril(np.ones((n, n), bool))
    rows = [np.nonzero(layout[i])[0] for i in range(n)]
    deg = max((len(r) for r in rows), default=0)
    idx = np.full((n, max(deg, 1)), -1, np.int32)
    for i, r in enumerate(rows):
        idx[i, :len(r)] = r
    count = np.array([len(r) for r in rows], np.int32)
    cols = [np.nonzero(layout[:, j])[0] for j in range(n)]
    cdeg = max((len(c) for c in cols), default=0)
    cidx = np.full((n, max(cdeg, 1)), -1, np.int32)
    for j, c in enumerate(cols):
        cidx[j, :len(c)] = c
    ccount = np.array([len(c) for c in cols], np.int32)
    return idx, count, cidx, ccount


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# kernels: flash-style online softmax over each row's adjacency list, with
# MANUAL double-buffered DMA — K/V stay in HBM (pltpu.ANY) and each listed
# block is copied into a 2-slot VMEM scratch one step ahead of its use.
# Work and HBM traffic are exactly proportional to the row's TRUE degree:
# no full-[S,D] VMEM residency (the round-2 design) and no padded grid
# steps (a slot-grid design pays max_deg steps per row, and global rows
# push max_deg to the full row width for BigBird/Longformer layouts).
# --------------------------------------------------------------------------

M_FLOOR = -1e20


# K/V (and the dK/dV pass's Q/dO) arrive CHANNEL-MAJOR ([B, N, D, S]): DMA
# slices then run along the 128-aligned sequence dim (Mosaic rejects lane
# slices of a D=64 minor dim). lse/delta keep [B, N, S, 1] — their minor dim
# is full. The dots below contract the channel dim of the transposed tiles
# directly, so no in-kernel transposes are needed.

def _seq_dma(hbm_ref, scratch, sem, b, n, j, slot, block):
    return pltpu.make_async_copy(
        hbm_ref.at[b, n, :, pl.ds(j * block, block)],
        scratch.at[slot], sem.at[slot])


def _make_dma_ops(streams, idx_ref, row, b, n, block):
    """Shared start/wait pair over a list of (hbm, scratch, sem) streams:
    descriptors are rebuilt identically for start and wait (the Pallas
    async-copy contract)."""
    def _descs(t, slot):
        j = jnp.maximum(idx_ref[row, t], 0)
        return [_seq_dma(hbm, scr, sem, b, n, j, slot, block)
                for hbm, scr, sem in streams]

    def start(t, slot):
        for d_ in _descs(t, slot):
            d_.start()

    def wait(t, slot):
        for d_ in _descs(t, slot):
            d_.wait()

    return start, wait


def _sp_fwd_kernel(idx_ref, cnt_ref, q_ref, kt_hbm, vt_hbm, o_ref, lse_ref,
                   *, sm_scale, causal, block):
    b, n, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cnt = cnt_ref[qi]
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale     # [block, D]
    d = q.shape[-1]
    q_start = qi * block

    def body(ks, vs, ksem, vsem):
        start, wait = _make_dma_ops(
            [(kt_hbm, ks, ksem), (vt_hbm, vs, vsem)], idx_ref, qi, b, n,
            block)

        @pl.when(cnt > 0)
        def _warm():
            start(0, 0)

        def step(t, carry):
            m, l, acc = carry
            slot = t % 2

            @pl.when(t + 1 < cnt)
            def _prefetch():
                start(t + 1, (t + 1) % 2)

            wait(t, slot)
            j = idx_ref[qi, t]
            kt = ks[slot].astype(jnp.float32)           # [D, block]
            vt = vs[slot].astype(jnp.float32)
            # s[qr, kr] = sum_d q[qr, d] * kt[d, kr]
            s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if causal:
                q_pos = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 0)
                k_pos = j * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            m_new = jnp.maximum(
                jnp.maximum(m, jnp.max(s, -1, keepdims=True)), M_FLOOR)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
            # acc[qr, d] = sum_kr p[qr, kr] * vt[d, kr]
            acc_new = acc * alpha + jax.lax.dot_general(
                p, vt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((block, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block, 1), jnp.float32)
        acc0 = jnp.zeros((block, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, cnt, step, (m0, l0, acc0))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(l_safe)

    pl.run_scoped(
        body,
        ks=pltpu.VMEM((2, kt_hbm.shape[2], block), kt_hbm.dtype),
        vs=pltpu.VMEM((2, vt_hbm.shape[2], block), vt_hbm.dtype),
        ksem=pltpu.SemaphoreType.DMA((2,)),
        vsem=pltpu.SemaphoreType.DMA((2,)))


def _sp_bwd_dq_kernel(idx_ref, cnt_ref, q_ref, kt_hbm, vt_hbm, do_ref,
                      lse_ref, delta_ref, dq_ref, *, sm_scale, causal,
                      block):
    b, n, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cnt = cnt_ref[qi]
    q_start = qi * block
    q = q_ref[0, 0].astype(jnp.float32)                 # [block, D]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    d = q.shape[-1]

    def body(ks, vs, ksem, vsem):
        start, wait = _make_dma_ops(
            [(kt_hbm, ks, ksem), (vt_hbm, vs, vsem)], idx_ref, qi, b, n,
            block)

        @pl.when(cnt > 0)
        def _warm():
            start(0, 0)

        def step(t, dq):
            slot = t % 2

            @pl.when(t + 1 < cnt)
            def _prefetch():
                start(t + 1, (t + 1) % 2)

            wait(t, slot)
            j = idx_ref[qi, t]
            kt = ks[slot].astype(jnp.float32)           # [D, block]
            vt = vs[slot].astype(jnp.float32)
            s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * sm_scale
            if causal:
                q_pos = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 0)
                k_pos = j * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            p = jnp.exp(s - lse)
            # dp[qr, kr] = sum_d do[qr, d] * vt[d, kr]
            dp = jax.lax.dot_general(do, vt, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            # dq[qr, d] = sum_kr ds[qr, kr] * kt[d, kr]
            return dq + jax.lax.dot_general(
                ds, kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(0, cnt, step,
                               jnp.zeros((block, d), jnp.float32))
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    pl.run_scoped(
        body,
        ks=pltpu.VMEM((2, kt_hbm.shape[2], block), kt_hbm.dtype),
        vs=pltpu.VMEM((2, vt_hbm.shape[2], block), vt_hbm.dtype),
        ksem=pltpu.SemaphoreType.DMA((2,)),
        vsem=pltpu.SemaphoreType.DMA((2,)))


def _sp_bwd_dkv_kernel(cidx_ref, ccnt_ref, qt_hbm, k_ref, v_ref, dot_hbm,
                       lset_hbm, deltat_hbm, dk_ref, dv_ref, *, sm_scale,
                       causal, block):
    """Computes in TRANSPOSED score space (s_t[kr, qr]) so the per-q-row
    lse/delta broadcast along lanes — their [B, N, 1, S] layout gives
    128-aligned DMA slices with no in-kernel transposes."""
    b, n, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cnt = ccnt_ref[ki]
    k_start = ki * block
    k = k_ref[0, 0].astype(jnp.float32)                 # [block, D]
    v = v_ref[0, 0].astype(jnp.float32)
    d = k.shape[-1]

    def body(qs, dos, ls, dls, qsem, dosem, lsem, dlsem):
        start, wait = _make_dma_ops(
            [(qt_hbm, qs, qsem), (dot_hbm, dos, dosem),
             (lset_hbm, ls, lsem), (deltat_hbm, dls, dlsem)],
            cidx_ref, ki, b, n, block)

        @pl.when(cnt > 0)
        def _warm():
            start(0, 0)

        def step(t, carry):
            dk, dv = carry
            slot = t % 2

            @pl.when(t + 1 < cnt)
            def _prefetch():
                start(t + 1, (t + 1) % 2)

            wait(t, slot)
            i = cidx_ref[ki, t]
            qt = qs[slot].astype(jnp.float32)           # [D, block]
            dot_ = dos[slot].astype(jnp.float32)        # [D, block]
            lse_row = ls[slot]                          # [1, block]
            delta_row = dls[slot]
            # s_t[kr, qr] = sum_d k[kr, d] * qt[d, qr]
            s_t = jax.lax.dot_general(k, qt, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32) \
                * sm_scale
            if causal:
                k_pos = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 0)
                q_pos = i * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 1)
                s_t = jnp.where(q_pos >= k_pos, s_t, NEG_INF)
            p_t = jnp.exp(s_t - lse_row)                # [bk, bq]
            # dv[kr, d] = sum_qr p_t[kr, qr] * dot_[d, qr]
            dv_new = dv + jax.lax.dot_general(
                p_t, dot_, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            # dp_t[kr, qr] = sum_d v[kr, d] * dot_[d, qr]
            dp_t = jax.lax.dot_general(v, dot_, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            ds_t = p_t * (dp_t - delta_row) * sm_scale
            # dk[kr, d] = sum_qr ds_t[kr, qr] * qt[d, qr]
            dk_new = dk + jax.lax.dot_general(
                ds_t, qt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_new, dv_new

        dk0 = jnp.zeros((block, d), jnp.float32)
        dv0 = jnp.zeros((block, d), jnp.float32)
        dk, dv = jax.lax.fori_loop(0, cnt, step, (dk0, dv0))
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    pl.run_scoped(
        body,
        qs=pltpu.VMEM((2, qt_hbm.shape[2], block), qt_hbm.dtype),
        dos=pltpu.VMEM((2, dot_hbm.shape[2], block), dot_hbm.dtype),
        ls=pltpu.VMEM((2, 1, block), jnp.float32),
        dls=pltpu.VMEM((2, 1, block), jnp.float32),
        qsem=pltpu.SemaphoreType.DMA((2,)),
        dosem=pltpu.SemaphoreType.DMA((2,)),
        lsem=pltpu.SemaphoreType.DMA((2,)),
        dlsem=pltpu.SemaphoreType.DMA((2,)))


def _compiler_params():
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))


def _sp_fwd(q, k, v, idx, cnt, sm_scale, causal, block):
    B, N, S, D = q.shape
    blk = pl.BlockSpec((1, 1, block, D),
                       lambda b, n, i, idx_, cnt_: (b, n, i, 0),
                       memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, N, S // block),
        in_specs=[blk, hbm, hbm],
        out_specs=[
            blk,
            pl.BlockSpec((1, 1, block, 1),
                         lambda b, n, i, idx_, cnt_: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
        ],
    )
    kernel = functools.partial(_sp_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, block=block)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, N, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, N, S, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="sparse_fwd",
    )(idx, cnt, q, jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3))
    return o, lse


def _sp_bwd(sm_scale, causal, block, adjacency, residuals, g):
    q, k, v, o, lse = residuals
    idx, cnt, cidx, ccnt = adjacency
    do = g
    B, N, S, D = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    blk = pl.BlockSpec((1, 1, block, D),
                       lambda b, n, i, idx_, cnt_: (b, n, i, 0),
                       memory_space=pltpu.VMEM)
    blk_vec = pl.BlockSpec((1, 1, block, 1),
                           lambda b, n, i, idx_, cnt_: (b, n, i, 0),
                           memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    dq = pl.pallas_call(
        functools.partial(_sp_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, N, S // block),
            in_specs=[blk, hbm, hbm, blk, blk_vec, blk_vec],
            out_specs=blk),
        out_shape=jax.ShapeDtypeStruct((B, N, S, D), q.dtype),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="sparse_dq",
    )(idx, cnt, q, jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3), do, lse,
      delta)

    # dK/dV pass: the grid's block index is a K block; Q/dO/lse/delta are
    # DMA'd per listed row of the TRANSPOSED adjacency (cidx)
    dk, dv = pl.pallas_call(
        functools.partial(_sp_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, N, S // block),
            in_specs=[hbm, blk, blk, hbm, hbm, hbm],
            out_specs=[blk, blk]),
        out_shape=[jax.ShapeDtypeStruct((B, N, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B, N, S, D), q.dtype)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="sparse_dkv",
    )(cidx, ccnt, jnp.swapaxes(q, 2, 3), k, v, jnp.swapaxes(do, 2, 3),
      jnp.swapaxes(lse, 2, 3), jnp.swapaxes(delta, 2, 3))
    return dq, dk, dv


# adjacency travels as nested tuples (hashable: custom_vjp nondiff args and
# jit static closure both require it); materialized to arrays at use
def _adj_arrays(adjacency):
    return tuple(np.asarray(a, np.int32) for a in adjacency)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _sparse(q, k, v, adjacency, sm_scale, causal, block):
    idx, cnt, _, _ = _adj_arrays(adjacency)
    o, _ = _sp_fwd(q, k, v, jnp.asarray(idx), jnp.asarray(cnt), sm_scale,
                   causal, block)
    return o


def _sparse_fwd(q, k, v, adjacency, sm_scale, causal, block):
    idx, cnt, _, _ = _adj_arrays(adjacency)
    o, lse = _sp_fwd(q, k, v, jnp.asarray(idx), jnp.asarray(cnt), sm_scale,
                     causal, block)
    return o, (q, k, v, o, lse)


def _sparse_bwd(adjacency, sm_scale, causal, block, residuals, g):
    adjacency = tuple(jnp.asarray(a) for a in _adj_arrays(adjacency))
    return _sp_bwd(sm_scale, causal, block, adjacency, residuals, g)


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


@functools.lru_cache(maxsize=64)
def _cached_adjacency(config: SparsityConfig, seq_len: int, causal: bool):
    layout = config.make_layout(seq_len)
    idx, cnt, cidx, ccnt = _adjacency(layout, causal)
    return (tuple(map(tuple, idx)), tuple(cnt),
            tuple(map(tuple, cidx)), tuple(ccnt))


def sparse_attention(q, k, v, config: SparsityConfig, *, causal: bool = True,
                     sm_scale: Optional[float] = None):
    """Block-sparse attention. q, k, v: [B, S, N, D] -> [B, S, N, D].

    The layout is built once per (config, S, causal) and baked into the
    compiled kernel as SMEM index tables (reference:
    sparse_self_attention.py:11 forward)."""
    B, S, N, D = q.shape
    if S % config.block:
        raise ValueError(f"seq len {S} not divisible by block {config.block}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    raw = _cached_adjacency(config, S, bool(causal))
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = _sparse(qt, kt, vt, raw, float(sm_scale), bool(causal),
                config.block)
    return jnp.swapaxes(o, 1, 2)


def reference_sparse_attention(q, k, v, config: SparsityConfig, *,
                               causal: bool = True,
                               sm_scale: Optional[float] = None):
    """XLA reference: dense attention masked by the block layout."""
    B, S, N, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    layout = config.make_layout(S)
    mask = np.repeat(np.repeat(layout, config.block, 0), config.block, 1)
    if causal:
        mask = mask & np.tril(np.ones((S, S), bool))
    s = jnp.einsum("bsnd,btnd->bnst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s = jnp.where(jnp.asarray(mask)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.asarray(mask)[None, None], p, 0.0)
    return jnp.einsum("bnst,btnd->bsnd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
