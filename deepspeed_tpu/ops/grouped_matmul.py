"""Grouped matmul for the dropless expert layer: a Pallas TPU kernel.

``rows [M, K]`` are sorted by expert, ``group_sizes [E]`` says how many rows
each expert has, and row i is multiplied by ITS expert's ``[K, N]`` matrix —
one call per projection of an expert layer (``moe/sharded_moe.py``). The
weights are the WHOLE stack ``[L, E, K, N]`` plus the layer's index: a
Pallas operand is a whole buffer, so handing the kernel ``stack[layer]``
makes XLA copy that layer's experts first (0.8 GB a layer at OLMoE's widths,
three stacks: 30 % of a decode step, PERF.md section 6, PR 26); the kernel's
index map picks ``(layer, expert)`` blocks out of the stack in place.

Structure (after JAX's megablox ``gmm``, which it replaced: tracing that
one's group metadata cost 0.6 s per program on the chip's host, 3.4 s of a
Mixtral run's set-up): the grid walks every (row tile, expert) pair that
shares a row — a *visit* —, at most ``row tiles + E - 1`` of them and exactly
``num_visits`` at run time (a dynamic grid bound: an expert with no row is
never visited and its matrices are never read); a visit multiplies the
tile's ``tm`` rows by the expert's matrix, tile by tile over K, into a
float32 accumulator, and stores the rows that belong to the expert. Visits
of one row tile are consecutive, so its output block stays in fast memory
between them. Rows past ``sum(group_sizes)`` belong to nobody: their output
is never written and never read.

A visit streams the expert's weight tile whatever ``tm`` is and is bound by
that up to ~240 rows (a v5e's 197 TFLOP/s over 819 GB/s), by the multiply
beyond: ``row_tile`` picks ``tm`` from the shapes.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# weight tile (contraction, columns): 4 MiB in bf16, double-buffered
TK, TN = 2048, 1024
# rows up to which streaming an expert's weight tile hides the multiply: a
# v5e's 197 TFLOP/s over 819 GB/s
WEIGHT_BOUND_ROWS = 240.0
# what a layer's sorted dispatch pays whatever the shapes — two argsorts of
# the (token, expert) pairs, two row gathers, three kernel launches — BEYOND
# what the one-hot form pays whatever ITS shapes, as the bytes the chip streams
# in that time: 0.016 ms at 819 GB/s. Measured on the chip (PERF.md section 6,
# PR 45 and PR 46: one expert layer alone, both forms, the stacks read in
# place): the sorted layer takes visited bytes / 764 GB/s + 0.09 ms (Trinity
# 1.496 ms for 19 visits of 56.6 MB, Mixtral 3.777 ms for 8 of 352 MB), and
# the one-hot layer comes down to all E experts' bytes at the same rate + 0.07
# to 0.10 ms where its rows are few (Trinity's 64 slots x 32 experts, Mixtral's
# and OLMoE's 32): net of it +0.016 ms at Trinity's shape, the largest
# reading. What the one-hot form pays that DOES follow the shapes is
# `one_hot_cost`'s to price. In visits this is the constant over ONE expert's
# bytes (`moe/sharded_moe._one_hot_is_cheaper`): 0.2 at Trinity's widths, 1.0
# at OLMoE's, 2.1 at Qwen3-Next's.
SORTED_FIXED_BYTES = 13e6
# what the two forms must differ by before the difference is one: 0.04 ms at
# 819 GB/s, as bytes. The sorted layer's time scatters by that around its line
# with the routing of the sample (OLMoE's 32-slot step: one-hot 1.154 / 1.146
# ms, sorted 1.117 / 1.082 in two calls), and within it the masks keep the
# call (`_one_hot_is_cheaper`): no sort, no kernel, the program it was.
TIE_BYTES = 33e6


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def visit_cost(rows: int, experts: int, tm: int) -> float:
    """The kernel's EXPECTED time in units of one weight-bound expert visit.
    It visits every (row tile, expert) pair that shares a row and no other:
    ``row tiles + experts TOUCHED - 1``, and ``rows`` assignments spread
    evenly over the experts touch ``E (1 - (1 - 1/E)^rows)`` of them — all E
    once rows >> E (a prompt), 20.4 of 32 at 32 rows (a skewed router touches
    fewer still). Each visit is bound by the expert's weight bytes up to
    ``WEIGHT_BOUND_ROWS`` rows and by the multiply beyond."""
    touched = experts * (1.0 - (1.0 - 1.0 / experts) ** rows)
    return (-(-rows // tm) + touched - 1) * max(1.0, tm / WEIGHT_BOUND_ROWS)


def one_hot_cost(tokens: int, experts: int, row_bytes: int,
                 expert_bytes: int) -> float:
    """The one-hot dispatch with capacity = tokens, in the same unit (one
    weight-bound expert visit), from the call's shapes: ``row_bytes`` one
    token's row of the model's width, ``expert_bytes`` one expert's matrices.

    It gives EVERY expert all T rows. What that costs, as fitted to one
    expert layer alone on the chip at 29 shapes of five families (PERF.md
    section 6, PR 46; rms 0.24 ms over 1.1 - 8.4 ms, no term's coefficient
    further than 6 % from the 1 it has here but the einsums'):

    - all E experts' matrices streamed, or E x T rows multiplied where that
      takes longer (``WEIGHT_BOUND_ROWS``): XLA's batched matmul overlaps the
      two, and the ``[E, T, F]`` rows between its projections never leave
      the fusion (coefficient 0.05);
    - the ``[E, T, H]`` rows INTO the experts and OUT of them, each written
      and read: four passes over E x T rows that the sorted form makes over
      T x k — 88 MB a pass at 128 tokens x 128 experts x 2688, a quarter of
      the experts' own bytes at Qwen3-Next's widths;
    - the dispatch and combine einsums, which make and consume those rows by
      contracting the ``[T, E, T]`` masks over T: E x T rows times a
      ``[T, H]`` matrix, twice, at about half the multiplier's peak (fitted
      1.9 x their FLOPs' time; the contraction is only T long) — T /
      ``WEIGHT_BOUND_ROWS`` of the four passes again."""
    rows = experts * tokens
    return (experts * max(1.0, tokens / WEIGHT_BOUND_ROWS)
            + 4 * rows * row_bytes * (1.0 + tokens / WEIGHT_BOUND_ROWS)
            / expert_bytes)


def row_tile(rows: int, experts: int) -> int:
    """Rows per tile, from the shapes alone: few experts want large tiles
    (Mixtral's 512 prefill rows over 8 experts: 9 visits at 256 rows, 11 at
    128), many experts and few rows small ones (a decode step's 256 rows
    over 64 experts: 65 visits at 128 rows, each cheaper than at 256)."""
    def cost(tm):
        return visit_cost(rows, experts, tm)
    return min((64, 128, 256), key=cost)


def _tile(dim: int, cap: int) -> int:
    """The tile of a contraction or column extent: the largest multiple of
    128 up to ``cap`` that divides it, or — a width off the 128 grid, such
    as 1856 — the whole extent (a block may span a whole dimension whatever
    its size). 0: no such tile."""
    if dim % 128:
        return dim if dim <= 2 * cap else 0
    return max((t for t in range(128, min(cap, dim) + 1, 128)
                if dim % t == 0), default=0)


def supported(K: int, N: int) -> bool:
    """The kernel tiles K and N without a remainder."""
    return bool(_tile(K, TK) and _tile(N, TN))


def visits(group_sizes, tm: int, tiles_m: int):
    """The walk over (row tile, expert) pairs: ``offsets [E + 1]`` (row at
    which each expert's group starts), ``expert [V]`` and ``tile [V]`` of
    each visit (V = tiles_m + E - 1, the most there can be; entries past
    ``num_visits`` repeat the last real one and are never run), and
    ``num_visits``."""
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    n = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    vend = jnp.cumsum(n)                          # visits up to and with e
    num_visits = vend[-1]
    v = jnp.minimum(jnp.arange(tiles_m + E - 1, dtype=jnp.int32),
                    jnp.maximum(num_visits - 1, 0))
    expert = jnp.minimum(
        jnp.sum((vend[None, :] <= v[:, None]).astype(jnp.int32), axis=1), E - 1)
    tile = jnp.take(starts // tm, expert) + v - jnp.take(vend - n, expert)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, expert, jnp.clip(tile, 0, tiles_m - 1), num_visits


def _kernel(layer_ref, offsets_ref, expert_ref, tile_ref, lhs_ref, rhs_ref,
            out_ref, acc_ref, *, tm: int, tiles_k: int,
            transposed: bool = False):
    del layer_ref                                  # used by the index maps
    v, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if transposed:                # rhs tile [tn, tk]: contract the last dims
        acc_ref[...] += lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        e = expert_ref[v]
        row = lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0) \
            + tile_ref[v] * tm
        mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def _gmm_call(rows, stack, layer, group_sizes, transposed: bool):
    """The forward kernel: rows [M, K] x the layer's experts out of the whole
    stack -> [M, N]; ``layer`` int32 [1]."""
    M, K = rows.shape
    L, E, K2, N = stack.shape
    if transposed:
        K2, N = N, K2
    assert K == K2 and supported(K, N), (rows.shape, stack.shape)
    tm = row_tile(M, E)
    pad = -M % tm
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    tiles_m = (M + pad) // tm
    tk, tn = _tile(K, TK), _tile(N, TN)
    tiles_k, tiles_n = K // tk, N // tn
    offsets, expert, tile, num_visits = visits(group_sizes, tm, tiles_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # (layer, offsets, expert, tile)
        grid=(tiles_n, num_visits, tiles_k),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda n, v, k, ly, off, ex, tl: (tl[v], k)),
            pl.BlockSpec((None, None, tn, tk),
                         lambda n, v, k, ly, off, ex, tl: (ly[0], ex[v], n, k))
            if transposed else
            pl.BlockSpec((None, None, tk, tn),
                         lambda n, v, k, ly, off, ex, tl: (ly[0], ex[v], k, n)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda n, v, k, ly, off, ex, tl: (tl[v], n)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k,
                          transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M + pad, N), rows.dtype),
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="moe_gmm",
    )(layer, offsets, expert, tile, rows, stack)
    return out[:M] if pad else out


# ---- the backward: d rows is the forward kernel with the matrices read the
# other way round; d stack is `moe_gmm_dw` ------------------------------------

# the weight-gradient kernel's output block (contraction of the forward x
# its columns): 1024 x 1024 float32 = 4 MiB of accumulator, the block itself
# double-buffered beside it
DW_TK, DW_TN = 1024, 1024
# its row tile: the rows are the CONTRACTION here, and a visit's multiply
# (tm x tk x tn) has to hide the read of its two row tiles and a grid step
DW_TM = 512


def _dw_kernel(offsets_ref, expert_ref, tile_ref, nv_ref, lhs_ref, rhs_ref,
               zeros_ref, out_ref, acc_ref, *, tm: int, max_visits: int):
    """One visit: ``lhs_tile^T @ rhs_tile`` over the rows of the tile that
    belong to the visit's expert, added to the expert's ``[tk, tn]`` block.
    Visits of one expert are consecutive, so its block is zeroed at its first
    visit and stored at its last; an expert with no row is never visited and
    keeps the zeros the output starts from (``zeros_ref`` is that buffer,
    aliased to the output)."""
    del zeros_ref
    v = pl.program_id(2)
    e = expert_ref[v]
    first = (v == 0) | (expert_ref[jnp.maximum(v - 1, 0)] != e)
    last = (v == nv_ref[0] - 1) \
        | (expert_ref[jnp.minimum(v + 1, max_visits - 1)] != e)

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row = tile_ref[v] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
    # BOTH tiles are masked by selection: a row past the groups is whatever
    # the forward left there, and 0 x NaN is NaN
    lhs = jnp.where(mine, lhs_ref[...], jnp.zeros_like(lhs_ref))
    rhs = jnp.where(mine, rhs_ref[...], jnp.zeros_like(rhs_ref))
    acc_ref[...] += lax.dot_general(lhs, rhs, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(last)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def grouped_matmul_dw(lhs, rhs, group_sizes, out_dtype=None):
    """lhs [M, K], rhs [M, N], both sorted by expert as ``group_sizes`` [E]
    says -> [E, K, N]: per expert ``lhs_e^T @ rhs_e`` over its rows,
    accumulated in float32; zeros for an expert with no row; rows past the
    groups' sum are never read. The gradient of ONE layer's experts (``rows^T
    @ dy``; for matrices stored transposed, ``dy^T @ rows``)."""
    M, K = lhs.shape
    N = rhs.shape[1]
    E = group_sizes.shape[0]
    out_dtype = out_dtype or lhs.dtype
    tk, tn = _tile(K, DW_TK), _tile(N, DW_TN)
    assert rhs.shape[0] == M and tk and tn, (lhs.shape, rhs.shape)
    tm = min(DW_TM, -(-M // 128) * 128)
    pad = -M % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        rhs = jnp.pad(rhs, ((0, pad), (0, 0)))
    tiles_m = (M + pad) // tm
    offsets, expert, tile, num_visits = visits(group_sizes, tm, tiles_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # (offsets, expert, tile, num_visits)
        grid=(K // tk, N // tn, num_visits),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda k, n, v, off, ex, tl, nv: (tl[v], k)),
            pl.BlockSpec((tm, tn), lambda k, n, v, off, ex, tl, nv: (tl[v], n)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, tk, tn),
                               lambda k, n, v, off, ex, tl, nv: (ex[v], k, n)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm, max_visits=tiles_m + E - 1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, K, N), out_dtype),
        input_output_aliases={6: 0},    # the zeros the output starts from
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="moe_gmm_dw",
    )(offsets, expert, tile, num_visits.reshape(1), lhs, rhs,
      jnp.zeros((E, K, N), out_dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gmm(rows, w, stack, layer, group_sizes, transposed):
    """``w`` is ``stack[layer]``, the operand the gradient is taken by: the
    forward reads the layer's experts out of the whole ``stack`` in place and
    never touches ``w`` (XLA drops the slice), the backward hands ``w`` an
    ``[E, K, N]`` gradient — not the whole stack's shape once a layer."""
    del w
    return _gmm_call(rows, stack, layer, group_sizes, transposed)


def _gmm_fwd(rows, w, stack, layer, group_sizes, transposed):
    del w
    return (_gmm_call(rows, stack, layer, group_sizes, transposed),
            (rows, stack, layer, group_sizes))


def _gmm_bwd(transposed, residuals, dy):
    rows, stack, layer, group_sizes = residuals
    # d rows: dy through the same kernel, each matrix read the other way
    # round; a row past the groups' sum has no gradient and comes back
    # undefined, as in the forward (its readers, this kernel, `moe_gmm_dw`
    # and `ops/moe_rows.py`, never read past the groups)
    d_rows = _gmm_call(dy, stack, layer, group_sizes, not transposed)
    d_w = (grouped_matmul_dw(dy, rows, group_sizes, stack.dtype) if transposed
           else grouped_matmul_dw(rows, dy, group_sizes, stack.dtype))
    return d_rows, d_w, None, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(rows, stack, layer, group_sizes, transposed: bool = False):
    """rows [M, K] sorted by expert, stack [L, E, K, N], layer (int32
    scalar, may be traced), group_sizes [E] int32 with sum <= M -> [M, N] in
    ``rows.dtype``; rows past the groups' sum come back undefined.

    ``transposed``: the stack holds each matrix as ``[N, K]`` (``[L, E, N,
    K]``). An operand of a Mosaic call is read in row-major order, and the
    TPU stores an array whose last extent is off the 128 grid (an expert
    width of 1856) with that extent second-to-last, so a ``[.., 2688, 1856]``
    stack handed to the kernel is first copied WHOLE into row-major order;
    stored ``[.., 1856, 2688]`` it is read in place.

    Differentiable in ``rows`` and ``stack`` (``_gmm``): d rows is this
    kernel with ``transposed`` flipped (rows past the groups' sum undefined
    there too), d stack the ``moe_gmm_dw`` kernel's
    ``[E, K, N]`` block of the layer, which JAX places in the stack's
    gradient as the transpose of the slice."""
    w = stack[layer] if isinstance(layer, int) else lax.dynamic_index_in_dim(
        stack, layer, 0, keepdims=False)
    return _gmm(rows, w, lax.stop_gradient(stack),
                jnp.asarray(layer, jnp.int32).reshape(1), group_sizes,
                transposed)
