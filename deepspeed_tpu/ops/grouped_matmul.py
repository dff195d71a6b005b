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


def grouped_matmul(rows, stack, layer, group_sizes, transposed: bool = False):
    """rows [M, K] sorted by expert, stack [L, E, K, N], layer (int32
    scalar, may be traced), group_sizes [E] int32 with sum <= M -> [M, N] in
    ``rows.dtype``; rows past the groups' sum come back undefined.

    ``transposed``: the stack holds each matrix as ``[N, K]`` (``[L, E, N,
    K]``). An operand of a Mosaic call is read in row-major order, and the
    TPU stores an array whose last extent is off the 128 grid (an expert
    width of 1856) with that extent second-to-last, so a ``[.., 2688, 1856]``
    stack handed to the kernel is first copied WHOLE into row-major order;
    stored ``[.., 1856, 2688]`` it is read in place."""
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
    )(jnp.asarray(layer, jnp.int32).reshape(1), offsets, expert, tile,
      rows, stack)
    return out[:M] if pad else out
