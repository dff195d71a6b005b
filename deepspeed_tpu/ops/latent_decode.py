"""Pallas decode read of a LATENT paged pool (``models/latent_attention.py``):
one token a slot, every head against the ONE row a cached token keeps, a
slot's live blocks streamed HBM -> VMEM -> MXU once.

The third body of the paged decode read, on ``paged_decode_int8``'s plan
(``ops/decode_attention.py``): the WHOLE leaf ``[planes, NB, block, lanes]``
stays in HBM with the plane a prefetched scalar; the grid is the slots; a
slot's live blocks — its table's first ``ceil(len / block)`` entries, nothing
of the table's width beyond them — arrive in waves of ``CHUNK`` block DMAs,
wave i + 1 on its way while wave i is worked on. What is different is what a
latent plane allows: it is both K and V, so ONE sweep serves both
contractions (flash-decoding's online softmax: a wave's scores, the running
maximum and sum, the rescaled accumulator), and every head reads the same
rows, so a wave is ONE matmul each way at ``heads`` rows — ``[heads, lanes] x
[wave, lanes]^T`` and ``[heads, wave] x [wave, rank]`` — with no per-head
layout at all. Nothing is quantised: the pool is the float dtype.

The wave pipeline runs ACROSS slots. The grid's steps run in order, so under
a slot's LAST wave the first wave of the slot after it is fetched into the
buffer that wave leaves free (that slot's table row and length are prefetched
scalars too), and the slot begins by WAITING for it; which buffer is next is
carried from grid step to grid step in SMEM. A slot that reads nothing hands
the baton on — it starts its successor's wave itself, both buffers being free
—, the last slot starts nothing (no copy is outstanding when the kernel ends),
and only slot 0 starts its own first wave with nothing to hide it behind. So
EVERY live slot but a call's first finds its first wave started before its
grid step began, by construction: a counter of that would read ``(live - 1) /
live`` and say nothing. What says how much of the fetch the work covered is the
kernel's share of its roofline (the benchmark's ``sat_mla_read_roofline``).
The rows enter a slot's softmax in the order they always did, whichever
buffer its waves start in: the result does not depend on the slots before it.

The fresh row (the token's own, not in the pool yet) is folded in by the
caller from what the kernel returns — the unnormalised accumulator, the running
maximum and the sum — in a few small XLA ops.

``latent_read_price`` prices this read against the XLA list read at an
engine's shapes, in bytes at the chip's stream rate, as ``paged_read_price``
does for the int8 pool; ``ServingEngine._select_backend`` takes the cheaper.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import decode_attention as _da

NEG_INF = _da.NEG_INF

CHUNK = 16          # blocks a DMA wave: 1.25 MiB of bf16 at 64 x 640
SUB = 4             # blocks of a wave worked on a loop trip (divides CHUNK)
LANES = 128
ROWS = 16           # query rows are padded to whole packed sublane tiles
VMEM_LIMIT = 32 * 2 ** 20


def kernel_fits(*, block_size: int, lanes: int, rank: int, itemsize: int
                ) -> bool:
    """Whether ``latent_decode`` can be built: rows in whole lane tiles, the
    latent's columns too, whole sublane tiles a block, a 16-bit pool."""
    return (lanes % LANES == 0 and rank % LANES == 0 and rank <= lanes
            and block_size % ROWS == 0 and itemsize == 2)


# ---- the price of the two reads (fitted on a v5e: PERF.md section 6, PR 52, 56)
#
# Both in BYTES at the chip's stream rate (819 GB/s: 1e6 bytes = 1.22 us), from
# the engine's shapes alone and at ONE load, every slot at a QUARTER of its
# table (``paged_read_price`` says why that load). A block's bytes are its
# rows as stored.
#
# The XLA list read moves every LISTED block XLA_LIST_PASSES times (the
# gather's read and write, the two contractions' reads, and — what a per-head
# pool's read does not have — a float32 partial sum of [heads, lanes] a RUN of
# two blocks, written and gathered back per slot) and the float32 scores'
# per-slot view, slots x heads x the WHOLE table's positions whatever is
# listed, VIEW_PASSES times. Fitted on three calls at the cell's shape (128
# slots x 76 columns, 20 heads, 640 lanes: 3.86 / 6.55 / 6.95 ms at 4 864 /
# 9 728 / 9 728 listed blocks).
XLA_LIST_PASSES = 5.5
VIEW_PASSES = 19.0
# The kernel streams the live blocks once, at KERNEL_STREAM of the stream rate
# (570 GB/s of stored bytes: both contractions run at `heads` rows, so the MXU
# is bound by loading the blocks as the stationary operand), and pays
# KERNEL_SLOT_BYTES a slot and KERNEL_CALL_BYTES a call (the query's padding
# before, the fold of the fresh row after). A slot's 1.5 us are what a grid step
# costs with its first wave fetched under the slot before it: the step itself
# and its q / out blocks, a last part rounded up to SUB blocks, and what of the
# fetch a short last wave does not cover. (Each slot starting its own first
# wave in the open, as PR 52 had it, paid 2.1e6 = 2.6 us: 0.68 / 1.00 / 1.37 ms
# on the same three calls.) Fitted on them, PR 56: 0.55 / 0.87 / 1.24 ms, within
# 2 %; the stream's rate did not move.
KERNEL_STREAM = 0.70
KERNEL_SLOT_BYTES = 1.2e6
KERNEL_CALL_BYTES = 10e6
# what the two must differ by before the difference is one (0.04 ms, as
# ``decode_attention.READ_TIE_BYTES``)
READ_TIE_BYTES = 33e6
PRICED_FILL = 0.25


def latent_read_price(*, slots: int, MB: int, block_size: int, heads: int,
                      lanes: int, rank: int, itemsize: int = 2) -> dict:
    """{"xla_bytes", "kernel_bytes", "choice", "why"}: what one plane's decode
    read of the latent pool costs either way at an engine's shapes (every slot
    at ``PRICED_FILL`` of its table), and which one it takes."""
    block = block_size * lanes * itemsize
    live = PRICED_FILL * slots * MB * block
    view = slots * heads * MB * block_size * 4
    xla = XLA_LIST_PASSES * live + VIEW_PASSES * view
    kernel = (live / KERNEL_STREAM + slots * KERNEL_SLOT_BYTES
              + KERNEL_CALL_BYTES)
    out = {"xla_bytes": int(xla), "kernel_bytes": int(kernel)}
    if not kernel_fits(block_size=block_size, lanes=lanes, rank=rank,
                       itemsize=itemsize):
        return dict(out, choice="xla", why="the kernel cannot be built")
    if not kernel + READ_TIE_BYTES < xla:
        return dict(out, choice="xla",
                    why="the XLA read is cheaper, or inside the tie band")
    return dict(out, choice="pallas", why="the kernel is the cheaper read")


def _kernel(layer_ref, tab_ref, len_ref, q_ref, pool, acc_ref, m_ref, l_ref,
            buf, sem, turn, *, bs, MB, rank, sm):
    s, S = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    ln = len_ref[s]
    blocks = lambda rows: (rows + bs - 1) // bs    # noqa: E731
    nb = blocks(ln)                                # live blocks of the slot
    n_chunks = (nb + CHUNK - 1) // CHUNK
    # the slot after this one: its first wave is started HERE (none after the
    # last slot: no copy may be outstanding when the kernel ends)
    nxt = jnp.minimum(s + 1, S - 1)
    nb_next = jnp.where(s + 1 < S, blocks(len_ref[nxt]), 0)
    wave_rows, part_rows = CHUNK * bs, SUB * bs

    def wave(first, count, slot, start: bool):
        """``count`` blocks from table entry ``first`` on into ``buf[slot]``:
        started, or waited for (a wait names a copy of the same size,
        whatever block)."""
        def one(j, carry):
            blk = tab_ref[first + j] if start else 0
            c = pltpu.make_async_copy(
                pool.at[layer, blk],
                buf.at[slot, pl.ds(pl.multiple_of(j * bs, bs), bs)],
                sem.at[slot])
            c.start() if start else c.wait()
            return carry
        lax.fori_loop(0, jnp.minimum(CHUNK, count), one, 0)

    @pl.when(s == 0)
    def _open():
        # what a wave's tail holds past the slot's last block is whatever the
        # buffer held: rows of an earlier wave (finite), never uninitialised
        # memory — their probabilities are 0, and 0 x NaN is not
        buf[...] = jnp.zeros_like(buf)
        # the ONE first wave nothing hides
        turn[0] = 0
        wave(0, nb, 0, True)

    first_buf = turn[0]            # where the slot before put this one's wave 0

    @pl.when(n_chunks == 0)
    def _idle():
        # a slot that reads nothing hands the baton on: both buffers are free
        wave(nxt * MB, nb_next, first_buf, True)

    q = q_ref[...]                                             # [R, lanes]
    R = q.shape[0]

    def chunk(i, carry):
        slot = (first_buf + i) % 2
        # under this wave's work the next one is fetched into the other
        # buffer: the slot's own, or — under its last — wave 0 of slot s + 1
        more = i + 1 < n_chunks
        wave(jnp.where(more, s * MB + (i + 1) * CHUNK, nxt * MB),
             jnp.where(more, nb - (i + 1) * CHUNK, nb_next), 1 - slot, True)
        live = jnp.minimum(CHUNK, nb - i * CHUNK)
        wave(0, live, slot, False)

        def part(u, carry):
            # SUB blocks of the wave a trip; past the slot's last block a
            # part works on what the buffer held, every score masked
            m, l, acc = carry
            at = pl.multiple_of(u * part_rows, part_rows)
            rows = buf[slot, pl.ds(at, part_rows)]             # [part, lanes]
            sc = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * sm
            pos = i * wave_rows + at \
                + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(pos < ln, sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = lax.dot_general(p.astype(rows.dtype), rows[:, :rank],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            return m_new, l, acc * alpha + pv

        return lax.fori_loop(0, (live + SUB - 1) // SUB, part, carry)

    m, l, acc = lax.fori_loop(
        0, n_chunks, chunk,
        (jnp.full((R, 1), NEG_INF, jnp.float32),
         jnp.zeros((R, 1), jnp.float32), jnp.zeros((R, rank), jnp.float32)))
    turn[0] = (first_buf + n_chunks) % 2
    acc_ref[...] = acc
    m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l, l_ref.shape)


def latent_decode(q, pool, block_tables, seq_lens, layer, row, *, rank: int,
                  sm_scale: float):
    """Softmax(q . rows) x the rows' first ``rank`` columns, one token a slot.

    q: [S, heads, lanes] (the absorbed query, zeros in the pad lanes); pool:
    the WHOLE leaf [planes, NB, block, lanes] with ``layer`` the (traced)
    plane to read; block_tables: [S, MB] int32 (0 = the trash block, in unused
    columns); seq_lens: [S] rows of each slot in the pool (0: a slot that reads
    nothing); row: the fresh row [S, lanes], folded into the same softmax.
    Returns [S, heads, rank] in q's dtype: ``latent_read``'s result (the
    softmax's sums in another order)."""
    S, Nq, lanes = q.shape
    L, NB, bs, _ = pool.shape
    MB = block_tables.shape[1]
    assert kernel_fits(block_size=bs, lanes=lanes, rank=rank,
                       itemsize=pool.dtype.itemsize), (q.shape, pool.shape)
    R = -(-Nq // ROWS) * ROWS
    qp = jnp.pad(q.astype(pool.dtype), [(0, 0), (0, R - Nq), (0, 0)])
    per_slot = lambda *shape: pl.BlockSpec(       # noqa: E731
        (None,) + shape, lambda s, *_: (s,) + (0,) * len(shape))
    kernel = functools.partial(_kernel, bs=bs, MB=MB, rank=rank,
                               sm=float(sm_scale))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # layer, tables, lengths
            grid=(S,),
            in_specs=[per_slot(R, lanes), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[per_slot(R, rank), per_slot(R, LANES),
                       per_slot(R, LANES)],
            scratch_shapes=[pltpu.VMEM((2, CHUNK * bs, lanes), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((S, R, rank), jnp.float32),
                   jax.ShapeDtypeStruct((S, R, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((S, R, LANES), jnp.float32)],
        compiler_params=None if _da._interpret() else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_da._interpret(),
        name="latent_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(block_tables, jnp.int32).reshape(-1),
      jnp.asarray(seq_lens, jnp.int32), qp, pool)
    acc, m, l = acc[:, :Nq], m[:, :Nq, :1], l[:, :Nq, :1]
    # the fresh row joins the softmax the kernel left open
    s_self = jnp.einsum("shd,sd->sh", q, row.astype(q.dtype),
                        preferred_element_type=jnp.float32)[..., None] \
        * sm_scale
    top = jnp.maximum(m, s_self)
    a, e = jnp.exp(m - top), jnp.exp(s_self - top)
    out = (acc * a + e * row.astype(jnp.float32)[:, None, :rank]) \
        / (l * a + e)
    return out.astype(q.dtype)
