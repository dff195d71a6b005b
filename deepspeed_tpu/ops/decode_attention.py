"""Pallas paged decode attention: single-token attention against a BLOCK
POOL through per-sequence block tables, reading ONLY the blocks that cover
each slot's valid prefix. TWO kernels read the pool, one a pool dtype:

- ``paged_decode_attention`` (a FLOAT pool; this header and the first half of
  the file): one 64-token block a grid step, resolved in the index maps.
  Whether it beats the XLA gather on given pool shapes is decided by a
  measured micro-bench at serving-engine init
  (``inference/serving.measure_paged_backends``), not a flag.
- ``paged_decode_int8`` (the int8 pool every serve cell has; second half of
  the file, with a header of its own): a SLOT a grid step, its live blocks
  streamed through VMEM in waves of manual DMAs, the int8 recipe of the XLA
  read kept product for product. Whether it takes an engine's decode step is
  decided by a PRICE computed from the engine's shapes
  (``paged_read_price`` below, its constants fitted on the chip), not by a
  flag and not by a measurement at init.

Capability-equivalent of the reference's fused softmax_context decode
kernels (``csrc/transformer/inference/csrc/softmax.cu``, bound at
``pt_binding.cpp:1716-1780``) lifted to the vLLM-style paged layout: the
fixed decode workspace of ``inference_context.h`` becomes a pool of
fixed-size blocks shared across requests, and the gather that XLA would
materialize per step is resolved inside the kernel's index maps instead.

Why a kernel HERE (and not for the old contiguous ring buffer): on the
contiguous layout the windowed-XLA loop already reads O(valid) bytes via
static slices, and the per-layer pallas_call overhead lost end-to-end on
v5e — that kernel was deleted (VERDICT r5 weak #4). On the PAGED layout the
XLA fallback must materialize a gather of the blocks it is handed every
step — a full extra HBM write+read of the working set, and since PR 27 the
only pass it makes over it (the layer's slice, the fill select, the
head-major turn and the widened view around the gather are gone:
models/transformer._gather_blocks). In the float kernel the block table
rides scalar prefetch, the KV index map translates (slot, j) -> pool block
directly, steps beyond a slot's valid prefix clamp to its last valid block
(the pipeline emitter elides same-index DMAs), and ``pl.when`` skips their
compute — per-step HBM traffic is exactly the valid blocks, with no
materialized gather. It walks ONE block a grid step (~0.35 us each before
any byte moves), which a long table does not amortise: the int8 kernel
moves a megabyte-scale wave a step instead.

GQA-native like the training kernel: each program holds the whole
[Nkv, rep, D] query group of one slot; K/V blocks are read once per group.

Layout: q [S, 1, Nq, D] (one in-flight token per slot); pools
[NB, bs, Nkv, D] (token-major, models/transformer.init_paged_cache: the
row a step writes is a whole minor tile); block_tables [S, MB] int32
(entry 0 = reserved trash block — never valid, masked by seq_lens);
seq_lens [S] int32 = valid prefix length per slot. The CURRENT token's (k, v) row arrives separately
(kv_row) and folds into the online softmax at finalize — the caller
scatters it into the pool afterwards, keeping the per-step pool update
O(row), exactly like the ring-buffer path.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
M_FLOOR = -1e20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, kr_ref, vr_ref, o_ref,
            m_s, l_s, acc_s, *, sm_scale, rep, block_size):
    """Grid (S, MB): program (s, j) folds block_tables[s, j] into slot s's
    online softmax. len_ref[s] = valid prefix length (rows < len are
    valid); the fresh (k, v) row joins at finalize."""
    s = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(1)
    ln = len_ref[s]
    nkv, d = q_ref.shape[1], q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(j * block_size < ln)
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale     # [nkv, rep, d]
        k = k_ref[0].astype(jnp.float32)                # [bs, nkv, d]
        v = v_ref[0].astype(jnp.float32)
        # batched over kv heads (dim 0 of q, dim 1 of a block):
        # [nkv, rep, bs]
        sc = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                                 preferred_element_type=jnp.float32)
        t_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, rep, block_size), 2)
        sc = jnp.where(t_pos < ln, sc, NEG_INF)
        m = m_s[:, 0:rep, 0:1]
        l = l_s[:, 0:rep, 0:1]
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(sc, -1, keepdims=True)),
                            M_FLOOR)
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((0,), (1,))),
                                 preferred_element_type=jnp.float32)
        acc_s[:, 0:rep] = acc_s[:, 0:rep] * alpha + pv
        m_s[:, 0:rep] = jnp.broadcast_to(m_new, (nkv, rep, m_s.shape[2]))
        l_s[:, 0:rep] = jnp.broadcast_to(l_new, (nkv, rep, l_s.shape[2]))

    @pl.when(j == nt - 1)
    def _finalize():
        # fold the CURRENT token's row (not yet in the pool), then emit
        q = q_ref[0].astype(jnp.float32) * sm_scale       # [nkv, rep, d]
        kr = kr_ref[0].astype(jnp.float32)                # [nkv, 1, d]
        vr = vr_ref[0].astype(jnp.float32)
        s1 = jax.lax.dot_general(q, kr, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        m = m_s[:, 0:rep, 0:1]
        l = l_s[:, 0:rep, 0:1]
        m_new = jnp.maximum(jnp.maximum(m, s1), M_FLOOR)
        p1 = jnp.exp(s1 - m_new)                          # [nkv, rep, 1]
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p1
        acc = acc_s[:, 0:rep] * alpha + p1 * vr           # [nkv, rep, d]
        l_safe = jnp.where(l_new == 0.0, 1.0, l_new)
        o_ref[0] = (acc / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, seq_lens, *,
                           kv_row=None, sm_scale: Optional[float] = None):
    """q: [S, 1, Nq, D]; k_pool/v_pool: [NB, bs, Nkv, D]; block_tables:
    [S, MB] int32; seq_lens: [S] int32. Returns [S, 1, Nq, D].

    Valid pool rows for slot s are positions < seq_lens[s] (the fresh row
    is NOT in the pool — it arrives as kv_row=(k_row, v_row)
    [S, Nkv, 1, D] and joins the softmax at finalize). Blocks past a
    slot's valid prefix clamp to its last valid block in the index map, so
    their DMAs are elided and per-step HBM traffic is O(valid prefix).
    """
    S, one, Nq, D = q.shape
    NB, bs, Nkv, _ = k_pool.shape
    MB = block_tables.shape[1]
    rep = Nq // Nkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if kv_row is None:
        raise ValueError("paged_decode_attention requires the fresh-row "
                         "fold (kv_row): the serving decode step never "
                         "pre-writes the current token into the pool")
    k_row, v_row = kv_row
    qg = q.reshape(S, Nkv, rep, D)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)

    def kv_index(s, j, tab_ref, len_ref):
        # clamp steps past the valid prefix to the LAST valid block: the
        # pipeline emitter elides the repeated DMA and pl.when skips the
        # compute. len == 0 (fresh slot) clamps to entry 0 (trash block).
        ln = len_ref[s]
        last_valid = jnp.maximum(jax.lax.div(ln + bs - 1, bs) - 1, 0)
        return (tab_ref[s, jnp.minimum(j, last_valid)], 0, 0, 0)

    q_spec = pl.BlockSpec((1, Nkv, rep, D), lambda s, j, t, ln: (s, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bs, Nkv, D), kv_index,
                           memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, Nkv, 1, D), lambda s, j, t, ln: (s, 0, 0, 0),
                            memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # (block_tables, seq_lens)
        grid=(S, MB),
        in_specs=[q_spec, kv_spec, kv_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, Nkv, rep, D),
                               lambda s, j, t, ln: (s, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((Nkv, max(rep, 8), 128), jnp.float32),   # m
            pltpu.VMEM((Nkv, max(rep, 8), 128), jnp.float32),   # l
            pltpu.VMEM((Nkv, max(rep, 8), D), jnp.float32),     # acc
        ],
    )
    kernel = functools.partial(_kernel, sm_scale=float(sm_scale), rep=rep,
                               block_size=bs)
    compiler_params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Nkv, rep, D), q.dtype),
        compiler_params=compiler_params,
        interpret=_interpret(),
        name="paged_decode",
    )(tables, lens, qg, k_pool, v_pool, k_row, v_row)
    return o.reshape(S, 1, Nq, D)


# ---- the int8 pool: a slot's live blocks streamed through VMEM ---------------
#
# One grid step is one SLOT. Its live blocks are fetched by block id out of
# the whole leaf (``pl.ANY``; the layer a prefetched scalar), ``CHUNK`` blocks
# a DMA wave into one of two VMEM buffers, K in a first sweep and V in a
# second: every live byte crosses HBM -> VMEM once and no gathered copy
# exists. Between the sweeps the slot's scores sit in VMEM (float32, one row a
# query head), where the ONE softmax per (slot, head) over pool + fresh row
# and the requantisation of the probabilities per row run: the arithmetic is
# ``models/transformer._paged_list_attention``'s int8 recipe, product for
# product (int8 query x int8 K -> int32, the q and k scales multiplied into
# the scores, probabilities x v scale requantised per row, int8 P x int8 V ->
# int32); only the softmax's sum runs in another order.
#
# A block is stored token-major, ``[bs, G, D]`` with G kv heads, which the
# chip keeps as the matrix ``[bs * G, D]`` of int8 rows (t, g) — four rows a
# 32-bit word: no head can be taken out of it without unpacking every byte.
# So both contractions run over the block AS STORED, against all G heads at
# once, and the result is read where query head and kv head belong together:
#
# - scores: the query rows ``[G * RP, D]`` (RP = the rep query heads of a kv
#   head, padded to whole sublane tiles of 8) against the block transposed
#   give ``[G * RP, bs * G]`` int32, row (g', r), lane (t, g). Of the G row
#   tiles, tile g' is wanted on the lanes with ``g == g'`` alone, and those
#   lane sets are disjoint: masked and OR-ed together the G tiles are ONE
#   dense ``[RP, bs * G]`` tile, row r, lane (t, g) = head (g, r) at position
#   t. Scores, softmax and probabilities live in that COMPACT layout, where a
#   per-(g, r) quantity (the query's scale, the row maximum, the fresh row's
#   score) is a ``[RP, 128]`` PATTERN tile: lane l holds kv head ``l % G``.
# - P.V: the compact int8 probabilities are spread back over the G row tiles
#   under the same masks — ``[G * RP, bs * G]``, zeros where ``g != g'`` —
#   and contracted with the block ``[bs * G, D]``: an exact ``[G * RP, D]``
#   int32 partial sum a block (the zeros add zeros).
#
# The MXU does G times the needed products; with 6 query rows a kv head it
# is bound by loading the stationary operand, the block, either way. The
# scales of a position multiply in the compact layout, so they are wanted in
# ITS lane order, (t, g), where the plane stores (g, t): XLA turns the layer's
# two planes once a call (a 64th of the pool's bytes) into ``[NB, bs * G /
# 128, 128]``, a slab a block, and the K sweep's waves fetch a live block's
# two slabs with it. The blocks of a wave are worked UNROLL to a loop trip, so
# that one block's vector work runs under the next one's matmul (3.45 -> 2.34
# ms a call at Trinity's shape from 1 to 8, PERF.md section 6, PR 50).

CHUNK = 16          # blocks a DMA wave: 1 MiB of int8 at 64 x 8 x 128
UNROLL = 8          # blocks of a wave worked on a loop trip (divides CHUNK)
LANES = 128
# the scores of one slot must fit beside the two waves' buffers
VMEM_LIMIT = 48 * 2 ** 20
SCORES_MAX_BYTES = 24 * 2 ** 20


def int8_kernel_fits(*, MB: int, block_size: int, n_kv: int, rep: int,
                     head_dim: int) -> bool:
    """Whether ``paged_decode_int8`` can be built at these shapes: whole lane
    tiles a block, kv heads that divide a lane tile, and a slot's float32
    scores (``MB`` blocks wide) inside VMEM."""
    G, bsG = n_kv, block_size * n_kv
    RP = -(-rep // 8) * 8
    return (LANES % G == 0 and bsG % LANES == 0 and head_dim % LANES == 0
            and block_size % 4 == 0
            and RP * (MB + CHUNK) * bsG * 4 <= SCORES_MAX_BYTES)


# ---- the price of the two reads (fitted on a v5e: PERF.md section 6, PR 50) --
#
# Both in BYTES at the chip's stream rate (819 GB/s: 1e6 bytes = 1.22 us), from
# the engine's shapes alone and at ONE load: every slot at a QUARTER of its
# table. That is the lightest load the XLA read has a list for
# (``serving._list_ladder``'s first rung: below it the list is no shorter) and
# the load where the kernel's fixed costs weigh most: a live byte costs the
# kernel less than it costs XLA, so a kernel that is the cheaper read there is
# the cheaper read at every load above. A block's bytes are its int8 K and V
# rows and their float32 scales.
#
# The XLA read moves every LISTED block three times (the gather's read and
# write, the contraction's read) and the float32 scores' per-slot view —
# slots x query heads x the WHOLE table's positions, whatever is listed —
# VIEW_PASSES times (scores laid into the view, mask, softmax, x v scale,
# requantise, back to the list). Fitted by relative error on 40 calls at ten
# shapes: within 25 % from 64 to 5 120 listed blocks; at Trinity's 11 264 the
# chip takes 1.4 x the price (6.4 / 10.8 ms measured at the half and whole
# rung against 4.7 / 7.3 priced), the side that cannot flip the choice.
XLA_LIST_PASSES = 3.0
VIEW_PASSES = 12.0
# The kernel streams the live blocks once, at KERNEL_STREAM of the stream rate
# (606 GB/s: waves of 16 block DMAs, both contractions over all kv heads at
# once). Ahead of it XLA turns the layer's two scale planes into the kernel's
# lane order — every block of the POOL, SCALE_PASSES times its scale rows:
# 24 ns a block — and it pays KERNEL_SLOT_BYTES a slot (a grid step: the
# first K wave nothing hides, the softmax between the sweeps) and
# KERNEL_CALL_BYTES a call (the query's quantisation and layouts before, the
# rescale after: 0.12 ms of small ops). Fitted on the same 40 calls: within
# 10 % at every shape of 8 kv heads from 1 536 table columns up.
KERNEL_STREAM = 0.74
SCALE_PASSES = 5.0
KERNEL_SLOT_BYTES = 1.15e6
KERNEL_CALL_BYTES = 98e6
# what the two must differ by before the difference is one (0.04 ms, as
# ``grouped_matmul.TIE_BYTES``): inside it the call keeps the program it was
READ_TIE_BYTES = 33e6
PRICED_FILL = 0.25


def paged_read_price(*, slots: int, MB: int, block_size: int, n_kv: int,
                     rep: int, head_dim: int, num_blocks: int = None) -> dict:
    """{"xla_bytes", "kernel_bytes", "choice", "why"}: what one layer's decode
    read of the int8 pool costs either way at an engine's shapes (every slot
    at ``PRICED_FILL`` of its table), and which one it takes — "pallas" where
    the kernel can be built, the chip stores the block as the kernel reads it,
    a slot's priced context fills a DMA wave, and the kernel is cheaper by
    more than the tie band; "xla" otherwise."""
    block = 2 * block_size * n_kv * (head_dim + 4)
    scales = 2 * block_size * n_kv * 4
    num_blocks = num_blocks or slots * MB + 1
    live = PRICED_FILL * slots * MB * block
    view = slots * n_kv * rep * MB * block_size * 4
    xla = XLA_LIST_PASSES * live + VIEW_PASSES * view
    kernel = (live / KERNEL_STREAM + SCALE_PASSES * num_blocks * scales
              + slots * KERNEL_SLOT_BYTES + KERNEL_CALL_BYTES)
    out = {"xla_bytes": int(xla), "kernel_bytes": int(kernel)}
    if not int8_kernel_fits(MB=MB, block_size=block_size, n_kv=n_kv, rep=rep,
                            head_dim=head_dim):
        return dict(out, choice="xla", why="the kernel cannot be built")
    if n_kv % 8:
        # fewer kv heads than an (8, 128) tile has rows: the chip stores the
        # block head-major ([L, NB, bs, 2, D] as {4,2,3,1,0}), and the
        # kernel's token-major view of it would be a copy of the whole pool
        return dict(out, choice="xla",
                    why=f"{n_kv} kv heads: the chip stores the block "
                        "head-major")
    if not kernel + READ_TIE_BYTES < xla:
        return dict(out, choice="xla",
                    why="the XLA read is cheaper, or inside the tie band")
    if PRICED_FILL * MB < CHUNK:
        # the kernel's design, not a constant of the fit: a sweep hides a
        # fetch only behind the wave before it, so a slot whose priced
        # context is under ONE wave of CHUNK blocks (a table under 4 096
        # positions at 64-token blocks) pays every fetch in the open, twice a
        # grid step, and its set-up is never amortised. Such engines read
        # 64-512 listed blocks a call, a few MB: where the two prices lie
        # within 0.1 ms of each other whichever is lower
        return dict(out, choice="xla",
                    why=f"a slot's priced context ({PRICED_FILL * MB:g} "
                        f"blocks) is under one wave of {CHUNK}")
    return dict(out, choice="pallas", why="the kernel is the cheaper read")


def _class_reduce(x, G, op):
    """[RP, 128] -> every lane holds ``op`` over the lanes of its class
    ``l % G`` (a butterfly of lane rotations)."""
    shift = G
    while shift < LANES:
        x = op(x, pltpu.roll(x, shift, 1))
        shift *= 2
    return x


def _int8_kernel(layer_ref, tab_ref, len_ref, qp_ref, qs_ref, self_ref,
                 mask_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
                 acc_ref, ps_ref, pself_ref, buf, ks_ref, vs_ref, sc, sem,
                 *, G, RP, bs, MB, sm):
    s = pl.program_id(0)
    layer = layer_ref[0]
    ln = len_ref[s]
    nb = (ln + bs - 1) // bs                       # live blocks of the slot
    n_chunks = (nb + CHUNK - 1) // CHUNK
    bsG = bs * G
    tiles = bsG // LANES                           # lane tiles of a block
    rows = G * RP

    def copy(pool, blk, j, slot):
        return pltpu.make_async_copy(pool.at[layer, blk], buf.at[slot, j],
                                     sem.at[slot])

    def scale_copies(blk, b, slot):
        """A block's K and V scale rows, in lane order, to their place among
        the slot's (they ride the K sweep's waves: the softmax between the
        sweeps wants every V scale)."""
        return [pltpu.make_async_copy(plane.at[blk], ref.at[b],
                                      sem.at[2 + slot])
                for plane, ref in ((ks_hbm, ks_ref), (vs_hbm, vs_ref))]

    def wave(pool, i, slot, start: bool):
        """Chunk i's live blocks into ``buf[slot]``: started, or waited for
        (a wait names a copy of the same size, whatever block)."""
        def one(j, carry):
            b = i * CHUNK + j
            blk = tab_ref[s * MB + b] if start else 0
            copies = [copy(pool, blk, j, slot)]
            if pool is k_hbm:
                copies += scale_copies(blk, b, slot)
            for c in copies:
                c.start() if start else c.wait()
            return carry
        lax.fori_loop(0, jnp.minimum(CHUNK, nb - i * CHUNK), one, 0)

    def first_wave(pool):
        @pl.when(nb > 0)
        def _first():
            wave(pool, 0, 0, True)

    def sweep(pool, block_fn, carry):
        """``carry = block_fn(b, buf[slot, j], carry)`` over the live blocks
        b of the slot, chunk i + 1 on its way while chunk i is worked on
        (chunk 0 started by the caller: ``first_wave``)."""
        def chunk(i, carry):
            slot = i % 2

            @pl.when(i + 1 < n_chunks)
            def _ahead():
                wave(pool, i + 1, 1 - slot, True)
            wave(pool, i, slot, False)

            def group(j, carry):
                # UNROLL blocks a trip, so that one block's vector work
                # runs under the next one's matmul. Past the slot's last
                # block a group works on what the buffer held: every score
                # there is masked by the length, every probability 0
                for u in range(UNROLL):
                    carry = block_fn(i * CHUNK + j * UNROLL + u,
                                     buf.at[slot, j * UNROLL + u], carry)
                return carry
            live = jnp.minimum(CHUNK, nb - i * CHUNK)
            return lax.fori_loop(0, (live + UNROLL - 1) // UNROLL, group,
                                 carry)
        return lax.fori_loop(0, n_chunks, chunk, carry)

    def lanes_of(b, c):
        return pl.ds(pl.multiple_of(b * bsG + c * LANES, LANES), LANES)

    def scale_row(ref, b, c):
        return jnp.broadcast_to(ref[b, pl.ds(c, 1), :], (RP, LANES))

    qp = qp_ref[...]                                           # [rows_p, D]
    qs = qs_ref[...]                                           # [RP, 128]
    lane = lax.broadcasted_iota(jnp.int32, (RP, LANES), 1)

    # ---- sweep 1: K -> the slot's scores, compact, and their maximum ----
    def score_block(b, kb, m):
        s_all = lax.dot_general(qp, kb[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)
        for c in range(tiles):
            cols = slice(c * LANES, (c + 1) * LANES)
            x = s_all[0:RP, cols] & mask_ref[0:RP, :]
            for g in range(1, G):
                x = x | (s_all[g * RP:(g + 1) * RP, cols]
                         & mask_ref[g * RP:(g + 1) * RP, :])
            x = x.astype(jnp.float32) * qs * scale_row(ks_ref, b, c)
            x = x * sm
            pos = b * bs + (c * LANES + lane) // G
            x = jnp.where(pos < ln, x, NEG_INF)
            sc[:, lanes_of(b, c)] = x
            m = jnp.maximum(m, x)
        return m

    first_wave(k_hbm)
    m = sweep(k_hbm, score_block, jnp.full((RP, LANES), NEG_INF, jnp.float32))
    first_wave(v_hbm)              # on its way under the softmax
    s_self = self_ref[...]
    m = jnp.maximum(_class_reduce(m, G, jnp.maximum), s_self)

    # ---- the softmax over pool + fresh row, and P x v-scale per row ----
    def exp_block(b, l):
        for c in range(tiles):
            e = jnp.exp(sc[:, lanes_of(b, c)] - m)
            sc[:, lanes_of(b, c)] = e
            l = l + e
        return l
    l = lax.fori_loop(0, nb, exp_block, jnp.zeros((RP, LANES), jnp.float32))
    e_self = jnp.exp(s_self - m)
    l = _class_reduce(l, G, jnp.add) + e_self

    def pv_block(b, mx):
        for c in range(tiles):
            pv = sc[:, lanes_of(b, c)] / l * scale_row(vs_ref, b, c)
            sc[:, lanes_of(b, c)] = pv
            mx = jnp.maximum(mx, pv)
        return mx
    mx = lax.fori_loop(0, nb, pv_block, jnp.zeros((RP, LANES), jnp.float32))
    ps = jnp.maximum(_class_reduce(mx, G, jnp.maximum) / 127.0, 1e-20)
    ps_ref[...] = ps
    pself_ref[...] = e_self / l

    # ---- sweep 2: V, against the probabilities spread over the heads ----
    def value_block(b, vb, acc):
        parts = []
        for c in range(tiles):
            pq = jnp.clip(jnp.round(sc[:, lanes_of(b, c)] / ps), 0, 127
                          ).astype(jnp.int32)
            parts.append(jnp.concatenate(
                [pq & mask_ref[g * RP:(g + 1) * RP, :] for g in range(G)],
                axis=0))
        pd = jnp.concatenate(parts, axis=1)                    # [rows, bsG]
        if qp.shape[0] > rows:
            pd = jnp.concatenate(
                [pd, jnp.zeros((qp.shape[0] - rows, bsG), jnp.int32)], axis=0)
        return acc + lax.dot_general(
            pd.astype(jnp.int8), vb[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    acc_ref[...] = sweep(v_hbm, value_block,
                         jnp.zeros(acc_ref.shape, jnp.int32))


def paged_decode_int8(q, k_pool, v_pool, k_scale, v_scale, block_tables,
                      seq_lens, layer, *, kv_row, sm_scale=None):
    """One token per slot against the int8 paged pool, its live blocks read
    once, HBM -> VMEM -> MXU (the header above says how).

    q: [S, 1, Nq, D]; k_pool / v_pool: the WHOLE leaves [L, NB, bs, Nkv, D]
    int8 and k_scale / v_scale the planes [L, NB, Nkv * bs] float32, with
    ``layer`` the (traced) plane to read; block_tables: [S, MB] int32 (0 =
    the trash block, in unused columns); seq_lens: [S] rows of each slot in
    the pool (0: an inactive slot, which reads nothing); kv_row: the fresh
    (k, v) [S, Nkv, 1, D], folded into the same softmax. Returns
    [S, 1, Nq, D], ``_paged_list_attention``'s int8 result (the softmax's
    sum in another order: a probability may round to the neighbouring int8
    step)."""
    from deepspeed_tpu.models.transformer import _quant_query
    S, _, Nq, D = q.shape
    L, NB, bs, G, _ = k_pool.shape
    MB = block_tables.shape[1]
    rep = Nq // G
    RP = -(-rep // 8) * 8
    rows, bsG = G * RP, bs * G
    rows_p = -(-rows // 32) * 32                   # whole int8 tiles
    MBp = -(-MB // CHUNK) * CHUNK                  # whole waves
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    assert int8_kernel_fits(MB=MB, block_size=bs, n_kv=G, rep=rep,
                            head_dim=D), (q.shape, k_pool.shape, MB)
    k_row, v_row = kv_row
    qg = q.reshape(S, G, rep, D)
    qi, qs = _quant_query(qg.astype(jnp.float32))

    # the query rows (g, r), r padded to RP and the rows to whole int8 tiles
    qp = jnp.pad(qi, [(0, 0), (0, 0), (0, RP - rep), (0, 0)])
    qp = jnp.pad(qp.reshape(S, rows, D), [(0, 0), (0, rows_p - rows), (0, 0)])

    def pattern(x):        # [S, G, rep] -> [S, RP, 128]: lane l = head l % G
        x = jnp.pad(x.transpose(0, 2, 1), [(0, 0), (0, RP - rep), (0, 0)])
        return jnp.tile(x, (1, 1, LANES // G))

    def unpattern(x):      # the way back
        return x[:, :rep, :G].transpose(0, 2, 1)

    s_self = jnp.einsum("bgrd,bgtd->bgrt", qg, k_row.astype(qg.dtype)
                        ).astype(jnp.float32)[..., 0] * sm_scale

    def lane_order(plane):
        """The layer's scale plane in the compact layout's lane order:
        [NB, G * bs], head-major as stored, -> [NB, bs * G / 128, 128],
        position-major; a block's rows are one slab, fetched by block id like
        its K and V. One pass over the plane (a 64th of the pool's bytes),
        where a gather of the table's rows costs 19 ns a row."""
        p = lax.dynamic_index_in_dim(plane, layer, 0, keepdims=False)
        return p.reshape(NB, G, bs).transpose(0, 2, 1).reshape(
            NB, bsG // LANES, LANES)

    # lane l of a block's tile belongs to kv head l % G: all ones there
    mask = jnp.where(
        jnp.arange(LANES)[None, :] % G == jnp.arange(rows)[:, None] // RP,
        -1, 0).astype(jnp.int32)
    per_slot = lambda *shape: pl.BlockSpec(       # noqa: E731
        (None,) + shape, lambda s, *_: (s,) + (0,) * len(shape))
    kernel = functools.partial(_int8_kernel, G=G, RP=RP, bs=bs, MB=MB,
                               sm=float(sm_scale))
    acc, ps, p_self = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # layer, tables, lengths
            grid=(S,),
            in_specs=[per_slot(rows_p, D), per_slot(RP, LANES),
                      per_slot(RP, LANES),
                      pl.BlockSpec((rows, LANES), lambda s, *_: (0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=[per_slot(rows_p, D), per_slot(RP, LANES),
                       per_slot(RP, LANES)],
            scratch_shapes=[pltpu.VMEM((2, CHUNK, bsG, D), jnp.int8),
                            pltpu.VMEM((MBp, bsG // LANES, LANES), jnp.float32),
                            pltpu.VMEM((MBp, bsG // LANES, LANES), jnp.float32),
                            pltpu.VMEM((RP, MBp * bsG), jnp.float32),
                            pltpu.SemaphoreType.DMA((4,))]),
        out_shape=[jax.ShapeDtypeStruct((S, rows_p, D), jnp.int32),
                   jax.ShapeDtypeStruct((S, RP, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((S, RP, LANES), jnp.float32)],
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(),
        name="paged_decode_int8",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(block_tables, jnp.int32).reshape(-1),
      jnp.asarray(seq_lens, jnp.int32),
      qp, pattern(qs), pattern(s_self), mask,
      k_pool.reshape(L, NB, bsG, D), v_pool.reshape(L, NB, bsG, D),
      lane_order(k_scale), lane_order(v_scale))
    acc = acc[:, :rows].reshape(S, G, RP, D)[:, :, :rep]
    out = (acc.astype(jnp.float32) * unpattern(ps)[..., None]).astype(q.dtype)
    out = out + unpattern(p_self)[..., None].astype(q.dtype) \
        * v_row.astype(q.dtype)
    return out.reshape(S, 1, Nq, D)
