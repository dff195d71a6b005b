"""The two permutations of the sorted expert dispatch as Pallas TPU kernels
that move the LIVE rows alone (``moe/sharded_moe._sorted_ffn``, training).

On a chip's share of a layer's experts most (token, expert) pairs are another
chip's: they sort behind the last group, the grouped matmul never visits
them, and nothing here reads, writes, pads, fills or selects their rows. As in
``ops/grouped_matmul.py`` every buffer keeps its static ``[T k, H]`` shape and
the GRID is bounded at run time — by the live rows here, by the visits there.

A row moves by DMA, and Mosaic slices an array only along an axis it does not
tile: one row of a ``[rows, H]`` array is refused whatever the dtype (a slice
of the second-to-last axis must be a multiple of 8 rows, and bf16 packs two
rows a sublane besides). So the SOURCE of a move is the rows *packed*
(``pack_rows``, the kernel ``moe_rows_pack`` over the live tiles): a bf16 row
of H columns as H / 2 uint32 words, columns j and j + H/2 in word j, shaped
``[rows, H / 256, 1, 128]`` — the rows on a leading axis, each one contiguous
run of bytes. The kernels DMA packed rows into VMEM, many in flight, and
unpack there with sublane-strided reads: the two halves of a word ARE the two
columns in float32, which is where the weights multiply and the choices sum.

- ``moe_rows_gather``: ``out[i] = x[index[i]]`` for ``i < n_live``, the
  dispatch (``index = order // k``); or, ``weighted``, the gradient of the
  combine: ``scale[i] * x[index[i]]`` by its rows (``x`` = d y, ``scale`` =
  the pair's router weight) and ``<x[index[i]], other[i]>`` by the weights.
- ``moe_rows_combine``: ``y[t] = sum_j w[t, j] * rows[pos[t, j]]`` over the
  live pairs of token t, summed in float32; the combine, and with unit
  weights the gradient of the dispatch.

Each entry point is a ``jax.jit``: a train step calls them some forty times
(layers x forward, replay, backward), and under the jit a kernel is traced
and lowered once for each of its few signatures, not once a call — the
step's set-up time is host time too.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows a grid step of the gather / the pack moves, and tokens one of the
# combine sums; each is walked in sub-tiles whose DMAs are issued one
# sub-tile ahead of the vector work (two slots of VMEM)
ROW_TILE, ROW_SUB = 256, 64
TOKEN_TILE, TOKEN_SUB = 128, 32
# the vector work runs over groups of 16 rows: one packed bf16 tile
GROUP = 16


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def supported(T: int, H: int, k: int) -> bool:
    """Both halves of a row of H fall on whole lanes, and the T tokens and
    their T k pairs on whole tiles."""
    return (H % (2 * LANES) == 0 and T % TOKEN_TILE == 0
            and (T * k) % ROW_TILE == 0)


def _halves(words):
    """uint32 [.., n] -> the two bf16 columns of each word, in float32."""
    return (lax.bitcast_convert_type(words << 16, jnp.float32),
            lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                     jnp.float32))


def _issue_eight(one, n: int):
    """``one(r)`` for r in 0 .. n - 1 (n a multiple of 8), eight a trip."""
    def eight(i, carry):
        for j in range(8):
            one(i * 8 + j)
        return carry
    lax.fori_loop(0, n // 8, eight, 0)


def _flat(ref):
    """Packed rows ``[n, chunks, 1, 128]`` in VMEM as ``[n x chunks, 128]``:
    row r's chunk c is line ``r x chunks + c``."""
    n, chunks = ref.shape[:2]
    return ref.reshape(n * chunks, LANES), chunks


def _group(ref, r0, c: int):
    """Chunk c of the packed rows r0 .. r0 + 15 of ``ref`` as one (16, 128)
    uint32 value: two sublane-strided reads."""
    flat, chunks = _flat(ref)
    return jnp.concatenate(
        [flat[pl.ds((r0 + half) * chunks + c, 8, stride=chunks), :]
         for half in (0, 8)], axis=0)


# ---- gather ------------------------------------------------------------------

def _gather_kernel(idx_ref, *refs):
    *refs, buf, sem = refs
    weighted = len(refs) > 2
    if weighted:
        scale_ref, other_ref, x_hbm, out_ref, dot_ref = refs
    else:
        x_hbm, out_ref = refs
    tm, sub, h2 = out_ref.shape[0], buf.shape[1], buf.shape[2] * LANES

    def copy(src, r, slot):
        return pltpu.make_async_copy(x_hbm.at[src], buf.at[slot, r],
                                     sem.at[slot])

    def issue(s, slot):
        _issue_eight(lambda r: copy(idx_ref[0, s * sub + r], r, slot).start(),
                     sub)

    def sub_tile(s, carry):
        slot = s % 2

        @pl.when(s + 1 < tm // sub)
        def _ahead():
            issue(s + 1, 1 - slot)
        _issue_eight(lambda r: copy(0, 0, slot).wait(), sub)

        def group(g, carry):
            r0 = pl.multiple_of(g * GROUP, GROUP)
            rows = pl.ds(pl.multiple_of(s * sub + r0, GROUP), GROUP)
            if weighted:
                scale = scale_ref[rows, :]
                dot = jnp.zeros((GROUP, LANES), jnp.float32)
            for c in range(h2 // LANES):
                lo, hi = _halves(_group(buf.at[slot], r0, c))
                cols_lo = pl.ds(c * LANES, LANES)
                cols_hi = pl.ds(h2 + c * LANES, LANES)
                if weighted:
                    dot = (dot
                           + lo * other_ref[rows, cols_lo].astype(jnp.float32)
                           + hi * other_ref[rows, cols_hi].astype(jnp.float32))
                    lo, hi = lo * scale, hi * scale
                out_ref[rows, cols_lo] = lo.astype(out_ref.dtype)
                out_ref[rows, cols_hi] = hi.astype(out_ref.dtype)
            if weighted:
                dot_ref[rows, :] = jnp.sum(dot, axis=1, keepdims=True)
            return carry
        return lax.fori_loop(0, sub // GROUP, group, carry)

    issue(0, 0)
    lax.fori_loop(0, tm // sub, sub_tile, 0)


@jax.jit
def moe_rows_gather(x, index, n_live, weighted=None):
    """x bf16 [R, H], index int32 [M] (entries in 0 .. R - 1), n_live int32
    scalar -> out bf16 [M, H] with ``out[i] = x[index[i]]`` for ``i <
    n_live``. Rows past ``n_live`` (to the end of a tile of ``ROW_TILE``) are
    undefined and cost nothing: never written, never to be read — the
    contract ``grouped_matmul`` states for its input.

    ``weighted`` = (scale float32 [M], other bf16 [M, H]), the combine's
    gradient: ``out[i] = scale[i] * x[index[i]]``, the product taken in
    float32, and also float32 [M], ``<x[index[i]], other[i]>`` over the row
    (before the scale), for ``i < n_live``."""
    R, H = x.shape
    M = index.shape[0]
    assert H % (2 * LANES) == 0 and M % ROW_TILE == 0, (x.shape, index.shape)
    tm, sub, h2 = ROW_TILE, ROW_SUB, H // 2
    dotted = weighted is not None
    rows = pl.BlockSpec((tm, H), lambda i: (i, 0))
    column = pl.BlockSpec((tm, 1), lambda i: (i, 0))
    args, in_specs = [index.reshape(M // tm, 1, tm)], [
        pl.BlockSpec((None, 1, tm), lambda i: (i, 0, 0),
                     memory_space=pltpu.SMEM)]
    if dotted:
        scale, other = weighted
        args += [scale.astype(jnp.float32).reshape(M, 1), other]
        in_specs += [column, rows]
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(pl.cdiv(n_live, tm),),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[rows, column] if dotted else rows,
            scratch_shapes=[pltpu.VMEM((2, sub, h2 // LANES, 1, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=([jax.ShapeDtypeStruct((M, H), x.dtype),
                    jax.ShapeDtypeStruct((M, 1), jnp.float32)] if dotted
                   else jax.ShapeDtypeStruct((M, H), x.dtype)),
        interpret=_interpret(),
        name="moe_rows_gather",
    )(*args, pack_rows(x, R))
    return (out[0], out[1].reshape(M)) if dotted else out


# ---- combine -----------------------------------------------------------------

def _pack_kernel(*refs):
    *sources, out_ref = refs
    tm, H = sources[0].shape
    h2 = H // 2

    def bits(rows, cols):
        total = sources[0][rows, cols].astype(jnp.float32)
        for more in sources[1:]:
            total = total + more[rows, cols].astype(jnp.float32)
        if len(sources) > 1:        # the sum is a bf16 row, as XLA's add gives
            total = total.astype(sources[0].dtype).astype(jnp.float32)
        return lax.bitcast_convert_type(total, jnp.uint32)

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        for c in range(h2 // LANES):
            out_ref[rows, c, 0, :] = (
                bits(rows, pl.ds(c * LANES, LANES)) >> 16
                | bits(rows, pl.ds(h2 + c * LANES, LANES))
                & jnp.uint32(0xFFFF0000))
        return carry
    lax.fori_loop(0, tm // GROUP, group, 0)


@jax.jit
def pack_rows(rows, n_live):
    """bf16 [M, H] -> uint32 [M, H / 256, 1, 128], the first ``n_live`` rows
    (to a tile's end) packed and the rest never written: word j of a row
    holds column j (low half) and column j + H / 2 (high half). ``rows`` may
    be several such arrays: their SUM is packed (in float32, rounded to bf16:
    a value's cotangents, added over the live rows alone)."""
    sources = rows if isinstance(rows, (tuple, list)) else (rows,)
    M, H = sources[0].shape
    tm = ROW_TILE if M % ROW_TILE == 0 else TOKEN_TILE
    assert H % (2 * LANES) == 0 and M % tm == 0 and tm % GROUP == 0, (M, H)
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(pl.cdiv(n_live, tm),),
            in_specs=[pl.BlockSpec((tm, H), lambda i: (i, 0))] * len(sources),
            out_specs=pl.BlockSpec((tm, H // 2 // LANES, 1, LANES),
                                   lambda i: (i, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((M, H // 2 // LANES, 1, LANES),
                                       jnp.uint32),
        interpret=_interpret(),
        name="moe_rows_pack",
    )(*sources)


def _combine_kernel(count_ref, most_ref, pos_ref, w_ref, rows_hbm, y_ref,
                    buf, wide, sem):
    tq, (k, sub) = y_ref.shape[0], wide.shape[:2]
    h2 = buf.shape[3] * LANES

    def copy(src, c, t, slot):
        return pltpu.make_async_copy(rows_hbm.at[src], buf.at[slot, c, t],
                                     sem.at[slot])

    def issue(s, slot):
        """One DMA for every live pair of the sub-tile's tokens: the c-th
        live pair of token t lands in ``buf[slot, c, t]``."""
        def token(t, issued):
            n = count_ref[0, s * sub + t]

            def pair(c, carry):
                copy(pos_ref[0, (s * sub + t) * k + c], c, t, slot).start()
                return carry
            lax.fori_loop(0, n, pair, 0)
            return issued + n
        return lax.fori_loop(0, sub, token, 0)

    # unrolled: a slot that is a constant reads 9 % faster than one that is
    # computed (1.80 against 1.98 ms a call at the cell's shapes on a v5e)
    issued = issue(0, 0)
    for s in range(tq // sub):
        slot = s % 2
        # the next sub-tile's rows are on their way while this one is summed
        ahead = issue(s + 1, 1 - slot) if s + 1 < tq // sub else None

        def wait(i, carry):
            copy(0, 0, 0, slot).wait()
            return carry
        lax.fori_loop(0, issued, wait, 0)
        issued = ahead
        # a pair's weight across the lanes, once a sub-tile
        for c in range(k):
            wide[c] = jnp.broadcast_to(
                w_ref[pl.ds(s * sub, sub), c:c + 1], (sub, LANES))

        def group(g, carry):
            r0 = pl.multiple_of(g * GROUP, GROUP)
            tokens = pl.ds(pl.multiple_of(s * sub + r0, GROUP), GROUP)
            most = most_ref[0, s * (sub // GROUP) + g]
            for col in range(h2 // LANES):
                def choice(c, acc):
                    w = wide[c, pl.ds(r0, GROUP), :]
                    lo, hi = _halves(_group(buf.at[slot, c], r0, col))
                    # a slot no pair filled holds whatever was there
                    on = w != 0
                    return (acc[0] + jnp.where(on, lo, 0.0) * w,
                            acc[1] + jnp.where(on, hi, 0.0) * w)
                zero = jnp.zeros((GROUP, LANES), jnp.float32)
                lo, hi = lax.fori_loop(0, most, choice, (zero, zero))
                y_ref[tokens, pl.ds(col * LANES, LANES)] = \
                    lo.astype(y_ref.dtype)
                y_ref[tokens, pl.ds(h2 + col * LANES, LANES)] = \
                    hi.astype(y_ref.dtype)
            return carry
        lax.fori_loop(0, sub // GROUP, group, 0)


def _live_first(pos, weights, n_live):
    """pos int32 [T, k], weights float32 [T, k] -> each token's LIVE pairs
    (``pos < n_live``) first, in the order of its choices: (count [T],
    positions [T, k], weights [T, k], zero behind the count). Computed with
    the tokens on the lanes: ``[T, 8]`` arrays fill a sixteenth of them."""
    k = pos.shape[1]
    pos, weights = pos.T, weights.T                             # [k, T]
    live = pos < n_live
    rank = jnp.cumsum(live.astype(jnp.int32), axis=0) - 1
    to = live[None] & (rank[None] == jnp.arange(k)[:, None, None])  # [c, j, T]

    def first(a):
        return jnp.sum(jnp.where(to, a[None], 0), axis=1).T
    return jnp.sum(live.astype(jnp.int32), axis=0), first(pos), first(weights)


@jax.jit
def moe_rows_combine(rows, pos, weights, n_live):
    """rows bf16 [M, H] (the first ``n_live`` defined; or several such, for
    their sum: ``pack_rows``), pos int32 [T, k] (pair (t, j)'s row; M = T k),
    weights float32 [T, k] -> y bf16 [T, H]:
    ``y[t] = sum_j weights[t, j] * rows[pos[t, j]]`` over the pairs with
    ``pos[t, j] < n_live``, each product and the sum in float32, the choices
    in their order. A token with no live pair gives zeros; a row past
    ``n_live`` is never read."""
    packed = pack_rows(rows, n_live)
    M, H = packed.shape[0], packed.shape[1] * 2 * LANES
    T, k = pos.shape
    tq, sub = TOKEN_TILE, TOKEN_SUB
    assert supported(T, H, k) and M == T * k, (packed.shape, pos.shape)
    count, first_pos, first_w = _live_first(
        pos, weights.astype(jnp.float32), n_live)
    most = jnp.max(count.reshape(T // GROUP, GROUP), axis=1)

    def scalars(a, per_tile):
        return (a.reshape(T // tq, 1, per_tile),
                pl.BlockSpec((None, 1, per_tile), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM))
    args, in_specs = zip(scalars(count, tq), scalars(most, tq // GROUP),
                         scalars(first_pos, tq * k))
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(T // tq,),
            in_specs=[*in_specs, pl.BlockSpec((tq, k), lambda i: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tq, H), lambda i: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k, sub, H // 2 // LANES, 1, LANES),
                                       jnp.uint32),
                            pltpu.VMEM((k, sub, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((T, H), jnp.bfloat16),
        interpret=_interpret(),
        name="moe_rows_combine",
    )(*args, first_w, packed)
