"""A packed row is attended ONCE (ISSUE 53).

``flash_attention_packed``: several sequences share a row and ONE call of the
flash forward walks, for every query tile, only the key tiles its segments
reach. Here in interpret mode on the CPU, through ``attention`` as a serving
prefill reaches it (``attention_impl="pallas"``), against
``reference_attention`` with the same segment ids; and the walk itself on
plain integers (``packed_walk``), which is also what the serving engine
counts (``prefill_attn_tiles_walked`` / ``_looped``,
tests/unit/test_packed_prefill.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import TransformerConfig
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import flash_attention as fa

BLOCK = 64          # a serving block: every segment starts at its edge


def _row(lengths, S):
    """(starts, lengths) of prompts laid one after the other from block
    edges, as ``ServingEngine._dispatch_prefill`` lays them."""
    starts, at = [], 0
    for n in lengths:
        starts.append(at)
        at += -(-n // BLOCK) * BLOCK
    assert at <= S
    return starts, list(lengths)


def _ids(starts, lengths, S):
    return T._packed_row(jnp.asarray(starts, jnp.int32),
                         jnp.asarray(lengths, jnp.int32), S)[0]


# (heads, kv heads, head dim) of GLM's expanded prompt, chat / Mixtral, OLMoE
HEADS = {"mha256": (4, 4, 256), "gqa128": (8, 2, 128), "mha128": (4, 4, 128)}
# name -> (row, the prompts' lengths). Starts are multiples of 64 that are
# no multiple of the key tile (1024 at S = 2048; 512 at 1536), so a key tile
# straddles two segments in every shared row
ROWS = {
    "one": (2048, (1900,)),
    "two": (2048, (700, 1200)),
    "three": (1536, (300, 520, 600)),
    "four": (2048, (450, 330, 700, 380)),
    "empty-in-the-middle": (2048, (640, 0, 900)),
    "tail-of-pad-rows": (2048, (200, 330)),
}
CASES = [("mha256", "one", "float32"), ("mha256", "two", "bfloat16"),
         ("mha256", "three", "float32"), ("mha256", "four", "bfloat16"),
         ("gqa128", "one", "bfloat16"), ("gqa128", "two", "float32"),
         ("gqa128", "three", "bfloat16"), ("gqa128", "four", "float32"),
         ("mha128", "two", "bfloat16"), ("mha128", "four", "float32"),
         ("mha256", "empty-in-the-middle", "float32"),
         ("gqa128", "empty-in-the-middle", "bfloat16"),
         ("mha128", "tail-of-pad-rows", "float32"),
         ("gqa128", "tail-of-pad-rows", "bfloat16")]


@pytest.mark.parametrize("heads,row,dtype", CASES,
                         ids=["-".join(c) for c in CASES])
def test_the_packed_call_is_the_reference_with_the_same_ids(heads, row, dtype):
    """Through ``attention`` (the kernel in interpret mode): every real and
    every pad row of every segment reads what the reference gives it, at the
    tolerance tests/unit/test_packed_prefill.py holds (float32; bf16 to its
    rounding of the output)."""
    N, Nkv, D = HEADS[heads]
    S, lengths = ROWS[row]
    dtype = jnp.dtype(dtype)
    cfg = TransformerConfig(vocab_size=32, hidden_size=N * D, num_layers=1,
                            num_heads=N, num_kv_heads=Nkv, dtype=dtype,
                            attention_impl="pallas")
    starts, lengths = _row(lengths, S)
    if row == "empty-in-the-middle":
        starts[1] = 0           # what the engine leaves for an unused entry
    ids = _ids(starts, lengths, S)
    assert int(ids.max()) + 1 == sum(n > 0 for n in lengths)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(len(row)), 3)
    q = (jax.random.normal(kq, (1, S, N, D)) * 2.0).astype(dtype)
    k = (jax.random.normal(kk, (1, S, Nkv, D)) * 2.0).astype(dtype)
    v = jax.random.normal(kv, (1, S, Nkv, D)).astype(dtype)
    assert T._attention_kernel(q, k, v, None, True, cfg, ids, None) is not None
    got = T.attention(q, k, v, cfg=cfg, segment_ids=ids)
    want = fa.reference_attention(q, k, v, segment_ids=ids)
    assert got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-4 if dtype == jnp.float32 else 2e-2, rtol=0)


def test_a_key_mask_rides_on_top():
    """A key-padding mask hides its keys from every segment, in a batch of
    two rows that are cut differently."""
    N, D, S = 4, 128, 1024
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (2, S, N, D)) * 2.0
    k = jax.random.normal(kk, (2, S, N, D)) * 2.0
    v = jax.random.normal(kv, (2, S, N, D))
    ids = np.stack([np.repeat([0, 1, 2, 3], [320, 192, 448, 64]),
                    np.repeat([0, 1, 2], [576, 320, 128])]).astype(np.int32)
    keep = np.broadcast_to(np.arange(S)[None] % 7 != 3, (2, S))
    got = fa.flash_attention_packed(q, k, v, jnp.asarray(ids),
                                    kv_mask=jnp.asarray(keep))
    # the reference takes no key mask: the scores by hand
    visible = (np.tril(np.ones((S, S), bool))[None] & keep[:, None, :]
               & (ids[:, :, None] == ids[:, None, :]))
    s = jnp.einsum("bsnd,btnd->bnst", q, k) / np.sqrt(D)
    p = jax.nn.softmax(jnp.where(jnp.asarray(visible)[:, None], s, -1e30),
                       axis=-1)
    want = jnp.einsum("bnst,btnd->bsnd", p, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=0)
    # and without the mask it is the reference with the same ids
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention_packed(q, k, v, jnp.asarray(ids))),
        np.asarray(fa.reference_attention(q, k, v,
                                          segment_ids=jnp.asarray(ids))),
        atol=2e-4, rtol=0)


@pytest.mark.parametrize("masked", [False, True], ids=["ids", "ids+mask"])
def test_under_a_mesh_the_packed_call_is_mapped_over_batch_and_heads(masked):
    """A Mosaic call has no partitioning rule: under a (data 2 x tensor 2)
    mesh ``attention`` maps the packed forward over the batch's axis and the
    kv heads', the ids (and a key mask) split with the batch, and gives
    what the bare call gives."""
    from deepspeed_tpu.parallel.mesh import MeshPlan, build_mesh
    N, Nkv, D, S = 4, 2, 128, 512
    cfg = TransformerConfig(vocab_size=32, hidden_size=N * D, num_layers=1,
                            num_heads=N, num_kv_heads=Nkv, dtype=jnp.float32,
                            attention_impl="pallas")
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (2, S, N, D)) * 2.0
    k = jax.random.normal(kk, (2, S, Nkv, D)) * 2.0
    v = jax.random.normal(kv, (2, S, Nkv, D))
    ids = jnp.asarray(np.stack([np.repeat([0, 1, 2], [192, 64, 256]),
                                np.repeat([0, 1], [320, 192])]), jnp.int32)
    mask = jnp.asarray(np.broadcast_to(np.arange(S)[None] % 5 != 2, (2, S))) \
        if masked else None
    want = fa.flash_attention_packed(q, k, v, ids, kv_mask=mask)
    mesh = build_mesh(MeshPlan(data=2, tensor=2), jax.devices()[:4])
    with mesh:
        fn = jax.jit(lambda q, k, v, ids, mask: T.attention(
            q, k, v, mask, cfg=cfg, segment_ids=ids))
        assert "shard_map" in str(jax.make_jaxpr(fn)(q, k, v, ids, mask))
        got = fn(q, k, v, ids, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6,
                               rtol=0)


# --------------------------------------------------------------------------
# the walk, on integers
# --------------------------------------------------------------------------

def _causal_tiles(S, bq, bk):
    """[(query tile, key tile)] of one causal pass, as ``_fwd_kernel`` walks
    them: ``_block_visible``."""
    return [(i, j) for i in range(S // bq) for j in range(S // bk)
            if (i + 1) * bq > j * bk]


def _walked_tiles(starts, lengths, S, rep=1):
    """[(query tile, key tile)] the packed kernel's predicate lets through,
    from the same bounds the call prefetches."""
    bq, bk = fa._packed_blocks(S, rep)
    lo, hi = fa._packed_tiles(starts, lengths, S, bq, bk)
    return [(i, j) for i in range(S // bq) for j in range(S // bk)
            if lo[i] <= j <= hi[i]]


WALKS = {
    # name: (S, lengths, walked, one causal pass) at 512 x 1024 tiles, what
    # two query heads stacked on a K/V head walk (rep 2)
    "2048+2048": (4096, (2048, 2048), 12, 20),
    "one-segment": (4096, (3900,), 20, 20),
    "4x1024": (4096, (1024,) * 4, 8, 20),
    "1024+1536+1536": (4096, (1024, 1536, 1536), 11, 20),
    # the first ends inside key tile 1 (1344 = 21 blocks): the second
    # segment's queries still start their walk at that tile
    "ends-inside-a-key-tile": (4096, (1300, 2700), 15, 20),
}


@pytest.mark.parametrize("name", sorted(WALKS))
def test_the_walk_on_integers(name):
    S, lengths, walked, causal = WALKS[name]
    assert fa._packed_blocks(S, rep=2) == (512, 1024)
    starts, lengths = _row(lengths, S)
    pad = [0] * (4 - len(lengths))
    assert fa.packed_walk(starts + pad, lengths + pad, S, 2) == (walked, causal)
    tiles = _walked_tiles(starts, lengths, S, rep=2)
    assert len(tiles) == walked
    assert len(_causal_tiles(S, 512, 1024)) == causal
    if len(lengths) == 1:
        # a prompt alone costs what a causal pass costs: tile for tile
        assert tiles == _causal_tiles(S, 512, 1024)
    # every pair a query may see lies in a tile that is walked
    ids = np.asarray(_ids(starts + pad, lengths + pad, S))[0]
    edges = np.flatnonzero(np.diff(ids)) + 1
    for first, last in zip([0, *edges], [*edges - 1, S - 1]):
        for i in range(first // 512, last // 512 + 1):
            for j in range(first // 1024, min(last, i * 512 + 511) // 1024 + 1):
                assert (i, j) in tiles, (name, i, j)


@pytest.mark.parametrize("S,rep,tiles", [
    (4096, 1, (1024, 1024)), (3072, 1, (1024, 1024)), (3584, 1, (512, 512)),
    (768, 1, (256, 256)), (1024, 4, (256, 1024)), (128, 4, (128, 128))])
def test_one_segment_walks_the_causal_pass_at_every_rows_tiles(S, rep, tiles):
    """The tiles are the kernel's, from the row's length and the heads a K/V
    head carries (GLM's buckets, OLMoE's, chat's): a row of ONE segment walks
    the causal pass tile for tile at each, so a prompt alone loses nothing."""
    assert fa._packed_blocks(S, rep) == tiles
    assert _walked_tiles([0], [S - 40], S, rep) == _causal_tiles(S, *tiles)
    walked, causal = fa.packed_walk([0], [S - 40], S, rep)
    assert walked == causal == len(_causal_tiles(S, *tiles))


def test_the_loop_walked_a_causal_pass_a_live_segment():
    """What the counter ``prefill_attn_tiles_looped`` stands for: 2048 +
    2048 in a 4096 row walked 2 x 20 = 40 tiles of 512 x 1024 where the
    packed call walks 12; three prompts 60. At GLM's own tiles (one head a
    K/V head: 1024 x 1024) the same row is 6 against 2 x 10."""
    walked, causal = fa.packed_walk([0, 2048, 0, 0], [2048, 2048, 0, 0], 4096,
                                    rep=2)
    assert (walked, 2 * causal) == (12, 40)
    walked, causal = fa.packed_walk([0, 1024, 2560, 0], [1024, 1536, 1536, 0],
                                    4096, rep=2)
    assert (walked, 3 * causal) == (11, 60)
    assert fa.packed_walk([0, 2048, 0, 0], [2048, 2048, 0, 0], 4096) == (6, 10)
    # four query heads on a K/V head are past the scratch's rows at 512:
    # chat's tiles are 256 x 1024, and two 500-token prompts in a 1024 row
    # share its one key tile: nothing to skip
    assert fa.packed_walk([0, 512, 0, 0], [500, 500, 0, 0], 1024, rep=4) \
        == (4, 4)
