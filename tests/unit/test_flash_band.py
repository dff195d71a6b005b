"""The banded flash forward (``flash_attention(..., window=W)``, kernel
``flash_fwd_band``) and its two backward kernels (``flash_bwd_band_dq``,
``flash_bwd_band_dkv``) in interpret mode against ``reference_attention``
under a band mask, and against its gradient, over every relation of (tile, window, length): window below, at
and above a tile; window off the tiles; length below, at and above the window;
length off the default tiles; the published 6 : 1 grouping. The kernel skips
key tiles, so a wrong tile count or first tile shows as a wrong row, not as
noise: the tolerance is float32 rounding of an online softmax (2e-6; sound
readings are 6e-7).

``window=None`` is the program it was: the lowered text of the forward and of
its gradient are pinned to what the parent commit lowers (PR 43's tree)."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import flash_attention as fa

TOL = 2e-6


def _qkv(S, nq=6, nkv=1, d=16, B=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, nq, d)),
            jax.random.normal(ks[1], (B, S, nkv, d)),
            jax.random.normal(ks[2], (B, S, nkv, d)))


# (S, window, block_q, block_k)
GRID = [
    (64, 8, 16, 16),      # window < tile
    (64, 16, 16, 16),     # window = tile
    (64, 24, 16, 16),     # window > tile, off the tiles
    (64, 32, 16, 16),     # window = two tiles
    (64, 7, 16, 8),       # odd window, key tile < query tile
    (64, 40, 8, 16),      # key tile > query tile
    (64, 1, 8, 8),        # a query sees itself alone
    (32, 48, 16, 16),     # S < window: plain causal
    (48, 48, 16, 16),     # S = window
    (64, 63, 16, 16),     # S = window + 1: ONE pair is outside the band
    (96, 33, 32, 16),     # S = 3 query tiles, window one past two key tiles
    (40, 12, 16, 16),     # S off the tiles asked for (blocks fall to 8)
    (128, 100, 512, 1024),  # the default tiles, clipped to S
]


@pytest.mark.parametrize("S, W, bq, bk", GRID)
def test_band_matches_the_masked_reference(S, W, bq, bk):
    q, k, v = _qkv(S)
    got = fa.flash_attention(q, k, v, window=W, block_q=bq, block_k=bk)
    want = fa.reference_attention(q, k, v, window=W)
    assert float(jnp.abs(got - want).max()) < TOL
    if W < S:       # ... and the band is not the plain causal triangle
        plain = fa.reference_attention(q, k, v)
        assert float(jnp.abs(got - plain).max()) > 1e-3


@pytest.mark.parametrize("nq, nkv, B", [(6, 1, 1), (12, 2, 2), (4, 4, 1)])
def test_band_groups_and_batches(nq, nkv, B):
    q, k, v = _qkv(64, nq, nkv, B=B, seed=3)
    got = fa.flash_attention(q, k, v, window=20, block_q=16, block_k=16)
    want = fa.reference_attention(q, k, v, window=20)
    assert float(jnp.abs(got - want).max()) < TOL


def test_band_visits_only_the_bands_tiles():
    """The grid's innermost extent is the key tiles ONE query tile's band can
    touch, not S / block_k: at S = 9216, window 4096 and the default tiles
    (the published prompt bucket) 6 of 9, and the count stops growing with
    S."""
    assert fa._band_tiles(4096, 512, 1024, 9216) == 6
    assert fa._band_tiles(4096, 512, 1024, 65536) == 6
    assert fa._band_tiles(4096, 512, 1024, 4096) == 4
    assert fa._band_tiles(16, 16, 16, 64) == 3
    assert fa._band_tiles(1, 8, 8, 64) == 2


def _grads(fn, q, k, v, seed=9):
    """d (q, k, v) of ``sum(fn(q, k, v) * c)`` for a seeded cotangent ``c``
    (a plain ``.sum()`` makes dP = 0 wherever V's rows are alike)."""
    c = jax.random.normal(jax.random.PRNGKey(seed), q.shape)
    return jax.grad(lambda *a: (fn(*a) * c).sum(), argnums=(0, 1, 2))(q, k, v)


# the banded backward (``flash_bwd_band_dq`` / ``flash_bwd_band_dkv``) skips
# tiles as the forward does, on BOTH walks, so every relation of the grid is
# a case: float32 rounding of three contractions over <= 128 positions
BWD_TOL = 2e-5


@pytest.mark.parametrize("S, W, bq, bk", GRID)
def test_band_backward_matches_the_masked_references_gradient(S, W, bq, bk):
    q, k, v = _qkv(S, seed=5)
    got = _grads(lambda *a: fa.flash_attention(
        *a, window=W, block_q=bq, block_k=bk), q, k, v)
    want = _grads(lambda *a: fa.reference_attention(*a, window=W), q, k, v)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) < BWD_TOL
    if W < S:       # ... and not the causal triangle's gradient
        plain = _grads(fa.reference_attention, q, k, v)
        assert float(jnp.abs(got[1] - plain[1]).max()) > 1e-3


@pytest.mark.parametrize("nq, nkv, B", [(6, 1, 1), (12, 2, 2), (4, 4, 1)])
def test_band_backward_groups_and_batches(nq, nkv, B):
    q, k, v = _qkv(64, nq, nkv, B=B, seed=3)
    got = _grads(lambda *a: fa.flash_attention(
        *a, window=20, block_q=16, block_k=16), q, k, v)
    want = _grads(lambda *a: fa.reference_attention(*a, window=20), q, k, v)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) < BWD_TOL


def test_band_backward_visits_only_the_bands_tiles():
    """Both backward grids end at the band: at S = 8192, window 1024 and the
    published 8 : 1 grouping (query tile 128) the key tile falls to a quarter
    of the window, a query tile walks 6 key tiles of 256 and a key tile 11
    query tiles of 128, whatever S is; and no program of the gradient holds a
    ``[heads, S, S]`` score."""
    bq, bk = fa._bwd_band_blocks(8192, 1024, 512, 1024, rep=8)
    assert (bq, bk) == (128, 256)
    assert fa._band_tiles(1024, bq, bk, 8192) == 6
    assert fa._band_tiles(1024, bq, bk, 65536) == 6
    q, k, v = _qkv(352, 4, 1, seed=1)
    text = jax.jit(lambda *a: _grads(lambda *b: fa.flash_attention(
        *b, window=32, block_q=32, block_k=32), *a)).lower(q, k, v).as_text(
            debug_info=True)
    assert "352x352" not in text
    for name in ("flash_fwd_band", "flash_bwd_band_dq", "flash_bwd_band_dkv"):
        assert re.search(rf'\b{name}\b[^"]*/pallas_call"', text), name


@pytest.mark.parametrize("kw", [{"causal": False}, {"window": 0},
                                {"kv_mask": np.ones((1, 32), bool)}])
def test_band_refuses_what_it_does_not_compute(kw):
    q, k, v = _qkv(32)
    with pytest.raises(ValueError, match="band"):
        fa.flash_attention(q, k, v, **{"window": 8, **kw})


def _plain(q, k, v):
    return fa.flash_attention(q, k, v, block_q=16, block_k=16)


def _plain_grad(q, k, v):
    return jax.grad(lambda *a: _plain(*a).sum(), argnums=(0, 1, 2))(q, k, v)


def _sha(fn):
    q, k = jnp.zeros((2, 64, 6, 16)), jnp.zeros((2, 64, 1, 16))
    return hashlib.sha256(
        jax.jit(fn).lower(q, k, k).as_text().encode()).hexdigest()[:16]


def test_no_window_lowers_to_the_text_it_had():
    """The two hashes are what this test reads in a checkout of the parent
    commit (copy the file there and run it ``-k no_window``: its failure
    message is the pair)."""
    assert (_sha(_plain), _sha(_plain_grad)) == GOLDEN


GOLDEN = ("0771dd52002b7ddb", "401832db04d7f760")
