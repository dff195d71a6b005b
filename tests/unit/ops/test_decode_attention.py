"""Paged Pallas decode-attention parity (reference test model:
tests/unit/ops kernel-vs-torch parity, SURVEY §4).

The kernel's reference is the materialized block-table gather, turned
head-major, fed through ``models/transformer._decode_attention`` (the
ring-buffer math with a per-slot cursor). The serving engine's XLA backend
has a contraction of its own since ISSUE 27 (``_paged_list_attention``,
the gathered view consumed token-major as stored); it is checked here
against a plain numpy reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.decode_attention import paged_decode_attention


def _gather_view(pool, tables):
    """One layer's token-major pool slice [NB, bs, Nkv, D] read through the
    block tables [S, MB] as the ring-buffer view [S, Nkv, MB*bs, D]."""
    S, MB = tables.shape
    _, bs, Nkv, D = pool.shape
    g = jnp.take(pool, tables, axis=0)           # [S, MB, bs, Nkv, D]
    return g.reshape(S, MB * bs, Nkv, D).transpose(0, 2, 1, 3)


def _ref_paged(q, k_pool, v_pool, tables, lens, k_row, v_row):
    from deepspeed_tpu.models.transformer import _decode_attention
    return _decode_attention(q, _gather_view(k_pool, tables),
                             _gather_view(v_pool, tables),
                             jnp.asarray(lens, jnp.int32), None,
                             kv_row=(k_row, v_row))


def _rand_case(key, S, NB, MB, Nkv, rep, bs, D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = jax.random.normal(ks[0], (S, 1, Nkv * rep, D), dtype)
    k_pool = jax.random.normal(ks[1], (NB, bs, Nkv, D), dtype)
    v_pool = jax.random.normal(ks[2], (NB, bs, Nkv, D), dtype)
    k_row = jax.random.normal(ks[3], (S, Nkv, 1, D), dtype)
    v_row = jax.random.normal(ks[4], (S, Nkv, 1, D), dtype)
    # distinct non-trash blocks per slot (block 0 reserved), shuffled so the
    # table gather is a REAL permutation, not identity
    rng = np.random.default_rng(key)
    ids = rng.permutation(np.arange(1, NB))[:S * MB].reshape(S, MB)
    return q, k_pool, v_pool, jnp.asarray(ids, jnp.int32), k_row, v_row


@pytest.mark.parametrize("lens", [[0, 1], [5, 37], [32, 64], [64, 63]])
@pytest.mark.parametrize("rep", [1, 4])
def test_paged_parity(lens, rep):
    """Mixed per-slot lengths: empty slot, partial block, exact block
    boundary, full table."""
    S, NB, MB, Nkv, bs, D = 2, 8, 2, 2, 32, 64
    q, kp, vp, tables, kr, vr = _rand_case(sum(lens) * 7 + rep, S, NB, MB,
                                           Nkv, rep, bs, D)
    lens = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(q, kp, vp, tables, lens, kv_row=(kr, vr))
    ref = _ref_paged(q, kp, vp, tables, lens, kr, vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_paged_bf16():
    S, NB, MB, Nkv, rep, bs, D = 2, 10, 3, 4, 2, 32, 64
    q, kp, vp, tables, kr, vr = _rand_case(11, S, NB, MB, Nkv, rep, bs, D,
                                           jnp.bfloat16)
    lens = jnp.asarray([70, 96], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tables, lens, kv_row=(kr, vr))
    ref = _ref_paged(q, kp, vp, tables, lens, kr, vr)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_trash_block_and_stale_rows_ignored():
    """Block 0 (the reserved trash block null table entries point at) and
    rows past each slot's length hold huge garbage — none of it may leak
    into the output (the scheduler reuses freed blocks without zeroing)."""
    S, NB, MB, Nkv, rep, bs, D = 2, 6, 2, 2, 1, 32, 64
    q, kp, vp, tables, kr, vr = _rand_case(3, S, NB, MB, Nkv, rep, bs, D)
    kp = kp.at[0].set(1e4)                    # trash block
    vp = vp.at[0].set(1e4)
    lens = jnp.asarray([40, 0], jnp.int32)
    # slot 0's second block is half stale; slot 1 is EMPTY with an all-null
    # table -> its output must be exactly the fresh-row value
    tables = tables.at[1].set(0)
    blk2 = int(tables[0, 1])
    kp = kp.at[blk2, 8:].set(1e4)             # rows 40.. of slot 0 stale
    vp = vp.at[blk2, 8:].set(1e4)
    out = paged_decode_attention(q, kp, vp, tables, lens, kv_row=(kr, vr))
    ref = _ref_paged(q, kp, vp, tables, lens, kr, vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert float(jnp.max(jnp.abs(out))) < 100.0
    # the empty slot attends only to itself
    np.testing.assert_allclose(np.asarray(out[1]),
                               np.asarray(vr[1].reshape(1, Nkv * rep, D)),
                               rtol=1e-5, atol=1e-5)


def test_table_permutation_invariance():
    """Physically scattered blocks must read identically to the same data
    laid out contiguously — the whole point of the table indirection."""
    S, NB, MB, Nkv, rep, bs, D = 1, 9, 4, 2, 2, 32, 64
    q, kp, vp, _, kr, vr = _rand_case(5, S, NB, MB, Nkv, rep, bs, D)
    lens = jnp.asarray([100], jnp.int32)
    t1 = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    t2 = jnp.asarray([[5, 7, 6, 8]], jnp.int32)
    # copy the logical contents of layout 1 into layout 2's blocks
    kp2, vp2 = kp, vp
    for a, b in zip([1, 2, 3, 4], [5, 7, 6, 8]):
        kp2 = kp2.at[b].set(kp[a])
        vp2 = vp2.at[b].set(vp[a])
    o1 = paged_decode_attention(q, kp, vp, t1, lens, kv_row=(kr, vr))
    o2 = paged_decode_attention(q, kp2, vp2, t2, lens, kv_row=(kr, vr))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


# ---------------------------------------------------------------------------
# The XLA read of the paged pool (ISSUE 27): one gather out of the whole
# pool, contracted token-major as gathered
# ---------------------------------------------------------------------------

def _plain_reference(q, k, v, tables, lens, k_row, v_row):
    """Single-token attention over each slot's first ``lens[s]`` positions
    plus its fresh row, in float64 with numpy and nothing else: k, v are a
    layer's DEQUANTISED pool [NB, bs, Nkv, D]."""
    q, k, v, k_row, v_row = (np.asarray(a, np.float64)
                             for a in (q, k, v, k_row, v_row))
    S, _, Nq, D = q.shape
    Nkv = k.shape[2]
    rep = Nq // Nkv
    out = np.zeros((S, 1, Nq, D))
    for s in range(S):
        n = int(lens[s])
        rows_k = k[np.asarray(tables[s])].reshape(-1, Nkv, D)[:n]
        rows_v = v[np.asarray(tables[s])].reshape(-1, Nkv, D)[:n]
        for h in range(Nq):
            g = h // rep
            ks = np.concatenate([rows_k[:, g], k_row[s, g]], 0)   # [n+1, D]
            vs = np.concatenate([rows_v[:, g], v_row[s, g]], 0)
            logit = ks @ q[s, 0, h] / np.sqrt(D)
            p = np.exp(logit - logit.max())
            out[s, 0, h] = (p / p.sum()) @ vs
    return out


@pytest.mark.parametrize("layer", [None, 2])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("bits", [0, 8])
def test_xla_read_against_plain_reference(bits, rep, layer):
    """Random tables with trash entries and junk ids past the live prefix,
    lengths 0, 1, one block and the full table, one and four query heads a
    kv head, a float and an int8 pool, a layer's slice and the whole pool
    with the layer as a (traced) coordinate of the gather."""
    from deepspeed_tpu.models.transformer import _paged_attention
    L, NB, bs, MB, Nkv, D = 3, 11, 16, 4, 2, 32
    lens = np.array([0, 1, bs, MB * bs], np.int32)
    S = len(lens)
    rng = np.random.default_rng(100 * bits + 10 * rep + (layer or 0))
    tables = rng.integers(1, NB, (S, MB)).astype(np.int32)  # junk, in bounds
    tables[0] = 0                                  # an empty slot: all trash
    tables[1, 1:] = 0                              # nulls past the one block
    q = rng.normal(size=(S, 1, Nkv * rep, D)).astype(np.float32)
    k_row = rng.normal(size=(S, Nkv, 1, D)).astype(np.float32)
    v_row = rng.normal(size=(S, Nkv, 1, D)).astype(np.float32)
    if bits == 8:
        pk = rng.integers(-127, 128, (L, NB, bs, Nkv, D)).astype(np.int8)
        pv = rng.integers(-127, 128, (L, NB, bs, Nkv, D)).astype(np.int8)
        ksc = (rng.random((L, NB, Nkv * bs)) * 0.02 + 1e-3).astype(np.float32)
        vsc = (rng.random((L, NB, Nkv * bs)) * 0.02 + 1e-3).astype(np.float32)
        # a plane is head-major within a block: [.., Nkv, bs] -> [.., bs, Nkv]
        deq = lambda p, sc: p.astype(np.float64) * np.swapaxes(
            sc.reshape(L, NB, Nkv, bs), 2, 3)[..., None]
        k_f, v_f = deq(pk, ksc), deq(pv, vsc)
        scales = (jnp.asarray(ksc), jnp.asarray(vsc))
        tol = 3e-2      # the query and the probabilities are quantised too
    else:
        pk = rng.normal(size=(L, NB, bs, Nkv, D)).astype(np.float32)
        pv = rng.normal(size=(L, NB, bs, Nkv, D)).astype(np.float32)
        k_f, v_f, scales, tol = pk, pv, None, 2e-5
    pk, pv = jnp.asarray(pk), jnp.asarray(pv)
    i = 1 if layer is None else layer

    if layer is None:           # a 4-D slice with no layer: still works
        def read(q, pk, pv, scales, i):
            sc = None if scales is None else tuple(s[1] for s in scales)
            return _paged_attention(q, pk[1], pv[1], jnp.asarray(tables),
                                    jnp.asarray(lens), None,
                                    kv_row=(k_row, v_row), kv_scale=sc)
    else:
        def read(q, pk, pv, scales, i):
            return _paged_attention(q, pk, pv, jnp.asarray(tables),
                                    jnp.asarray(lens), None,
                                    kv_row=(k_row, v_row), kv_scale=scales,
                                    layer=i)
    out = jax.jit(read)(jnp.asarray(q), pk, pv, scales, jnp.int32(i))
    ref = _plain_reference(q, k_f[i], v_f[i], tables, lens, k_row, v_row)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               rtol=tol, atol=tol)
    # the empty slot attends only to its fresh row
    np.testing.assert_allclose(
        np.asarray(out[0]),
        np.repeat(v_row[0], rep, axis=0).reshape(1, Nkv * rep, D),
        rtol=1e-5, atol=1e-5)


class TestInt8KVCache:
    """int8 KV storage (contiguous ring buffers AND the paged pool share
    this math): the per-position scales factor out of the d-contraction so
    both attention einsums run on int8 bytes (int8 MXU path on TPU) —
    dequant is fused into the read, nothing materializes. Parity vs the
    float-cache XLA decode attention."""

    def test_decode_attention_int8_parity(self):
        from deepspeed_tpu.models.transformer import (_decode_attention,
                                                      _quant_kv)
        B, Nkv, rep, T, D = 2, 4, 2, 128, 64
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        q = jax.random.normal(ks[0], (B, 1, Nkv * rep, D), jnp.float32)
        ck = jax.random.normal(ks[1], (B, Nkv, T, D), jnp.float32)
        cv = jax.random.normal(ks[2], (B, Nkv, T, D), jnp.float32)
        k_row = jax.random.normal(ks[3], (B, Nkv, 1, D), jnp.float32)
        v_row = jax.random.normal(ks[4], (B, Nkv, 1, D), jnp.float32)
        index = jnp.int32(100)
        ref = _decode_attention(q, ck, cv, index, kv_row=(k_row, v_row))
        kq, ksc = _quant_kv(ck)
        vq, vsc = _quant_kv(cv)
        got = _decode_attention(q, kq, vq, index, kv_row=(k_row, v_row),
                                kv_scale=(ksc, vsc))
        rel = (np.linalg.norm(np.asarray(got - ref).ravel())
               / np.linalg.norm(np.asarray(ref).ravel()))
        assert rel < 2e-2, rel

    @pytest.mark.slow
    def test_generate_int8_vs_float_first_logits(self):
        """Engine-level: prefill logits are exact (cache unused); the first
        decode step's logits (read through the quantized cache) stay close
        to the float-cache path."""
        import deepspeed_tpu
        from deepspeed_tpu.models import TransformerConfig, make_model

        cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                                num_layers=2, num_heads=4, max_seq_len=256,
                                dtype=jnp.float32, attention_impl="xla")
        ids = np.random.default_rng(0).integers(0, 128, (2, 40),
                                                dtype=np.int32)
        outs = {}
        for kvb in (0, 8):
            model = make_model(cfg, name="tiny")
            eng = deepspeed_tpu.init_inference(
                model, config={"kv_cache_bits": kvb}, dtype=jnp.float32)
            assert eng.model.config.kv_cache_bits == kvb
            outs[kvb] = np.asarray(jax.device_get(
                eng.generate(ids, max_new_tokens=8)))
        # prompt region identical by construction; the check is on the
        # GENERATED region: greedy argmax through a ~1% attention
        # perturbation on this fixed seed keeps the first tokens equal
        assert (outs[0][:, :40] == outs[8][:, :40]).all()
        gen0, gen8 = outs[0][:, 40:], outs[8][:, 40:]
        assert (gen0[:, :4] == gen8[:, :4]).all(), (gen0, gen8)
