"""The int8 paged decode kernel against the XLA read of the same pools
(ISSUE 50): ``ops/decode_attention.paged_decode_int8`` in interpret mode and
``models/transformer._paged_list_attention`` over the blocks the tables list.

Tolerance, from the recipe. Both sides run the int8 recipe product for
product — int8 q x int8 K -> int32, the scales multiplied into the scores in
the same order, probabilities x v scale requantised per row, int8 P x int8 V
-> int32 — and the int32 sums are exact in any order. What differs is the
order of the softmax's float32 SUM (the kernel adds lane tiles, XLA reduces a
row), so a probability may land on the neighbouring int8 step: one step of
one position moves a head's output by ``ps x |v| <= max(p x v scale) / 127 x
127`` of that position, a 127th of the largest term of the sum. The outputs
are bf16 (8 bits): the bound below is two bf16 steps of the output's scale
and the test also counts how many outputs are bit-equal (most).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import (_as_block_list, _gather_blocks,
                                              _gather_scales,
                                              _paged_list_attention,
                                              _quant_kv)
from deepspeed_tpu.ops.decode_attention import (int8_kernel_fits,
                                                paged_decode_int8)

BS = 64


def _pools(seed, L, NB, G, D):
    """Whole int8 leaves [L, NB, bs, G, D] and scale planes [L, NB, G * bs]
    as ``init_paged_cache`` lays them out, filled by quantising random rows;
    the trash block 0 holds LARGE rows, so that reading it would show."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    out = {}
    for name, key in zip("kv", ks):
        x = jax.random.normal(key, (L, NB, G, BS, D), jnp.float32)
        x = x.at[:, 0].multiply(50.0)
        xq, xs = _quant_kv(x)                      # [L, NB, G, bs, D], [.., bs]
        out[name] = xq.transpose(0, 1, 3, 2, 4)    # token-major
        out[name + "_scale"] = xs.reshape(L, NB, G * BS)
    return out


def _xla(q, pools, tables, lens, layer, kv_row):
    blocks = _as_block_list(tables)
    G = pools["k"].shape[3]
    sc = tuple(_gather_scales(pools[n], blocks.ids, G, layer)
               for n in ("k_scale", "v_scale"))
    vk, vv = (_gather_blocks(pools[n], blocks.ids, layer) for n in "kv")
    return _paged_list_attention(q, vk, vv, blocks, lens, None, kv_row, sc,
                                 None)


# lengths: 0 (an inactive slot), 1, a block's edge on both sides, a partial
# block, the table's end; a table two DMA waves long (MB 20 > CHUNK 16)
@pytest.mark.parametrize("G,rep,D,MB,lens", [
    (8, 6, 128, 3, [0, 1, 64, 65, 192]),         # Trinity's heads
    (8, 4, 128, 3, [63, 128, 129, 0, 191]),      # chat's / Mixtral's
    (2, 4, 256, 3, [192, 0, 17, 64, 127]),       # Qwen3-Next's: D 256
    (2, 1, 128, 2, [128, 1, 0, 100, 64]),        # rep 1 on 2 kv heads
    (16, 1, 128, 2, [5, 128, 64, 0, 90]),        # OLMoE's / Ouro's
    (8, 6, 128, 20, [1280, 1025, 0, 1024, 700]),  # past one DMA wave
])
def test_int8_kernel_matches_the_xla_read(G, rep, D, MB, lens):
    S, L, layer = len(lens), 3, 1
    NB = S * MB + 1
    assert int8_kernel_fits(MB=MB, block_size=BS, n_kv=G, rep=rep,
                            head_dim=D)
    pools = _pools(G * 100 + rep, L, NB, G, D)
    rng = np.random.default_rng(G + rep + MB)
    # blocks scattered through the pool; columns past a slot's length hold
    # the trash block 0, as the engine's tables do
    ids = rng.permutation(np.arange(1, NB)).reshape(S, MB)
    for s, n in enumerate(lens):
        ids[s, -(-n // BS):] = 0
    tables = jnp.asarray(ids, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (S, 1, G * rep, D), jnp.bfloat16)
    kv_row = tuple(jax.random.normal(k, (S, G, 1, D), jnp.bfloat16)
                   for k in ks[1:])
    want = _xla(q, pools, tables, lens, jnp.int32(layer), kv_row)
    got = jax.jit(paged_decode_int8)(
        q, pools["k"], pools["v"], pools["k_scale"], pools["v_scale"],
        tables, lens, jnp.int32(layer), kv_row=kv_row)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    # the layer was picked out of the whole leaf: another layer's answer
    # is far away
    other = np.asarray(_xla(q, pools, tables, lens, jnp.int32(0), kv_row),
                       np.float32)
    live = np.asarray(lens) > 0
    assert np.abs(other - want)[live].max() > 0.1
    # an inactive slot's softmax is the fresh row's alone: its V row, exact
    v_row = np.asarray(kv_row[1], np.float32)
    for s in np.flatnonzero(~live):
        np.testing.assert_array_equal(
            got[s, 0].reshape(G, rep, D), np.broadcast_to(v_row[s], (G, rep, D)))
    step = np.abs(want).max() * 2.0 ** -7          # two bf16 steps
    assert np.abs(got - want).max() <= step, np.abs(got - want).max()
    assert (got == want).mean() > 0.9, (got == want).mean()


def test_int8_kernel_refuses_what_it_cannot_hold():
    """A slot's float32 scores must fit in VMEM: a 262k-token table does
    not, and three kv heads do not divide a lane tile."""
    assert not int8_kernel_fits(MB=4096, block_size=BS, n_kv=8, rep=6,
                                head_dim=128)
    assert not int8_kernel_fits(MB=32, block_size=BS, n_kv=3, rep=1,
                                head_dim=128)
    assert int8_kernel_fits(MB=176, block_size=BS, n_kv=8, rep=6,
                            head_dim=128)


# ---- through the serving engine ----------------------------------------------

def _plain_model():
    from deepspeed_tpu.models import TransformerConfig, make_model
    return make_model(TransformerConfig(
        vocab_size=128, hidden_size=512, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=128, intermediate_size=256, max_seq_len=256,
        position_type="rotary", activation="silu_glu", norm_type="rmsnorm",
        tie_embeddings=False, dtype=jnp.float32, attention_impl="xla"))


def _window_model():
    """Trinity's kind of stack at toy widths: three sliding blocks (rings),
    one full-attention block on the paged pool, heads of 128."""
    from benchmark.families import afmoe as fam
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    hf = {"model_type": "afmoe", "hidden_act": "silu", "rms_norm_eps": 1e-5,
          "rope_theta": 10000, "rope_scaling": None,
          "max_position_embeddings": 512, "tie_word_embeddings": False,
          "num_experts_per_tok": 4, "num_shared_experts": 1,
          "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
          "mup_enabled": True, **fam.TOY, "sliding_window": 16,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128}
    return make_model(hf_config_to_transformer(hf, dtype=jnp.float32))


@pytest.mark.parametrize("build", [_plain_model, _window_model])
def test_served_tokens_are_the_xla_reads_tokens(build):
    """The same requests through ``init_serving`` on an int8 pool with the
    decode read forced either way: the same tokens (the layer scan hands the
    kernel the whole leaf and the layer's index; the hybrid walker its one
    paged plane), and ``stats()`` says which read ran and what was priced."""
    import deepspeed_tpu
    model = build()
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 128, n).astype(np.int32), m)
            for n, m in [(70, 12), (5, 20), (130, 9)]]
    outs = {}
    for backend in ("xla", "pallas"):
        srv = deepspeed_tpu.init_serving(
            model, config={"kv_cache_bits": 8}, params=params,
            dtype=jnp.float32,
            serving=dict(max_seqs=2, block_size=BS, max_model_len=256,
                         decode_quantum=4, prompt_bucket=64,
                         decode_backend=backend))
        assert srv.decode_backend == backend, srv.backend_bench
        res = srv.run(reqs)
        outs[backend] = [list(res[r]) for r in range(len(reqs))]
        st = srv.stats()
        assert st["decode_backend"] == backend
        choice = st["decode_backend_choice"]
        assert choice["reason"] == "forced by config"
        assert choice["priced"] == "xla"           # 2 kv heads, and tiny
        assert choice["xla_bytes"] > 0 and choice["kernel_bytes"] > 0
        srv.close()
    assert outs["pallas"] == outs["xla"]
