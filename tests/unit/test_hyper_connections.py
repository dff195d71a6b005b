"""A residual stream of several rows (``TransformerConfig.hc_mult``;
manifold-constrained hyper-connections, ISSUE 59) in ``models/hybrid.py``: the
mappings by themselves — Sinkhorn's doubly stochastic ``H_res``, the clamp, the
read and the write against the equations written out in numpy —, every seeded
defect of the family's list on LOGITS at toy widths, and that a stack with
``hc_mult`` 1 is walked as it was.

Tolerances: the program and the reference both compute in float32 at the
"highest" matmul precision (tests/conftest.py) and differ in the order of their
sums alone: 3e-7 was measured on logits of mean size 0.18; 2e-5 is held. A
defect must move the logits by at least 1e-3, fifty times that (the weakest
measured: YaRN against plain rotary 1.7e-3, ``bf16_mappings`` 1.8e-3).
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import glm4_moe_lite as glm  # noqa: E402
from benchmark.families import xing4_0 as fam  # noqa: E402
from deepspeed_tpu.models import hybrid, make_model  # noqa: E402
from deepspeed_tpu.models.hf_import import hf_config_to_transformer  # noqa: E402
from deepspeed_tpu.models.transformer import TransformerConfig  # noqa: E402

HF = {"model_type": "xing4_0", "hc_mult": 4, "hc_sinkhorn_iters": 20,
      "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
      "rms_norm_eps": 1e-6, "rope_theta": 10000, "routed_scaling_factor": 2,
      "n_shared_experts": 1, "num_experts_per_tok": 4, "norm_topk_prob": True,
      "scoring_func": "sigmoid", "topk_method": "noaux_tc",
      "tie_word_embeddings": False, "max_position_embeddings": 256, **fam.TOY}
DRAW = dict(norm_init_jitter=0.5, hc_init_std=1.0)
TOL, MOVES = 2e-5, 1e-3


def _cfg(n=4, H=8, **kw):
    return TransformerConfig(hidden_size=H, hc_mult=n, norm_type="rmsnorm",
                             norm_eps=1e-6, dtype=jnp.float32, **kw)


# the seams' pieces, jitted (op by op each costs a compile a primitive)
_sinkhorn = jax.jit(hybrid._sinkhorn, static_argnums=1)
_read = jax.jit(hybrid._hc_read, static_argnums=4)
_write = jax.jit(hybrid._hc_write, static_argnums=3)


def test_h_res_is_doubly_stochastic_and_the_clamp_holds():
    """Rows AND columns of ``H_res`` sum to 1 within 1e-5 after the 20 rounds
    on standard-normal logits (after ONE round only the columns do; the rate
    falls with the logits' spread: at a std of 3 the slowest of 257 tokens is
    still 4e-2 off in its rows, the columns — normalised last — never);
    logits of +-100 are clipped to +-30 first, so the exponentials stay
    finite."""
    cfg = _cfg()
    R = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 257))
    M = np.asarray(_sinkhorn(R, cfg))
    assert np.abs(M.sum(axis=1) - 1).max() < 1e-5       # rows (over columns j)
    assert np.abs(M.sum(axis=0) - 1).max() < 1e-5       # columns
    wide = np.asarray(_sinkhorn(3.0 * R, cfg))
    assert np.abs(wide.sum(axis=0) - 1).max() < 1e-5 < \
        np.abs(wide.sum(axis=1) - 1).max() < 0.1
    one = np.asarray(_sinkhorn(
        R, dataclasses.replace(cfg, hc_sinkhorn_iters=1)))
    assert np.abs(one.sum(axis=1) - 1).max() > 1e-2
    # through the read: a_res m + b of +-100 on the unit-RMS stream
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
    phi = jnp.zeros((32, 24))
    b = jnp.concatenate([jnp.zeros(8), jnp.tile(jnp.asarray([100., -100.]), 8)])
    h, (post, res) = _read(x, phi, b, jnp.ones(3), cfg)
    assert np.isfinite(np.asarray(res)).all() and np.isfinite(np.asarray(h)).all()
    assert np.abs(np.asarray(res).sum(axis=0) - 1).max() < 1e-5


@pytest.mark.parametrize("lead", [(3, 7), (5, 1)])
def test_the_read_and_the_write_are_the_equations(lead):
    """``_hc_read`` / ``_hc_write`` on the flat stream [.., n H] (T prompt
    tokens or one a slot) against the layer's equations in numpy on [n, H]."""
    n, H = 4, 8
    cfg = _cfg(n, H)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], lead + (n * H,))
    y = jax.random.normal(ks[1], lead + (H,))
    phi = jax.random.normal(ks[2], (n * H, 24)) / np.sqrt(n * H)
    b, a = jax.random.normal(ks[3], (24,)), jnp.asarray([0.7, 1.3, 0.9])
    h, hc = _read(x, phi, b, a, cfg)
    out = _write(x, y, hc, cfg)
    X = np.asarray(x, np.float64).reshape(-1, n, H)
    Y = np.asarray(y, np.float64).reshape(-1, H)
    sig = lambda z: 1 / (1 + np.exp(-z))                          # noqa: E731
    want_h, want_out = [], []
    for Xt, yt in zip(X, Y):
        v = Xt.reshape(-1)
        m = v / np.sqrt(np.mean(v * v) + 1e-6) @ np.asarray(phi, np.float64)
        bb, aa = np.asarray(b, np.float64), np.asarray(a, np.float64)
        pre = sig(aa[0] * m[:n] + bb[:n])
        post = 2 * sig(aa[1] * m[n:2 * n] + bb[n:2 * n])
        R = np.clip(aa[2] * m[2 * n:] + bb[2 * n:], -30, 30).reshape(n, n)
        M = np.exp(R - R.max(axis=1, keepdims=True))
        for _ in range(20):
            M = M / (M.sum(axis=1, keepdims=True) + 1e-6)
            M = M / (M.sum(axis=0, keepdims=True) + 1e-6)
        want_h.append(pre @ Xt)
        want_out.append(M @ Xt + post[:, None] * yt[None])
    np.testing.assert_allclose(np.asarray(h).reshape(-1, H), want_h, atol=TOL)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, n, H), want_out,
                               atol=TOL)


@pytest.fixture(scope="module")
def toy():
    cfg = hf_config_to_transformer(HF, dtype=jnp.float32, **DRAW)
    model = make_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(7))
    ids = np.random.default_rng(0).integers(0, HF["vocab_size"], 64)
    return cfg, model, params, ids, np.asarray(
        jax.jit(model.apply)(params, ids[None])[0])


def test_the_forward_is_the_reference(toy):
    """Two leading dense layers and two expert layers, n = 4, V narrower than
    the keys, YaRN with a factor of 8 over 16 positions."""
    cfg, _, params, ids, got = toy
    assert cfg.block_pattern == "LDLDLELE" and cfg.hc_mult == 4
    assert hybrid.period(cfg) == ("LDLDLELE", 1)
    want = fam.Reference(HF, params).logits(ids, pad_to=64)
    assert np.abs(want).mean() > 0.05 and np.abs(got - want).max() < TOL


@pytest.mark.parametrize("defect", fam.DEFECTS)
def test_each_seeded_defect_moves_the_logits(toy, defect):
    """Every entry of the defects tool's list, on logits: the precision below
    and its three parts, Sinkhorn of one round, columns never normalised,
    ``H_post`` without its 2, ``H_res`` the identity, the closing read a plain
    sum, the stream opened in row 0 only, the softmax scale without
    ``mscale^2``, plain rotary for YaRN, V from the wrong columns of ``W_kvb``,
    ``routed_scaling_factor`` left out."""
    _, _, params, ids, got = toy
    with jax.disable_jit():          # sixty small ops beat fourteen compiles
        bad = fam.Reference(HF, params, defect=defect).logits(ids, pad_to=64)
    assert np.abs(bad - got).max() > MOVES, defect


def test_the_start_of_a_trained_from_scratch_stack_is_the_plain_residual():
    """``hc_init_std`` 0: phi 0, so the mappings are their biases — ``H_pre``
    1 / n each, ``H_post`` 1, ``H_res`` within e^-8 of the identity, the
    closing read the rows' mean: on n equal rows a block reads the row and
    writes ``row + y`` into every row, the one-row residual. (That the closing
    read is NOT HC's sum of the rows is the defect ``close_by_sum`` above.)"""
    cfg = _cfg(4, 8)
    leaves = hybrid._hc_init(iter(()), cfg, blocks=2)
    assert {k: v.shape for k, v in leaves.items()} == {
        "hc_phi": (2, 32, 24), "hc_b": (2, 24), "hc_a": (2, 3)}
    row = jax.random.normal(jax.random.PRNGKey(4), (3, 5, 8))
    y = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 8))
    x = jnp.tile(row, 4)
    h, hc = _read(x, leaves["hc_phi"][1], leaves["hc_b"][1],
                  leaves["hc_a"][1], cfg)
    np.testing.assert_allclose(np.asarray(h), np.asarray(row), atol=1e-5)
    out = np.asarray(_write(x, y, hc, cfg)).reshape(3, 5, 4, 8)
    np.testing.assert_allclose(out, np.asarray(row + y)[:, :, None].repeat(4, 2),
                               atol=1e-5)
    closing = hybrid._hc_init(iter(()), cfg, 1, closing=True)
    assert closing["hc_phi"].shape == (1, 32, 4) and closing["hc_a"].shape == (1, 1)
    np.testing.assert_allclose(np.asarray(jax.nn.sigmoid(closing["hc_b"][0])),
                               0.25, atol=1e-6)


def test_one_row_is_walked_bit_for_bit_as_before():
    """``hc_mult`` 1 (every other family): GLM's toy through ``forward``,
    ``prefill_paged`` and ``decode_step_paged`` holds no ``hc`` scope, no
    mapping leaf, and gives the logits of the seams written out as they
    were (``x + mixer(RMSNorm(x))``) bit for bit."""
    from deepspeed_tpu.models.transformer import _norm
    hf = {"model_type": "glm4_moe_lite", "max_position_embeddings": 256,
          "n_shared_experts": 1, "num_experts_per_tok": 4,
          "routed_scaling_factor": 1.8, "first_k_dense_replace": 1,
          "rope_theta": 1e6, **glm.TOY}
    cfg = hf_config_to_transformer(hf, dtype=jnp.float32, norm_init_jitter=0.5)
    model = make_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(3))
    assert not [k for k in jax.tree_util.tree_flatten_with_path(params)[0]
                if "hc_" in jax.tree_util.keystr(k[0])]
    ids = np.random.default_rng(2).integers(0, hf["vocab_size"], (1, 32))
    got = np.asarray(jax.jit(model.apply)(params, ids))
    text = jax.jit(model.apply).lower(params, ids).as_text(debug_info=True)
    assert "hc/" not in text

    # the walk with the two seams as the parent had them
    old_in = lambda p, x, cfg: (_norm(x, p["ln_scale"], None, cfg), None)  # noqa: E731
    old_res = lambda p, x, y, cfg, hc=None: x + y                          # noqa: E731
    saved = hybrid._norm_in, hybrid._residual
    hybrid._norm_in, hybrid._residual = old_in, old_res
    try:
        want = np.asarray(jax.jit(lambda p, i: model.apply(p, i))(params, ids))
    finally:
        hybrid._norm_in, hybrid._residual = saved
    np.testing.assert_array_equal(got, want)
