"""Every ``pl.pallas_call`` of the program has a stable name (ISSUE 23).

A per-kernel reading of a device trace keys on the Mosaic custom call's
instruction name. Unnamed, that is the enclosing ``named_scope`` on one
chip (``%attn.N``) and ``%shard_map.N`` under a mesh, so the forward, dQ
and dK/dV kernels cannot be told apart. ``name=`` puts the kernel's own
name innermost: in the lowered text's locations (checked here on the CPU,
interpret mode) and — the reading that matters — in the instruction names
of the program the TPU's compiler builds, on one chip AND mapped over a
2x2 mesh (compiled here for a described v5e, no chip attached, behind the
``topo`` fixture of tests/conftest.py, so only a worker that runs such a
test loads libtpu).
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import decode_attention as da
from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops import gated_delta as gd
from deepspeed_tpu.ops import sparse_attention as sa
from deepspeed_tpu.ops import ssm


def _flash_grads(wrap=lambda f: f):
    def loss(q, k, v):
        with jax.named_scope("attn"):
            o = wrap(lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
                     )(q, k, v)
        return (o.astype(jnp.float32) ** 2).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def _paged(q, kp, vp, tables, lens, kr, vr):
    return da.paged_decode_attention(q, kp, vp, tables, lens, kv_row=(kr, vr))


# ---- the lowered text, on the CPU ------------------------------------------

def test_flash_kernel_names_in_the_lowered_text():
    q = jnp.zeros((1, 128, 4, 64), jnp.float32)
    k = jnp.zeros((1, 128, 2, 64), jnp.float32)
    text = _flash_grads().lower(q, k, k).as_text(debug_info=True)
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert re.search(rf'"jit\(loss\)/[^"]*\b{name}\b[^"]*/pallas_call"',
                         text), name


def test_paged_decode_kernel_name_in_the_lowered_text():
    S, NB, Nkv, bs, D, MB = 2, 5, 2, 16, 64, 2
    text = jax.jit(_paged).lower(
        jnp.zeros((S, 1, 4, D)), jnp.zeros((NB, bs, Nkv, D)),
        jnp.zeros((NB, bs, Nkv, D)), jnp.zeros((S, MB), jnp.int32),
        jnp.zeros((S,), jnp.int32), jnp.zeros((S, Nkv, 1, D)),
        jnp.zeros((S, Nkv, 1, D))).as_text(debug_info=True)
    assert re.search(r'"jit\(_paged\)/paged_decode/pallas_call"', text)


def test_sparse_kernel_names_in_the_lowered_text():
    cfg = sa.get_sparsity_config("fixed", block=16, num_local_blocks=2)
    q = jnp.zeros((1, 64, 2, 64), jnp.float32)

    def loss(q, k, v):
        return sa.sparse_attention(q, k, v, cfg, causal=True).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text(
        debug_info=True)
    for name in ("sparse_fwd", "sparse_dq", "sparse_dkv"):
        assert re.search(rf'\b{name}\b[^"]*/pallas_call"', text), name


def _scan(x, dt, A, B, C, S0):
    with jax.named_scope("ssm"), jax.named_scope("scan"):
        return ssm.ssm_scan(x, dt, A, B, C, S0, chunk=16, kernel=True)


def _step(pool, x, dt, A, B, C):
    with jax.named_scope("ssm"), jax.named_scope("step"):
        return ssm.ssm_step(pool, 1, x, dt, A, B, C, kernel=True)


def test_ssm_kernel_names_in_the_lowered_text():
    f32 = jnp.float32
    text = jax.jit(_scan).lower(
        jnp.zeros((32, 8, 16), f32), jnp.zeros((32, 8), f32), jnp.zeros((8,), f32),
        jnp.zeros((32, 2, 32), f32), jnp.zeros((32, 2, 32), f32),
        jnp.zeros((8, 16, 32), f32)).as_text(debug_info=True)
    assert re.search(r'"jit\(_scan\)/ssm/scan/ssm_scan/pallas_call"', text)
    text = jax.jit(_step).lower(
        jnp.zeros((2, 3, 8, 16, 32), f32), jnp.zeros((3, 8, 16), f32),
        jnp.zeros((3, 8), f32), jnp.zeros((8,), f32), jnp.zeros((3, 2, 32), f32),
        jnp.zeros((3, 2, 32), f32)).as_text(debug_info=True)
    assert re.search(r'"jit\(_step\)/ssm/step/ssm_step/pallas_call"', text)


def test_the_hybrid_scopes_in_the_lowered_text():
    """``ssm/conv``, ``ssm/scan``, ``ssm/step`` and ``moe/shared`` reach the
    lowered text of the programs a serving engine builds."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.families.nemotron_h import TOY
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    hf = {"model_type": "nemotron_h", "n_shared_experts": 1,
          "max_position_embeddings": 256, "num_experts_per_tok": 2, **TOY}
    model = make_model(hf_config_to_transformer(hf, dtype=jnp.float32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: model.init_paged_cache(
        9, 16, dtype=jnp.float32, max_seqs=2))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)       # noqa: E731
    step = jax.jit(model.decode_step_paged).lower(
        params, i32(2), pools, i32(2, 4), i32(2)).as_text(debug_info=True)
    prefill = jax.jit(model.prefill_paged).lower(
        params, i32(1, 32), pools, i32(2), length=i32(), slot=i32()
    ).as_text(debug_info=True)
    for text, scopes in ((step, ("ssm/conv", "ssm/step", "moe/shared")),
                         (prefill, ("ssm/conv", "ssm/scan", "moe/shared",
                                    "ssm/state_write"))):
        for scope in scopes:
            assert re.search(rf'/layer\d/{scope}/', text), scope
    # the K/V blocks are written once, after the walk, by the writer every
    # model's prefill shares (transformer._write_prefill_blocks)
    assert re.search(r'"jit\([^)]*\)/attn/kv_write/scatter"', prefill)


def test_the_streams_scopes_in_the_lowered_text():
    """``hc/read`` (with ``hc/sinkhorn`` inside it), ``hc/write`` under every
    block's ``layerN`` scope, ``hc/open`` at the embedding and ``hc/close`` at
    the head reach the lowered text of all three walkers of a stack whose
    stream is several rows wide (ISSUE 59); there is no kernel of that name
    today (the read and the write are XLA fusions: ``%hc_read`` /
    ``%hc_write`` are what ``benchmark/families/xing4_0.hc_op`` would find)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.families.xing4_0 import TOY
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    hf = {"model_type": "xing4_0", "hc_mult": 4, "n_shared_experts": 1,
          "num_experts_per_tok": 4, "max_position_embeddings": 256, **TOY,
          "num_hidden_layers": 2, "first_k_dense_replace": 1}
    model = make_model(hf_config_to_transformer(hf, dtype=jnp.float32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: model.init_paged_cache(9, 16,
                                                          dtype=jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)       # noqa: E731
    texts = (
        jax.jit(model.apply).lower(params, i32(1, 32)),
        jax.jit(model.decode_step_paged).lower(params, i32(2), pools,
                                               i32(2, 4), i32(2)),
        jax.jit(lambda p, ids, pools, blocks, s, n: model.prefill_paged(
            p, ids, pools, blocks, segments=(s, n))).lower(
                params, i32(1, 32), pools, i32(2), i32(4), i32(4)))
    for lowered in texts:
        text = lowered.as_text(debug_info=True)
        for scope in ("hc/read/", "hc/read/hc/sinkhorn/", "hc/write/"):
            for layer in range(4):
                assert f"/layer{layer}/{scope}" in text, (layer, scope)
        assert "/embed/hc/open/" in text and "/hc/close/" in text
        assert "pallas_call" not in text or "hc_read" not in text


def test_gdn_kernel_names_and_scopes_in_the_lowered_text():
    """``%gdn_chunk`` / ``%gdn_step`` by name, and ``gdn/conv``, ``gdn/chunk``
    | ``gdn/step``, ``gdn/state_write``, ``gdn/gate_norm``, ``attn/out_gate``
    and ``moe/shared`` in the programs a serving engine builds for a
    ``qwen3_next`` model."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.families.qwen3_next import TOY
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    f32 = jnp.float32
    z = lambda *s: jnp.zeros(s, f32)                          # noqa: E731
    chunk = jax.jit(lambda *a: gd.gdn_chunk(*a, chunk=16, kernel=True)).lower(
        z(32, 2, 16), z(32, 2, 16), z(32, 4, 16), z(32, 4), z(32, 4),
        z(4, 16, 16)).as_text(debug_info=True)
    assert re.search(r'"jit\([^)]*\)/gdn_chunk/pallas_call"', chunk)
    step = jax.jit(lambda pool, *a: gd.gdn_step(pool, 1, *a, kernel=True)).lower(
        z(2, 3, 4, 16, 16), z(3, 2, 16), z(3, 2, 16), z(3, 4, 16), z(3, 4),
        z(3, 4)).as_text(debug_info=True)
    assert re.search(r'"jit\([^)]*\)/gdn_step/pallas_call"', step)
    hf = {"model_type": "qwen3_next", "max_position_embeddings": 256,
          "num_experts_per_tok": 4, **TOY}
    model = make_model(hf_config_to_transformer(hf, dtype=f32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: model.init_paged_cache(
        9, 16, dtype=f32, max_seqs=2))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)       # noqa: E731
    step = jax.jit(model.decode_step_paged).lower(
        params, i32(2), pools, i32(2, 4), i32(2)).as_text(debug_info=True)
    prefill = jax.jit(model.prefill_paged).lower(
        params, i32(1, 32), pools, i32(2), length=i32(), slot=i32()
    ).as_text(debug_info=True)
    for text, scopes in (
            (step, ("gdn/conv", "gdn/step", "gdn/gate_norm", "attn/out_gate",
                    "moe/shared")),
            (prefill, ("gdn/conv", "gdn/chunk", "gdn/state_write",
                       "gdn/gate_norm", "attn/out_gate", "moe/shared"))):
        for scope in scopes:
            assert re.search(rf'/layer\d/{scope}/', text), scope


# ---- the instruction names the TPU's compiler gives -------------------------

@pytest.fixture()
def mosaic(monkeypatch):
    """The kernels ask ``jax.default_backend()`` (the CPU here) whether to
    interpret: steer them to the Mosaic lowering, in the test. The suite's
    "highest" matmul precision is the CPU parity tests' (conftest.py); the
    chip runs the kernels at the default, and at "highest" their fp32
    passes do not fit VMEM."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(da, "_interpret", lambda: False)
    monkeypatch.setattr(ssm, "_interpret", lambda: False)
    monkeypatch.setattr(gd, "_interpret", lambda: False)
    with jax.default_matmul_precision("default"):
        yield


def _mosaic_calls(compiled):
    return sorted(set(re.findall(
        r'(%[\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())))


B, S, NQ, NKV, D = 4, 2048, 32, 8, 128          # the train cells' attention


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_instruction_names_on_one_chip(topo, mosaic):
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    q, kv = (_sds((B, S, n, D), jnp.bfloat16, one) for n in (NQ, NKV))
    calls = _mosaic_calls(_flash_grads().lower(q, kv, kv).compile())
    assert [c.split(".")[0] for c in calls] == [
        "%flash_dkv", "%flash_dq", "%flash_fwd"], calls


@pytest.mark.parametrize("cell,heads,kv_heads,dim,row", [
    ("glm", 20, 20, 256, 4096), ("glm-odd-row", 20, 20, 256, 3584),
    ("chat", 32, 8, 128, 1024), ("olmoe", 16, 16, 128, 768),
    ("mixtral-one-tile", 32, 8, 128, 256)])
def test_the_packed_forward_is_one_flash_fwd_at_the_serve_cells_shapes(
        cell, heads, kv_heads, dim, row, topo, mosaic):
    """A shared row's attention (ISSUE 53) at the shapes the serve cells
    hand it: Mosaic takes the prefetched bounds, the clamped index maps and
    the segments' ends at every one, and the call keeps the name the
    benchmark reads (``%flash_fwd``): ONE call, no loop."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    q, kv = (_sds((1, row, n, dim), jnp.bfloat16, one)
             for n in (heads, kv_heads))
    compiled = jax.jit(fa.flash_attention_packed).lower(
        q, kv, kv, _sds((1, row), jnp.int32, one)).compile()
    calls = _mosaic_calls(compiled)
    assert [c.split(".")[0] for c in calls] == ["%flash_fwd"], calls
    assert " while(" not in compiled.as_text()


def test_flash_instruction_names_under_a_mesh(topo, mosaic):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "tensor"))
    spec = P("fsdp", None, "tensor", None)

    def over_mesh(f):
        return jax.shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)
    ns = NamedSharding(mesh, spec)
    q, kv = (_sds((B, S, n, D), jnp.bfloat16, ns) for n in (NQ, NKV))
    calls = _mosaic_calls(_flash_grads(over_mesh).lower(q, kv, kv).compile())
    assert [c.split(".")[0] for c in calls] == [
        "%flash_dkv", "%flash_dq", "%flash_fwd"], calls


def test_paged_decode_instruction_name(topo, mosaic):
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    slots, NB, bs, MB = 8, 65, 64, 16
    row = _sds((slots, NKV, 1, D), jnp.bfloat16, one)
    pool = _sds((NB, bs, NKV, D), jnp.bfloat16, one)
    calls = _mosaic_calls(jax.jit(_paged).lower(
        _sds((slots, 1, NQ, D), jnp.bfloat16, one), pool, pool,
        _sds((slots, MB), jnp.int32, one), _sds((slots,), jnp.int32, one),
        row, row).compile())
    assert [c.split(".")[0] for c in calls] == ["%paged_decode"], calls


def test_paged_decode_int8_instruction_name(topo, mosaic):
    """The int8 pool's kernel at Trinity's heads and table (48 query heads
    over 8, 176 columns), the whole leaves and the layer a scalar: ONE Mosaic
    call, named for ``sat_paged_read_roofline`` to find, and no other."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    slots, bs, MB, L = 8, 64, 176, 2
    NB = slots * MB + 1
    row = _sds((slots, NKV, 1, D), jnp.bfloat16, one)
    pool = _sds((L, NB, bs, NKV, D), jnp.int8, one)
    plane = _sds((L, NB, NKV * bs), jnp.float32, one)

    def read(q, k, v, ks, vs, tables, lens, layer, kr, vr):
        return da.paged_decode_int8(q, k, v, ks, vs, tables, lens, layer,
                                    kv_row=(kr, vr))
    calls = _mosaic_calls(jax.jit(read).lower(
        _sds((slots, 1, 48, D), jnp.bfloat16, one), pool, pool, plane, plane,
        _sds((slots, MB), jnp.int32, one), _sds((slots,), jnp.int32, one),
        _sds((), jnp.int32, one), row, row).compile())
    assert [c.split(".")[0] for c in calls] == ["%paged_decode_int8"], calls


def test_latent_decode_instruction_name(topo, mosaic):
    """The latent pool's kernel at GLM-4.7-Flash's heads, row and table (20
    heads against one 576-wide row stored in 640 lanes, 76 columns), the whole
    leaf and the plane a scalar: ONE Mosaic call, named for
    ``sat_mla_read_roofline`` to find, and no other."""
    from deepspeed_tpu.ops import latent_decode as ld
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    slots, bs, MB, L, lanes = 8, 64, 76, 2, 640
    NB = slots * MB + 1

    def read(q, pool, tables, lens, layer, row):
        return ld.latent_decode(q, pool, tables, lens, layer, row, rank=512,
                                sm_scale=1 / 16)
    calls = _mosaic_calls(jax.jit(read).lower(
        _sds((slots, 20, lanes), jnp.bfloat16, one),
        _sds((L, NB, bs, lanes), jnp.bfloat16, one),
        _sds((slots, MB), jnp.int32, one), _sds((slots,), jnp.int32, one),
        _sds((), jnp.int32, one), _sds((slots, lanes), jnp.bfloat16, one)
    ).compile())
    assert [c.split(".")[0] for c in calls] == ["%latent_decode"], calls


@pytest.mark.parametrize("H,per_group,P,N,step,scan", [
    (64, 8, 64, 128, 16, 8),        # Nemotron-3-Nano: what it always ran at
    (32, 16, 128, 256, 4, 8),       # Falcon-H1-34B: a quarter / half a group
    (8, 4, 16, 32, 8, 4),           # the toys: everything in one step
    (24, 12, 64, 128, 12, 12),      # whole groups only, never 16 of 24
    (6, 6, 128, 256, 3, 6)])        # a divisor of the group, not 4
def test_heads_a_grid_step_come_from_a_byte_budget(H, per_group, P, N, step,
                                                   scan):
    """``ssm.block_heads``: the most heads whose float32 state fits the
    budget (512 KiB a step of the step kernel, 1 MiB of the scan), as whole
    groups that divide the heads, or as a divisor of one group; a scan's
    grid row is never more than a group."""
    assert ssm.block_heads(H, per_group, P, N, ssm.STEP_STATE_BYTES) == step
    assert min(per_group, ssm.block_heads(H, per_group, P, N,
                                          ssm.SCAN_STATE_BYTES)) == scan
    hb = ssm.block_heads(H, per_group, P, N, ssm.STEP_STATE_BYTES)
    assert H % hb == 0 and (hb % per_group == 0 or per_group % hb == 0)


@pytest.mark.parametrize("model,T,H,P,G,N,S", [
    ("nemotron-3-nano", 1024, 64, 64, 8, 128, 128),
    ("falcon-h1-34b", 1024, 32, 128, 2, 256, 64)])
def test_ssm_instruction_names_at_the_published_sizes(topo, mosaic, model, T,
                                                      H, P, G, N, S):
    """Nemotron-3-Nano's Mamba blocks (64 heads of 64, 8 groups, state 128)
    and Falcon-H1-34B's (32 heads of 128, 2 groups, state 256: a head's state
    is 128 KiB, and a grid step's heads come from a byte budget); a
    1024-token prompt, and one step over the cell's slots whose state pool
    is updated in place (the call's output aliases the donated pool: no
    temporary of the pool's size)."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    bf, f32 = jnp.bfloat16, jnp.float32
    scan = jax.jit(lambda *a: ssm.ssm_scan(*a, kernel=True)).lower(
        _sds((T, H, P), bf, one), _sds((T, H), f32, one), _sds((H,), f32, one),
        _sds((T, G, N), bf, one), _sds((T, G, N), bf, one),
        _sds((H, P, N), f32, one)).compile()
    assert [c.split(".")[0] for c in _mosaic_calls(scan)] == ["%ssm_scan"]
    step = jax.jit(lambda pool, *a: ssm.ssm_step(pool, 2, *a, kernel=True),
                   donate_argnums=(0,)).lower(
        _sds((4, S, H, P, N), f32, one), _sds((S, H, P), bf, one),
        _sds((S, H), f32, one), _sds((H,), f32, one), _sds((S, G, N), bf, one),
        _sds((S, G, N), bf, one)).compile()
    assert [c.split(".")[0] for c in _mosaic_calls(step)] == ["%ssm_step"]
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * S * H * P * N * 4
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_gdn_instruction_names_at_the_published_sizes(topo, mosaic):
    """Qwen3-Next's Gated DeltaNet blocks: 16 key heads serving 32 value
    heads of 128 x 128; a 1024-token prompt in chunks of 64, and one step
    over 128 slots whose state pool is updated in place (the call's output
    aliases the donated pool: no temporary of the pool's size)."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    bf, f32 = jnp.bfloat16, jnp.float32
    T, Hk, Hv, dk, dv, S = 1024, 16, 32, 128, 128, 128
    chunk = jax.jit(lambda *a: gd.gdn_chunk(*a, chunk=64, kernel=True)).lower(
        _sds((T, Hk, dk), bf, one), _sds((T, Hk, dk), bf, one),
        _sds((T, Hv, dv), bf, one), _sds((T, Hv), f32, one),
        _sds((T, Hv), f32, one), _sds((Hv, dk, dv), f32, one)).compile()
    assert [c.split(".")[0] for c in _mosaic_calls(chunk)] == ["%gdn_chunk"]
    step = jax.jit(lambda pool, *a: gd.gdn_step(pool, 2, *a, kernel=True),
                   donate_argnums=(0,)).lower(
        _sds((9, S, Hv, dk, dv), f32, one), _sds((S, Hk, dk), bf, one),
        _sds((S, Hk, dk), bf, one), _sds((S, Hv, dv), bf, one),
        _sds((S, Hv), f32, one), _sds((S, Hv), f32, one)).compile()
    assert [c.split(".")[0] for c in _mosaic_calls(step)] == ["%gdn_step"]
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes == 9 * S * Hv * dk * dv * 4
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


# ---- the banded flash forward (afmoe's sliding layers) ---------------------

def test_band_kernel_name_and_window_scopes_in_the_lowered_text():
    """``flash_fwd_band`` by name, and the two attention kinds told apart
    under ``attn/``: a sliding block's attention, ring read and ring write
    lie under ``attn/window``, the full block's under ``attn`` alone."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.families.afmoe import TOY
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    q = jnp.zeros((1, 128, 6, 64), jnp.float32)
    k = jnp.zeros((1, 128, 1, 64), jnp.float32)
    band = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, window=32)
                   ).lower(q, k, k).as_text(debug_info=True)
    assert re.search(r'"jit\([^)]*\)/[^"]*\bflash_fwd_band\b[^"]*/pallas_call"',
                     band)
    hf = {"model_type": "afmoe", "max_position_embeddings": 256,
          "num_experts_per_tok": 4, "sliding_window": 32, **TOY}
    model = make_model(hf_config_to_transformer(hf, dtype=jnp.float32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: model.init_paged_cache(
        17, 16, dtype=jnp.float32, max_seqs=2))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)       # noqa: E731
    step = jax.jit(model.decode_step_paged).lower(
        params, i32(2), pools, i32(2, 8), i32(2)).as_text(debug_info=True)
    prefill = jax.jit(model.prefill_paged).lower(
        params, i32(1, 128), pools, i32(8), length=i32(), slot=i32()
    ).as_text(debug_info=True)
    # layer0 is a sliding block, layer6 the full one ("WDWEWE*EWE")
    for text in (step, prefill):
        assert re.search(r'/layer0/attn/window/', text)
        assert re.search(r'/layer6/attn/', text)
        assert not re.search(r'/layer6/attn/window/', text)
        assert re.search(r'/layer0/attn/out_gate/', text)
        assert re.search(r'/layer1/mlp/', text)
        assert re.search(r'"jit\([^)]*\)/attn/window/kv_write/', text)
        assert re.search(r'"jit\([^)]*\)/attn/kv_write/scatter"', text)


def test_band_instruction_name_at_the_published_sizes(topo, mosaic):
    """Trinity's sliding layers at the cell's longest prompt bucket: 48
    query heads over 8 K/V heads of 128, 9216 positions, window 4096 — ONE
    Mosaic call, by its own name, whose grid's innermost extent is the six
    key tiles a query tile's band can touch (of nine)."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    q, kv = (_sds((1, 9216, n, 128), jnp.bfloat16, one) for n in (48, 8))
    compiled = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, window=4096)).lower(q, kv, kv).compile()
    calls = _mosaic_calls(compiled)
    assert [c.split(".")[0] for c in calls] == ["%flash_fwd_band"], calls
    assert fa._band_tiles(4096, *fa._pick_blocks(9216, 512, 1024, 6), 9216) == 6


def test_row_kernel_instruction_names_at_the_published_widths(topo, monkeypatch):
    """ONE sorted expert layer of Mellum2 at the PUBLISHED widths
    (benchmark/configs/mellum2-12b-train.json: top-8 of a 64-wide router, 16
    experts of [2304, 896] held; 2 x 1024 tokens, an eighth of the cell's,
    which shortens the grids and nothing else), its TRAINING gradient
    compiled for the described v5e with every backend branch taken as on the
    chip: Mosaic takes the three row kernels at these widths (it refuses a
    one-row slice of a ``[rows, H]`` array, which no CPU run shows), each
    mover is there by its own name — two gathers (the dispatch, the
    combine's gradient), two combines (the combine, the dispatch's gradient),
    a pack for each — beside the grouped matmul's, and XLA neither pads,
    selects, adds nor gathers an array of T k rows of H."""
    import json
    import os
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    from deepspeed_tpu.moe import sharded_moe as sm
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2-12b-train.json")) as f:
        conf = json.load(f)
    cfg = hf_config_to_transformer(
        {k: v for k, v in conf.items() if k not in (
            "source", "reduced", "assumed", "deployment", "run", "correct")},
        dtype=jnp.bfloat16)
    T, k, H, F, E, R = 2048, 8, 2304, 896, 16, 64
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    p = {"wg": _sds((H, R), jnp.float32, one),
         "w_in_t": _sds((E, F, H), jnp.bfloat16, one),
         "w_gate": _sds((E, H, F), jnp.bfloat16, one),
         "w_out": _sds((E, F, H), jnp.bfloat16, one)}
    fn = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
        sm.moe_ffn(p, x, cfg, train=True)[0].astype(jnp.float32)),
        argnums=(0, 1)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        with jax.default_matmul_precision("default"):
            compiled = fn.lower(p, _sds((2, T // 2, H), jnp.bfloat16,
                                        one)).compile()
    finally:
        monkeypatch.undo()
    hlo = compiled.as_text()
    calls = [l.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for l in hlo.splitlines() if "custom_call_target=\"tpu_custom_call\"" in l]
    assert calls.count("moe_rows_gather") == 2
    assert calls.count("moe_rows_combine") == 2
    assert calls.count("moe_rows_pack") == 4
    assert calls.count("moe_gmm") == 6 and calls.count("moe_gmm_dw") == 3
    rows = f"bf16[{T * k},{H}]"
    moved = [l for l in hlo.splitlines()
             if f" = {rows}" in l and "tpu_custom_call" not in l
             and " parameter(" not in l and "get-tuple-element" not in l
             and " bitcast(" not in l]
    assert not moved, moved[:3]


# ---- a train cell's WHOLE step: the flash forward runs once (ISSUE 58) ------

def _described_train_step(topo, monkeypatch, cell):
    """A benchmark train cell's own engine step compiled for the described
    v5e — the configuration's model, engine settings, token batch and
    devices, the state abstract (nothing of its gigabytes is built; verify
    skill, "Tier-1 and the described v5e") — as (kernel instruction names,
    one entry a Mosaic call of the text; memory analysis)."""
    import json
    import os
    import deepspeed_tpu
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmark.harness import common, loadgen
    from deepspeed_tpu.models import make_model
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        wl = next(w for w in json.load(f)["workloads"] if w["name"] == cell)
    traffic = loadgen.load_traffic(wl["traffic"])
    cfg = common.load_config(wl["config"])
    seq = traffic["seq_len"]
    rows = traffic["tokens_per_step"] // seq
    model = make_model(common.model_config(cfg, common.hf_of(cfg, False), seq),
                       name=wl["config"])
    real_jit = jax.jit

    def jit(fn, *a, **kw):      # the state's init program hands out shapes
        if getattr(fn, "__name__", "") != "make_state":
            return real_jit(fn, *a, **kw)
        return lambda key: jax.tree.map(
            lambda s, sh: _sds(s.shape, s.dtype, sh),
            jax.eval_shape(fn, key), kw["out_shardings"])
    with monkeypatch.context() as m:
        m.setattr(jax, "jit", jit)
        engine, *_ = deepspeed_tpu.initialize(
            model=model, rng=jax.random.PRNGKey(0),
            config=dict(cfg["run"]["engine"], train_batch_size=rows),
            devices=list(topo.devices[:wl["chips"]]))
    try:
        batch = {"input_ids": _sds((rows, seq), jnp.int32, NamedSharding(
            engine.mesh, P(*engine._batch_spec()[:2])))}
        key = _sds((2,), jnp.uint32, NamedSharding(engine.mesh, P()))
        with monkeypatch.context() as m:    # every backend branch as on the chip
            m.setattr(jax, "default_backend", lambda: "tpu")
            with engine.mesh, jax.default_matmul_precision("default"):
                compiled = engine._train_step.lower(
                    engine.state, batch, key).compile()
    finally:
        engine.close()
    calls = [c.lstrip("%").split(".")[0] for c in _mosaic_calls(compiled)]
    return calls, compiled.memory_analysis()


HBM = 15.75 * 2 ** 30          # one v5e chip


@pytest.mark.parametrize("cell", ["mistral-7b-train.seq2048",
                                  "mistral-7b-zero3.seq2048"])
def test_a_dense_train_step_holds_the_flash_forward_once(topo, monkeypatch,
                                                         cell):
    """Mistral's step at the cells' 4 x 2048 tokens under ``dots_saveable``,
    on one chip and sharded fsdp 2 x tensor 2 over the described 2 x 2 host
    (the kernel through ``_flash_per_shard``): the scanned layer's forward
    body calls ``flash_fwd`` and the backward body only ``flash_dq`` /
    ``flash_dkv`` — three Mosaic calls in the text where the replayed
    forward made four — and the step fits the chip with its temporaries."""
    calls, memory = _described_train_step(topo, monkeypatch, cell)
    assert sorted(calls) == ["flash_dkv", "flash_dq", "flash_fwd"], calls
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes) < HBM


def test_mellums_train_step_holds_each_flash_forward_once(topo, monkeypatch):
    """Mellum's step at 2 x 8192 tokens under ``save_nothing`` (four
    unrolled blocks a period, three banded and one full): ONE ``flash_fwd``
    and THREE ``flash_fwd_band`` (two and six with the replay), each with
    its two backward kernels, beside the expert kernels the cell's readers
    find by name."""
    calls, memory = _described_train_step(topo, monkeypatch,
                                          "mellum2-12b-train.seq8192")
    count = collections.Counter(calls)
    assert count["flash_fwd"] == count["flash_dq"] == count["flash_dkv"] == 1
    assert (count["flash_fwd_band"] == count["flash_bwd_band_dq"]
            == count["flash_bwd_band_dkv"] == 3)
    assert {"moe_gmm", "moe_gmm_dw", "moe_rows_gather", "moe_rows_combine",
            "moe_rows_pack"} <= set(count)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes) < HBM
