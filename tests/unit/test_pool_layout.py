"""The paged pool is written IN PLACE (ISSUE 24).

A decode step, a span and a prefill each write a few K/V rows into a pool
of gigabytes. Stored head-major (``[L, NB, n_kv, block_size, head_dim]``)
a token's row is one line inside the ``(block_size, head_dim)`` tile of
every head, and the TPU's compiler answered each such write by moving the
WHOLE pool to another layout, scattering, and moving it back: four copies
of 1.6 GB a step in the chat cell (PERF.md §5-6). Stored token-major the
row is a whole minor tile and the scatter is in place.

Nothing on the CPU shows this: the copies exist only in the program the
TPU's compiler builds. So the serving engine's own jitted functions are
compiled here for a described v5e (no chip attached) at the three serve
cells' pool shapes and a bf16 pool, and the compiled text must hold no op
that produces a whole pool or scale plane other than the scatters.

And it is READ once (ISSUE 27): a decode step gathers a layer's blocks
straight out of the whole pool and contracts the gathered token-major view
as it is. The same compiles must hold no copy of a layer's slice of a pool
(the parent sliced the pool per layer ahead of the gather: 100 MB of each
pool a layer in the chat cell), no array of the gathered view's size in a
wider type than the pool's (with one query head per kv head the parent
widened both views to s32, 268 MB each a layer in OLMoE's cell, and
multiplied them elementwise), and few temporaries.

And what it reads is a flat LIST of the round's live blocks (ISSUE 38):
handed ``N`` blocks the step gathers ``[N, block, kv heads, head_dim]`` of
each pool a layer and holds nothing of the ``slots x table`` view's size, nor
an int32 partial sum larger than the K and V it gathered.

libtpu is loaded behind a fixture (``topo``, tests/conftest.py), shared
with ``test_kernel_names.py``, the only other file that describes a chip.
Under several workers each of the two files' workers loads it; the driver's command allows that
(``ALLOW_MULTIPLE_LIBTPU_LOAD=1``), and where it is not allowed the later
file's tests skip, they do not fail.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import _SEGMENTS
from deepspeed_tpu.models import TransformerConfig, make_model


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


# the pools of four of the benchmark's serve cells (benchmark/configs/*-serve
# .json: layers x blocks, slots, table width at 64-token blocks, kv and
# query heads of 128) and a float pool of the chat cell's shape at half the
# blocks
CASES = {
    "chat-int8": dict(layers=16, blocks=1537, slots=48, mb=32, bits=8,
                      nkv=8, nq=32),
    "mixtral-int8": dict(layers=4, blocks=1025, slots=32, mb=32, bits=8,
                         nkv=8, nq=32),
    "olmoe-int8": dict(layers=14, blocks=513, slots=32, mb=16, bits=8,
                       nkv=16, nq=16),
    "chat-bf16": dict(layers=16, blocks=769, slots=48, mb=16, bits=0,
                      nkv=8, nq=32),
    # one pass of PR 35's looped cell (48 of its 192 planes x 161 blocks,
    # 16 slots x 20 columns, one query head per kv head)
    "ouro-int8": dict(layers=48, blocks=161, slots=16, mb=20, bits=8,
                      nkv=16, nq=16),
}
BS, HD = 64, 128


@pytest.fixture(scope="module")
def engines():
    """One toy-sized engine per case on the CPU: only its jitted FUNCTIONS
    are used, re-lowered on abstract arguments at the cell's shapes (a
    program is shaped by its arguments, not by the engine's own pool)."""
    built = {}

    def get(case):
        if case not in built:
            c = CASES[case]
            cfg = TransformerConfig(
                vocab_size=512, hidden_size=256, num_layers=c["layers"],
                num_heads=c["nq"], num_kv_heads=c["nkv"], head_dim=HD,
                intermediate_size=512, max_seq_len=4096, position_type="rotary",
                activation="silu_glu", norm_type="rmsnorm",
                tie_embeddings=False, dtype=jnp.bfloat16,
                attention_impl="xla")
            built[case] = deepspeed_tpu.init_serving(
                make_model(cfg), config={"kv_cache_bits": c["bits"]},
                serving=dict(max_seqs=2, block_size=BS, max_model_len=128,
                             decode_backend="xla"),
                dtype=jnp.bfloat16)
        return built[case]
    yield get
    for srv in built.values():
        srv.close()


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _block_list(sds, slots, columns, mb):
    """The shapes of a ``BlockList`` of ``slots x columns`` blocks in the
    engine's runs, for slots whose tables are ``mb`` wide."""
    from deepspeed_tpu.inference.serving import _RUN
    from deepspeed_tpu.models.transformer import BlockList
    n = slots * columns
    return BlockList(sds((n,), jnp.int32), sds((n // _RUN,), jnp.int32),
                     sds((slots, -(-mb // _RUN)), jnp.int32))


def _program(srv, case, kind, one_chip, shape=None):
    """(lowered-and-compiled program, abstract pools) of one of the
    engine's three pool-writing functions at the case's shapes. ``shape``:
    the step handed a block list of another ``(slots, columns a slot)``
    than the case's full ``(slots, table width)`` — the first ``slots``
    rows of the case's slots, ``slots x columns`` blocks."""
    c = CASES[case]
    S, W = shape or (c["slots"], c["mb"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _abstract(srv.engine.params, one_chip)
    pools = _abstract(jax.eval_shape(
        lambda: srv.model.init_paged_cache(c["blocks"], BS,
                                           dtype=jnp.bfloat16)), one_chip)
    key = sds((2,), jnp.uint32)
    if kind == "step":                    # the decode quantum's one step
        fn = jax.jit(srv._quantum_step_fn().__wrapped__,
                     donate_argnums=(1, 4))
        args = (params, pools, sds((c["slots"],), jnp.int32),
                _block_list(sds, S, W, c["mb"]), sds((S,), jnp.int32),
                sds((S,), jnp.bool_), key)
    elif kind == "span":                  # speculation verify, T = 4
        fn = jax.jit(srv._get_spec_step().__wrapped__, donate_argnums=(1,))
        args = (params, pools, sds((S, 4), jnp.int32),
                sds((S, W), jnp.int32), sds((S,), jnp.int32),
                sds((S,), jnp.bool_), key)
    else:                                 # prefill of one 256-token bucket
        fn = jax.jit(srv._get_prefill_fn(256).__wrapped__,
                     donate_argnums=(2,))
        args = (params, sds((1, 256), jnp.int32), pools,
                sds((256 // BS,), jnp.int32), *[sds((_SEGMENTS,), jnp.int32)] * 2,
                key)
    # the suite's "highest" matmul precision is the CPU parity tests'
    # (conftest.py); the chip runs at the default
    with jax.default_matmul_precision("default"):
        return fn.lower(*args).compile(), pools


_INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")
_BYTES = {"s8": 1, "u8": 1, "pred": 1, "bf16": 2, "f16": 2, "f32": 4,
          "s32": 4, "u32": 4}
_HLO_NAME = {"int8": "s8", "bfloat16": "bf16", "float32": "f32"}
# not arrays a program writes: arguments and views of them
_VIEWS = ("parameter", "bitcast", "get-tuple-element", "tuple", "while")


def _computations(hlo: str):
    """(computation name -> its lines, the names of the FUSED ones)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps, set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))


def _instructions(hlo: str, fused_too: bool = False):
    """(type, element count, opcode, line, holds a scatter) of every
    instruction outside fused computations (``fused_too``: inside them as
    well) — outside them each result is an array the program writes."""
    comps, fused = _computations(hlo)
    for name, lines in comps.items():
        if name in fused and not fused_too:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m or m.group(1) not in _BYTES:
                continue
            dims = [int(d) for d in m.group(2).split(",") if d]
            scatters = False
            if m.group(3) == "fusion":
                callee = re.search(r"calls=%([\w.\-]+)", line).group(1)
                scatters = any(" scatter(" in l for l in comps.get(callee, ()))
            yield (m.group(1), int(np.prod(dims)) if dims else 1, m.group(3),
                   line.strip()[:160], scatters)


def whole_pool_ops(hlo: str, pools) -> list:
    """Instructions OUTSIDE fused computations whose result has a whole
    pool leaf's BYTES (whatever its shape: a relayout may merge or split
    dims) and that are a copy, a transpose, a reshape, or a fusion that
    holds no scatter: each one reads and writes the leaf's every byte."""
    sizes = {int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
             for a in jax.tree.leaves(pools)}
    return [line for ty, n, op, line, scatters in _instructions(hlo)
            if n * _BYTES[ty] in sizes and not scatters
            and op in ("copy", "transpose", "reshape", "fusion")]


def layer_slice_ops(hlo: str, pools) -> list:
    """Instructions outside fused computations that produce ONE LAYER's
    slice of a pool leaf or of a scale plane (its type and element count):
    the slice taken ahead of the gather is a copy."""
    slices = {(_HLO_NAME[np.dtype(a.dtype).name], int(np.prod(a.shape[1:])))
              for a in jax.tree.leaves(pools)}
    return [line for ty, n, op, line, _ in _instructions(hlo)
            if (ty, n) in slices and op not in _VIEWS]


def widened_view_ops(hlo: str, pools, slots: int, mb: int) -> list:
    """Instructions, fused or not, whose result has the gathered view's
    element count (slots x table x block x heads x head_dim) in a type
    wider than the pool's."""
    leaf = pools["k"]
    count = slots * mb * int(np.prod(leaf.shape[2:]))
    width = np.dtype(leaf.dtype).itemsize
    return [line for ty, n, op, line, _ in _instructions(hlo, fused_too=True)
            if n == count and _BYTES[ty] > width and op not in _VIEWS]


@pytest.mark.parametrize("case,kind", [
    ("chat-int8", "step"), ("mixtral-int8", "step"), ("chat-bf16", "step"),
    ("chat-int8", "span"), ("chat-int8", "prefill")])
def test_pool_is_written_in_place(case, kind, one_chip, engines):
    compiled, pools = _program(engines(case), case, kind, one_chip)
    bad = whole_pool_ops(compiled.as_text(), pools)
    assert not bad, "\n".join(bad)
    # no temporary of a whole leaf's size either (a relayout's scratch, a
    # scatter that lost its alias). At the cells' table width the READ
    # side's gathered views (slots x table x block, K and V) are temporaries
    # too — 0.2 GB in the chat cell, a quarter of the whole Mixtral pool —
    # and share their space with whatever the write needs, so the write's
    # own temporaries are read off the same program with a list of one run
    # a slot (a span: a table one run wide): under the bytes of ONE K/V leaf.
    if kind != "prefill":                 # a prefill reads no table
        compiled, pools = _program(engines(case), case, kind, one_chip,
                                   shape=(CASES[case]["slots"], 2))
    leaf = pools["k"]
    leaf_bytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < leaf_bytes, (temp, leaf_bytes)


@pytest.mark.parametrize("case,kind", [
    ("chat-int8", "step"), ("mixtral-int8", "step"), ("olmoe-int8", "step"),
    ("chat-int8", "span")])
def test_pool_is_read_once(case, kind, one_chip, engines):
    """The read of a decode step (and of a span): one gather per pool and
    layer out of the WHOLE pool, contracted as gathered (ISSUE 27). The
    parent fails the first assertion in all four cases (a
    ``dynamic-slice_bitcast_fusion`` per pool and plane) and the widened
    view in OLMoE's."""
    c = CASES[case]
    compiled, pools = _program(engines(case), case, kind, one_chip)
    hlo = compiled.as_text()
    bad = layer_slice_ops(hlo, pools)
    assert not bad, "\n".join(bad)
    # OLMoE's cut has 14 layers, not a multiple of the 8-row tile: the
    # compiler keeps its scale planes [14, 513, 1024] layer-major between
    # steps and moves them layer-second-minor around the row scatters, four
    # copies of 29 MB a step (0.33 ms of 58 on the chip, parent and change
    # alike: PERF.md section 7, PR 27). The write's business, not the
    # read's: that shape checks its payload leaves.
    leaves = ({n: pools[n] for n in "kv"} if case == "olmoe-int8" else pools)
    bad = whole_pool_ops(hlo, leaves)
    assert not bad, "\n".join(bad)
    if kind == "step":
        bad = widened_view_ops(hlo, pools, c["slots"], c["mb"])
        assert not bad, "\n".join(bad)
    if (case, kind) == ("chat-int8", "step"):
        # the gathered K and V views (0.1 GB each) and the scores; the
        # parent's step held 1.0 GB (slices, views, their head-major twins)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 0.3e9, temp


# every list length of the cells' engines (``ServingEngine._step_shapes`` at
# their slots and table widths) but the full one, which the tests above
# compile; PR 35's looped cell is checked at its published widths below
@pytest.mark.parametrize("case,slots,columns", [
    ("chat-int8", 16, 4), ("chat-int8", 16, 8), ("chat-int8", 48, 16),
    ("mixtral-int8", 32, 8), ("mixtral-int8", 32, 16),
    ("olmoe-int8", 32, 4), ("olmoe-int8", 32, 8),
    ("chat-bf16", 16, 2), ("chat-bf16", 16, 4), ("chat-bf16", 48, 8),
    ("ouro-int8", 16, 6), ("ouro-int8", 16, 10)])
def test_the_read_is_sized_by_the_list(case, slots, columns, one_chip,
                                       engines):
    """The read is sized by the LIST the step is handed and by nothing
    else (ISSUE 38; ISSUE 29 and ISSUE 33 sized it by the tables' width and
    rows): handed ``slots x columns`` blocks for the first ``slots`` of the
    cell's slots, the step holds two gathers of ``[blocks, block, kv heads,
    head_dim]`` a layer, no array of the ``slots x table`` view's size in
    any type — nor of the full engine's —, no int32 array larger than the K
    and V it gathered (P.V's partial sums are a block's ``[query heads,
    head_dim]``, not every (query head, kv head) pair's), no widened view,
    no slice of a layer; the pool leaves are still written in place; and
    the per-slot token vector goes in and comes back ``max_seqs`` long."""
    from deepspeed_tpu.inference.serving import _list_ladder, _slot_ladder
    c = CASES[case]
    assert slots in _slot_ladder(c["slots"])
    assert columns in _list_ladder(c["mb"], (8, 4, 2, 1))
    compiled, pools = _program(engines(case), case, "step", one_chip,
                               shape=(slots, columns))
    hlo = compiled.as_text()
    leaf = pools["k"]
    ty = _HLO_NAME[np.dtype(leaf.dtype).name]
    view = int(np.prod(leaf.shape[2:]))           # block x kv heads x head_dim
    n = slots * columns
    gathers = [line for t, m, op, line, _ in _instructions(hlo)
               if (t, m, op) == (ty, n * view, "fusion")
               and f"{ty}[{n},{BS},{c['nkv']},{HD}]" in line]
    assert len(gathers) == 2, "\n".join(gathers)        # K and V, a layer
    tables = {slots * c["mb"] * view, c["slots"] * c["mb"] * view} - {n * view}
    bad = [line for t, m, op, line, _ in _instructions(hlo, fused_too=True)
           if m in tables and op not in _VIEWS]
    assert not bad, "\n".join(bad)
    gathered = 2 * n * view * np.dtype(leaf.dtype).itemsize
    bad = [line for t, m, op, line, _ in _instructions(hlo, fused_too=True)
           if t == "s32" and m * 4 > gathered and op not in _VIEWS]
    assert not bad, "\n".join(bad)
    assert not layer_slice_ops(hlo, pools)
    assert not widened_view_ops(hlo, pools, slots, columns)
    # OLMoE's and the looped cell's scale planes are relayouted around the
    # row scatters (``test_pool_is_read_once``; PERF.md section 7, PR 35):
    # the write's business, those shapes check their payload leaves
    leaves = ({n: pools[n] for n in "kv"}
              if case in ("olmoe-int8", "ouro-int8") else pools)
    bad = whole_pool_ops(hlo, leaves)
    assert not bad, "\n".join(bad)
    (_, (tokens, _), lens) = compiled.out_info
    assert tokens.shape == (c["slots"],) and lens.shape == (slots,)


# ---- the dropless expert dispatch (ISSUE 26) --------------------------------
#
# Same file because it is the same kind of test: a property that only the
# program the TPU's compiler builds can show, compiled for the described v5e.

def _moe_engine(**overrides):
    """An OLMoE-shaped engine (64 experts, top-8, q/k norm, dropless) at
    narrow widths; only its jitted functions are used."""
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    hf = {"model_type": "olmoe", "vocab_size": 512, "hidden_size": 256,
          "intermediate_size": 128, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "num_experts": 64, "num_experts_per_tok": 8, "norm_topk_prob": False,
          "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False}
    cfg = hf_config_to_transformer(hf, max_seq_len=1024, dtype=jnp.bfloat16,
                                   **overrides)
    return deepspeed_tpu.init_serving(
        make_model(cfg), config={"kv_cache_bits": 8},
        serving=dict(max_seqs=2, block_size=BS, max_model_len=128,
                     decode_backend="xla"), dtype=jnp.bfloat16)


def _largest_arrays(hlo: str, at_least: int) -> list:
    """Instructions (anywhere, fused or not) whose result has at least
    ``at_least`` elements and is not a parameter or a view of one."""
    bad = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(3) in ("parameter", "bitcast", "get-tuple-element"):
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        if dims and int(np.prod(dims)) >= at_least:
            bad.append(line.strip()[:160])
    return bad


def test_dropless_dispatch_is_proportional_to_the_assignments(one_chip, monkeypatch):
    """A prompt of T = 512 tokens, E = 64, k = 8, H = 256: the sorted
    dispatch's largest arrays are the T*k rows ([4096, 256]); nothing in the
    compiled program has the E x T x H = 8.4 M elements of an expert-major
    dispatch buffer, let alone the T x E x T = 16.8 M of a one-hot mask, the
    program's temporaries stay under that buffer's bytes, the kernel reads
    the layer's experts out of the whole stack (no copy of one layer's), and
    a decode step of 32 slots — which takes the one-hot masks, [32, 64, 32]
    — has no such array either, and a step of 8 slots, which sorts, copies
    no layer's experts. The control — the same prompt through the
    capacity path with C = T — holds both."""
    T_, E, H = 512, 64, 256

    def compiled_text(srv, kind, S=32):
        MB = 2                 # a small pool: the largest arrays must be the layer's

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        params = _abstract(srv.engine.params, one_chip)
        pools = _abstract(jax.eval_shape(
            lambda: srv.model.init_paged_cache(S * MB + 1, BS)), one_chip)
        key = sds((2,), jnp.uint32)
        if kind == "prefill":
            fn = jax.jit(srv._get_prefill_fn(T_).__wrapped__, donate_argnums=(2,))
            args = (params, sds((1, T_), jnp.int32), pools,
                    sds((T_ // BS,), jnp.int32),
                    *[sds((_SEGMENTS,), jnp.int32)] * 2, key)
        else:
            fn = jax.jit(srv._quantum_step_fn().__wrapped__, donate_argnums=(1, 4))
            args = (params, pools, sds((S,), jnp.int32), _block_list(sds, S, MB, MB),
                    sds((S,), jnp.int32), sds((S,), jnp.bool_), key)
        # the program asks the backend which dispatch to build; the test
        # answers for the chip it compiles for — and prices the narrow
        # experts at OLMoE's published widths, as the rule prices the cell's
        # (against a 128 KiB expert the sort's fixed work is a hundred
        # visits and nothing sorts: tests/unit/test_olmoe.py)
        from deepspeed_tpu.moe import sharded_moe
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(sharded_moe, "_expert_shapes",
                            lambda p: (2048 * 2, 3 * 2048 * 1024 * 2))
        try:
            with jax.default_matmul_precision("default"):
                c = fn.lower(*args).compile()
        finally:
            monkeypatch.undo()
        return c.as_text(), c.memory_analysis().temp_size_in_bytes

    srv = _moe_engine()
    text, temp = compiled_text(srv, "prefill")
    assert "tpu_custom_call" in text and "%moe_gmm" in text  # the kernel is in
    assert not _largest_arrays(text, E * T_ * H), _largest_arrays(text, E * T_ * H)[:3]
    assert temp < E * T_ * H * 2, temp
    per_layer = f"bf16[{E},{H},128]"
    assert not [l for l in text.splitlines()
                if f" = {per_layer}" in l and "parameter" not in l], per_layer
    text, temp = compiled_text(srv, "step")
    assert "%moe_gmm" not in text                            # few tokens: one-hot
    assert not _largest_arrays(text, E * T_ * H) and temp < E * T_ * H * 2
    # a step of 8 slots puts 64 rows on 64 experts and is expected to touch
    # 41 of them: it sorts (PR 45), and its kernel reads the layer's experts
    # out of the whole stack too — the step hands them whole beside its
    # slices, and no copy of one layer's is made
    text, _ = compiled_text(srv, "step", S=8)
    assert "%moe_gmm" in text
    assert not [l for l in text.splitlines()
                if f" = {per_layer}" in l and "parameter" not in l], per_layer
    srv.close()
    srv = _moe_engine(drop_tokens=True, eval_capacity_factor=float(E))
    text, _ = compiled_text(srv, "prefill")
    srv.close()
    assert _largest_arrays(text, E * T_ * H)                 # the control


# ---- a looped model's pool: 192 planes, 8.4 GB (ISSUE 35) -------------------

def _dus_fusions(hlo: str) -> set:
    """Names of the fused computations whose ROOT is a dynamic-update-slice:
    a prefill's block write once the compiler has turned its scatter into
    one, in place like the scatter."""
    comps, _ = _computations(hlo)
    return {name for name, lines in comps.items()
            if any(l.lstrip().startswith("ROOT") and " dynamic-update-slice(" in l
                   for l in lines)}


@pytest.mark.parametrize("kind,width", [("step", 10), ("prefill", 128),
                                        ("prefill", 192)])
def test_the_looped_cells_programs_fit_the_chip_and_write_the_pool_in_place(
        kind, width, one_chip, monkeypatch):
    """Ouro-2.6B at the PUBLISHED widths — 48 layers walked 4 times, a pool
    of 192 planes x 161 blocks (benchmark/configs/ouro-2.6b-serve.json) —
    through the engine's own step (16 slots x 10 columns, the one shape the
    cell's rounds take) and prefill functions: the program compiles for the
    described v5e with the layers SCANNED (a loop of passes around a loop
    of layers, not 192 unrolled bodies), arguments + temporaries fit the
    chip's 15.75 GiB, and no op writes a whole K or V leaf (4.2 GB each:
    beside 12.75 GiB of arguments ONE copy does not fit) but the row
    scatter and the block write."""
    import json
    import os
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs", "ouro-2.6b-serve.json")) as f:
        conf = json.load(f)
    hf = {k: v for k, v in conf.items() if k not in (
        "source", "reduced", "assumed", "deployment", "run", "correct")}
    serving = conf["run"]["serving"]
    cfg = hf_config_to_transformer(hf, max_seq_len=serving["max_model_len"],
                                   dtype=jnp.bfloat16, kv_cache_bits=8)
    model = make_model(cfg)
    # the engine's parameters (bf16, fused projections) and pool, as shapes
    params = _abstract(jax.eval_shape(lambda: T.fuse_layer_stack(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                     model.init(jax.random.PRNGKey(0))), cfg)), one_chip)
    pools = _abstract(jax.eval_shape(lambda: model.init_paged_cache(
        serving["num_blocks"], BS, dtype=jnp.bfloat16)), one_chip)
    assert pools["k"].shape == (192, 161, BS, 16, HD) and pools["k"].dtype == jnp.int8
    # the engine's jitted functions without an engine: nothing of this size
    # is ever placed here
    srv = object.__new__(ServingEngine)
    srv.model, srv.decode_backend = model, "xla"
    srv.config = ServingConfig(max_seqs=serving["max_seqs"])
    srv._moe_forms, srv._prefill_fns, srv._slot_state = {}, {}, 0
    srv._repl_sharding = srv._pool_shardings = None

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    S, key = serving["max_seqs"], sds((2,), jnp.uint32)
    if kind == "step":
        fn = jax.jit(srv._quantum_step_fn().__wrapped__, donate_argnums=(1, 4))
        args = (params, pools, sds((S,), jnp.int32),
                _block_list(sds, S, width, serving["max_model_len"] // BS),
                sds((S,), jnp.int32), sds((S,), jnp.bool_), key)
    else:
        fn = jax.jit(srv._get_prefill_fn(width).__wrapped__, donate_argnums=(2,))
        args = (params, sds((1, width), jnp.int32), pools,
                sds((width // BS,), jnp.int32),
                *[sds((_SEGMENTS,), jnp.int32)] * 2, key)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        with jax.default_matmul_precision("default"):
            compiled = fn.lower(*args).compile()
    finally:
        monkeypatch.undo()
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{kind} {width}: arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB")
    assert resident < 15.75 * 2**30, resident
    assert mem.alias_size_in_bytes >= 161 * 64 * 811_008     # the pool, donated
    # passes around layers (+ the flash kernel's own loops in a prefill)
    assert 2 <= hlo.count(" while(") <= 6, hlo.count(" while(")
    in_place = _dus_fusions(hlo)
    whole = {l.strip().split(" = ")[0]: l for l in hlo.splitlines()}

    def callee(line):              # `line` is cut short: find it whole
        m = re.search(r"calls=%([\w.\-]+)", whole[line.split(" = ")[0]])
        return m and m.group(1)
    bad = [line for line in whole_pool_ops(hlo, {n: pools[n] for n in "kv"})
           if callee(line) not in in_place]
    assert not bad, "\n".join(bad)
    assert not layer_slice_ops(hlo, pools)
    if kind == "step":
        assert not widened_view_ops(hlo, pools, S, width)
        # the exit gate's counter leaves with the tokens
        (_, (tokens, (load, exits)), lens) = compiled.out_info
        assert load is None and exits.shape == (cfg.ut_steps + 1,)


# ---- window rings beside the pool (ISSUE 44) ---------------------------------

def _hybrid_cell(name, one_chip, kv_cache_bits=8):
    """A hybrid serve cell's engine at the PUBLISHED widths and the cell's
    own shapes (benchmark/configs/<name>.json), as shapes: -> (cfg, params,
    pools, the engine's jitted functions without an engine — nothing of this
    size is ever placed here —, slots, table columns, sds)."""
    import json
    import os
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        conf = json.load(f)
    hf = {k: v for k, v in conf.items() if k not in (
        "source", "reduced", "assumed", "deployment", "run", "correct")}
    serving = conf["run"]["serving"]
    S, MB = serving["max_seqs"], serving["max_model_len"] // BS
    cfg = hf_config_to_transformer(hf, max_seq_len=serving["max_model_len"],
                                   dtype=jnp.bfloat16,
                                   kv_cache_bits=kv_cache_bits)
    model = make_model(cfg)
    params = _abstract(jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), model.init(jax.random.PRNGKey(0)))),
        one_chip)
    pools = _abstract(jax.eval_shape(lambda: model.init_paged_cache(
        S * MB + 1, BS, dtype=jnp.bfloat16, max_seqs=S)), one_chip)
    srv = object.__new__(ServingEngine)
    srv.model, srv.decode_backend = model, "xla"
    srv.config = ServingConfig(max_seqs=S)
    srv._moe_forms, srv._prefill_fns = {}, {}
    srv._slot_state = int(cfg.slot_state_blocks)
    srv._repl_sharding = srv._pool_shardings = None

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return cfg, params, pools, srv, S, MB, sds


def _compiled_for_the_chip(fn, args, monkeypatch):
    """``fn`` compiled for the described v5e, its backend branches taken as
    on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        with jax.default_matmul_precision("default"):
            return fn.lower(*args).compile()
    finally:
        monkeypatch.undo()


@pytest.mark.parametrize("kind,width", [("step", 88), ("prefill", 9216)])
def test_the_window_cells_programs_fit_the_chip_and_write_the_rings_in_place(
        kind, width, one_chip, monkeypatch):
    """Trinity-Large at the PUBLISHED widths and the cell's shapes
    (benchmark/configs/trinity-large-serve.json: 64 slots, a ring of 4096
    rows a slot and sliding block, one full plane of 11 265 blocks) through
    the engine's own step (64 slots x 88 columns) and its longest prefill
    (9216 tokens): the program compiles for the described v5e, arguments +
    temporaries fit the chip's 15.75 GiB, the banded kernel is in the
    prefill, the grouped-matmul kernel in both (it reads the expert stacks in
    place), and no op outside a fused scatter or block write reads and
    writes a whole ring or pool leaf (a ring leaf is 268 MB: stacked on
    their blocks, the rings were split and put together again around every
    step, 4.3 GB of copies)."""
    cfg, params, pools, srv, S, MB, sds = _hybrid_cell("trinity-large-serve",
                                                       one_chip)
    assert pools["k"].shape == (1, S * MB + 1, BS, 8, HD)
    assert len(pools["wk"]) == 4 and pools["wk"][0].shape == (S, 4096, 8, HD)
    assert pools["wk"][0].dtype == jnp.int8
    assert srv._slot_state == 4
    key = sds((2,), jnp.uint32)
    if kind == "step":
        fn = jax.jit(srv._quantum_step_fn().__wrapped__, donate_argnums=(1, 4))
        args = (params, pools, sds((S,), jnp.int32),
                _block_list(sds, S, width, MB),
                sds((S,), jnp.int32), sds((S,), jnp.bool_), key)
    else:
        fn = jax.jit(srv._get_prefill_fn(width).__wrapped__, donate_argnums=(2,))
        args = (params, sds((1, width), jnp.int32), pools,
                sds((width // BS,), jnp.int32), sds((), jnp.int32), key,
                sds((), jnp.int32))
    compiled = _compiled_for_the_chip(fn, args, monkeypatch)
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{kind} {width}: arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB")
    assert resident < 15.75 * 2**30, resident
    in_place = _dus_fusions(hlo)
    whole = {l.strip().split(" = ")[0]: l for l in hlo.splitlines()}

    def callee(line):              # `line` is cut short: find it whole
        m = re.search(r"calls=%([\w.\-]+)", whole[line.split(" = ")[0]])
        return m and m.group(1)
    # (a block's scale planes, 8 MB each, ARE copied once a step into the
    # [slots, heads, window] view the scores take them in: 0.1 GB a step)
    leaves = {"k": pools["k"], "wk": pools["wk"][0]}
    bad = [line for line in whole_pool_ops(hlo, leaves)
           if callee(line) not in in_place]
    assert not bad, "\n".join(b[:300] for b in bad)
    if kind == "prefill":
        assert "flash_fwd_band" in hlo and "flash_fwd" in hlo
    # both sort (PR 45: the step's 32 expected rows touch ~20 of the 32 held
    # experts), and the kernel reads a layer's experts out of the whole stack:
    # nothing makes one layer's [32, 3072, 3072] (0.6 GB)
    assert srv._moe_forms == {"step" if kind == "step"
                              else f"prefill_{width}": "sorted/moe_gmm"}
    assert "%moe_gmm" in hlo
    per_layer = f"bf16[{cfg.num_experts},3072,3072]"
    assert not [l for l in hlo.splitlines()
                if f" = {per_layer}" in l and "parameter" not in l], per_layer


# ---- a latent pool (ISSUE 52) -------------------------------------------------

@pytest.mark.parametrize("name,kind,backend,width", [
    ("glm-4.7-flash-serve", "step", "pallas", 76),
    ("glm-4.7-flash-serve", "step", "xla", 38),
    ("glm-4.7-flash-serve", "prefill", "xla", 4096),
    ("xing4.0-29b-a4b-serve", "prefill", "xla", 4096)])
def test_the_latent_cells_programs_fit_the_chip_and_write_the_pool_in_place(
        name, kind, backend, width, one_chip, monkeypatch):
    """GLM-4.7-Flash — and Xing4.0-29B-A4B, whose stream is four rows a token,
    whose V is 128 wide under keys of 192 and whose pool has the same shape
    (ISSUE 59) — at the PUBLISHED widths and the cell's shapes
    (benchmark/configs/glm-4.7-flash-serve.json: 128 slots, one latent leaf
    of 6 planes x 9 729 blocks of 64 rows stored in 640 lanes) through the
    engine's own step — the kernel's (rectangular tables) and the list read's
    (128 slots x 38 columns) — and its longest prefill (4096 tokens, four
    segments): the program compiles for the described v5e, arguments +
    temporaries fit the chip's 15.75 GiB, the kernels are in it
    (``latent_decode`` / ``flash_fwd``, ``moe_gmm``), and NO op outside a
    fused scatter reads and writes the whole leaf. Two faults a CPU run
    cannot show were found this way (PR 52): a leaf whose last extent is off
    the 128 grid (576) is stored by the TPU with the BLOCK index innermost
    and relayouted around every read and write (4.45 GB of copies a step:
    ``latent_attention.stored_width``), and a row scatter whose window spans
    the planes is answered the same way (one scatter a plane)."""
    cfg, params, pools, srv, S, MB, sds = _hybrid_cell(
        name, one_chip, kv_cache_bits=0)
    srv.decode_backend = backend
    assert set(pools) == {"latent"} and srv._slot_state == 0
    assert pools["latent"].shape == (6, S * MB + 1, BS, 640)
    assert pools["latent"].dtype == jnp.bfloat16
    key = sds((2,), jnp.uint32)
    if kind == "step":
        fn = jax.jit(srv._quantum_step_fn().__wrapped__, donate_argnums=(1, 4))
        tables = (_block_list(sds, S, width, MB) if backend == "xla"
                  else sds((S, MB), jnp.int32))
        args = (params, pools, sds((S,), jnp.int32), tables,
                sds((S,), jnp.int32), sds((S,), jnp.bool_), key)
    else:
        fn = jax.jit(srv._get_prefill_fn(width).__wrapped__, donate_argnums=(2,))
        args = (params, sds((1, width), jnp.int32), pools,
                sds((width // BS,), jnp.int32), sds((_SEGMENTS,), jnp.int32),
                sds((_SEGMENTS,), jnp.int32), key)
    compiled = _compiled_for_the_chip(fn, args, monkeypatch)
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    print(f"{name} {kind} {backend} {width}: arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    leaf = "bf16[" + ",".join(map(str, pools["latent"].shape)) + "]"
    made = [l.strip() for l in hlo.splitlines()
            if re.search(r"= \(?" + re.escape(leaf), l) and " parameter(" not in l]
    # the leaf is produced by scatters alone (fused or not), in the layout it
    # arrives in: no copy, no transpose, no relayout of 4.45 GB
    assert made and all("scatter" in l or "tuple(" in l for l in made), \
        "\n".join(l[:200] for l in made)
    assert not [l for l in made if "{3,2,1,0" not in l], made
    assert "%moe_gmm" in hlo
    assert ("%latent_decode" in hlo) == (kind == "step" and backend == "pallas")
    assert ("%flash_fwd" in hlo) == (kind == "prefill")
    if kind == "prefill":
        # a row of up to four prompts is attended ONCE (ISSUE 53): one call
        # of the flash forward a latent layer, and in no loop's body (the
        # parent ran a `while` over the live segments around each)
        call = re.compile(r"%flash_fwd[.\d]* = ")
        assert len(call.findall(hlo)) == cfg.latent_planes == 6
        holders = {name for name, lines in _computations(hlo)[0].items()
                   if any(call.search(l) for l in lines)}
        assert len(holders) == 1 and not holders & set(
            re.findall(r"(?:body|condition)=%([\w.\-]+)", hlo)), holders


# ---- a K/V plane AND a state layer in every layer (ISSUE 57) -----------------

@pytest.mark.parametrize("kind,width,stored", [
    ("step", 40, True), ("prefill", 1024, True), ("step", 40, False)])
def test_the_parallel_mixer_cells_programs_fit_the_chip_and_write_both_pools_in_place(
        kind, width, stored, one_chip, monkeypatch):
    """Falcon-H1-34B at the PUBLISHED widths and the cell's shapes
    (benchmark/configs/falcon-h1-34b-serve.json: 64 slots, six layers) through
    the engine's own step (64 slots x the whole table) and its longest
    prefill (1024 tokens): the pool holds SIX K/V planes and SIX state layers
    for the same six layers; the program compiles for the described v5e,
    arguments + temporaries fit the chip's 15.75 GiB; the step holds six
    ``%ssm_step`` calls (each its layer of the state pool in place, a literal
    in its block index), the prefill six ``%ssm_scan`` and six ``%flash_fwd``;
    and no op outside a fused scatter, a block write or a kernel that aliases
    its operand makes an array of a whole pool leaf's bytes. BOTH SIDES of
    ``hybrid.blocks_head_major``'s rule (``stored`` False: the rule turned
    off, ``k`` / ``v`` declared token-major like every other pool): FOUR K/V
    heads of int8 pack into one sublane word, the compiler keeps that order,
    and the step holds a relayout of each whole leaf ahead of its gathers —
    at least two whole-leaf copies and over a GiB of temporaries (1.54 GiB
    against 0.17; on the chip 24.3 ms a step against 20.6, PERF.md section 6,
    PR 57). The stacks of 8 heads (Trinity's plane, above and below) and 16
    (``olmoe-int8``, ``ouro-int8``) are held free of such copies token-major
    by their own cases."""
    if not stored:
        from deepspeed_tpu.models import hybrid
        monkeypatch.setattr(hybrid, "blocks_head_major", lambda cfg: False)
    cfg, params, pools, srv, S, MB, sds = _hybrid_cell("falcon-h1-34b-serve",
                                                       one_chip)
    L = 6
    assert (cfg.recurrent_blocks, cfg.attention_blocks, cfg.kv_planes) \
        == (L, L, L)
    # four K/V heads of int8: stored head-major (hybrid.blocks_head_major)
    assert pools["k"].shape == ((L, S * MB + 1, 4, BS, HD) if stored
                                else (L, S * MB + 1, BS, 4, HD))
    assert pools["k_scale"].shape == (L, S * MB + 1, 4 * BS)
    assert pools["ssm"].shape == (L, S, 32, 128, 256)
    assert pools["ssm"].dtype == jnp.float32
    assert pools["conv"].shape == (L, S, 3, 5120)
    assert srv._slot_state == L
    key = sds((2,), jnp.uint32)
    if kind == "step":
        fn = jax.jit(srv._quantum_step_fn().__wrapped__, donate_argnums=(1, 4))
        args = (params, pools, sds((S,), jnp.int32),
                _block_list(sds, S, width, MB),
                sds((S,), jnp.int32), sds((S,), jnp.bool_), key)
    else:
        fn = jax.jit(srv._get_prefill_fn(width).__wrapped__, donate_argnums=(2,))
        args = (params, sds((1, width), jnp.int32), pools,
                sds((width // BS,), jnp.int32), sds((), jnp.int32), key,
                sds((), jnp.int32))
    compiled = _compiled_for_the_chip(fn, args, monkeypatch)
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    print(f"{kind} {width}: arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30

    def calls(name):
        return [l for l in hlo.splitlines()
                if "custom_call_target=\"tpu_custom_call\"" in l
                and f"%{name}" in l.split(" = ")[0]]
    if kind == "step":
        assert len(calls("ssm_step")) == L and not calls("paged_decode_int8")
    else:
        assert len(calls("ssm_scan")) == L and len(calls("flash_fwd")) == L
    in_place = _dus_fusions(hlo)
    whole = {l.strip().split(" = ")[0]: l for l in hlo.splitlines()}

    def callee(line):              # `line` is cut short: find it whole
        m = re.search(r"calls=%([\w.\-]+)", whole[line.split(" = ")[0]])
        return m and m.group(1)
    # (the convolution tails, 12 MB for all six layers, ARE relayouted around
    # each layer's update, as the accepted Mamba-2 cell's are: 0.2 GB a step)
    def copies(*names):
        return [line for line in whole_pool_ops(hlo, {n: pools[n] for n in names})
                if callee(line) not in in_place
                and "tpu_custom_call" not in whole[line.split(" = ")[0]]]
    if stored:
        bad = copies("k", "ssm")
        assert not bad, "\n".join(b[:300] for b in bad)
        assert mem.temp_size_in_bytes < 0.6 * 2**30
    else:
        assert not copies("ssm") and len(copies("k")) >= 2
        assert mem.temp_size_in_bytes > 2**30


# ---- a hybrid stack's step that sorts (ISSUE 46) -----------------------------

def test_nemotrons_step_sorts_and_reads_its_experts_in_place(one_chip,
                                                             monkeypatch):
    """Nemotron-3-Nano at the PUBLISHED widths and the cell's shapes
    (benchmark/configs/nemotron-3-nano-30b-serve.json: 128 slots x top-6
    over 128 experts of [2688, 1856], nine blocks) through the engine's own
    step: since PR 46 the rule sorts it from its shapes, the program compiles
    for the described v5e and fits it, the grouped-matmul kernel is in it
    (eight calls: four expert blocks x two projections, the up projection on
    the kernel's ``transposed`` path — ``w_in_t``, F = 1856 is off the 128
    grid), and nothing makes a copy of one block's experts ([128, 1856, 2688]
    or its transpose, 1.28 GB a stack: PR 26's finding, 30 % of a step)."""
    cfg, params, pools, srv, S, MB, sds = _hybrid_cell(
        "nemotron-3-nano-30b-serve", one_chip)
    fn = jax.jit(srv._quantum_step_fn().__wrapped__, donate_argnums=(1, 4))
    args = (params, pools, sds((S,), jnp.int32), _block_list(sds, S, MB, MB),
            sds((S,), jnp.int32), sds((S,), jnp.bool_), sds((2,), jnp.uint32))
    compiled = _compiled_for_the_chip(fn, args, monkeypatch)
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    assert srv._moe_forms == {"step": "sorted/moe_gmm"}
    calls = [l for l in hlo.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in l and "%moe_gmm" in l]
    assert len(calls) == 8, len(calls)
    E, H, F = cfg.num_experts, cfg.hidden_size, 1856
    for per_block in (f"bf16[{E},{F},{H}]", f"bf16[{E},{H},{F}]"):
        assert not [l for l in hlo.splitlines()
                    if f" = {per_block}" in l and "parameter" not in l], per_block


# ---- the int8 pool read by a kernel (ISSUE 50) -------------------------------

def test_trinitys_step_reads_its_full_plane_through_the_kernel(one_chip,
                                                               monkeypatch):
    """Trinity-Large at the PUBLISHED widths and the cell's shapes with the
    read its price chooses (``paged_read_price`` -> "pallas"): the step is
    handed rectangular tables [64, 176], compiles for the described v5e and
    fits it, holds ONE ``paged_decode_int8`` call — its one "A" block — and
    no gather of the listed blocks (the XLA read's two ``s8[11264,64,8,128]``,
    738 MB each), nor any other array of a whole pool leaf's bytes: the
    kernel's view of the leaf, ``[L, NB, 512, 128]``, is a bitcast of what
    the chip stores."""
    from deepspeed_tpu.ops.decode_attention import paged_read_price
    cfg, params, pools, srv, S, MB, sds = _hybrid_cell("trinity-large-serve",
                                                       one_chip)
    price = paged_read_price(slots=S, MB=MB, block_size=BS,
                             n_kv=cfg.kv_heads,
                             rep=cfg.num_heads // cfg.kv_heads,
                             head_dim=cfg.dim_per_head)
    assert price["choice"] == "pallas", price
    srv.decode_backend = price["choice"]
    fn = jax.jit(srv._quantum_step_fn().__wrapped__, donate_argnums=(1, 4))
    args = (params, pools, sds((S,), jnp.int32), sds((S, MB), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.bool_), sds((2,), jnp.uint32))
    compiled = _compiled_for_the_chip(fn, args, monkeypatch)
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    calls = [l for l in hlo.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in l
             and "%paged_decode_int8" in l]
    assert len(calls) == 1, len(calls)
    assert f"s8[{S * MB},{BS},8,{HD}]" not in hlo
    in_place = _dus_fusions(hlo)
    whole = {l.strip().split(" = ")[0]: l for l in hlo.splitlines()}

    def callee(line):              # `line` is cut short: find it whole
        m = re.search(r"calls=%([\w.\-]+)", whole[line.split(" = ")[0]])
        return m and m.group(1)
    leaves = {"k": pools["k"], "wk": pools["wk"][0]}
    bad = [line for line in whole_pool_ops(hlo, leaves)
           if callee(line) not in in_place]
    assert not bad, "\n".join(b[:300] for b in bad)
    # the XLA read's step at the whole table holds 1.18 GiB of temporaries
    # (the two gathers, the float32 view); this one 0.62 GiB, the rings'
    # and the experts' own and the table's scale rows in the kernel's order
    print(f"temporaries {mem.temp_size_in_bytes / 2**20:.0f} MiB")
    assert mem.temp_size_in_bytes < 0.75 * 2**30, mem.temp_size_in_bytes


# the serve cells' engines (benchmark/configs/*-serve.json: slots, table
# columns at 64-token blocks, kv heads, query heads a kv head, head dim), the
# read each one's rule chooses and what decides it. The three whose
# configuration pins ``"expect": {"decode_backend": "xla"}`` keep XLA: Mixtral
# and OLMoE by the two prices, chat — which the prices alone would hand the
# kernel, by 0.06 ms — because a quarter of its table is under one DMA wave
@pytest.mark.parametrize("cell,shape,choice,by", [
    ("trinity-large-serve", (64, 176, 8, 6, 128), "pallas", "price"),
    ("mistral-7b-serve", (48, 32, 8, 4, 128), "xla", "wave"),
    ("mixtral-8x7b-serve", (32, 32, 8, 4, 128), "xla", "price"),
    ("olmoe-1b-7b-serve", (32, 16, 16, 1, 128), "xla", "price"),
    ("ouro-2.6b-serve", (16, 20, 16, 1, 128), "xla", "price"),
    ("nemotron-3-nano-30b-serve", (128, 40, 2, 16, 128), "xla", "layout"),
    ("qwen3-next-80b-a3b-serve", (128, 40, 2, 8, 256), "xla", "layout"),
    ("falcon-h1-34b-serve", (64, 40, 4, 5, 128), "xla", "layout")])
def test_the_price_of_the_read_at_each_serve_cell(cell, shape, choice, by):
    """No chip and no compile: the rule is arithmetic on the engine's
    shapes, and the shapes are the configuration files'."""
    import json
    import os
    from deepspeed_tpu.ops.decode_attention import (CHUNK, PRICED_FILL,
                                                    READ_TIE_BYTES,
                                                    paged_read_price)
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs", cell + ".json")) as f:
        conf = json.load(f)
    S, MB, G, rep, D = shape
    serving = conf["run"]["serving"]
    assert (serving["max_seqs"], serving["max_model_len"] // BS) == (S, MB)
    assert conf["num_key_value_heads"] == G
    assert conf["num_attention_heads"] == G * rep
    assert conf.get("head_dim", conf["hidden_size"] // (G * rep)) == D
    price = paged_read_price(slots=S, MB=MB, block_size=BS, n_kv=G, rep=rep,
                             head_dim=D, num_blocks=serving.get("num_blocks"))
    assert price["choice"] == choice, price
    assert conf["run"]["expect"].get("decode_backend") in (None, choice)
    cheaper = price["kernel_bytes"] + READ_TIE_BYTES < price["xla_bytes"]
    a_wave = PRICED_FILL * MB >= CHUNK
    assert (choice == "pallas") == (G % 8 == 0 and a_wave and cheaper), price
    decided_by = ("layout" if G % 8 else
                  "wave" if cheaper and not a_wave else "price")
    assert decided_by == by, price
