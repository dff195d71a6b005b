"""The seam between a model and the serving engine (PR 47): what a model keeps
for a request is said ONCE, by the model — the tree its ``init_paged_cache``
builds, read abstractly (``kv_cache.abstract_cache``), and the names of the
leaves of it that belong to a serving slot (``ModelSpec.slot_leaves``) — and
``inference/`` derives every byte it reports from that and names no layer kind.

Over the toy configurations ``test_program_text.py`` loads that take an int8
pool. The bytes
family by family are pinned in ``test_ouro.py``, ``test_nemotron_h.py``,
``test_qwen3_next.py``, ``test_afmoe.py`` and ``test_span_vocabulary.py``;
here it is the agreement of the three places a byte count can come from, the
definition of a per-slot leaf, and the source of ``inference/``.
"""
import ast
import functools
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from tests.unit.test_program_text import CONFIGS as PROGRAMS, WINDOW  # noqa: E402

PKG = os.path.join(ROOT, "deepspeed_tpu")
# (a latent pool takes no int8 row — ``kv_cache_bits`` 8 is refused on it —
# and its bytes are held in ``test_glm4_moe_lite.py``)
CONFIGS = tuple(c for c in PROGRAMS if c != "glm-4.7-flash-serve")
SLOTTED = {"nemotron-3-nano-30b-serve", "qwen3-next-80b-a3b-serve",
           "trinity-large-serve", "falcon-h1-34b-serve"}


@functools.lru_cache(maxsize=None)
def toy(name):
    """The configuration's model at its toy widths, int8 K/V as in its cell."""
    import jax.numpy as jnp
    from benchmark.harness import common
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    cfgf = common.load_config(name)
    cfg = hf_config_to_transformer(
        dict(common.hf_of(cfgf, rehearsal=True), **WINDOW.get(name, {})),
        max_seq_len=256, dtype=jnp.float32, kv_cache_bits=8,
        **cfgf["run"].get("overrides", {}))
    return cfg, make_model(cfg, name=name)


def _shapes(tree):
    import jax
    return [(jax.tree_util.keystr(path), tuple(x.shape), str(x.dtype))
            for path, x in jax.tree_util.tree_leaves_with_path(tree)]


@pytest.mark.parametrize("name", CONFIGS)
def test_the_three_byte_counts_are_one(name):
    """The model's abstract cache, the pools an engine allocated and the
    engine's ``stats()`` agree leaf for leaf and byte for byte, the K/V part
    and the per-slot part apart."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.inference import kv_cache
    cfg, model = toy(name)
    S, NB, BS = 3, 19, 16
    srv = deepspeed_tpu.init_serving(
        model, serving=dict(max_seqs=S, block_size=BS, max_model_len=96,
                            num_blocks=NB),
        config={"kv_cache_bits": 8}, dtype=jnp.float32,
        rng=jax.random.PRNGKey(0))
    try:
        tree = kv_cache.abstract_cache(srv.model, NB, BS, dtype=jnp.float32,
                                       max_seqs=S)
        assert _shapes(tree) == _shapes(srv.pools)
        said = kv_cache.cache_bytes(srv.model, tree)
        held = {"kv": 0, "state": 0, "rings": 0}
        for key, leaf in srv.pools.items():
            part = ("kv" if key not in srv.model.slot_leaves else
                    "rings" if isinstance(leaf, tuple) else "state")
            held[part] += sum(a.nbytes for a in jax.tree.leaves(leaf))
        assert said == held == kv_cache.cache_bytes(srv.model, srv.pools)
        st = srv.stats()
        assert st["pool_bytes_logical"] == sum(said.values()) \
            == kv_cache.pool_bytes(cfg, NB, BS, dtype=jnp.float32, max_seqs=S)
        assert st["kv_pool_bytes"] == said["kv"] > 0
        assert st.get("state_pool_bytes", 0) == said["state"] + said["rings"]
        assert (said["state"] + said["rings"] > 0) == (name in SLOTTED)
        if srv.model.ring_rows:
            assert st["window_rows"] == srv.model.ring_rows
            assert st["ring_bytes_per_slot"] * S == said["rings"] > 0
    finally:
        srv.close()


@pytest.mark.parametrize("name", CONFIGS)
def test_a_per_slot_leaf_is_one_that_follows_the_slots(name):
    """The leaves the model names as per slot are exactly those whose shape
    moves with ``max_seqs``; they do not move with ``num_blocks``, and every
    other leaf does and is untouched by the slots. The rings are among them
    exactly where the model says a ring has rows."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference import kv_cache
    _, model = toy(name)

    def tree(num_blocks, max_seqs):
        t = kv_cache.abstract_cache(model, num_blocks, 16, dtype=jnp.float32,
                                    max_seqs=max_seqs)
        return {key: _shapes(leaf) for key, leaf in t.items()}

    base, more_blocks, more_slots = tree(9, 3), tree(17, 3), tree(9, 5)
    by_slots = {key for key in base if base[key] != more_slots[key]}
    by_blocks = {key for key in base if base[key] != more_blocks[key]}
    assert by_slots == set(model.slot_leaves)
    assert by_blocks == set(base) - by_slots
    assert bool(by_slots) == (name in SLOTTED)
    rings = kv_cache.ring_leaves(model, kv_cache.abstract_cache(
        model, 9, 16, dtype=jnp.float32, max_seqs=3))
    assert bool(rings) == bool(model.ring_rows)
    assert set(rings) <= by_slots


def _sources(*parts):
    """{path: text} of the named files and directories of the package."""
    out = {}
    for part in parts:
        path = os.path.join(PKG, part)
        files = [path] if path.endswith(".py") else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if f.endswith(".py")]
        assert files, part
        for f in files:
            with open(f) as fh:
                out[os.path.relpath(f, ROOT)] = fh.read()
    return out


def _imports(text):
    """The modules a source imports, anywhere in it (``from a.b import c``
    gives ``a.b`` and ``a.b.c``)."""
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""
            yield from (f"{node.module}.{a.name}" for a in node.names)


def test_inference_names_no_layer_kind():
    """No file of ``inference/`` imports ``models.hybrid`` or spells a state
    leaf's name: what it knows of a model's cache it asks the ``ModelSpec``."""
    for path, text in _sources("inference").items():
        assert not [m for m in _imports(text) if "models.hybrid" in m], path
        assert not re.search(r"""["'](ssm|gdn)["']""", text), path


def test_the_serving_path_imports_no_measurement_package():
    """The engine, the cache manager, the scheduler, the models, the expert
    layer and the kernels import nothing from ``analysis``, ``profiling`` or
    ``autotuning`` (``abstractify`` lives in ``utils/memory.py``)."""
    for path, text in _sources(
            "inference/serving.py", "inference/kv_cache.py",
            "inference/scheduler.py", "models", "moe", "ops").items():
        bad = [m for m in _imports(text) if re.match(
            r"deepspeed_tpu\.(analysis|profiling|autotuning)\b", m)]
        assert not bad, (path, bad)
