"""The serving and training programs of the families the benchmark already
ran are, as lowered TEXT, what they were at the commit before PR 40 (the
expert layer's held range, the hybrid walker's period scan, the K/V writer per
(plane, head), rotary / q-k norm / output gate in a hybrid attention block
all default to the program it was).

For each family's toy widths (``benchmark/families/<model_type>.TOY``, what a
``--rehearsal`` runs): the decode step, two prompt buckets of the prefill and
the no-cache forward, lowered for abstract arguments (nothing is compiled or
run) and hashed. ``GOLDEN`` was printed by THIS file run against a checkout of
the parent commit ``bdfc2d3`` (``python tests/unit/test_program_text.py
<checkout>``), jax 0.9.0. A PR that means to change one of these programs
prints the table again from its own tree and says which rows moved and why; a
PR that does not has the parent's text letter for letter.

PR 43 added ``packed128``: the 128-token bucket in the serving engine's form,
``prefill_paged(..., segments=(starts[4], lengths[4]))`` — a row of up to four
prompts; how many are live is data, not text — for the four families whose
stack keeps no recurrent state, printed from PR 43's tree. No other row moved.

PR 44 added ``trinity-large-serve`` (``afmoe``: window rings beside the pool,
a toy window of 64, so ``prefill32`` lands in a ring as it is and
``prefill128`` keeps the last 64 positions), printed from PR 44's tree, and
``qwen3-next-80b-a3b-serve`` (one period, unrolled), printed from a checkout
of PR 43's tree: PR 44 changed the hybrid walker it shares. No other row
moved.

PR 45 (the dropless dispatch chosen by the sorted form's EXPECTED visits)
printed the table again from its tree: NO row moved, Trinity's ``step``
neither. The rule prices the sort's fixed work against one expert's bytes,
and a toy expert is 25-400 KB where a published one is 6-350 MB: at these
widths the fixed term is hundreds of visits and every toy program keeps the
one-hot masks, as the parent's did (no toy row is long enough to sort).
What the change does to the cells' own shapes is held elsewhere: the rule by
shape at the published widths (``test_olmoe.py
test_the_dispatch_is_chosen_by_the_calls_shapes``), the sorted step's logits
and counters against the one-hot step's (``test_afmoe.py``), and Trinity's
step at the published sizes compiled for the described chip with
``%moe_gmm`` in it (``test_pool_layout.py -k window_cells``).

PR 46 (the one-hot form priced by what it costs: its ``[E, T, H]`` rows in
and out and the einsums around them, not its streaming alone) printed the
table again from its tree: THREE rows moved, all OLMoE's and all of 128
tokens — ``prefill128``, ``packed128`` and ``forward`` (2 x 64 tokens at
inference) now take the sorted dispatch (``ragged_dot`` on the CPU). The new
terms do not shrink with a toy expert as its bytes do: 64 experts x 128
tokens x 256 floats, four passes and the einsums, are 262 visits of a 196 KB
expert on top of the 64, against 71 sorted + 234 of fixed work and tie. No
other toy row has the experts or the tokens for it (Mixtral's toy holds 8
experts, Nemotron's 8, Qwen3-Next's and Trinity's shares 8 and 16; 32 tokens
and the 4-slot step are under it everywhere). What the change does at the
PUBLISHED widths is held by shape in ``test_olmoe.py``, by logits in
``test_nemotron_h.py`` and by the compiled step in ``test_pool_layout.py -k
nemotrons_step``.

PR 57 (a block kind with TWO mixers, ``P``; the muP multipliers; the planes and
state layers counted over two kinds each) printed the table again from its
tree: NO row moved — a multiplier left at 1 puts no multiply into a program,
and the walkers' shared pieces (the state write, the attention read) are the
ops they were, in the order they were. It added ``glm-4.7-flash-serve``,
printed from a checkout of PR 56's tree (the rows of PR 57's tree are the
same), and ``falcon-h1-34b-serve`` (two ``PD`` layers, unrolled), printed from
PR 57's tree.
"""
import functools
import hashlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIGS = ("mistral-7b-serve", "mixtral-8x7b-serve", "olmoe-1b-7b-serve",
           "nemotron-3-nano-30b-serve", "ouro-2.6b-serve",
           "qwen3-next-80b-a3b-serve", "trinity-large-serve",
           "glm-4.7-flash-serve", "falcon-h1-34b-serve")
# a family's toy keeps the published window: this one is cut below a bucket
WINDOW = {"trinity-large-serve": {"sliding_window": 64}}
PROGRAMS = ("step", "prefill32", "prefill128", "forward")
PACKED = tuple(c for c in CONFIGS if c not in (
    "nemotron-3-nano-30b-serve", "qwen3-next-80b-a3b-serve",
    "trinity-large-serve", "glm-4.7-flash-serve", "falcon-h1-34b-serve"))
GOLDEN = {
    "mistral-7b-serve": {
        "step": "050edea2bb48db05", "prefill32": "40748e39cf90df13",
        "prefill128": "e8c63a26dd58a410", "forward": "07b44c1bda28a187",
        "packed128": "85363415e9febad3"},
    "mixtral-8x7b-serve": {
        "step": "56e1f8bb3482451e", "prefill32": "7ddfdcf887781e68",
        "prefill128": "18ecf3f5d2a8f210", "forward": "dd490bcb5efa13b1",
        "packed128": "7a87a1bf20fcb551"},
    "olmoe-1b-7b-serve": {
        "step": "95a0e4a3ef4f8f1d", "prefill32": "8306be4eb9138aae",
        "prefill128": "323b28795b09f45a", "forward": "2580a662878249f6",
        "packed128": "7aa493eb6b2d1c85"},
    "nemotron-3-nano-30b-serve": {
        "step": "1acd3b9372fd7517", "prefill32": "efe837a6e02bed9a",
        "prefill128": "8a4ca89d7fca6ca0", "forward": "cda6540c5ea4d05a"},
    "ouro-2.6b-serve": {
        "step": "7017150c3367e0e7", "prefill32": "6bb86f01468a6b01",
        "prefill128": "906f6315e0dd77a9", "forward": "ac33f278ea46fd12",
        "packed128": "f4ad5b70a63c4b85"},
    "qwen3-next-80b-a3b-serve": {
        "step": "87836351bbac143f", "prefill32": "e5ddcd5648f5e3d8",
        "prefill128": "859d3c6691ee4065", "forward": "d8797f82f05dbbbf"},
    "trinity-large-serve": {
        "step": "905ed8ffb948fa92", "prefill32": "91900aa4b4516e0f",
        "prefill128": "ad76a41a43090cac", "forward": "f9d41ce519bcc2ed"},
    "glm-4.7-flash-serve": {
        "step": "3dfe64cce8bd75a7", "prefill32": "6c2eec876880a534",
        "prefill128": "5a42b3340e962cd1", "forward": "41acc4a557b8572d"},
    "falcon-h1-34b-serve": {
        "step": "d8774e09986272f8", "prefill32": "76155bad0742ec57",
        "prefill128": "d640f8fff4768b72", "forward": "f7cfc2fbdcfe1fd2"},
}


def lowered(name):
    """{program: sha256 of its lowered text} at the configuration's toy
    widths, from whatever tree is first on ``sys.path``."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import common
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    from deepspeed_tpu.moe.sharded_moe import expert_load_tap

    cfgf = common.load_config(name)
    cfg = hf_config_to_transformer(dict(common.hf_of(cfgf, rehearsal=True),
                                        **WINDOW.get(name, {})),
                                   max_seq_len=256,
                                   **cfgf["run"].get("overrides", {}))
    model = make_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    slotted = {"max_seqs": 4} if cfg.block_pattern else {}
    pools = jax.eval_shape(
        lambda: model.init_paged_cache(33, 16, dtype=jnp.float32, **slotted))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def step(p, tok, pools, tab, lens):
        with expert_load_tap() as tap:
            logits, pools = model.decode_step_paged(p, tok, pools, tab, lens)
        return logits, pools, tap.stacked()

    def prefill(p, ids, pools, blk, n, *slot):
        with expert_load_tap() as tap:
            logits, pools = model.prefill_paged(
                p, ids, pools, blk, length=n,
                **({"slot": slot[0]} if slot else {}))
        return logits, pools, tap.stacked()

    texts = {"step": jax.jit(step).lower(
        params, i32(4), pools, i32(4, 8), i32(4)).as_text()}
    for P in (32, 128):
        texts[f"prefill{P}"] = jax.jit(prefill).lower(
            params, i32(1, P), pools, i32(P // 16), i32(),
            *((i32(),) if cfg.block_pattern else ())).as_text()
    texts["forward"] = jax.jit(lambda p, ids: model.apply(p, ids)).lower(
        params, i32(2, 64)).as_text()
    if not cfg.block_pattern:
        def packed(p, ids, pools, blk, starts, lengths):
            with expert_load_tap() as tap:
                logits, pools = model.prefill_paged(
                    p, ids, pools, blk, segments=(starts, lengths))
            return logits, pools, tap.stacked()

        texts["packed128"] = jax.jit(packed).lower(
            params, i32(1, 128), pools, i32(128 // 16), i32(4), i32(4)
        ).as_text()
    return {k: hashlib.sha256(t.encode()).hexdigest()[:16]
            for k, t in texts.items()}


@pytest.fixture(scope="module")
def hashes():
    sys.path.insert(0, ROOT)
    return functools.lru_cache(maxsize=None)(lowered)


@pytest.mark.parametrize("name,program",
                         [(n, p) for n in CONFIGS for p in PROGRAMS]
                         + [(n, "packed128") for n in PACKED])
def test_the_program_is_the_text_it_was(name, program, hashes):
    assert hashes(name)[program] == GOLDEN[name][program], (
        f"{name} {program}: the lowered text moved; if that is meant, print "
        "GOLDEN again (this file's docstring) and say why")


if __name__ == "__main__":
    tree = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else ROOT
    sys.path[:0] = [tree, os.path.join(tree, "tests")]
    import conftest  # noqa: F401  (the suite's devices and matmul precision)
    import deepspeed_tpu
    print("# from", os.path.dirname(os.path.dirname(deepspeed_tpu.__file__)))
    for name in CONFIGS:
        print(f'    "{name}": {lowered(name)!r},')
