"""The plain reference against the program's own forward at toy widths on
the CPU (float32), and the comparisons that decide ``correct``."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, correct, loadgen  # noqa: E402


@pytest.mark.parametrize("name", ["mistral-7b-serve", "mixtral-8x7b-serve"])
def test_reference_matches_the_programs_forward_at_toy_widths(name):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import make_model
    cfg = common.load_config(name)
    hf = common.hf_of(cfg, rehearsal=True)
    mcfg = common.model_config(cfg, hf, 128)
    import dataclasses
    mcfg = dataclasses.replace(mcfg, dtype=jnp.float32, attention_impl="xla")
    model = make_model(mcfg, name=name)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, hf["vocab_size"], 96, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply(params, jnp.asarray(ids)[None]))[0]
    family = loadgen.load_family(hf)
    assert family.__name__ == "benchmark.families." + hf["model_type"]
    got = np.asarray(family.Reference(hf, params).logits(ids, pad_to=128))
    assert got.shape == want.shape == (96, hf["vocab_size"])
    # float32 both ways, different op order: agreement to rounding
    assert np.abs(got - want).max() < 2e-4 * max(1.0, np.abs(want).max())


def test_a_model_type_without_a_family_file_raises_with_the_path():
    with pytest.raises(FileNotFoundError) as e:
        loadgen.load_family({"model_type": "no-such-family"})
    assert os.path.join(ROOT, "benchmark", "families", "no_such_family.py") in str(e.value)
    with pytest.raises(KeyError, match="model_type"):
        loadgen.load_family({"hidden_size": 8})


def test_a_family_file_without_the_whole_protocol_raises(monkeypatch, tmp_path):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half.py").write_text("TOY = {}\nclass Reference: pass\n")
    monkeypatch.setattr(loadgen, "BENCH_DIR", str(tmp_path))
    with pytest.raises(AttributeError, match="train_flops_per_token"):
        loadgen.load_family({"model_type": "half"})


class _FakeRef:
    """Logits that put 5.0 on token t+1 of a fixed sequence and 4.0 (or 4.9
    at the positions in ``close``) on the runner-up 0."""
    def __init__(self, seq, close=()):
        self.seq, self.close = seq, set(close)

    def logits(self, ids):
        lg = np.zeros((len(ids), 16), np.float32)
        for t in range(len(ids) - 1):
            lg[t, self.seq[t + 1]] = 5.0
            lg[t, 0] = 4.9 if t in self.close else 4.0
        return lg


def test_token_check_judges_only_outside_the_margin():
    seq = np.array([3, 4, 5, 6, 7, 8, 9, 10], np.int32)
    prompt, gen = seq[:3], seq[3:].copy()
    ok = correct.check_tokens_vs_reference([(prompt, gen)], _FakeRef(seq), 0.5, 0.5, 0.9)
    assert ok["ok"] and ok["judged"] == 5 and ok["agreement"] == 1.0
    wrong = gen.copy()
    wrong[2] = 0                                   # a flip at a wide margin
    bad = correct.check_tokens_vs_reference([(prompt, wrong)], _FakeRef(seq), 0.5, 0.5, 0.5)
    assert not bad["ok"] and bad["mismatched"] == 1 and bad["worst_mismatch_margin"] == pytest.approx(1.0)
    # the same flip at a near-tie (position 4 predicts generated[2]) is not judged
    near = correct.check_tokens_vs_reference([(prompt, wrong)], _FakeRef(seq, close=[4]), 0.5, 0.5, 0.5)
    assert near["ok"] and near["mismatched"] == 0 and near["judged"] == 4
    # ... but it still lowers the plain agreement, which has its own floor
    assert not correct.check_tokens_vs_reference(
        [(prompt, wrong)], _FakeRef(seq, close=[4]), 0.5, 0.5, 0.9)["ok"]
    # a sparse-expert config may allow a share of judged mismatches
    assert correct.check_tokens_vs_reference(
        [(prompt, wrong)], _FakeRef(seq), 0.5, 0.5, 0.5, max_mismatch_share=0.25)["ok"]


def test_loss_checks():
    assert correct.check_losses([5.0, 4.0, 3.0, 2.0])["ok"]
    assert not correct.check_losses([2.0, 3.0, 4.0, 5.0])["ok"]
    assert not correct.check_losses([5.0, float("nan"), 3.0, 2.0])["ok"]
    assert correct.check_loss_vs_reference(1.001, 1.0, 0.01)["ok"]
    assert not correct.check_loss_vs_reference(1.02, 1.0, 0.01)["ok"]
    assert not correct.verdict([]) and correct.verdict([{"ok": True}])
