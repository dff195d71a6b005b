"""The trace reducer: a hand-built trace with nested lines and two device
planes, and a small trace recorded on the chip."""
import glob
import gzip
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen, peaks, trace_reduce as tr  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def hand_built():
    """Two chips. Chip 0: a while [100, 900) holding fusion [100, 300), an
    all-reduce [300, 400) and a fusion [500, 900) (so 100 ns of the while's
    own time), then idle to 1000. An async all-gather spans [250, 450).
    Chip 1: one op [0, 500). Window [0, 1000) from the bench:window span."""
    ops0 = [["%while.1 = (s32[]) while(...)", 100.0, 800.0],
            ["%fusion.1 = bf16[8] fusion(...)", 100.0, 200.0],
            ["%all-reduce.1 = bf16[8] all-reduce(bf16[8] %x)", 300.0, 100.0],
            ["%fusion.2 = bf16[8] fusion(...)", 500.0, 400.0]]
    async0 = [["%all-gather-start.1 = (bf16[4], bf16[8]) all-gather-start(bf16[4] %p)", 250.0, 200.0],
              ["%copy-start.3 = (f32[4], f32[4], u32[]) copy-start(f32[4] %q)", 0.0, 1000.0]]
    mods0 = [["jit_train_step(123)", 100.0, 800.0]]
    ops1 = [["%fusion.9 = bf16[8] fusion(...)", 0.0, 500.0]]
    host = [["bench:window", 0.0, 1000.0], ["bench:step", 880.0, 200.0],
            ["bench:sleep", 0.0, 90.0]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops0},
            {"name": "Async XLA Ops", "events": async0},
            {"name": "XLA Modules", "events": mods0}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]}]}


def test_flatten_gives_self_time_without_overlap():
    segs = tr.flatten(hand_built()["planes"][0]["lines"][0]["events"])
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))      # disjoint
    by = {}
    for s, e, n in segs:
        by[n.split(" = ")[0]] = by.get(n.split(" = ")[0], 0) + e - s
    assert by == {"%fusion.1": 200, "%all-reduce.1": 100, "%while.1": 100,
                  "%fusion.2": 400}


def test_hand_built_planes_kept_apart_and_busy_plus_idle_is_window():
    r = tr.reduce(hand_built())
    assert r["window_s"] == pytest.approx(1000e-9)
    d0, d1 = r["devices"]
    assert (d0["plane"], d1["plane"]) == ("/device:TPU:0", "/device:TPU:1")
    assert d0["busy_ns"] == 800 and d1["busy_ns"] == 500           # not unioned
    for d in r["devices"]:
        assert d["busy_ns"] + tr.length(d["gaps"]) == pytest.approx(1000)
        assert 0 <= d["busy_ns"] / 1000 <= 1
    assert r["busy_s"] == pytest.approx(650e-9)                    # mean of chips
    assert r["idle_share_per_device"] == pytest.approx([0.2, 0.5])
    # nested parent and children never both count: self times sum to busy
    assert sum(d0["op_self_ns"].values()) == pytest.approx(d0["busy_ns"])


def test_hand_built_collectives_and_gap_labels():
    r = tr.reduce(hand_built())
    d0 = r["devices"][0]
    # collective intervals: all-reduce [300,400) + async all-gather [250,450)
    # = [250,450); compute covers [100,300) + [400,900) -> exposed [300,400)
    assert d0["collective_ns"] == 200
    assert d0["collective_exposed_ns"] == 100
    assert d0["modules"]["jit_train_step"]["count"] == 1
    # the idlest chip is chip 1 (idle [500,1000)): 380 ns before the step
    # span starts are unlabelled... the longest overlap wins the whole gap
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(500e-9)
    assert set(gaps) == {"bench:step"}
    ops = r["breakdown"]["device_ops"]
    assert len(ops) <= 10 and ops[0][0].startswith("%fusion")


def test_is_collective_reads_the_opcode_not_the_operands():
    assert tr.is_collective("%all-gather.3 = bf16[8] all-gather(bf16[4] %x)")
    assert tr.is_collective("%ar-done = bf16[8] all-reduce-done((bf16[8]) %s)")
    assert not tr.is_collective("%fusion.1 = bf16[8] fusion(bf16[8] %all-gather.3)")
    assert tr.module_name("jit_step(5540584557887382392)") == "jit_step"
    assert tr.is_mosaic('%attn.36 = (bf16[4]) custom-call(bf16[4] %x), custom_call_target="tpu_custom_call"')
    assert not tr.is_mosaic('%custom-call.3 = f32[8] custom-call(), custom_call_target="Sharding"')


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The train cell's five traced steps on one TPU v5 lite chip (PR 22)."""
    src = glob.glob(os.path.join(FIXTURES, "*.xplane.pb.gz"))
    assert src, "no recorded trace under benchmark/tests/fixtures"
    dst = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(src[0], "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return tr.reduce(tr.read_xplane(str(dst)))


def test_recorded_trace_reduces_within_bounds(recorded):
    r = recorded
    assert r["n_devices"] == 1 and r["window_s"] > 0
    d = r["devices"][0]
    assert d["busy_ns"] + tr.length(d["gaps"]) == pytest.approx(r["window_s"] * 1e9)
    assert 0.0 <= r["idle_share_per_device"][0] <= 1.0
    assert sum(d["op_self_ns"].values()) == pytest.approx(d["busy_ns"])
    steps, secs = tr.module_stats(r, "jit_train_step")
    assert steps >= 2 and secs > 0
    # the flash kernels are there under their scope name
    flash = tr.op_seconds(r, tr.is_mosaic)
    assert 0 < flash < r["busy_s"]
    assert d["collective_ns"] == 0                                  # one chip
    assert any(n == "bench:window" for n, _, _ in r["spans"])
    assert len(r["breakdown"]["device_ops"]) == 10


def test_the_roofline_readers_read_what_they_read_before_the_family_seam(recorded):
    """The readers reach the cost model through the configuration's family;
    the numbers are those of harness/flops.py and harness/bytes.py (PR 24)."""
    pk = peaks.peaks_for("TPU v5 lite")
    hf = common.hf_of(common.load_config("mistral-7b-train"))
    run = {"trace": recorded, "chips": 1, "peaks": pk, "hf": hf,
           "family": loadgen.load_family(hf),
           "counters": {"sequences_per_step": 4, "seq_len": 2048, "num_layers": 2}}
    got = loadgen.load_module("layer_metrics", "flash_attention_roofline").read(run)
    assert got == pytest.approx(30.461886738808406, rel=1e-12)
    # a decode step of 32.777 ms over 4321 live int8 rows of 16 layers
    step = {"n_devices": 1, "devices": [
        {"modules": {"jit_step": {"count": 80, "total_ns": 80 * 32.777e6}}}]}
    hf = common.hf_of(common.load_config("mistral-7b-serve"))
    run = {"trace": step, "peaks": pk, "hf": hf, "family": loadgen.load_family(hf),
           "counters": {"kv_cache_bits": 8, "mean_live_tokens": 4321.0}}
    for name in ("decode_step_roofline", "sat_decode_step_roofline"):
        got = loadgen.load_module("layer_metrics", name).read(run)
        assert got == pytest.approx(27.519673638744937, rel=1e-12)
