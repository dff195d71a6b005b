"""The five readers of the serving round's record (PR 37): each ``read`` on
hand-built ``run`` dicts, nothing from an engine without the counter (the
parent commit), ``work_pending_idle_share`` on hand-built gaps and spans —
a window that opens on an empty engine and one that closes on one included —
and on a trace recorded here, and the five ``BENCHMARK.json`` entries against
their files' ``HEADER``s."""
import glob
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import loadgen, program_spans as ps  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402

CHAT = ["mistral-7b-serve.chat"]
SATURATING = ["mixtral-8x7b-serve.batch-decode", "olmoe-1b-7b-serve.batch-longprompt",
              "nemotron-3-nano-30b-serve.batch-reasoning",
              "ouro-2.6b-serve.batch-worked-answers"]
LAYER = "serve entry / scheduler (inference/serving.py)"
ENTRIES = [
    ("ahead_covered_share", "%", "higher", "program_counter", "tpot_p90_ms", CHAT),
    ("sat_ahead_covered_share", "%", "higher", "program_counter",
     "serve_tokens_per_s", SATURATING),
    ("engine_occupied_share", "%", "lower", "program_counter", "ttft_p90_ms", CHAT),
    ("work_pending_idle_share", "%", "lower", "program_span", "tpot_p90_ms", CHAT),
    ("sat_round_max_over_median", "ratio", "lower", "program_counter",
     "serve_tokens_per_s", SATURATING),
]
PARENT = {"completed": 3.0, "rounds_ahead": 400.0, "dropped_slot_rounds": 0.0}


def reader(name):
    return loadgen.load_module("layer_metrics", name)


def run_with(**stats):
    return {"counters": {"stats": stats}, "trace": None,
            "cell": {"name": "mistral-7b-serve.chat"}}


@pytest.mark.parametrize("entry", ENTRIES, ids=[e[0] for e in ENTRIES])
def test_the_entry_is_appended_and_agrees_with_its_header(entry):
    name, unit, better, source, moves, cells = entry
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    names = [m["name"] for m in b["per_layer"]]
    # appended, in this order, behind everything the benchmark had
    assert names[-5:] == [e[0] for e in ENTRIES]
    m = b["per_layer"][names.index(name)]
    assert m == {"name": name, "unit": unit, "better": better, "source": source,
                 "layer": LAYER, "moves": moves, "workloads": cells}
    # the cells in BENCHMARK.json's own order, each reporting what it moves
    order = [w["name"] for w in b["workloads"]]
    assert cells == [c for c in order if c in cells]
    h = reader(name).HEADER
    assert {k: h[k] for k in ("layer", "unit", "moves", "source", "better")} == {
        "layer": LAYER, "unit": unit, "moves": moves, "source": source,
        "better": better}
    assert h["jobs"] == ["serve"]


def test_what_the_benchmark_had_before_the_five_is_as_the_cell_before_holds_it(monkeypatch):
    """``test_ouro_family.test_benchmark_json_gains_the_cell_and_nothing_else_
    moves`` pins the SET of per-layer metrics PR 35's cell reports, the two
    ``sat_`` metrics here list that cell like every saturating one, and this PR
    may not edit that file. So the whole of that test is run here on
    ``BENCHMARK.json`` cut back BY ORDER to the entries before
    ``ahead_covered_share``: whatever was appended since, nothing it holds has
    moved."""
    import types
    import test_ouro_family as before
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    names = [m["name"] for m in b["per_layer"]]
    b["per_layer"] = b["per_layer"][:names.index(ENTRIES[0][0])]
    assert len(b["per_layer"]) == 38
    monkeypatch.setattr(before, "json", types.SimpleNamespace(load=lambda fh: b))
    before.test_benchmark_json_gains_the_cell_and_nothing_else_moves()


@pytest.mark.parametrize("name", [e[0] for e in ENTRIES])
def test_an_engine_without_the_counter_reads_nothing(name):
    """What the parent commit's ``stats()`` gives, and a run with no stats."""
    assert reader(name).read(run_with(**PARENT)) is None
    assert reader(name).read({"counters": {}, "trace": None,
                              "cell": {"name": "x.y"}}) is None


@pytest.mark.parametrize("name", ["ahead_covered_share", "sat_ahead_covered_share"])
def test_ahead_covered_share(name):
    read = reader(name).read
    assert read(run_with(ahead_covered_rounds=300.0, ahead_dry_rounds=100.0)) == 75.0
    assert read(run_with(ahead_covered_rounds=7.0, ahead_dry_rounds=0.0)) == 100.0
    # no round ahead at all (a speculating engine): nothing to divide by
    assert read(run_with(ahead_covered_rounds=0.0, ahead_dry_rounds=0.0)) is None
    assert reader(name).HEADER["moves"] == (
        "serve_tokens_per_s" if name.startswith("sat_") else "tpot_p90_ms")


def test_engine_occupied_share():
    read = reader("engine_occupied_share").read
    assert read(run_with(engine_empty_s=0.45, stats_window_s=45.0)) == pytest.approx(99.0)
    assert read(run_with(engine_empty_s=0.0, stats_window_s=45.0)) == 100.0
    assert read(run_with(engine_empty_s=0.0, stats_window_s=0.0)) is None


def test_sat_round_max_over_median():
    read = reader("sat_round_max_over_median").read
    assert read(run_with(round_ms_max=1350.0, round_ms_median=430.0)) \
        == pytest.approx(3.1395, rel=1e-4)
    assert read(run_with(round_ms_max=230.0, round_ms_median=220.0)) \
        == pytest.approx(1.04545, rel=1e-4)
    # a window without a decode-dominated round has neither key
    assert read(run_with(slow_rounds=[], gc_ms_total=0.0)) is None


# ---------------------------------------------------------------------------
# work_pending_idle_share: the empty intervals as spans of their own
# ---------------------------------------------------------------------------

W = reader("work_pending_idle_share")


def round_spans(t0, length=100.0):
    """One round [t0, t0 + length): dispatch the first 30, fetch the rest
    but its last 10 (commit)."""
    return [("ds:serve.round", t0, t0 + length, "main"),
            ("ds:serve.decode_dispatch", t0, t0 + 30.0, "main"),
            ("ds:serve.fetch", t0 + 30.0, t0 + length - 10.0, "main"),
            ("ds:serve.commit", t0 + length - 10.0, t0 + length, "main")]


def drained(t):
    return ("ds:serve.drained", t, t + 1.0, "main")


def submit(t):
    return ("ds:serve.submit", t, t + 2.0, "main")


def test_an_interval_runs_from_a_drained_to_the_next_submit():
    spans = (round_spans(100.0) + [drained(201.0), submit(400.0), submit(410.0)]
             + round_spans(420.0))
    assert W.empty_spans(spans, 0.0, 1000.0) == [(W.EMPTY, 202.0, 400.0, "engine")]
    # a submit while the engine holds work opens nothing
    busy = round_spans(100.0) + [submit(205.0)] + round_spans(210.0)
    assert W.empty_spans(busy, 0.0, 1000.0) == []


def test_a_window_that_opens_empty_and_one_that_closes_empty():
    opens = [submit(300.0)] + round_spans(310.0) + round_spans(420.0)
    assert W.empty_spans(opens, 50.0, 1000.0) == [(W.EMPTY, 50.0, 300.0, "engine")]
    # ... but a round before the first submit says the engine held work
    assert W.empty_spans(round_spans(60.0) + opens, 50.0, 1000.0) == []
    closes = round_spans(100.0) + [drained(201.0)]
    assert W.empty_spans(closes, 0.0, 1000.0) == [(W.EMPTY, 202.0, 1000.0, "engine")]
    both = [submit(300.0)] + round_spans(310.0) + [drained(411.0)]
    assert W.empty_spans(both, 50.0, 1000.0) == [
        (W.EMPTY, 50.0, 300.0, "engine"), (W.EMPTY, 412.0, 1000.0, "engine")]
    # an operator's step() on an empty engine: a round with no submit
    # before it ends no interval, and its drained opens none twice
    idle_step = (round_spans(100.0) + [drained(201.0)] + round_spans(300.0, 20.0)
                 + [submit(500.0)])
    assert W.empty_spans(idle_step, 0.0, 1000.0) == [(W.EMPTY, 202.0, 500.0, "engine")]


def test_the_table_still_adds_up_and_the_share_drops_by_the_interval():
    """Window [0, 1000): a round, 198 ns of empty engine, a round. The chip
    idles through the empty interval, 20 ns under a dispatch and 30 under a
    fetch."""
    spans = (round_spans(100.0) + [drained(201.0), submit(400.0)]
             + round_spans(410.0))
    gaps = [(105.0, 125.0), (150.0, 180.0), (195.0, 415.0)]
    plain = ps.idle_by_span(gaps, spans)
    table = ps.idle_by_span(gaps, spans + W.empty_spans(spans, 0.0, 1000.0))
    assert sum(table.values()) == sum(plain.values()) == tr.length(gaps) == 270.0
    assert table[W.EMPTY] == 198.0
    assert table[ps.OUTSIDE] == plain[ps.OUTSIDE] - 198.0 - 0.0
    assert table["ds:serve.drained"] == 1.0 and table["ds:serve.submit"] == 2.0
    window_s = 1000.0 / 1e9
    host_bound = ps.share_outside(plain, window_s, "ds:serve.fetch")
    pending = ps.share_outside(table, window_s, "ds:serve.fetch", W.EMPTY)
    assert host_bound == pytest.approx(100.0 * (270.0 - 30.0) / 1000.0)
    assert host_bound - pending == pytest.approx(100.0 * 198.0 / 1000.0)


def test_read_on_a_trace_recorded_here(tmp_path, monkeypatch):
    """The engine's two names under a real profiler session (the host plane
    needs no chip); the device's gaps are made up on the spans' clock."""
    import jax
    from deepspeed_tpu.telemetry.tracing import span
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    d = tmp_path / "trace" / "toy.cell.seed0"
    jax.profiler.start_trace(str(d), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        with span("ds:serve.submit"):
            pass
        with span("ds:serve.round", index=0):
            with span("ds:serve.fetch"):
                time.sleep(0.003)
        with span("ds:serve.drained"):
            pass
        time.sleep(0.004)
        with span("ds:serve.submit"):
            pass
        with span("ds:serve.round", index=1):
            with span("ds:serve.decode_dispatch"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    spans = ps.read_spans(path)
    names = [s[0] for s in spans]
    assert names.count("ds:serve.submit") == 2 and names.count("ds:serve.drained") == 1
    reduced = {"spans": tr.host_spans(tr.read_xplane(path))}
    lo, hi = W.window_of(reduced)
    (first, gone) = W.empty_spans(spans, lo, hi)
    sub0, sub1 = [s for s in spans if s[0] == "ds:serve.submit"]
    (dr,) = [s for s in spans if s[0] == "ds:serve.drained"]
    assert first[1:3] == (lo, sub0[1]) and gone[1:3] == (dr[2], sub1[1])
    assert gone[2] - gone[1] >= 4e6
    (disp,) = [s for s in spans if s[0] == "ds:serve.decode_dispatch"]
    gaps = [(gone[1] + 1000.0, gone[2] - 1000.0), (disp[1] + 500.0, disp[2] - 500.0)]
    monkeypatch.setattr(ps.common, "OUT_DIR", str(tmp_path))
    run = {"cell": {"name": "toy.cell"}, "counters": {"stats": {"engine_empty_s": 0.004}},
           "trace": {"devices": [{"gaps": gaps}], "spans": reduced["spans"],
                     "window_s": (hi - lo) / 1e9}}
    table = W.idle_table(run)
    assert table[W.EMPTY] == pytest.approx(gaps[0][1] - gaps[0][0])
    assert sum(table.values()) == pytest.approx(tr.length(gaps))
    want = 100.0 * (gaps[1][1] - gaps[1][0]) / (hi - lo)
    assert W.read(run) == pytest.approx(want)
    host_bound = reader("host_bound_idle_share").read(run)
    assert host_bound - W.read(run) == pytest.approx(
        100.0 * (gaps[0][1] - gaps[0][0]) / (hi - lo))
    # the same trace from an engine that does not know the spans: nothing
    run["counters"]["stats"] = dict(PARENT)
    assert W.read(run) is None
    # ... and an untraced run, or one without the window span
    assert W.read(dict(run, trace=None)) is None
