"""Idle time by the program's own spans: hand-built gaps and spans, the
trace recorded on the chip (a program from before the spans: nothing to
attribute, so None), a trace recorded here with the spans in it, and the
readers that use the table."""
import glob
import gzip
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import loadgen, program_spans as ps  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def round_spans(t0):
    """One serving round [t0, t0 + 100) on the main line: schedule
    [0, 10), prefill_dispatch [10, 30), decode_dispatch [30, 40), fetch
    [40, 90), commit [90, 98); the last 2 ns are the round's own."""
    cuts = [("ds:serve.schedule", 0, 10), ("ds:serve.prefill_dispatch", 10, 30),
            ("ds:serve.decode_dispatch", 30, 40), ("ds:serve.fetch", 40, 90),
            ("ds:serve.commit", 90, 98)]
    return ([("ds:serve.round", t0 + 0.0, t0 + 100.0, "main")]
            + [(n, t0 + float(a), t0 + float(b), "main") for n, a, b in cuts])


def test_nested_spans_the_innermost_one_gets_the_gap():
    by = ps.idle_by_span([(45.0, 60.0)], round_spans(0.0))
    assert by == {"ds:serve.fetch": 15.0, ps.OUTSIDE: 0.0}
    # a span nested two deep (a request's span inside a phase inside the round)
    spans = round_spans(0.0) + [("ds:request.prefill", 12.0, 20.0, "main")]
    by = ps.idle_by_span([(10.0, 30.0)], spans)
    assert by["ds:request.prefill"] == 8.0
    assert by["ds:serve.prefill_dispatch"] == 12.0
    # the round's own time: inside the round, under no phase
    assert ps.idle_by_span([(98.0, 100.0)], round_spans(0.0))["ds:serve.round"] == 2.0


def test_a_gap_straddling_two_phases_is_split_by_overlap():
    by = ps.idle_by_span([(35.0, 50.0)], round_spans(0.0))
    assert by["ds:serve.decode_dispatch"] == 5.0 and by["ds:serve.fetch"] == 10.0
    assert sum(by.values()) == 15.0


def test_a_gap_outside_every_round_is_outside():
    spans = round_spans(0.0) + round_spans(200.0)
    by = ps.idle_by_span([(95.0, 205.0), (400.0, 450.0)], spans)
    assert by[ps.OUTSIDE] == 100.0 + 50.0          # between and after the rounds
    assert by["ds:serve.commit"] == 3.0 and by["ds:serve.round"] == 2.0
    assert by["ds:serve.schedule"] == 5.0
    assert sum(by.values()) == 110.0 + 50.0        # all idle time accounted for


def test_spans_of_two_host_lines_and_unsorted_gaps():
    """The fetch on the watchdog's thread is another line of the same plane;
    gaps may come in any order."""
    spans = [s for s in round_spans(0.0) if s[0] != "ds:serve.fetch"]
    spans.append(("ds:serve.fetch", 40.0, 90.0, "serving-round"))
    by = ps.idle_by_span([(80.0, 95.0), (5.0, 12.0)], spans)
    assert by["ds:serve.fetch"] == 10.0 and by["ds:serve.commit"] == 5.0
    assert by["ds:serve.schedule"] == 5.0 and by["ds:serve.prefill_dispatch"] == 2.0


def test_no_program_span_gives_none_not_zeros():
    assert ps.idle_by_span([(0.0, 10.0)], []) is None
    assert ps.idle_by_span([(0.0, 10.0)], [("bench:step", 0.0, 10.0, "main")]) is None
    assert ps.idle_by_span([], round_spans(0.0)) == {}


def test_share_outside_exempts_the_named_spans():
    table = {"ds:serve.fetch": 6e7, "ds:serve.commit": 1e7, ps.OUTSIDE: 2e7}
    assert ps.share_outside(table, 3.0, "ds:serve.fetch") == pytest.approx(1.0)
    assert ps.share_outside(table, 3.0) == pytest.approx(3.0)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """PR 22's trace of five train steps on the chip, as a cell's traced
    run leaves it: <out>/trace/<cell>.seed<n>/.../*.xplane.pb."""
    out = tmp_path_factory.mktemp("out")
    d = out / "trace" / "mistral-7b-train.seq2048.seed5" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    src = os.path.join(FIXTURES, "train_5steps_v5e.xplane.pb.gz")
    with gzip.open(src, "rb") as f, open(d / "host.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    return str(out)


def test_the_recorded_trace_has_no_program_span(recorded, monkeypatch):
    path = ps.find_xplane("mistral-7b-train.seq2048", recorded)
    assert path and path.endswith("host.xplane.pb")
    assert ps.find_xplane("mistral-7b-serve.chat", recorded) is None
    assert ps.read_spans(path) == []
    reduced = tr.reduce(tr.read_xplane(path))
    gaps = ps.idlest_gaps(reduced)
    assert tr.length(gaps) / 1e9 == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-6)
    assert ps.idle_by_span(gaps, ps.read_spans(path)) is None
    # ... so a reader over it reports nothing: what the parent commit gives
    monkeypatch.setattr(ps.common, "OUT_DIR", recorded)
    run = {"trace": reduced, "cell": {"name": "mistral-7b-train.seq2048"},
           "counters": {"stats": {"completed": 3.0}}}
    assert ps.idle_table(run) is None
    for name in ("host_bound_idle_share", "sat_host_bound_idle_share",
                 "queue_wait_p90_ms", "first_token_wait_p90_ms",
                 "token_gap_max_p90_ms"):
        assert loadgen.load_module("layer_metrics", name).read(run) is None
    assert ps.idle_table(dict(run, trace=None)) is None


def test_find_xplane_takes_the_newest_trace_of_the_cell(tmp_path):
    for seed, age in ((1, 100), (22, 10)):
        d = tmp_path / "trace" / f"a.b.seed{seed}" / "plugins"
        d.mkdir(parents=True)
        (d / "x.xplane.pb").write_bytes(b"")
        os.utime(d / "x.xplane.pb", (time.time() - age,) * 2)
    (tmp_path / "trace" / "a.bc.seed3").mkdir()
    assert "seed22" in ps.find_xplane("a.b", str(tmp_path))
    assert ps.find_xplane("a", str(tmp_path)) is None


def test_read_spans_and_the_table_on_a_trace_recorded_here(tmp_path, monkeypatch):
    """The program's primitive under a real profiler session (the host
    plane needs no chip), read back by name with its line; the device's
    gaps are made up, inside the spans' own clock."""
    import jax
    from deepspeed_tpu.telemetry.tracing import span
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    d = tmp_path / "trace" / "toy.cell.seed0"
    jax.profiler.start_trace(str(d), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:step"):
        with span("ds:serve.round", index=0):
            with span("ds:serve.schedule"):
                time.sleep(0.002)
            with span("ds:serve.fetch"):
                time.sleep(0.004)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    spans = ps.read_spans(path)
    assert [s[0] for s in spans] == ["ds:serve.round", "ds:serve.schedule",
                                     "ds:serve.fetch"]      # no bench:, no args
    assert all(isinstance(s[3], str) and s[3] for s in spans)
    (_, r0, r1, _), (_, s0, s1, _), (_, f0, f1, _) = spans
    assert r0 <= s0 < s1 <= f0 < f1 <= r1
    gaps = [(r0 - 1000.0, s0 + 500.0), (f0 + 100.0, f1 - 100.0)]
    monkeypatch.setattr(ps.common, "OUT_DIR", str(tmp_path))
    run = {"cell": {"name": "toy.cell"},
           "trace": {"devices": [{"gaps": gaps}, {"gaps": []}],
                     "window_s": (r1 - r0 + 1000.0) / 1e9}}
    table = ps.idle_table(run)
    assert table["ds:serve.fetch"] == pytest.approx(f1 - f0 - 200.0)
    assert table["ds:serve.schedule"] == pytest.approx(500.0)
    assert table[ps.OUTSIDE] == pytest.approx(1000.0)
    assert sum(table.values()) == pytest.approx(tr.length(gaps))
    share = loadgen.load_module("layer_metrics", "host_bound_idle_share").read(run)
    want = 100.0 * (tr.length(gaps) - table["ds:serve.fetch"]) / (r1 - r0 + 1000.0)
    assert share == pytest.approx(want)
