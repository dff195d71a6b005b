"""Tier-1 guards the yardstick: ``benchmark/tests`` collected from here.

``benchmark/`` is the on-chip benchmark and carries its own CPU tests
(``python -m pytest benchmark/tests``: the trace reducer, the generators, the
families' arithmetic, the family seam, ``BENCHMARK.json`` itself). The
driver's tier-1 command collects ``tests/`` only, so until ISSUE 26 a PR
could break the instrument and stay green. This module runs that suite ONCE,
in a process of its own (its modules put ``benchmark/tests`` on ``sys.path``
and two of them define a fixture of the same name, so they are not imported
into this one), and reports every one of its tests as a case here: the count
of passes moves with the suite's. That process runs the suite's files on TWO
workers of its own (``_SUITE``): alone the suite is 530 s, 440 s of them nine
cells' CPU rehearsals one after another, and beside tier-1's other workers it
was tier-1's longest case by 400 s and more (979-1247 s of whole runs of
1015-1288 s, PR 57). A cell's CPU rehearsal that failed in that run is run
once more, alone (``_REHEARSAL``), before it is reported.
"""
import os
import subprocess
import sys
import time
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SUITE = os.path.join("benchmark", "tests")
_OPTIONS = ["-q", "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
_SUITE = ["-q", "-p", "no:cacheprovider", "-p", "xdist", "-n", "2", "--dist",
          "loadfile", "-p", "no:randomly"]
_PYTEST = [sys.executable, "-m", "pytest", SUITE]
_ENV = {k: v for k, v in os.environ.items()
        if k != "XLA_FLAGS" and not k.startswith("PYTEST_XDIST")}
_ENV["JAX_PLATFORMS"] = "cpu"


def _node_ids():
    """The suite's test ids, by the suite's own collection (parametrised
    cases included): every worker gets the same list."""
    p = subprocess.run(_PYTEST + _OPTIONS + ["--collect-only"], cwd=ROOT, env=_ENV,
                       capture_output=True, text=True, timeout=300)
    ids = [ln.strip() for ln in p.stdout.splitlines() if "::" in ln and " " not in ln.strip()]
    if p.returncode != 0 or not ids:
        return [f"COLLECTION FAILED rc={p.returncode}: {p.stdout[-1500:]}{p.stderr[-1500:]}"]
    return ids


NODE_IDS = _node_ids()


def _run(command, xml):
    """One pytest process -> ({test id: (outcome, detail)}, the end of its
    output)."""
    p = subprocess.run(command + [f"--junitxml={xml}", "-o", "junit_family=xunit1"],
                       cwd=ROOT, env=_ENV, capture_output=True, text=True, timeout=1200)
    out = {}
    for case in ET.parse(xml).getroot().iter("testcase"):
        path = case.get("classname").replace(".", "/") + ".py"
        bad = [c for c in case if c.tag in ("failure", "error", "skipped")]
        out[f"{path}::{case.get('name')}"] = (
            bad[0].tag if bad else "passed",
            (bad[0].get("message") or "") + "\n" + (bad[0].text or "") if bad else "")
    return out, p.stdout[-3000:]


# a cell's CPU rehearsal (`test_the_cell_rehearses_on_the_cpu` of each family)
# is a process with a window on the HOST's clock in which a request has to
# finish or a step to run: beside tier-1's five other workers a toy engine can
# starve inside it (PERF.md section 7, PR 40 and PR 48: the same case passes
# alone, in a fifth of the time). It is the load that fails, not the
# arithmetic — so a rehearsal that failed in the suite's run is run ONCE more,
# alone and once the machine has gone quiet (the other workers' files end
# before this one's: the suite is tier-1's longest case), and that outcome is
# the one reported; any other failure stands. Seen in a whole tier-1 run of
# PR 48's tree: the retry made at once, beside the workers still busy, failed
# as the first had (no request finished in 75 s); alone it passes in 131 s.
# Seen in whole runs of PR 49's tree, with timestamps: the suite's first pass
# ends at ~580 s and the other five workers at 800-890 s (xdist hands files
# out by their test counts, and `test_pool_layout.py`, 360 s of 28 cases,
# starts at ~450 s), so a wait of 120 s started the retry at ~700 s beside a
# load of 15 and it failed in two whole runs of three; after 300 s it starts
# at ~885 s on an idle machine and passes, the whole run 1049 s of tier-1's
# 1470.
_REHEARSAL = "rehears"
_QUIET_WAIT_S, _RETRY_BUDGET_S = 300.0, 450.0


def _wait_for_a_quiet_machine():
    """Until the 1-minute load is under half the cores, at most
    ``_QUIET_WAIT_S``."""
    end = time.monotonic() + _QUIET_WAIT_S
    while os.getloadavg()[0] > (os.cpu_count() or 2) / 2 and time.monotonic() < end:
        time.sleep(5.0)


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    """One run of the whole suite -> {test id: (outcome, detail)}; the
    rehearsals that failed in it, once more and alone."""
    tmp = tmp_path_factory.mktemp("benchmark_suite")
    out, log = _run(_PYTEST + _SUITE, tmp / "junit.xml")
    again = [n for n, (outcome, _) in out.items()
             if outcome in ("failure", "error") and _REHEARSAL in n]
    started = time.monotonic()
    for i, node_id in enumerate(again):
        if time.monotonic() - started > _RETRY_BUDGET_S:
            break           # tier-1 has a time limit of its own
        _wait_for_a_quiet_machine()
        alone, _ = _run([sys.executable, "-m", "pytest", node_id] + _OPTIONS,
                        tmp / f"alone{i}.xml")
        if node_id in alone:
            first = out[node_id][1][-1500:]
            out[node_id] = (alone[node_id][0], alone[node_id][1]
                            + "\n---- in the suite's run it had failed with:\n" + first)
    out["__log__"] = ("", log)
    return out


# SIX assertions of the suite cannot hold once the benchmark grows, and only a
# `benchmark` PR may edit a file under benchmark/. (1) PR 32's cell test pins
# that cell's entries as the LAST of BENCHMARK.json's lists, and a new entry
# has to go at the end of its list (the driver reads one put first or in the
# middle as a change to what was there). (2) PR 35's cell test pins the SET of
# per-layer metrics its cell reports, and PR 37's two `sat_` metrics of the
# round's record list every saturating cell, that one too. Each is reported as
# an expected failure when, and only when, it fails AT that pin; everything it
# asserts, the pin too, is held on BENCHMARK.json cut back BY ORDER to what
# it had then — (1) by benchmark/tests/test_ouro_family.py::test_what_the_
# benchmark_had_up_to_the_cell_before_is_as_that_cells_test_holds_it, (2) by
# benchmark/tests/test_round_record_metrics.py::test_what_the_benchmark_had_
# before_the_five_is_as_the_cell_before_holds_it — which are cases here like
# any other. Any other failure of them, or of any other test, fails.
PINS = {
    "benchmark/tests/test_nemotron_h_family.py::"
    "test_benchmark_json_has_the_cell_and_its_metrics":
        '>       assert b["workloads"][-1] == cell and',
    "benchmark/tests/test_ouro_family.py::"
    "test_benchmark_json_gains_the_cell_and_nothing_else_moves":
        ">       assert reports == {",
    # (3) PR 37's five entries pinned as the LAST of `per_layer`: PR 40
    # appended four behind them (and its cell to the two `sat_` lists); held
    # on the lists cut back by order by benchmark/tests/test_qwen3_next_
    # family.py::test_what_the_benchmark_had_before_this_cell_is_as_the_
    # tests_before_hold_it
    **{"benchmark/tests/test_round_record_metrics.py::"
       f"test_the_entry_is_appended_and_agrees_with_its_header[{name}]":
           ">       assert names[-5:] == [e[0] for e in ENTRIES]"
       for name in ("ahead_covered_share", "sat_ahead_covered_share",
                    "engine_occupied_share", "work_pending_idle_share",
                    "sat_round_max_over_median")},
    # (4) PR 40's cell test pins `sat_moe_held_assignment_share` to its cell
    # ALONE: PR 44's cell holds a share of its experts too and is appended to
    # that list; held on the lists cut back by order by benchmark/tests/
    # test_afmoe_family.py::test_what_the_benchmark_had_before_this_cell_is_
    # as_the_cell_before_holds_it
    "benchmark/tests/test_qwen3_next_family.py::"
    "test_benchmark_json_has_the_cell_and_its_metrics":
        ">           assert where[name] == [CELL], name",
    # (5) PR 55's test of the five set-up metrics pins the NUMBER of serve
    # cells (8), of cells (11) and of per-layer metrics (63): PR 57 appended a
    # ninth serve cell to the five lists and two metrics behind them; held on
    # the lists cut back by order by benchmark/tests/test_falcon_h1_family.py
    # ::test_what_the_benchmark_had_before_this_cell_is_as_the_tests_before_
    # hold_it
    **{"benchmark/tests/test_setup_metrics.py::"
       f"test_the_entry_is_appended_and_agrees_with_its_header[{name}]":
           ">       assert len(serve) == 8"
       for name in ("setup_programs_built", "setup_trace_lower_s",
                    "setup_compile_or_load_s", "setup_engine_init_s",
                    "setup_unattributed_share")},
    "benchmark/tests/test_setup_metrics.py::"
    "test_nothing_the_benchmark_had_moved":
        ">       assert len(names) == len(set(names)) == 63",
    # (6) PR 52's cell test pins the two `sat_mla_*` metrics to its cell
    # ALONE: PR 59's cell is latent attention too and is appended to both
    # lists; held on the lists cut back by order by benchmark/tests/
    # test_xing4_0_family.py::test_what_the_benchmark_had_before_this_cell_
    # is_as_the_cell_before_holds_it
    "benchmark/tests/test_glm4_moe_lite_family.py::"
    "test_benchmark_json_has_the_cell_and_its_metrics":
        ">           assert where[name] == [CELL], name",
}


@pytest.mark.parametrize("node_id", NODE_IDS)
def test_benchmark_suite(node_id, outcomes):
    assert node_id in outcomes, (node_id, outcomes["__log__"][1])
    outcome, detail = outcomes[node_id]
    if outcome == "failure" and PINS.get(node_id, "\0") in detail:
        pytest.xfail("pins what BENCHMARK.json held when it was written; "
                     "entries were appended since (PERF.md section 7)")
    assert outcome == "passed", f"{node_id}: {outcome}\n{detail[-3000:]}"
