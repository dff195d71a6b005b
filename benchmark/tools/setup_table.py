#!/usr/bin/env python3
"""Print where a run's set-up went, from the run's own detail file: the five
``setup_*`` readings beside ``setup_s`` and the harness's three stopwatch
parts, one markdown row a file, then its costliest programs — the engine's
build record (``counters.stats.setup.programs``), ``--top`` of them — and
whether the window built anything (``build_ms_total``, and the rounds of
``slow_rounds`` with a ``build_ms`` of their own).

A builder's tool, not part of a run. Give it detail files of runs
(``benchmark/out/<cell>.seed<n>.trace<t>.json``), cold and warm ones alike:
``cache_hits`` against ``programs_built`` says which a run was.

    python benchmark/tools/setup_table.py benchmark/out/*.json --top 10
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
NAMES = ["setup_programs_built", "setup_trace_lower_s", "setup_compile_or_load_s",
         "setup_engine_init_s", "setup_unattributed_share"]


def row(path: str, top: int) -> None:
    from benchmark.harness import loadgen
    with open(path) as f:
        d = json.load(f)
    stats = d["counters"].get("stats") or {}
    setup = stats.get("setup")
    setup_s = d["end_to_end"]["setup_s"]["value"]
    tag = os.path.basename(path)
    if not setup:
        print(f"| {tag} | {setup_s:.2f} | no set-up record (a parent-shaped engine) |")
        return
    run = {"counters": d["counters"], "e2e": {"setup_s": setup_s}}
    built, lower, load, init, rest = (
        loadgen.load_module("layer_metrics", n).read(run) for n in NAMES)
    parts = d["counters"].get("setup_parts", {})
    kind = ("warm" if setup["cache_hits"] >= setup["programs_built"] > 0 else
            "cold" if not setup["cache_hits"] else "mixed")
    print(f"| {tag} | {kind} | {setup_s:.2f} | {built:.0f} | {lower:.2f} | {load:.2f} "
          f"| {init:.2f} | {rest:.1f} | hits {setup['cache_hits']}/{setup['programs_built']} "
          f"| overlap {setup['overlap_s']:.2f} | init {setup['engine_init_s']:.2f} "
          f"(weights {setup['weights_s']:.2f}, pools {setup['pools_s']:.2f}, "
          f"building {setup['init_build_s']:.2f}) | harness: imports "
          f"{parts.get('program_imports_s', 0):.2f}, init_serving "
          f"{parts.get('init_serving_s', 0):.2f}, warm-up {parts.get('warm_up_s', 0):.2f} "
          f"| window build_ms_total {stats.get('build_ms_total', 0.0):.1f}, built after "
          f"reset {setup['built_after_first_reset']} |")
    cost = lambda r: r["trace_s"] + r["lower_s"] + r["compile_or_load_s"]  # noqa: E731
    for r in sorted(setup["programs"], key=cost, reverse=True)[:top]:
        print(f"    {r['kind']:10s} {str(r['shape']):24s} trace {r['trace_s']:6.2f} lower "
              f"{r['lower_s']:6.2f} backend {r['compile_or_load_s']:6.2f} (read "
              f"{r['cache_load_s']:5.2f}, hits {r['cache_hit']}) span {r['wall_s']:6.2f} "
              f"builds {r['builds']} at {r['built_at_s']:6.2f} s round {r['round']}")
    named = [r for r in setup["programs"] if r["kind"] != "other"]
    other = [r for r in setup["programs"] if r["kind"] == "other"]
    for label, recs in (("named", named), ("other", other)):
        print(f"    {label}: {sum(r['builds'] for r in recs)} builds, trace+lower "
              f"{sum(r['trace_s'] + r['lower_s'] for r in recs):.2f} s, backend "
              f"{sum(r['compile_or_load_s'] for r in recs):.2f} s, spans "
              f"{sum(r['wall_s'] for r in recs):.2f} s")
    for rec, _ in stats.get("slow_rounds", []):
        if rec.get("build_ms"):
            print(f"    ROUND {rec['index']} of the window built for {rec['build_ms']:.1f} ms")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("details", nargs="+")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    print("| run | kind | setup_s | programs_built | trace_lower_s | compile_or_load_s "
          "| engine_init_s | unattributed % | cache | overlap | constructors | harness "
          "| window |")
    print("|" + " --- |" * 13)
    for path in args.details:
        row(path, args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
