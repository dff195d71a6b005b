#!/usr/bin/env python3
"""Print where the chip's idle time of a traced run went, by the program's
own spans: seconds and share of the traced window per ``ds:`` span (each
idle interval split over the INNERMOST span covering it) and ``outside``
(no span of the program: the caller between two calls).

A builder's tool, not part of a run. Give it a trace directory of a traced
run (``benchmark/out/trace/<cell>.seed<n>``), an ``.xplane.pb``, or a cell's
name (the newest trace of that cell):

    python benchmark/tools/idle_by_span.py benchmark/out/trace/mistral-7b-serve.chat.seed7
    python benchmark/tools/idle_by_span.py mistral-7b-serve.chat

It also says whether the rows add up to window - busy, and how many
``ds:serve.round`` spans each ``bench:step`` span of the harness holds.
"""
import argparse
import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def resolve(arg: str) -> str:
    from benchmark.harness import program_spans
    if os.path.isfile(arg):
        return arg
    if os.path.isdir(arg):
        files = glob.glob(os.path.join(glob.escape(arg), "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            return max(files, key=os.path.getmtime)
    path = program_spans.find_xplane(arg)
    if path is None:
        raise SystemExit(f"idle_by_span: no .xplane.pb at or for {arg!r}")
    return path


def table(path: str) -> str:
    from benchmark.harness import program_spans, trace_reduce
    reduced = trace_reduce.reduce(trace_reduce.read_xplane(path))
    spans = program_spans.read_spans(path)
    if not reduced["devices"]:
        return (f"trace {os.path.relpath(path, ROOT)}: no device plane (not "
                f"recorded on a chip); {len(spans)} ds: spans on the host plane")
    gaps = program_spans.idlest_gaps(reduced)
    idle_ns = trace_reduce.length(gaps)
    window_ns = reduced["window_s"] * 1e9
    out = [f"trace {os.path.relpath(path, ROOT)}",
           f"window {reduced['window_s']:.6f} s, idlest chip idle "
           f"{idle_ns / 1e9:.6f} s ({100 * idle_ns / window_ns:.3f} %), "
           f"{len(gaps)} gaps, {len(spans)} ds: spans"]
    by = program_spans.idle_by_span(gaps, spans)
    if by is None:
        out.append("no ds: span in this trace (a program without "
                   "telemetry.tracing.span): nothing to attribute")
        return "\n".join(out)
    out.append(f"{'idle under':34s} {'seconds':>10s} {'% of window':>12s} "
               f"{'% of idle':>10s}")
    for name, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        out.append(f"{name:34s} {ns / 1e9:10.6f} {100 * ns / window_ns:12.3f} "
                   f"{100 * ns / max(idle_ns, 1.0):10.1f}")
    total = sum(by.values())
    out.append(f"{'sum of rows':34s} {total / 1e9:10.6f}   (window - busy "
               f"{idle_ns / 1e9:.6f}; difference {abs(total - idle_ns) / 1e3:.3f} us)")
    steps = [sp for sp in reduced["spans"] if sp[0] == "bench:step"]
    if steps:
        rounds = [sp for sp in spans if sp[0] == "ds:serve.round"]
        per = [sum(1 for r in rounds if s <= r[1] and r[2] <= e)
               for _, s, e in steps]
        out.append(f"{len(steps)} bench:step spans; ds:serve.round spans "
                   f"inside each: min {min(per)}, max {max(per)}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="+",
                    help="trace directory, .xplane.pb file, or cell name")
    args = ap.parse_args(argv)
    for arg in args.trace:
        print(table(resolve(arg)), flush=True)
        print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
