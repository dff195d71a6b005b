"""Share of the traced window in which the chip was idle although the
engine held work and the host was not in the round's one fetch: idle seconds
of the idlest chip outside ``ds:serve.fetch`` AND outside the intervals in
which the engine held no request / window. ``host_bound_idle_share`` books
an empty engine's idle as the chip waiting for the host; this does not.

The engine marks the moment it comes to hold nothing (``ds:serve.drained``,
at the end of that ``step()``) and the next submission (``ds:serve.submit``):
an empty interval is [a drained's end, the next submit's start], from the
window's start when the trace opens on a submit before any round, to the
window's end when nothing follows the last drained. Each becomes one
top-level ``ds:serve.empty`` span, and ``program_spans.idle_by_span`` splits
the idle time as it does for every reader. An engine without the two spans
(no ``engine_empty_s`` in its ``stats()``) reads nothing."""
from benchmark.harness import program_spans, trace_reduce

HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "%", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "program_span", "better": "lower"}

EMPTY = "ds:serve.empty"
DRAINED, SUBMIT, ROUND = "ds:serve.drained", "ds:serve.submit", "ds:serve.round"


def empty_spans(spans, lo: float, hi: float):
    """The intervals of [lo, hi] in which the engine held no request, as
    ``(EMPTY, start, end, line)`` spans, from the program's spans by name."""
    out, since, begun = [], None, False
    for name, start, end, *_ in sorted(spans, key=lambda sp: sp[1]):
        if name == DRAINED:
            since = end if since is None else since
        elif name == SUBMIT:
            if since is None and not begun:
                since = lo                  # the trace opened on an empty engine
            if since is not None and start > since:
                out.append((EMPTY, since, start, "engine"))
            since = None
        elif name != ROUND:
            continue
        begun = True
    if since is not None and hi > since:
        out.append((EMPTY, since, hi, "engine"))
    return out


def window_of(reduced: dict):
    """[lo, hi] of the traced window in ns: the harness's window span."""
    for name, start, end in reduced.get("spans", ()):
        if name == trace_reduce.WINDOW_SPAN:
            return start, end
    return None


def idle_table(run):
    """``idle_by_span`` with the empty intervals as spans of their own, or
    None: untraced, no raw trace, no window span, or an engine from before
    the two spans."""
    t = run.get("trace")
    stats = run["counters"].get("stats") or {}
    if not t or not t.get("devices") or "engine_empty_s" not in stats:
        return None
    path = program_spans.find_xplane(run["cell"]["name"])
    window = window_of(t)
    if path is None or window is None:
        return None
    spans = program_spans.read_spans(path)
    return program_spans.idle_by_span(program_spans.idlest_gaps(t),
                                      spans + empty_spans(spans, *window))


def read(run):
    table = idle_table(run)
    if table is None:
        return None
    return program_spans.share_outside(table, run["trace"]["window_s"],
                                       "ds:serve.fetch", EMPTY)
