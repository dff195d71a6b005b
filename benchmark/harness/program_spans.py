"""The program's own spans against the device's idle time.

The program (``deepspeed_tpu/telemetry/tracing.span``) names its host phases
``ds:<layer>.<phase>`` and opens each as a ``jax.profiler.TraceAnnotation``,
so a traced run holds them on the host plane, on the device planes' clock.
``trace_reduce.read_xplane`` keeps only ``bench:`` spans and ``label_gaps``
hands a gap to the span that overlaps it longest, so the reduced trace cannot
say what the program was doing while the chip waited. This module reads the
``ds:`` spans from the raw trace and splits every idle interval over the
INNERMOST span that covers it.

A trace of a program without such spans gives ``None``, never zeros: a
metric that reads this is then left out of the line.
"""
import collections
import glob
import os

from . import common, trace_reduce

SPAN_PREFIX = "ds:"
OUTSIDE = "outside"


def find_xplane(cell_name: str, out_dir: str = None):
    """The newest ``*.xplane.pb`` of a traced run of ``cell_name``
    (``benchmark/out/trace/<cell>.seed<n>/``), or None."""
    root = os.path.join(out_dir or common.OUT_DIR, "trace")
    files = glob.glob(os.path.join(glob.escape(root),
                                   glob.escape(cell_name) + ".seed*", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def read_spans(path: str):
    """Every ``ds:`` event of the host plane: ``[(name, start_ns, end_ns,
    line), ...]`` sorted by start. Annotation arguments are not part of
    the name."""
    import jax
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for ln in plane.lines:
            spans += [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns), ln.name)
                      for e in ln.events if e.name.startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda t: t[1])


def idle_by_span(gaps, spans):
    """Idle nanoseconds by what the program was doing: each ``(start,
    end)`` of ``gaps`` split by overlap over the innermost ``ds:`` span
    covering it (``trace_reduce.flatten``: every instant belongs to the
    innermost event). What no span covers — the caller's own time between
    two calls into the program — is ``outside``. The values add up to the
    gaps' total length. ``None`` when there is no ``ds:`` span at all."""
    spans = [sp for sp in spans if sp[0].startswith(SPAN_PREFIX)]
    if not spans:
        return None
    segs = trace_reduce.flatten([[n, s, e - s] for n, s, e, *_ in spans])
    by = collections.Counter()
    j = 0
    for gs, ge in sorted(gaps):
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        covered, k = 0.0, j
        while k < len(segs) and segs[k][0] < ge:
            ov = min(segs[k][1], ge) - max(segs[k][0], gs)
            if ov > 0:
                by[segs[k][2]] += ov
                covered += ov
            k += 1
        by[OUTSIDE] += (ge - gs) - covered
    return dict(by)


def idlest_gaps(reduced: dict):
    """The gaps the harness's own breakdown labels: those of the idlest
    chip (``trace_reduce.reduce``)."""
    return max(reduced["devices"],
               key=lambda d: trace_reduce.length(d["gaps"]))["gaps"]


def idle_table(run: dict):
    """``idle_by_span`` for a traced run as ``run.py`` hands it to a
    reader (``run["trace"]`` reduced, the raw trace found by the cell's
    name), or None: untraced, no raw trace, or a program without spans."""
    t = run.get("trace")
    if not t or not t.get("devices"):
        return None
    path = find_xplane(run["cell"]["name"])
    if path is None:
        return None
    return idle_by_span(idlest_gaps(t), read_spans(path))


def share_outside(table: dict, window_s: float, *exempt: str):
    """100 x idle seconds of ``table`` not under the ``exempt`` spans /
    the traced window."""
    ns = sum(v for k, v in table.items() if k not in exempt)
    return 100.0 * ns / 1e9 / window_s
