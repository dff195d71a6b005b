"""From a profiler trace to numbers: the benchmark's own reducer.

Reads the ``.xplane.pb`` the JAX profiler writes with nothing but
``jax.profiler.ProfileData``. What a TPU v5e trace from this stack looks like
(recorded in PR 22, see tests/fixtures):

- one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one
  event per program execution, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one
  event per HLO instruction, named by its full HLO text, NESTED: a ``%while``
  event contains the events of its body) and ``Async XLA Ops`` (start-to-done
  spans of asynchronous copies and collectives, overlapping the ops line);
- one ``/host:CPU`` plane whose lines hold, among the runtime's own events,
  the ``jax.profiler.TraceAnnotation`` spans the benchmark records
  (``bench:<what>``), on the same clock as the device planes.

Rules that keep the 1.36 "share" of BENCH_r06 from recurring: every device
plane is reduced on its own and never unioned with another; only ONE line
(``XLA Ops``) says when the device is busy; nested events are flattened to
self time first, so a parent and its children never both count; everything
is clipped to the traced window, so busy + idle = window exactly.
"""
import collections
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
_COLLECTIVE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)(-start|-done)?\(")
_MODULE_HASH = re.compile(r"\(\d+\)$")


# --------------------------------------------------------------------------
# reading
# --------------------------------------------------------------------------

def read_xplane(path: str) -> dict:
    """The planes and lines the reducer reads, as plain lists:
    ``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]}``. Host lines keep only the ``bench:`` spans."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            keep = (OPS_LINE, ASYNC_LINE, MODULES_LINE)
            lines = [{"name": ln.name,
                      "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                                 for e in ln.events]}
                     for ln in plane.lines if ln.name in keep]
        elif plane.name == HOST_PLANE:
            lines = []
            for ln in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in ln.events if e.name.startswith(SPAN_PREFIX)]
                if evs:
                    lines.append({"name": ln.name, "events": evs})
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# --------------------------------------------------------------------------
# interval arithmetic (all intervals are (start, end) in ns)
# --------------------------------------------------------------------------

def union(intervals):
    """Sorted, disjoint union."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(disjoint) -> float:
    return float(sum(e - s for s, e in disjoint))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the disjoint sorted ``a`` not covered by the disjoint sorted
    ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def flatten(events):
    """Nested ``[name, start, dur]`` events of one line -> disjoint
    ``(start, end, name)`` SELF segments: each instant belongs to the
    innermost event covering it. A child that runs past its parent is cut
    at the parent's end."""
    evs = sorted(((s, s + d, n) for n, s, d in events if d > 0),
                 key=lambda t: (t[0], -t[1]))
    out, stack = [], []          # stack entries: [end, name, cursor]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, cur = stack.pop()
            if end > cur:
                out.append((cur, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for s, e, n in evs:
        close(s)
        if stack:
            top = stack[-1]
            e = min(e, top[0])
            if e <= s:
                continue
            if s > top[2]:
                out.append((top[2], s, top[1]))
            top[2] = max(top[2], s)
        stack.append([e, n, s])
    close(float("inf"))
    out.sort()
    return out


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

def op_label(name: str, width: int = 120) -> str:
    """An HLO event's name as the breakdown prints it: the instruction and
    the head of its text (result shape and opcode), one line."""
    return " ".join(name.split())[:width]


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


def is_mosaic(name: str) -> bool:
    """A Pallas kernel: XLA runs it as a custom call to Mosaic. On one chip
    the instruction carries the ``named_scope`` it was called under
    (``%attn.36``); mapped over a mesh it may not, the target always does."""
    return 'custom_call_target="tpu_custom_call"' in name


def module_name(name: str) -> str:
    """``jit_train_step(8669531687149571977)`` -> ``jit_train_step``."""
    return _MODULE_HASH.sub("", name)


def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def host_spans(trace: dict):
    """Every ``bench:`` span on the host plane: (name, start, end)."""
    spans = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for ln in plane["lines"]:
            spans += [(n, s, s + d) for n, s, d in ln["events"]
                      if n.startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda t: t[1])


def reduce_device(plane: dict, lo: float, hi: float) -> dict:
    """One chip's plane inside the window [lo, hi] ns."""
    segs = [(max(s, lo), min(e, hi), n) for s, e, n in flatten(_line(plane, OPS_LINE))
            if min(e, hi) > max(s, lo)]
    busy = [(s, e) for s, e, _ in segs]          # disjoint by construction
    self_ns = collections.Counter()
    for s, e, n in segs:
        self_ns[n] += e - s
    coll = [(s, e) for s, e, n in segs if is_collective(n)]
    coll += clip([(s, s + d) for n, s, d in _line(plane, ASYNC_LINE)
                  if is_collective(n)], lo, hi)
    coll = union(coll)
    compute = union((s, e) for s, e, n in segs if not is_collective(n))
    modules = collections.defaultdict(list)
    for n, s, d in _line(plane, MODULES_LINE):
        if s >= lo and s + d <= hi:               # whole executions only
            modules[module_name(n)].append(d)
    return {
        "plane": plane["name"],
        "busy_ns": length(busy),
        "gaps": subtract([(lo, hi)], union(busy)),
        "op_self_ns": dict(self_ns),
        "collective_ns": length(coll),
        "collective_exposed_ns": length(subtract(coll, compute)),
        "modules": {k: {"count": len(v), "total_ns": float(sum(v)),
                        "durations_ns": v} for k, v in modules.items()},
    }


def label_gaps(gaps, spans):
    """Idle seconds by what the host was doing: each gap goes to the
    ``bench:`` span (other than the window) that overlaps it longest, or
    to ``host:unlabelled``."""
    spans = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    by = collections.Counter()
    for gs, ge in gaps:
        best, best_ov = "host:unlabelled", 0.0
        for n, s, e in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = n, ov
        by[best] += ge - gs
    return by


def reduce(trace: dict) -> dict:
    """The whole trace -> what the per-layer readers and the result line
    use. The window is the ``bench:window`` span; a trace without one is
    reduced over the extent of its device events."""
    spans = host_spans(trace)
    devs = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    devs.sort(key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))
    win = [sp for sp in spans if sp[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        ev = [(s, s + d) for p in devs for n, s, d in _line(p, OPS_LINE)]
        if not ev:
            raise ValueError("trace holds no device op and no window span")
        lo, hi = min(s for s, _ in ev), max(e for _, e in ev)
    devices = [reduce_device(p, lo, hi) for p in devs]
    window_ns = hi - lo
    out = {"window_s": window_ns / 1e9, "n_devices": len(devices),
           "devices": devices, "spans": spans}
    if not devices:
        return out
    out["busy_s"] = sum(d["busy_ns"] for d in devices) / len(devices) / 1e9
    out["idle_share_per_device"] = [1.0 - d["busy_ns"] / window_ns
                                    for d in devices]
    # the breakdown: ops summed over chips then averaged (each chip runs the
    # same program), gaps of the idlest chip
    ops = collections.Counter()
    for d in devices:
        for n, ns in d["op_self_ns"].items():
            ops[n] += ns / len(devices)
    idlest = max(devices, key=lambda d: length(d["gaps"]))
    gaps = label_gaps(idlest["gaps"], spans)
    out["breakdown"] = {
        "device_ops": [[op_label(n), ns / 1e9] for n, ns in ops.most_common(10)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in gaps.most_common(10)],
    }
    return out


def module_stats(reduced: dict, prefix: str):
    """(count, total seconds) of the executions of programs whose name
    starts with ``prefix``, averaged over the chips."""
    count = total = 0.0
    for d in reduced["devices"]:
        for name, m in d["modules"].items():
            if name.startswith(prefix):
                count += m["count"]
                total += m["total_ns"]
    n = max(1, reduced["n_devices"])
    return count / n, total / n / 1e9


def op_seconds(reduced: dict, pred) -> float:
    """Self seconds of the ops whose event name satisfies ``pred``,
    averaged over the chips."""
    tot = 0.0
    for d in reduced["devices"]:
        tot += sum(ns for n, ns in d["op_self_ns"].items() if pred(n))
    return tot / max(1, reduced["n_devices"]) / 1e9
