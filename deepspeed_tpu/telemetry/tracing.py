"""Host spans: the one primitive (:func:`span`), the train step's span
recorder (:class:`StepTracer`) and the windowed jax.profiler capture.

:func:`span` is the only way this package opens a host span. It enters a
``jax.profiler.TraceAnnotation``, so during ANY profiler session — the
benchmark's or an operator's — the span lies on the host plane of the trace,
on the same clock as the device planes, and it hands the elapsed seconds to
its caller. No ring, no export, no switch: with no session active an
annotation costs well under a microsecond. Every program span is named
``ds:<layer>.<phase>``; the whole vocabulary:

  ``ds:serve.round`` and, inside it, once per round in this order:
  ``ds:serve.schedule``, ``ds:serve.housekeeping``,
  ``ds:serve.prefill_dispatch``, ``ds:serve.decode_dispatch``,
  ``ds:serve.fetch``, ``ds:serve.commit`` (``ServingEngine.step`` / ``_round``);
  ``ds:serve.submit`` — the engine's part of ``add_request`` — and
  ``ds:serve.drained`` — a marker of no length at the end of the ``step()``
  that left the engine holding no request: from its end to the next
  ``ds:serve.submit`` the engine is empty, and a chip idle then waits for
  a request, not for the host;
  ``ds:train.dispatch``, ``ds:train.prefetch``, ``ds:train.data_wait``,
  ``ds:train.block`` (``Engine.train_batch`` / ``train_batches``);
  ``ds:request.<phase>`` — ``RequestTracer``'s per-request spans, only
  while request tracing is armed;
  set-up, wherever in an engine's life it falls: ``ds:setup.weights`` — the
  parameters' initialisation or load, placement and quantisation
  (``InferenceEngine``) —, ``ds:setup.pools`` — the serving engine's fresh
  cache pool, at construction and at a recovery —, ``ds:setup.state`` — the
  train engine's state initialisation and sharding —, and
  ``ds:setup.program`` (``kind``, ``shape``) around every BUILD of a program:
  a decode step shape's lowering and its compile, a prompt bucket's, a chunk
  width's, the speculation step's and the train step's first call
  (:class:`BuildLog`).

The build log: :class:`BuildLog` keeps ONE record a program built — Python's
tracing and lowering, the backend's compile or cache load, when and in which
round — from JAX's own compile events, which one process-wide listener
(``_BuildClock``) hands to the ``ds:setup.program`` open on the firing
thread, or, under none, to :func:`build_log`'s record of that function's
name. Nothing is timed twice: a build is timed by JAX, where it happens.

Step tracing: host-side span recorder + windowed jax.profiler capture.

Reference analogue: ``deepspeed/utils/timer.py`` wall-clock timers plus the
``flops_profiler``'s latency printouts — all eager, all per step. Under async
dispatch a per-step host timestamp measures DISPATCH, not execution
(utils/timer.py docs), so the tracer records exactly the phases the HOST owns
in ``engine.train_batches``:

  * ``dispatch``  — queueing the jitted step (Python + jax dispatch overhead)
  * ``prefetch``  — the sharding-aware device_put of the next batch
                    (PrefetchLoader top-up)
  * ``data_wait`` — blocking on the wrapped iterator for the next batch
  * ``block``     — backpressure: waiting on the oldest in-flight step's
                    output once the dispatch window is full (the honest
                    "device is the bottleneck" signal)

Spans are appended to a bounded ring and exported as Chrome-trace JSON
(``chrome://tracing`` / Perfetto "traceEvents" format). Device-side timing
comes from the complementary windowed ``jax.profiler.start_trace`` capture
(:meth:`StepTracer.maybe_profile`), configured via ``telemetry.trace``.

Per-span cost is one :func:`span` and a deque append — safe to leave on in
the steady-state loop.
"""

import collections
import contextlib
import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from deepspeed_tpu.utils.logging import logger


class span:
    """``with span("ds:serve.fetch") as sp: ...`` — a TraceAnnotation on the
    profiler's clock around the block; afterwards ``sp.seconds`` is the
    elapsed host time and ``sp.t0`` the ``perf_counter`` reading at entry.
    ``**args`` (and :meth:`note`, for what is known only at the end) become
    the annotation's arguments; they are encoded only while a profiler
    session is active."""
    __slots__ = ("t0", "seconds", "_ann")

    def __init__(self, name: str, **args: Any):
        self._ann = TraceAnnotation(name, **args)
        self.t0 = self.seconds = 0.0

    def note(self, **args: Any) -> None:
        self._ann.set_metadata(**args)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        return False


_JIT_OF = re.compile(r"^\w+\((.*)\)$")   # "jit(step)": tracing's "step"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class _BuildClock:
    """The process's ONE ``jax.monitoring`` listener for builds, hung up by
    the first engine and shared by all of them (as the serving engine's
    ``_GcClock`` is): JAX times a program's tracing, its lowering and the
    backend's compile or cache load itself and says so on the thread that did
    it; this hands each to the innermost ``ds:setup.program`` open on THAT
    thread (``BuildLog.program``) or, under none, to the process's own log
    (``unspanned``, :func:`build_log`) by the function's name. ``seconds`` is
    the running total of all of it: a serving round reads it before and
    after itself, its ``build_ms``.

    Tracing reports every jitted function a program calls, the inner ones
    first and inside the outer one's time, and so does a lowering of the
    helpers its rules trace, so a thread's traces wait — an inner one
    dropped when the one around it arrives — until the lowering that
    follows them (or a span's edge) says whose they were. The
    persistent cache reports a hit, and its read, before the backend event
    of the program it was for: they wait for that likewise."""

    def __init__(self):
        self.seconds = 0.0
        self.unspanned: Optional["BuildLog"] = None
        self._lock = threading.RLock()
        self._tls = threading.local()

    def install(self) -> "_BuildClock":
        with self._lock:
            if self.unspanned is None:
                from jax import monitoring
                self.unspanned = BuildLog()
                monitoring.register_event_time_span_listener(self._on_span)
                monitoring.register_event_duration_secs_listener(
                    self._on_secs)
                monitoring.register_event_listener(self._on_event)
        return self

    def thread(self):
        """The calling thread's part: ``open``, its open program spans;
        ``traces``, (start, end, name) of what it traced and has not yet
        lowered; ``cache``, [hits, seconds read] the persistent cache
        reported since its last backend event; ``round``, the index of the
        ``ds:serve.round`` open on it (``ServingEngine.step``), else None."""
        tls = self._tls
        if not hasattr(tls, "open"):
            tls.open, tls.traces, tls.cache, tls.round = [], [], [0, 0.0], None
        return tls

    def _on_event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT:
            self.thread().cache[0] += 1

    def _on_secs(self, event: str, secs: float, **kw) -> None:
        if event == _CACHE_LOAD:
            self.thread().cache[1] += secs

    def _on_span(self, event: str, start: float, end: float, **kw) -> None:
        if event not in (_TRACE, _LOWER, _BACKEND):
            return
        tls = self.thread()
        name = _JIT_OF.sub(r"\1", str(kw.get("fun_name", "?")))
        if event != _BACKEND:
            # what was traced inside this one is in its time: a jitted
            # function a traced one calls, a helper a lowering rule traces
            while tls.traces and tls.traces[-1][0] >= start:
                tls.traces.pop()
        if event == _TRACE:
            tls.traces.append((start, end, name))
        elif event == _LOWER:
            self.settle(tls)
            self._add(tls, name, lower_s=end - start, builds=1)
        else:
            (hits, read_s), tls.cache = tls.cache, [0, 0.0]
            self._add(tls, name, compile_or_load_s=end - start,
                      cache_hit=hits, cache_load_s=read_s)

    def settle(self, tls) -> None:
        """The thread's waiting traces go to whatever is being built."""
        traces, tls.traces = tls.traces, []
        for start, end, name in traces:
            self._add(tls, name, trace_s=end - start)

    def _add(self, tls, name: str, **parts) -> None:
        rec = tls.open[-1].record if tls.open else \
            self.unspanned.record("other", name, tls.round)
        with self._lock:
            if "lower_s" in parts or "name" not in rec:
                rec["name"] = name
            for key, value in parts.items():
                rec[key] += value
            self.seconds += sum(parts.get(k, 0.0) for k in (
                "trace_s", "lower_s", "compile_or_load_s"))


_BUILD_CLOCK = _BuildClock()


class _ProgramSpan(span):
    """``ds:setup.program`` around one build, or one part of one, of the
    program ``record`` is for."""
    __slots__ = ("record",)

    def __init__(self, record: Dict[str, Any]):
        super().__init__("ds:setup.program", kind=record["kind"],
                         shape=str(record["shape"]))
        self.record = record

    def __enter__(self) -> "_ProgramSpan":
        tls = _BUILD_CLOCK.thread()
        _BUILD_CLOCK.settle(tls)        # traced before the span: not its
        tls.open.append(self)
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        tls = _BUILD_CLOCK.thread()
        _BUILD_CLOCK.settle(tls)        # traced inside it, never lowered
        tls.open.remove(self)
        with _BUILD_CLOCK._lock:
            self.record["wall_s"] += self.seconds
        return False


class BuildLog:
    """What an engine built, ONE record a program: ``{kind, shape, name,
    trace_s, lower_s, compile_or_load_s, cache_load_s, cache_hit, builds,
    wall_s, built_at_s, round}`` — ``kind`` ``step`` | ``prefill`` | ``span``
    | ``spec_step`` | ``train_step`` | ``other`` and ``shape`` as the
    ``ds:setup.program`` span says them; ``name``, the jitted function's;
    ``trace_s`` and ``lower_s``, Python's part, paid by every process;
    ``compile_or_load_s``, the backend's — a compile when cold and, when the
    persistent cache held the program (``cache_hit``, a count), the key and
    the read (``cache_load_s`` of it is the read); ``builds``, lowerings: a
    program built again (a backend swap, a second engine over one log) is
    folded into its record; ``wall_s``, the seconds of its spans, which also
    hold whatever ran between JAX's events — a first call's execution is
    dispatched there; ``built_at_s``, since ``t0``, and ``round``, the index
    of the ``ds:serve.round`` it fell in (else None), of the first build. A
    shape lowered on one thread and compiled on another is one record: both
    spans name it. Bounded by construction: step shapes, buckets, chunk
    widths and a handful. The process's own log (:func:`build_log`) also
    takes what was built under no span, ``kind`` ``other`` and for its
    ``shape`` the function's name."""

    def __init__(self, t0: Optional[float] = None):
        self.t0 = time.perf_counter() if t0 is None else t0
        self._records: Dict[tuple, Dict[str, Any]] = {}

    def record(self, kind: str, shape, round: Optional[int] = None):
        """The record of the program ``(kind, shape)``, made at its first
        build; ``round``: the calling thread's, unless given."""
        key = (kind, str(shape))
        with _BUILD_CLOCK._lock:
            rec = self._records.get(key)
            if rec is None:
                if round is None:
                    round = _BUILD_CLOCK.thread().round
                rec = self._records[key] = {
                    "kind": kind, "shape": key[1],
                    "trace_s": 0.0, "lower_s": 0.0, "compile_or_load_s": 0.0,
                    "cache_load_s": 0.0, "cache_hit": 0, "builds": 0,
                    "wall_s": 0.0,
                    "built_at_s": time.perf_counter() - self.t0,
                    "round": round}
        return rec

    def program(self, kind: str, shape, round: Optional[int] = None) -> span:
        """The span to build the program ``(kind, shape)``, or a part of
        it, under (the listener is hung up by the first)."""
        _BUILD_CLOCK.install()
        return _ProgramSpan(self.record(kind, shape, round))

    def records(self, since: Optional[float] = None) -> List[Dict[str, Any]]:
        """Copies of the records, in the order built; ``since``: only those
        first built at or after that ``perf_counter`` reading, their
        ``built_at_s`` counted from it."""
        with _BUILD_CLOCK._lock:
            recs = [dict(r) for r in self._records.values()]
        if since is not None:
            for r in recs:
                r["built_at_s"] -= since - self.t0
            recs = [r for r in recs if r["built_at_s"] >= 0.0]
        return sorted(recs, key=lambda r: r["built_at_s"])


def build_log() -> BuildLog:
    """The process's own build log: the train engine's programs, and every
    program built under no ``ds:setup.program`` at all."""
    return _BUILD_CLOCK.install().unspanned


def build_clock() -> _BuildClock:
    """The process's build listener, hung up: ``seconds``, and ``thread()``
    for the round a thread is in."""
    return _BUILD_CLOCK.install()


class StepTracer:
    def __init__(self, trace_cfg=None, max_events: int = 20000):
        self.events: "collections.deque[Dict[str, Any]]" = collections.deque(
            maxlen=max(16, int(max_events)))
        self._window_s: Dict[str, float] = {}
        self._window_n: Dict[str, int] = {}
        self._t0 = time.perf_counter()
        self._trace_cfg = trace_cfg
        self._pid = os.getpid()
        self._profiling = False
        self._profile_done = False
        self._first_step = None   # first step this run observed
        self._stop_at = None      # dynamic stop step of an open capture

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "step"):
        """``ds:train.<name>`` through :func:`span`, then into the ring and
        the window sums under the bare ``name``."""
        sp = span(f"ds:train.{name}")
        try:
            with sp:
                yield
        finally:
            self.events.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": (sp.t0 - self._t0) * 1e6, "dur": sp.seconds * 1e6,
                "pid": self._pid, "tid": 0,
            })
            self._window_s[name] = self._window_s.get(name, 0.0) + sp.seconds
            self._window_n[name] = self._window_n.get(name, 0) + 1

    def instant(self, name: str, args: Optional[Dict[str, Any]] = None):
        """Point event (anomalies, phase switches) in the same timeline."""
        ev = {"name": name, "cat": "event", "ph": "i", "s": "g",
              "ts": (time.perf_counter() - self._t0) * 1e6,
              "pid": self._pid, "tid": 0}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def drain_window(self) -> Dict[str, float]:
        """Per-window phase totals (``<phase>_ms`` / ``<phase>_count``),
        resetting the window. Pure host work — called from the engine's
        boundary drain."""
        out: Dict[str, float] = {}
        for name, sec in self._window_s.items():
            out[f"{name}_ms"] = sec * 1000.0
            out[f"{name}_count"] = self._window_n.get(name, 0)
        self._window_s.clear()
        self._window_n.clear()
        return out

    def export_chrome_trace(self, path: str) -> str:
        """Write the span ring as Chrome-trace JSON ({"traceEvents": [...]})
        loadable by chrome://tracing and Perfetto."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": list(self.events),
                       "displayTimeUnit": "ms"}, f)
        return path

    # -- windowed device-side profiler capture ---------------------------
    def maybe_profile(self, step: int) -> None:
        """Drive the configured ``jax.profiler`` capture window: start
        inside [start_step, start_step+num_steps), stop once past the end.
        One window per run; failures disable the capture rather than the
        training. The start is bounded above so a job resumed from a
        checkpoint PAST the window doesn't begin a mis-placed capture; an
        ``atexit`` hook finalizes a capture still open when the process
        exits before the stop step (the profile files are written at stop)."""
        cfg = self._trace_cfg
        if cfg is None or not getattr(cfg, "enabled", False):
            return
        end = cfg.start_step + cfg.num_steps
        if self._first_step is None:
            self._first_step = step
        if not self._profiling and not self._profile_done:
            if step >= end and self._first_step >= end:
                # the RUN began past the window (checkpoint resume): a
                # capture here would be mis-placed. A fused K-step stride
                # that jumps over the window mid-run is different — the
                # branch below starts a shifted capture instead of losing it
                self._profile_done = True
                return
            if step >= cfg.start_step:
                try:
                    import atexit
                    import jax
                    os.makedirs(cfg.output_dir, exist_ok=True)
                    jax.profiler.start_trace(cfg.output_dir)
                    self._profiling = True
                    self._stop_at = step + cfg.num_steps
                    atexit.register(self.stop_profile)  # idempotent
                    logger.info(f"telemetry: jax.profiler trace started at "
                                f"step {step} -> {cfg.output_dir}")
                except Exception as e:  # noqa: BLE001 - best-effort
                    logger.warning(f"telemetry: profiler trace failed to "
                                   f"start ({e!r}); disabling capture")
                    self._profile_done = True
        elif self._profiling and step >= (self._stop_at or end):
            self.stop_profile()

    def stop_profile(self) -> None:
        if not self._profiling:
            return
        try:
            import jax
            jax.profiler.stop_trace()
            logger.info("telemetry: jax.profiler trace stopped")
        except Exception as e:  # noqa: BLE001
            logger.warning(f"telemetry: profiler trace failed to stop ({e!r})")
        finally:
            self._profiling = False
            self._profile_done = True

    def close(self) -> None:
        self.stop_profile()
