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
  while request tracing is armed.

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
import time
from typing import Any, Dict, Optional

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
