"""Windowed one-step ``jax.profiler`` capture -> normalized trace artifact.

The raw profiler dump is a TensorBoard run directory
(``plugins/profile/<ts>/``) containing an xplane protobuf plus a
Chrome-trace JSON. This harness drives a capture window around N engine
steps, locates the trace JSON, pairs it with the compiled step program's
text (the scope/census join input), and writes ONE self-contained gzipped
artifact next to the bench results — with rotation so repeated bench runs
can't grow the directory unbounded.

The capture perturbs nothing: profiling is observation-only (the
numerics-parity test in tests/unit/test_trace_analysis.py pins train
bits with capture on vs off), and the window is placed AFTER a warmup
step so compilation never pollutes the timeline.
"""

import contextlib
import dataclasses
import glob
import gzip
import json
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

from deepspeed_tpu.profiling import trace_analysis
from deepspeed_tpu.utils.logging import logger

# artifact rotation defaults: a one-step trace of the bench model is a few
# hundred KiB gzipped; 16 artifacts / 256 MiB is ample headroom while still
# bounding a long-lived bench dir
MAX_ARTIFACTS = 16
MAX_TOTAL_BYTES = 256 << 20


@dataclasses.dataclass
class CaptureResult:
    """One captured window, ready for attribution."""
    trace: Dict[str, Any]              # Chrome-trace dict (device rows kept)
    artifact_path: str = ""            # normalized .json.gz in the out dir
    hlo_text: str = ""                 # compiled step program (scope join)
    cost: Optional[Dict[str, Any]] = None   # static_step_cost of the step
    steps: int = 1
    wall_s: float = 0.0

    def attribution(self) -> trace_analysis.Attribution:
        scope_map = (trace_analysis.parse_hlo_scopes(self.hlo_text)
                     if self.hlo_text else None)
        return trace_analysis.attribute(self.trace, scope_map,
                                        steps=self.steps)


@contextlib.contextmanager
def trace_window(log_dir: str):
    """Start/stop a jax.profiler capture; yields the log dir. Failures to
    START disable the capture (yielding None) rather than the caller."""
    import jax
    started = False
    try:
        os.makedirs(log_dir, exist_ok=True)
        jax.profiler.start_trace(log_dir)
        started = True
    except Exception as e:  # noqa: BLE001 - capture must never kill a run
        logger.warning(f"capture: profiler failed to start ({e!r})")
    try:
        yield log_dir if started else None
    finally:
        if started:
            jax.profiler.stop_trace()


def find_trace_json(log_dir: str) -> Optional[str]:
    """Newest ``*.trace.json.gz`` under the profiler run directory."""
    pats = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.trace.json.gz")),
        key=os.path.getmtime)
    # the perfetto variant duplicates the same events; prefer the plain one
    plain = [p for p in pats if not p.endswith("perfetto_trace.json.gz")]
    return (plain or pats)[-1] if pats else None


def capture_traced_step(engine, batch, out_dir: str, *, tag: str = "step",
                        steps: int = 1, keep_raw: bool = False
                        ) -> Optional[CaptureResult]:
    """Capture `steps` engine steps under the profiler and write the
    normalized artifact ``{out_dir}/trace_{tag}.json.gz``.

    The engine must be on the plain jitted path (the layer-streamed
    infinity executor compiles per-layer programs and has no single step
    to join against). Returns None when the platform yields no usable
    trace — callers degrade, they don't fail.
    """
    import jax

    def sync():
        jax.block_until_ready(engine.state)

    engine.train_batch(batch)    # warmup: compile outside the window
    sync()
    raw_dir = tempfile.mkdtemp(prefix="dstpu-trace-")
    try:
        t0 = time.perf_counter()
        with trace_window(raw_dir) as ld:
            if ld is None:
                return None
            for _ in range(steps):
                engine.train_batch(batch)
            sync()
        wall = time.perf_counter() - t0
        path = find_trace_json(raw_dir)
        if path is None:
            logger.warning("capture: profiler produced no trace.json.gz "
                           "(platform without host-trace export)")
            return None
        trace = trace_analysis.load_trace(path)
    finally:
        if not keep_raw:
            shutil.rmtree(raw_dir, ignore_errors=True)
    hlo_text, cost = step_program_text(engine, batch)
    res = CaptureResult(trace=trace, hlo_text=hlo_text, cost=cost,
                        steps=steps, wall_s=wall)
    if out_dir:
        res.artifact_path = write_artifact(res, out_dir, tag)
    return res


def step_program_text(engine, batch) -> tuple:
    """(compiled HLO text, static per-step cost) of the engine's own train
    step — the same artifacts graft-lint and the telemetry join read, so
    the trace join, census join and roofline all describe ONE program.

    One AOT lower+compile on abstract shapes (no execution); the dense
    jitted path is required — host-driven executors (1-bit/NVMe/infinity)
    have no single step program to join a trace against.
    """
    try:
        import jax
        from deepspeed_tpu.analysis.hlo_parse import (collective_census,
                                                      parse_overlap)
        from deepspeed_tpu.analysis.program import abstractify
        if engine._train_step is None:
            raise ValueError("capture: engine has no dense jitted step")
        batch_abs = abstractify(engine._device_batch(batch))
        state_abs = abstractify(engine.state)
        rng_abs = jax.ShapeDtypeStruct(engine._rng.shape, engine._rng.dtype)
        with engine.mesh:
            compiled = engine._train_step.lower(
                state_abs, batch_abs, rng_abs).compile()
        text = compiled.as_text()
        census = collective_census(parse_overlap(text))
        cost: Dict[str, Any] = {
            "census": {k: dict(v) for k, v in census.items()}}
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            if ca:
                cost["flops_per_step"] = int(ca.get("flops", 0))
                cost["bytes_accessed_per_step"] = int(
                    ca.get("bytes accessed", 0))
        except Exception:  # noqa: BLE001 - cost model is backend-dependent
            pass
        cost["comm_bytes_per_step"] = sum(
            c["bytes"] for c in census.values())
        return text, cost
    except Exception as e:  # noqa: BLE001 - join degrades to op heuristics
        logger.warning(f"capture: step program text unavailable ({e!r}); "
                       "attribution falls back to op-kind heuristics")
        return "", None


def write_artifact(res: CaptureResult, out_dir: str, tag: str) -> str:
    """Write the normalized artifact (device events + meta, gzipped JSON)
    and rotate older artifacts past the size/count caps."""
    os.makedirs(out_dir, exist_ok=True)
    events = trace_analysis.device_events(res.trace)
    # metadata rows keep the artifact loadable by chrome://tracing
    meta_rows = [e for e in res.trace.get("traceEvents", [])
                 if e.get("ph") == "M"]
    payload = {
        "displayTimeUnit": "ms",
        "traceEvents": meta_rows + events,
        "metadata": {
            "tool": "deepspeed_tpu.profiling.capture",
            "steps": res.steps,
            "wall_s": round(res.wall_s, 4),
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        },
    }
    path = os.path.join(out_dir, f"trace_{tag}.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump(payload, f)
    if res.hlo_text:
        hlo_path = os.path.join(out_dir, f"trace_{tag}.hlo.txt.gz")
        with gzip.open(hlo_path, "wt") as f:
            f.write(res.hlo_text)
    rotate_artifacts(out_dir)
    return path


def rotate_artifacts(out_dir: str, max_files: int = MAX_ARTIFACTS,
                     max_total_bytes: int = MAX_TOTAL_BYTES) -> List[str]:
    """Delete the oldest capture artifacts past the count/total-size caps.

    One capture = one tag = a ``trace_<tag>.json.gz`` + ``.hlo.txt.gz``
    PAIR: rotation counts and removes whole pairs (deleting just the trace
    half would orphan an hlo file the doctor's auto-guess can never use).
    Returns the paths removed; newest captures always survive."""
    groups: Dict[str, List[str]] = {}
    for p in glob.glob(os.path.join(out_dir, "trace_*")):
        tag = os.path.basename(p).split(".", 1)[0]
        groups.setdefault(tag, []).append(p)
    ordered = sorted(groups.values(),
                     key=lambda ps: max(os.path.getmtime(p) for p in ps),
                     reverse=True)
    removed = []
    total = 0
    kept = 0
    for ps in ordered:
        sz = sum(os.path.getsize(p) for p in ps)
        if kept >= max_files or total + sz > max_total_bytes:
            for p in ps:
                try:
                    os.remove(p)
                    removed.append(p)
                except OSError:
                    pass
        else:
            kept += 1
            total += sz
    return removed
