"""Python's part of the engine's builds: seconds JAX spent tracing the
programs and lowering them to MLIR (``trace_lower_s`` of
``stats()["setup"]``: ``jaxpr_trace_duration``, a traced function's inner
ones counted once, plus ``jaxpr_to_mlir_module_duration``, summed over
``setup.programs``). Every process pays it, warm or cold: the persistent
cache keys on the LOWERED module. The decode step's shapes are lowered on a
worker thread while the caller compiles: ``overlap_s`` of the record is the
wall time this and ``setup_compile_or_load_s`` share. An engine without the record
reads nothing."""
HEADER = {"layer": "engine set-up (inference/engine.py, serving.py builds, runtime/engine.py)",
          "unit": "s", "moves": "setup_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    setup = (run["counters"].get("stats") or {}).get("setup")
    if not setup:
        return None
    return setup["trace_lower_s"]
