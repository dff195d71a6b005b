"""What the set-up's spans and build record do NOT explain, as a share of
``setup_s``: 100 x (1 - (tracing and lowering + the backend's compile or load
+ the constructors' own seconds) / ``setup_s``), the three as
``setup_trace_lower_s``, ``setup_compile_or_load_s`` and
``setup_engine_init_s`` read them, less ``overlap_s``: a step shape's lowering
that ran beside another's compile is in the first two twice and in the wall
once. What is left: each program's first
execution on the device (a first call dispatches it inside its
``ds:setup.program``; the record's ``wall_s`` holds the host's part), the
warm-up requests' own decode steps, scheduling, the program's imports and the
harness. A share, because what the record does not explain is the next
finding. An engine without the record reads nothing."""
HEADER = {"layer": "engine set-up (inference/engine.py, serving.py builds, runtime/engine.py)",
          "unit": "%", "moves": "setup_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    setup = (run["counters"].get("stats") or {}).get("setup")
    total = (run.get("e2e") or {}).get("setup_s")
    if not setup or not total:
        return None
    known = (setup["trace_lower_s"] + setup["compile_or_load_s"]
             - setup["overlap_s"]
             + setup["engine_init_s"] - setup["init_build_s"])
    return 100.0 * (1.0 - known / total)
