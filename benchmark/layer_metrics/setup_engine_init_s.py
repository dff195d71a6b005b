"""The two constructors — ``InferenceEngine`` (the parameters initialised,
placed and quantised: ``weights_s``, ``ds:setup.weights``) and
``ServingEngine`` (the cache pool: ``pools_s``, ``ds:setup.pools``; the
scheduler, the backend's price) — less the seconds they spent BUILDING
programs (``init_build_s``: the init program, the pool's), which
``setup_trace_lower_s`` and ``setup_compile_or_load_s`` count:
``engine_init_s - init_build_s`` of ``stats()["setup"]``, so that the three
add up to no more than the set-up they are part of. The host's seconds: the
device runs the init program behind whatever comes next. An engine without
the record reads nothing."""
HEADER = {"layer": "engine set-up (inference/engine.py, serving.py builds, runtime/engine.py)",
          "unit": "s", "moves": "setup_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    setup = (run["counters"].get("stats") or {}).get("setup")
    if not setup:
        return None
    return setup["engine_init_s"] - setup["init_build_s"]
