"""The backend's part of the engine's builds: ``backend_compile_duration``
summed over ``setup.programs`` (``compile_or_load_s`` of
``stats()["setup"]``) — compiles in a cold run; in a warm one the persistent
cache's key (the lowered module serialised and hashed) and its read
(``cache_load_s`` of each program is the read alone). ``cache_hits`` /
``programs_built`` says which kind of run it was, in the run's detail. An
engine without the record reads nothing."""
HEADER = {"layer": "engine set-up (inference/engine.py, serving.py builds, runtime/engine.py)",
          "unit": "s", "moves": "setup_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    setup = (run["counters"].get("stats") or {}).get("setup")
    if not setup:
        return None
    return setup["compile_or_load_s"]
