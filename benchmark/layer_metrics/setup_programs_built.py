"""Programs the engine built before the window: lowerings, one a program and
shape — the decode step's shapes, a prefill program a prompt bucket the
warm-up met, and the small ones nobody named (the pool's init, a first
token's scatter, the sampler's key): ``programs_built`` less
``built_after_first_reset`` of ``stats()["setup"]``
(``ServingEngine._setup``; the record is ``telemetry.tracing.BuildLog``'s,
fed by JAX's own compile events). Each costs a tracing and a lowering in every
process, warm or cold. Which they were, and what each cost, is
``counters.stats.setup.programs`` in the run's detail; ``cache_hits`` there
equals this count when every program was warm. An engine without the record
(the parent commit) reads nothing."""
HEADER = {"layer": "engine set-up (inference/engine.py, serving.py builds, runtime/engine.py)",
          "unit": "count", "moves": "setup_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    setup = (run["counters"].get("stats") or {}).get("setup")
    if not setup:
        return None
    return setup["programs_built"] - setup["built_after_first_reset"]
