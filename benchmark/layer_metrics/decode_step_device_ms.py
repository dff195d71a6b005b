"""Device time of one decode step: executions of the quantum step program
(``jit_step``, one token for every slot) in the traced stretch, mean."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "decode step (models/transformer.py decode_step_paged)",
          "unit": "ms", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t = run["trace"]
    if not t or not t.get("devices"):
        return None
    n, s = trace_reduce.module_stats(t, "jit_step")
    return 1e3 * s / n if n else None
