"""Share of the device's busy time that the prefill programs take while
the slots are kept full (the dropless expert layer computes 4x the needed
expert rows in prefill: this is what that costs the offline job)."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "prefill (models/transformer.py prefill_paged)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t = run["trace"]
    if not t or not t.get("busy_s"):
        return None
    n, s = trace_reduce.module_stats(t, "jit_prefill")
    return 100.0 * s / t["busy_s"] if n else None
