"""Device time of the prefill programs (``jit_prefill``, one per prompt
bucket) per thousand PADDED prompt tokens in the traced stretch. The
padded token count of an execution is read from its event's own shape
text when present; otherwise the mean padded prompt of the mix is used."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "prefill (models/transformer.py prefill_paged)",
          "unit": "ms", "moves": "ttft_p90_ms", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t = run["trace"]
    if not t or not t.get("devices"):
        return None
    n, s = trace_reduce.module_stats(t, "jit_prefill")
    padded = run["counters"].get("mean_padded_prompt")
    if not n or not padded:
        return None
    return 1e3 * s / (n * padded / 1e3)
