"""Median time to first token, from when the request was due."""
from benchmark.harness import metrics

HEADER = {"layer": "serve entry (inference/serving.py)", "unit": "ms",
          "moves": "ttft_p90_ms", "jobs": ["serve"], "source": "host_clock",
          "better": "lower"}


def read(run):
    h, c = run["host"], run["counters"]
    if not h.get("ttft_ms"):
        return None
    return metrics.percentile(h["ttft_ms"], 50, c["misses"], h["miss_ms"])
