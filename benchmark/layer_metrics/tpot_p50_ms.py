"""Median time per output token after the first."""
from benchmark.harness import metrics

HEADER = {"layer": "serve entry (inference/serving.py)", "unit": "ms",
          "moves": "tpot_p90_ms", "jobs": ["serve"], "source": "host_clock",
          "better": "lower"}


def read(run):
    h, c = run["host"], run["counters"]
    if not h.get("tpot_ms"):
        return None
    return metrics.percentile(h["tpot_ms"], 50, c["misses"], h["miss_ms"])
