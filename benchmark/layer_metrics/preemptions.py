"""Requests the scheduler evicted and re-prefilled (``Request.preemptions``
summed). With a full-residency pool this stays 0; the reader returns
count + 1 so the metric is never 0 (1 = none)."""
HEADER = {"layer": "scheduler / cache (inference/scheduler.py, kv_cache.py)",
          "unit": "count", "moves": "ttft_p90_ms", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    return run["counters"]["preemptions"] + 1
