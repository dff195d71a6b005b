"""Time a request waited for a slot: first admission by the scheduler minus
``add_request`` (``Request.admit_t - submit_t``), 90th percentile over the
window's finished requests, from ``ServingEngine.stats()``. With
``gen_late_p95_ms`` before it and ``first_token_wait_p90_ms`` after it, it
splits TTFT into its three stretches."""
HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "ms", "moves": "ttft_p90_ms", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    return (run["counters"].get("stats") or {}).get("queue_wait_p90_ms")
