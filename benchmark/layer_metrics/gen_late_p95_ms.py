"""How late the benchmark's own generator handed requests to the engine
(time of ``add_request`` minus time due), 95th percentile. A health
reading: a late generator is not a fast server. It shares the server's
thread, so a long round makes it late — and that wait is inside TTFT."""
from benchmark.harness import metrics

HEADER = {"layer": "load generator (benchmark/harness/serve_job.py)",
          "unit": "ms", "moves": "ttft_p90_ms", "jobs": ["serve"],
          "source": "host_clock", "better": "lower"}


def read(run):
    late = run["host"].get("late_ms")
    return metrics.percentile(late, 95) if late else None
