"""Share of the stats window in which the engine held a request — running,
waiting or in flight: 100 x (1 - ``engine_empty_s`` / ``stats_window_s``)
of the engine's ``stats()``. The WHOLE window (the first request to the
reading after the drain), not the traced seconds. A load reading, like
``gen_late_p95_ms``: a chip idle while the engine is empty waits for a
request, not for the host. An engine without the counter reads nothing."""
HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "%", "moves": "ttft_p90_ms", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    stats = run["counters"].get("stats") or {}
    empty, window = stats.get("engine_empty_s"), stats.get("stats_window_s")
    if empty is None or not window:
        return None
    return 100.0 * (1.0 - empty / window)
