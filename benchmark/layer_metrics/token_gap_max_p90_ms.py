"""A request's longest interval between two deliveries of tokens
(``Request.max_gap_ms``: one round plus whatever was admitted in between),
90th percentile over the window's finished requests, from
``ServingEngine.stats()``. ``tpot`` averages a request's gaps; this is the
stall a streaming client sees."""
HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "ms", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    return (run["counters"].get("stats") or {}).get("token_gap_max_p90_ms")
