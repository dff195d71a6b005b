"""From a request's first admission to its first token at the host
(``Request.first_token_t - admit_t``): the prefill dispatch, the decode
quantum the request rides and the round's one fetch. 90th percentile over
the window's finished requests, from ``ServingEngine.stats()``."""
HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "ms", "moves": "ttft_p90_ms", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    return (run["counters"].get("stats") or {}).get("first_token_wait_p90_ms")
