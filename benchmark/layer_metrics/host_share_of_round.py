"""Share of a serving round the host spends on its own work — schedule +
housekeeping + prefill dispatch + decode dispatch + commit over the round,
from ``phase_decomposition()`` (the engine's ring of the last 256 rounds;
the rest of a round is the token fetch, i.e. waiting for the device)."""
HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "%", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "program_span", "better": "lower"}


def read(run):
    p = run["counters"].get("phases") or {}
    if not p.get("serve_round_ms"):
        return None
    host = sum(p[k] for k in ("serve_schedule_ms", "serve_housekeeping_ms",
                              "serve_prefill_dispatch_ms",
                              "serve_decode_dispatch_ms", "serve_commit_ms"))
    return 100.0 * host / p["serve_round_ms"]
