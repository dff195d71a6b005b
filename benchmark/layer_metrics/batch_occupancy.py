"""Mean share of the slots that hold a running request, sampled after
every round of the whole window."""
HEADER = {"layer": "scheduler / cache (inference/scheduler.py, kv_cache.py)",
          "unit": "%", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    c = run["counters"]
    if not c.get("rounds"):
        return None
    return 100.0 * c["mean_occupancy"] / c["max_seqs"]
