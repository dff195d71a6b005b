"""What share of the LIVE cache is window rings: the rings of the live slots
(mean occupancy x ``ring_bytes_per_slot`` of the engine's ``stats()``: fixed
whatever the context) over that plus the live rows of the full planes
(``kv_bytes_per_token`` x the mean of the live rows sampled after each
round). An engine without window blocks reads nothing."""
HEADER = {"layer": "scheduler / cache (inference/scheduler.py, kv_cache.py)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    c = run["counters"]
    stats = c.get("stats") or {}
    if not stats.get("ring_bytes_per_slot"):
        return None
    rings = c["mean_occupancy"] * stats["ring_bytes_per_slot"]
    kv = stats["kv_bytes_per_token"] * c["mean_live_tokens"]
    return 100.0 * rings / (rings + kv) if rings + kv else None
