"""What share of the LIVE cache is recurrent state: the state of the live
slots (mean occupancy x the family's ``state_bytes_per_slot``: every Mamba
block's float32 state and convolution tail) over that plus the live K/V rows
of the attention blocks (``kv_bytes_per_token`` x the mean of the live rows
sampled after each round). A model whose cache is K/V alone reads nothing."""
HEADER = {"layer": "scheduler / cache (inference/scheduler.py, kv_cache.py)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    fam, hf, c = run["family"], run["hf"], run["counters"]
    if not hasattr(fam, "state_bytes_per_slot"):
        return None
    state = c["mean_occupancy"] * fam.state_bytes_per_slot(hf)
    kv = fam.kv_bytes_per_token(hf, c["kv_cache_bits"]) * c["mean_live_tokens"]
    return 100.0 * state / (state + kv) if state + kv else None
