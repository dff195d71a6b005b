"""Resident device memory after the window (weights + KV pool); what is
left is room for slots. Program temporaries are NOT included."""
from benchmark.layer_metrics import hbm_in_use_gib as _base

HEADER = {"layer": "scheduler / cache (inference/scheduler.py, kv_cache.py)",
          "unit": "GiB", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}
read = _base.read
