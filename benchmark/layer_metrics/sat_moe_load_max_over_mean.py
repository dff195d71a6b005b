"""Routing imbalance while the slots are kept full: the fullest expert's
assignments over the mean expert's, per layer, mean over layers and rounds
(``moe_load_max_over_mean`` of the engine's ``stats()``: counted over active
slots and real prompt tokens). 1 is a perfectly even router; the grouped
matmul's work does not depend on it, the experts a step touches do."""
HEADER = {"layer": "expert layer (moe/sharded_moe.py)", "unit": "ratio",
          "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    return (run["counters"].get("stats") or {}).get("moe_load_max_over_mean")
