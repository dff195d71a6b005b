"""Distinct experts a decode step's active slots read, mean over layers and
steps (``moe_experts_touched_per_step`` of the engine's ``stats()``): what
the step's weight traffic is proportional to, and what the family's
``decode_step_bytes`` charges the step for."""
HEADER = {"layer": "expert layer (moe/sharded_moe.py)", "unit": "count",
          "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    return (run["counters"].get("stats") or {}).get("moe_experts_touched_per_step")
