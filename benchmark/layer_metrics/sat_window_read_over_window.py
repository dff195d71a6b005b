"""Rows of a window plane a decode step READS per live slot over the rows
inside that slot's band: ``window_rows_read`` over ``window_rows_in_window``
of the engine's ``stats()`` — a step reads a slot's whole ring, ``window``
rows whatever the context, and min(context, window) of them are visible. 1 is
the window exactly; a read that followed the context (the whole table gathered
and masked) would be context / window. An engine without window blocks
reports no such counters and reads nothing."""
HEADER = {"layer": "window attention (models/hybrid.py, ops/flash_attention.py)",
          "unit": "ratio", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    stats = run["counters"].get("stats") or {}
    inside = stats.get("window_rows_in_window")
    return stats["window_rows_read"] / inside if inside else None
