"""Of the (token, expert) assignments the router made, the share that landed
on the experts THIS CHIP HOLDS (``moe_assignments_held`` over
``moe_assignments_asked`` of the engine's ``stats()``, counted over active
slots and real prompt tokens): what ties a cell cut to one chip's share of a
deployment's experts to that deployment — held / router width under an even
router (128 of 512: 25 %). An engine that holds every expert reports no such
counters and reads nothing."""
HEADER = {"layer": "expert layer (moe/sharded_moe.py)", "unit": "%",
          "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "higher"}


def read(run):
    stats = run["counters"].get("stats") or {}
    asked = stats.get("moe_assignments_asked")
    return 100.0 * stats["moe_assignments_held"] / asked if asked else None
