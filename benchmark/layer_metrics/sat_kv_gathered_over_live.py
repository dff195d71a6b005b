"""K/V the decode steps GATHERED over the K/V that was live: a step program
of shape slots x columns gathers slots x columns x block size positions of
every plane whatever the slots hold (``step_shape_rounds`` of the engine's
``stats()``, weighted by rounds), the live K/V is the mean of the rows
sampled after each round. Both sides times the engine's
``kv_bytes_per_token``, which a looped engine reports (192 planes here: a
padded column costs 12 x what it costs Mistral) and which cancels. A paged
read of the live blocks only would read 1; an engine that does not report
the bytes a token keeps reads nothing here."""
HEADER = {"layer": "decode step (models/transformer.py decode_step_paged)",
          "unit": "ratio", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    c = run["counters"]
    stats = c.get("stats") or {}
    per_token = stats.get("kv_bytes_per_token")
    shapes = {k: n for k, n in (stats.get("step_shape_rounds") or {}).items() if n}
    if not per_token or not shapes or not c.get("mean_live_tokens"):
        return None
    block = c["pool"]["k"]["shape"][2]
    gathered = sum(n * int(k.split("x")[0]) * int(k.split("x")[1]) * block
                   for k, n in shapes.items()) / sum(shapes.values())
    return gathered / c["mean_live_tokens"]        # x per_token on both sides
