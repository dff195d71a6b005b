"""The passes an exit policy at the gate's distribution would run, of the
``total_ut_steps`` every token runs today: the mean over every sampled
position of sum_t (t + 1) p_t (``exit_step_expected`` of a looped engine's
``stats()``, from the counter the exit gate leaves each step and prefill).
1 - this / ut_steps is the share of passes adaptive depth could skip on the
traffic as served, with the weights as seeded. An engine without the counter
reads nothing here."""
HEADER = {"layer": "decode step (models/transformer.py decode_step_paged)",
          "unit": "passes", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    return (run["counters"].get("stats") or {}).get("exit_step_expected")
