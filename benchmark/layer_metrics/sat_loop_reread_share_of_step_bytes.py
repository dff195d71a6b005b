"""What the loop costs a decode step in bytes: the layers' weights read again
by every pass after the first ((``total_ut_steps`` - 1) x the layers, the
family's ``loop_reread_bytes``) over everything the step must read (the
family's ``decode_step_bytes`` at the run's counters: the weights of every
pass, the head, the live K/V of every plane). An unlooped model of the same
weights would read 0 %; a family that is not looped reads nothing here."""
HEADER = {"layer": "decode step (models/transformer.py decode_step_paged)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    fam = run["family"]
    if not hasattr(fam, "loop_reread_bytes"):
        return None
    need = fam.decode_step_bytes(run["hf"], run["counters"])
    return 100.0 * fam.loop_reread_bytes(run["hf"]) / need if need else None
