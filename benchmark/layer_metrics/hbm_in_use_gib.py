"""Resident device memory after the window on the fullest chip
(``memory_stats()["bytes_in_use"]``): parameters, optimizer state and what
else lives between steps. Program temporaries are NOT included (PR 21)."""
HEADER = {"layer": "sharding plan (parallel/, runtime/zero.py)", "unit": "GiB",
          "moves": "train_tokens_per_s_per_chip", "jobs": ["train"],
          "source": "program_counter", "better": "lower"}


def read(run):
    b = run["counters"].get("bytes_in_use")
    return b / 2**30 if b else None
