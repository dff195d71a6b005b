"""Steady-state time of one train step on the host clock: the median over
groups of steps of (time between two groups' returns) / steps per group.
The loop is never blocked inside the window, so this is the device's pace
as the host sees it through the engine's in-flight bound."""
import numpy as np

HEADER = {"layer": "train entry (runtime/engine.py)", "unit": "ms",
          "moves": "train_tokens_per_s_per_chip", "jobs": ["train"],
          "source": "host_clock", "better": "lower"}


def read(run):
    g = run["counters"].get("step_ms_groups")
    return float(np.median(g)) if g else None
