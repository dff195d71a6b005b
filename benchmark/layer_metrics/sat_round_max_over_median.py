"""The slowest decode-dominated round of the stats window over the median
one: ``round_ms_max`` / ``round_ms_median`` of the engine's ``stats()``
(rounds that admitted fewer prompts than requests were decoding). A round's
device time is constant, so a sound window reads 1.1-2 (a round with a few
admissions is longer by their prefills) and one that lost a second in a
single round reads 3-9; which round, and what the one after it found, is
``slow_rounds`` in the run's ``counters.stats``. An engine without the
counters reads nothing."""
HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "ratio", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "program_counter", "better": "lower"}


def read(run):
    stats = run["counters"].get("stats") or {}
    top, median = stats.get("round_ms_max"), stats.get("round_ms_median")
    if top is None or not median:
        return None
    return top / median
