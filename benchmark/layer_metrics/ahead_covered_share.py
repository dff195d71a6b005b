"""Share of the look-ahead rounds in which the chip still had work when the
host came to issue the next round's first step: 100 x
``ahead_covered_rounds`` / (``ahead_covered_rounds`` + ``ahead_dry_rounds``)
of the engine's ``stats()``, over the whole stats window. The engine leaves a
round's last steps on the device's queue and asks the newest array on that
queue ``is_ready()`` just before it issues the next round's first step: not
ready is covered, ready means the queue had run dry and the chip waited for
the host. An engine without the probe reads nothing."""
HEADER = {"layer": "serve entry / scheduler (inference/serving.py)",
          "unit": "%", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "program_counter", "better": "higher"}


def read(run):
    stats = run["counters"].get("stats") or {}
    covered, dry = stats.get("ahead_covered_rounds"), stats.get("ahead_dry_rounds")
    if covered is None or dry is None or not covered + dry:
        return None
    return 100.0 * covered / (covered + dry)
