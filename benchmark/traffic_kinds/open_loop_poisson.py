"""open-loop-poisson: independent users; requests are due on a schedule
whether or not earlier ones have finished.

Parameters: ``rate_per_s``; ``prompt`` and ``output`` = ``{"median",
"sigma", "min", "max"}`` (clipped lognormals, see loadgen.lognormal_lengths);
``drain_s`` (how long after the window the last requests may take; what has
not finished then is a miss).

The number of requests is fixed at round(rate x seconds) and their due
times are that many uniform draws over the window, sorted — a Poisson
process conditioned on its count: the gaps are as irregular as Poisson
arrivals, and every seed offers the same load. No shared prefixes: every
prompt is fresh uniform random ids."""
import numpy as np

from benchmark.harness import loadgen

JOB = "serve"


def generate(seed: int, params: dict, ctx: dict) -> list:
    rng = np.random.default_rng([seed, 0x63686174])
    n = max(1, int(round(params["rate_per_s"] * ctx["seconds"])))
    due = np.sort(rng.random(n) * ctx["seconds"])
    p, o = params["prompt"], params["output"]
    plen = loadgen.lognormal_lengths(rng, n, p["median"], p["sigma"], p["min"], p["max"])
    olen = loadgen.lognormal_lengths(rng, n, o["median"], o["sigma"], o["min"], o["max"])
    return [{"due_s": float(t),
             "prompt": loadgen.random_prompt(rng, pl, ctx["vocab_size"]),
             "max_new_tokens": int(ol)}
            for t, pl, ol in zip(due, plen, olen)]
