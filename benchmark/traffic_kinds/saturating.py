"""saturating: an offline batch job — every request is due at t = 0 and
there are enough of them to outlast the window, so the slots stay full.

Parameters: ``requests`` (how many are queued; the run FAILS if they run out
before the window ends), ``prompt`` and ``output`` as in open-loop-poisson."""
import numpy as np

from benchmark.harness import loadgen

JOB = "serve"


def generate(seed: int, params: dict, ctx: dict) -> list:
    rng = np.random.default_rng([seed, 0x62617463])
    n = int(params["requests"])
    p, o = params["prompt"], params["output"]
    plen = loadgen.lognormal_lengths(rng, n, p["median"], p["sigma"], p["min"], p["max"])
    olen = loadgen.lognormal_lengths(rng, n, o["median"], o["sigma"], o["min"], o["max"])
    return [{"due_s": 0.0,
             "prompt": loadgen.random_prompt(rng, pl, ctx["vocab_size"]),
             "max_new_tokens": int(ol)}
            for pl, ol in zip(plen, olen)]
