#!/usr/bin/env python3
"""How far will a saturating cell's tokens/s follow the seed? Ask BEFORE the chip.

    python benchmark/tools/saturating_spread.py <traffic> --slots 128 \
        --step-ms 13.2 --row-ns 16.2 --prefill-us 21.7 --program-ms 1.5 [--seeds 120]

A saturating window is a deterministic function of the lengths its seed draws
(`traffic_kinds/saturating.py`): this replays the serving round on the host —
FIFO admission into free slots, a round's prompts packed into shared prefill
rows as `ServingEngine._pack_prefills` packs them (first fit, longest first,
`_SEGMENTS` a row, no longer than the longest bucket, run at the smallest
bucket that holds the row), then `decode_quantum` steps — with FOUR measured
constants of the cell's traced run: a decode step's time at no live context
(`--step-ms`) and what a live row adds (`--row-ns`), a prefill program's time
a padded prompt token (`--prefill-us`) and a program (`--program-ms`). The
window closes at the first round boundary past `--seconds`, as
`serve_job.drive` closes it. No device, no model: a second a seed.

It prints the median tokens/s, sigma of log tokens/s over the seeds and what
the driver's check of a new cell would read: the spread (quartile distance /
median) of sets of six, each set's farthest run left out — to be held against
HALF the metric's bound. PR 59: with the constants of Xing4.0's traced run it
reproduced twelve chip runs seed for seed (r = 0.975, 0.6 % high, residual
0.27 %) and said why the cell spreads 1.0-1.4 %: prefill is 47 % of the device
and a 45 s window reaches 450 of the 1 200 queued, so the draw of the part it
serves moves tokens/s (PERF.md section 6, PR 59). What it leaves out: the
host (a cell whose device idles), preemption, a prefix cache.
"""
import argparse
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from benchmark.harness import loadgen  # noqa: E402
from benchmark.harness.serve_job import pad_prompt  # noqa: E402

BLOCK, SEGMENTS = 64, 4      # ServingConfig.block_size, serving._SEGMENTS


def prefill_ms(prompts, bucket: int, longest: int, us_token: float,
               ms_program: float) -> float:
    """The prefill programs of one round's admissions, in ms."""
    rows = []
    for p in sorted(prompts, reverse=True):
        held = -(-p // BLOCK) * BLOCK            # a segment starts at a block's edge
        for row in rows:
            if row[1] < SEGMENTS and row[0] + held <= longest:
                row[0] += held
                row[1] += 1
                break
        else:
            rows.append([held, 1])
    return sum(ms_program + us_token * 1e-3 * pad_prompt(r[0], bucket, longest)
               for r in rows)


def window(lengths, slots: int, seconds: float, step_ms: float, row_ns: float,
           prefill_us: float, program_ms: float, bucket: int = 512,
           quantum: int = 8) -> dict:
    """One saturating window over ``lengths`` = (prompt lengths, output
    lengths) in queue order -> tokens/s and what it reached."""
    plen, olen = lengths
    longest = pad_prompt(int(max(plen)), bucket, 1 << 30)
    ctx = np.zeros(slots, np.int64)              # live rows a slot
    left = np.zeros(slots, np.int64)             # tokens a slot still owes
    t = tokens = nxt = rounds = 0
    while t < seconds * 1e3:
        free = np.flatnonzero(left == 0)[:max(0, len(plen) - nxt)]
        if len(free):
            new = np.arange(nxt, nxt + len(free))
            t += prefill_ms([int(p) for p in plen[new]], bucket, longest,
                            prefill_us, program_ms)
            ctx[free], left[free] = plen[new] + 1, olen[new] - 1
            tokens += len(free)                  # a prefill emits the first token
            nxt += len(free)
        for _ in range(quantum):
            live = left > 0
            t += step_ms + row_ns * 1e-6 * int(ctx[live].sum())
            ctx[live] += 1
            left[live] -= 1
            tokens += int(live.sum())
        rounds += 1
        if nxt >= len(plen) and not (left > 0).any():
            break
    if nxt >= len(plen):         # `serve_job.drive`: an empty queue at the edge
        raise RuntimeError(f"the {len(plen)} queued requests ran out before "
                           "the window ended")
    return {"tokens_per_s": tokens / (t * 1e-3), "reached": nxt, "rounds": rounds}


def lengths_of(traffic: dict, seed: int):
    """The lengths `traffic_kinds/saturating.py` draws for a seed, in order."""
    rng = np.random.default_rng([seed, 0x62617463])
    p, o, n = traffic["prompt"], traffic["output"], int(traffic["requests"])
    return (loadgen.lognormal_lengths(rng, n, p["median"], p["sigma"], p["min"], p["max"]),
            loadgen.lognormal_lengths(rng, n, o["median"], o["sigma"], o["min"], o["max"]))


def trimmed_spread(values) -> float:
    """Quartile distance / median of a set, its farthest run left out."""
    v = sorted(values)
    med = statistics.median(v)
    v.remove(max(v, key=lambda x: abs(x - med)))
    q = statistics.quantiles(v, n=4)
    return (q[2] - q[0]) / statistics.median(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("traffic", help="a saturating mix: benchmark/traffic/<name>.json")
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--row-ns", type=float, default=0.0)
    ap.add_argument("--prefill-us", type=float, required=True)
    ap.add_argument("--program-ms", type=float, default=0.0)
    ap.add_argument("--bucket", type=int, default=512)
    ap.add_argument("--quantum", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seeds", type=int, default=120, help="a multiple of 6")
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    args = ap.parse_args(argv)
    traffic = loadgen.load_traffic(args.traffic)
    if traffic["kind"] != "saturating":
        print(f"{args.traffic} is a {traffic['kind']} mix, not a saturating one",
              file=sys.stderr)
        return 2
    runs = [window(lengths_of(traffic, args.first_seed + i), args.slots,
                   args.seconds, args.step_ms, args.row_ns, args.prefill_us,
                   args.program_ms, args.bucket, args.quantum)
            for i in range(args.seeds)]
    tps = [r["tokens_per_s"] for r in runs]
    sets = [trimmed_spread(tps[i:i + 6]) for i in range(0, len(tps) - 5, 6)]
    print(f"{args.traffic} at {args.slots} slots, {args.seeds} seeds (HOST "
          f"arithmetic, not a device number): median {statistics.median(tps):.1f} "
          f"tokens/s, reached {statistics.median(r['reached'] for r in runs):.0f} "
          f"of {traffic['requests']}, sigma of log tokens/s "
          f"{100 * float(np.std(np.log(tps))):.2f} %, a set of six spreads "
          f"{100 * statistics.mean(sets):.2f} % in the mean "
          f"({100 * min(sets):.2f}-{100 * max(sets):.2f} %), its farthest run left out")
    return 0


if __name__ == "__main__":
    sys.exit(main())
