#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: several rates in ONE
process so they share the engine and its compiled programs.

    python benchmark/tools/sweep.py --workload mistral-7b-serve.chat \
        --rates 4,6,8,10,12,14 --seconds 20

For each rate: the cell's own traffic file with ``rate_per_s`` replaced, a
window of ``--seconds``, then a drain until the engine is empty. The knee
is the highest swept rate at which the queue (``scheduler.num_waiting``)
does not grow through the window: its mean over the last quarter of the
window is no higher than over the second quarter plus 2. Not part of a
run; the result goes into PERF.md and 0.8 x the knee into the traffic file.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from benchmark.harness import common, loadgen, metrics, serve_job
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    if not args.rehearsal:
        enable_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = {w["name"]: w for w in json.load(f)["workloads"]}[args.workload]
    cfg = common.load_config(cell["config"])
    traffic = loadgen.load_traffic(cell["traffic"])
    srv, hf, traffic = serve_job.build(cell, cfg, traffic, args.seed, args.rehearsal)
    serve_job.warm(srv, traffic, hf["vocab_size"], args.seed)
    print("device", jax.devices()[0].device_kind, flush=True)
    rows = []
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        t = dict(traffic, rate_per_s=rate)
        ctx = {"vocab_size": hf["vocab_size"], "seconds": args.seconds,
               "max_model_len": srv.max_model_len}
        schedule = loadgen.generate(t, args.seed + k, ctx)
        d = serve_job.drive(srv, schedule, args.seconds, drain_s=120.0)
        q = np.array(d["queue"])
        def mean_q(lo, hi):
            m = (q[:, 0] >= lo * args.seconds) & (q[:, 0] < hi * args.seconds)
            return float(q[m, 1].mean()) if m.any() else 0.0
        q2, q4 = mean_q(0.25, 0.5), mean_q(0.75, 1.0)
        row = {"rate_per_s": rate, "offered": d["offered"], "misses": d["misses"],
               "queue_q2": q2, "queue_q4": q4, "sustained": q4 <= q2 + 2.0,
               "ttft_p50_ms": metrics.percentile(d["ttft_ms"], 50),
               "ttft_p90_ms": metrics.percentile(d["ttft_ms"], 90),
               "tpot_p50_ms": metrics.percentile(d["tpot_ms"], 50),
               "tpot_p90_ms": metrics.percentile(d["tpot_ms"], 90),
               "gen_late_p95_ms": metrics.percentile(d["late_ms"], 95),
               "mean_occupancy": d["mean_occupancy"], "rounds": d["rounds"]}
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
        srv.reset_stats()
    ok = [r["rate_per_s"] for r in rows if r["sustained"]]
    print("KNEE " + json.dumps({"knee_rate_per_s": max(ok) if ok else None,
                                "device": jax.devices()[0].device_kind}), flush=True)
    srv.close()


if __name__ == "__main__":
    main()
