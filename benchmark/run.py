#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: builds the cell's engine from the seed, warms the cell's own
programs, measures for ``--seconds``, checks the outputs against the plain
reference, and prints ONE JSON object as the last line of stdout. Detail
goes on earlier lines and under ``benchmark/out/``. Exits non-zero, with no
result line, off the TPU, on another chip count than the cell asks for, or
on a ``device_kind`` that ``harness/peaks.py`` does not list.

``--rehearsal``: the same harness code at toy widths on the CPU, to find a
NameError before chip time is spent. It prints no result line.
"""
import time
T_PROCESS = time.perf_counter()

import argparse
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy widths on the CPU; prints no result line")
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json "
              f"(have {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={cell['chips']}")

    # the configuration and its model family, BEFORE the backend comes up: a
    # `model_type` without benchmark/families/<model_type>.py fails here
    from benchmark.harness import common, loadgen
    cfg = common.load_config(cell["config"])
    family = loadgen.load_family(cfg)
    import jax
    devs = jax.devices()
    # setup_s starts HERE, with the backend up. Before it: the interpreter,
    # `import jax` and the TPU runtime coming up — 10-16 s that no file of
    # this repository can change and that drift by seconds with the shared
    # host (PERF.md section 2); they are logged as `runtime start` instead.
    t_start = time.perf_counter()
    import deepspeed_tpu  # noqa: F401 — a checkout without the program fails here
    from benchmark.harness import correct, peaks, trace_reduce
    from benchmark.harness.common import log
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    if not args.rehearsal:
        # the program's own rule: JAX_COMPILATION_CACHE_DIR if set, else
        # <checkout>/.jax_cache. Small programs are cached too: every run is
        # a new process and pays for each of them again otherwise.
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    else:
        cache_dir = None
    compiles = common.CompileCounter()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"jax {jax.__version__} platform={device['platform']} "
        f"kind={device['kind']!r} devices={device['count']} cache={cache_dir} "
        f"cell={cell['name']} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace}{' REHEARSAL' if args.rehearsal else ''}")
    if not args.rehearsal and device["platform"] != "tpu":
        print(f"benchmark: needs a TPU; JAX found platform={device['platform']!r} "
              f"({device['kind']}). --rehearsal is the CPU dry run.", file=sys.stderr)
        return 1
    if device["count"] != cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} chip(s), "
              f"JAX found {device['count']}", file=sys.stderr)
        return 1
    pk = peaks.peaks_for("TPU v5 lite" if args.rehearsal else device["kind"])

    traffic = loadgen.load_traffic(cell["traffic"])
    job = importlib.import_module(f"benchmark.harness.{cfg['run']['job']}_job")
    log(f"runtime start (interpreter + import jax + backend up; NOT in setup_s) "
        f"{t_start - T_PROCESS:.1f} s")
    env = {"t_start": t_start, "compiles": compiles, "peaks": pk,
           "device": device, "family": family}
    res = job.run(cell, cfg, traffic, args, env)

    # ---- per-layer metrics: one reader per file ---------------------------
    reduced = None
    if res["tracer"] is not None:
        t = time.perf_counter()
        reduced = res["tracer"].reduce()
        log(f"trace reduced in {time.perf_counter() - t:.1f} s: window "
            f"{reduced['window_s']:.3f} s, {reduced['n_devices']} device plane(s)")
    run = {"job": res["job"], "cell": cell, "config": cfg, "traffic": traffic,
           "hf": res["hf"], "family": family, "chips": cell["chips"], "peaks": pk,
           "counters": res["counters"], "host": res["host"], "trace": reduced,
           "e2e": res["e2e"]}
    per_layer = {}
    wanted = [m for m in bench["per_layer"]
              if cell["name"] in m.get("workloads", [cell["name"]])]
    for m in wanted:
        reader = loadgen.load_module("layer_metrics", m["name"])
        if res["job"] not in reader.HEADER["jobs"] or m["moves"] not in res["e2e"]:
            continue
        value = reader.read(run)
        if value is not None:
            per_layer[m["name"]] = {"value": float(value), "unit": m["unit"]}

    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    e2e = {k: {"value": float(v), "unit": units[k]} for k, v in res["e2e"].items()}
    ok = correct.verdict(res["checks"])
    for c in res["checks"]:
        log("check " + json.dumps(c))
    mem = res["memory"]
    device["memory_peak_bytes"] = mem["peak_bytes_in_use"]
    line = {"correct": ok, "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "device": device}
    detail = {"cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
              "trace": args.trace, "rehearsal": args.rehearsal,
              "end_to_end": e2e, "per_layer": per_layer, "checks": res["checks"],
              "counters": res["counters"], "device": device,
              "runtime_start_s": t_start - T_PROCESS}
    if args.trace:
        if reduced is None or not reduced.get("devices"):
            if not args.rehearsal:
                raise RuntimeError("traced run found no device plane in its trace")
        else:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = reduced["breakdown"]
            kernels = {}
            for d in reduced["devices"]:
                for n, ns in d["op_self_ns"].items():
                    if trace_reduce.is_mosaic(n):
                        k = n.split(" = ")[0]
                        kernels[k] = kernels.get(k, 0.0) + ns / 1e9 / len(reduced["devices"])
            detail["mosaic_kernels_s"] = kernels
            log(f"Mosaic kernels in the traced stretch (s per chip): {kernels}")
            idle = [100 * x for x in reduced["idle_share_per_device"]]
            detail["device_idle_share"] = max(idle)
            log(f"device_idle_share {max(idle):.2f} % (per chip: "
                + ", ".join(f"{x:.2f}" for x in idle) + ")")
        line["metrics"] = per_layer
        log("end-to-end of this TRACED run (the difference to an untraced run "
            "is the tracing overhead): " + json.dumps(e2e))
    else:
        line["metrics"] = e2e
    os.makedirs(common.OUT_DIR, exist_ok=True)
    tag = f"{cell['name']}.seed{args.seed}.trace{args.trace}"
    with open(os.path.join(common.OUT_DIR, tag + ".json"), "w") as f:
        json.dump(detail, f)
    log(f"elapsed {time.perf_counter() - T_PROCESS:.1f} s; detail in benchmark/out/{tag}.json")
    if args.rehearsal:
        print("REHEARSAL (not a result) " + json.dumps(
            {"correct": ok, "end_to_end": e2e, "per_layer": per_layer}), flush=True)
        return 0 if ok else 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
