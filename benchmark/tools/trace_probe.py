#!/usr/bin/env python3
"""Record a few traced train steps of a train cell and print what the raw
trace holds: planes, lines, the names with most time on each.

A builder's tool, not part of a run: look at a trace by hand before writing
a reader against it (which planes are devices, which lines hold ops, how
programs and kernels are named). The raw ``.xplane.pb`` goes under
``chiprun_out/trace_probe/``.

    python benchmark/tools/trace_probe.py [--config mistral-7b-train]
"""
import argparse
import collections
import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def describe(path, top=25):
    import jax
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            tot = collections.Counter()
            for e in line.events:
                tot[e.name] += e.duration_ns
            if not tot:
                continue
            print(f"  LINE {line.name!r}: {len(tot)} names, "
                  f"sum {sum(tot.values()) / 1e6:.2f} ms")
            for name, ns in tot.most_common(top if plane.name.startswith("/device:") else 6):
                print(f"      {ns / 1e6:9.3f} ms  {name[:150]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mistral-7b-train")
    args = ap.parse_args()
    import jax
    import numpy as np
    import deepspeed_tpu
    from benchmark.harness import common
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = os.path.join(ROOT, "chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    cfg = common.load_config(args.config)
    hf = common.hf_of(cfg)
    B, S = 4, 2048
    engine, *_ = deepspeed_tpu.initialize(
        model=make_model(common.model_config(cfg, hf, S)),
        config=dict(cfg["run"]["engine"], train_batch_size=B))
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, hf["vocab_size"], (B, S), dtype=np.int32)}
    engine.train_batches((batch for _ in range(2)), 2)
    jax.block_until_ready(engine.state)
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench:window"):
        engine.train_batches((batch for _ in range(3)), 3)
        jax.block_until_ready(engine.state)
    jax.profiler.stop_trace()
    engine.close()
    for f in glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True):
        print("TRACE", f, os.path.getsize(f))
        describe(f)


if __name__ == "__main__":
    main()
