#!/usr/bin/env python3
"""The decode step's read of a LATENT paged pool, alone, on the chip: the XLA
list read (``models/latent_attention.latent_read`` handed the ``BlockList`` the
engine would hand it: the rung of ``serving._list_ladder`` that holds the live
blocks) against the kernel (``ops/latent_decode.latent_decode``, rectangular
tables), one plane's call each, at the GLM cell's shape and a few around it,
slots filled to a share of their table.

    python benchmark/tools/latent_read_bench.py [--shapes glm,s32x76] \
        [--fills 0.25,0.5,0.75,1.0] [--iters 30]

Prints one JSON line a (shape, fill): ms a call of both, GB/s over the LIVE
rows' bytes (the row's values, 1 152 B, not the 1 280 B it is stored in), their
largest difference, and the two prices of ``ops/latent_decode.latent_read_
price`` beside them. The lines are what the price's constants were fitted on
(PERF.md section 6, PR 52); written to ``chiprun_out/latent_read_bench.json``
too. Not part of a run. ``--rehearsal``: tiny shapes on the CPU (interpret
mode), no timing worth reading.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# name: (slots, table columns, heads, row width, latent rank)
SHAPES = {
    "glm": (128, 76, 20, 576, 512),
    "glm32": (32, 76, 20, 576, 512),
    "s64x160": (64, 160, 20, 576, 512),
    "s128x24": (128, 24, 20, 576, 512),
    "s16x512": (16, 512, 20, 576, 512),
}
BS = 64


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--fills", default="0.25,0.5,0.75,1.0")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import loadgen
    from deepspeed_tpu.inference.serving import _RUN, _list_ladder
    from deepspeed_tpu.models.latent_attention import LANES, latent_read
    from deepspeed_tpu.ops.latent_decode import latent_read_price
    block_list = loadgen.load_module("tools", "paged_read_bench").block_list
    if not a.rehearsal and jax.default_backend() != "tpu":
        sys.exit(f"no chip: {jax.devices()}")
    lines = []
    for name in a.shapes.split(","):
        S, MB, Nq, width, rank = SHAPES[name]
        if a.rehearsal:
            S, MB = 4, 6
        lanes = -(-width // LANES) * LANES
        L, NB = 2, S * MB + 1
        keys = jax.random.split(jax.random.PRNGKey(0), 3)

        def stored(key, shape):     # values in the row's width, zero pad lanes
            x = jax.random.normal(key, shape + (width,), jnp.bfloat16)
            return jnp.pad(x, [(0, 0)] * len(shape) + [(0, lanes - width)])
        pool, q, row = (stored(keys[0], (L, NB, BS)), stored(keys[1], (S, Nq)),
                        stored(keys[2], (S,)))
        rng = np.random.default_rng(0)
        ids = rng.permutation(np.arange(1, NB)).reshape(S, MB).astype(np.int32)
        price = latent_read_price(slots=S, MB=MB, block_size=BS, heads=Nq,
                                  lanes=lanes, rank=rank)
        for fill in (float(f) for f in a.fills.split(",")):
            lo = max(1, int(BS * MB * fill * 0.8))
            hi = min(BS * MB, max(lo, int(BS * MB * fill * 1.2)))
            lens = rng.integers(lo, hi + 1, size=(S,)).astype(np.int32)
            listed = sum(-(-(-(-int(n) // BS)) // _RUN) for n in lens) * _RUN
            W = next(w for w in _list_ladder(MB, (4, 2, 1)) if S * w >= listed)
            tabs = np.where(np.arange(MB)[None] < -(-lens // BS)[:, None], ids, 0)

            def timed(backend, tables):
                f = jax.jit(lambda q, pool, t, ln, row: latent_read(
                    q, pool, t, ln, row, 1, 1.0 / 16, rank, backend))
                args = (q, pool, jax.tree.map(jnp.asarray, tables),
                        jnp.asarray(lens), row)
                out = jax.block_until_ready(f(*args))
                t0 = time.perf_counter()
                for _ in range(a.iters):
                    o = f(*args)
                jax.block_until_ready(o)
                return (time.perf_counter() - t0) / a.iters * 1e3, out
            xla_ms, want = timed("xla", block_list(ids, lens, W))
            ker_ms, got = timed("pallas", tabs.astype(np.int32))
            live = int(lens.sum()) * 2 * width
            line = {"shape": name, "dims": [S, MB, Nq, width, rank], "fill": fill,
                    "mean_len": float(lens.mean()), "list_columns": W,
                    "xla_ms": round(xla_ms, 4), "kernel_ms": round(ker_ms, 4),
                    "live_mb": round(live / 1e6, 2),
                    "xla_gbps": round(live / xla_ms / 1e6, 1),
                    "kernel_gbps": round(live / ker_ms / 1e6, 1),
                    "max_diff": float(jnp.max(jnp.abs(
                        got.astype(jnp.float32) - want.astype(jnp.float32)))),
                    "price": price,
                    "device": jax.devices()[0].device_kind}
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "latent_read_bench.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
