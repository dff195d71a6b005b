#!/usr/bin/env python3
"""The decode step's read of the int8 paged pool, alone, on the chip: the XLA
read (``models/transformer._paged_attention`` handed the ``BlockList`` the
engine would hand it: the rung of ``serving._list_ladder`` that holds the
live blocks) against the kernel (``ops/decode_attention.paged_decode_int8``,
rectangular tables), one layer's call each, at the serve cells' shapes and a
few between them, slots filled to a share of their table.

    python benchmark/tools/paged_read_bench.py [--shapes trinity,chat] \
        [--fills 0.25,0.5,0.6,1.0] [--iters 30]

Prints one JSON line a (shape, fill): ms a call of both, GB/s over the LIVE
rows' bytes (int8 K and V rows + their float32 scales), their largest
difference, and the two prices of ``ops/decode_attention.paged_read_price``
beside them. The lines are what the price's constants were fitted on (PERF.md
section 6, PR 50); written to ``chiprun_out/paged_read_bench.json`` too. Not
part of a run. ``--rehearsal``: tiny shapes on the CPU (interpret mode), no timing
worth reading.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# name: (slots, table columns, kv heads, query heads a kv head, head dim)
SHAPES = {
    "trinity": (64, 176, 8, 6, 128),
    "chat": (48, 32, 8, 4, 128),
    "chat16": (16, 32, 8, 4, 128),
    "mixtral": (32, 32, 8, 4, 128),
    "olmoe": (32, 16, 16, 1, 128),
    "ouro": (16, 20, 16, 1, 128),
    "s64x64": (64, 64, 8, 6, 128),
    "s16x176": (16, 176, 8, 6, 128),
    "s128x40": (128, 40, 8, 4, 128),
    "s32x96": (32, 96, 8, 4, 128),
}
BS = 64


def block_list(ids, lens, W):
    """The ``BlockList`` of ``ServingEngine._tables_device``: every slot's
    live blocks in runs of ``_RUN``, padded to ``S x W`` blocks."""
    import numpy as np
    from deepspeed_tpu.inference.serving import _RUN
    from deepspeed_tpu.models.transformer import BlockList
    S, MB = ids.shape
    runs, wide = S * W // _RUN, -(-MB // _RUN)
    out = BlockList(np.zeros((runs * _RUN,), np.int32),
                    np.full((runs,), S * wide, np.int32),
                    np.full((S, wide), runs, np.int32))
    n = 0
    for s in range(S):
        k = -(-int(lens[s]) // BS)
        r = -(-k // _RUN)
        out.ids[n * _RUN:n * _RUN + k] = ids[s, :k]
        out.where[n:n + r] = s * wide + np.arange(r)
        out.inv[s, :r] = np.arange(n, n + r)
        n += r
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--fills", default="0.25,0.5,0.6,1.0")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.serving import _RUN, _list_ladder
    from deepspeed_tpu.models.transformer import _paged_attention
    from deepspeed_tpu.ops.decode_attention import paged_read_price
    if not a.rehearsal and jax.default_backend() != "tpu":
        sys.exit(f"no chip: {jax.devices()}")
    lines = []
    for name in a.shapes.split(","):
        S, MB, G, rep, D = SHAPES[name]
        if a.rehearsal:
            S, MB = 4, 6
        L, NB = 2, S * MB + 1
        keys = jax.random.split(jax.random.PRNGKey(0), 7)
        pools = [jax.random.randint(k, (L, NB, BS, G, D), -127, 128, jnp.int8)
                 for k in keys[:2]]
        scales = [jax.random.uniform(k, (L, NB, G * BS), jnp.float32,
                                     0.005, 0.02) for k in keys[2:4]]
        q = jax.random.normal(keys[4], (S, 1, G * rep, D), jnp.bfloat16)
        row = tuple(jax.random.normal(k, (S, G, 1, D), jnp.bfloat16)
                    for k in keys[5:])
        rng = np.random.default_rng(0)
        ids = rng.permutation(np.arange(1, NB)).reshape(S, MB).astype(np.int32)
        price = paged_read_price(slots=S, MB=MB, block_size=BS, n_kv=G,
                                 rep=rep, head_dim=D)
        for fill in (float(f) for f in a.fills.split(",")):
            lo = max(1, int(BS * MB * fill * 0.8))
            hi = min(BS * MB, max(lo, int(BS * MB * fill * 1.2)))
            lens = rng.integers(lo, hi + 1, size=(S,)).astype(np.int32)
            listed = sum(-(-(-(-int(n) // BS)) // _RUN) for n in lens) * _RUN
            W = next(w for w in _list_ladder(MB, (4, 2, 1)) if S * w >= listed)
            tabs = np.where(np.arange(MB)[None] < -(-lens // BS)[:, None], ids, 0)

            def timed(backend, tables):
                f = jax.jit(lambda q, k, v, ks, vs, t, ln, kr, vr:
                            _paged_attention(q, k, v, t, ln, None, (kr, vr),
                                             kv_scale=(ks, vs),
                                             backend=backend, layer=1))
                args = (q, *pools, *scales, jax.tree.map(jnp.asarray, tables),
                        jnp.asarray(lens), *row)
                out = jax.block_until_ready(f(*args))
                t0 = time.perf_counter()
                for _ in range(a.iters):
                    o = f(*args)
                jax.block_until_ready(o)
                return (time.perf_counter() - t0) / a.iters * 1e3, out
            xla_ms, want = timed("xla", block_list(ids, lens, W))
            ker_ms, got = timed("pallas", tabs.astype(np.int32))
            live = int(lens.sum()) * 2 * (G * D + 4 * G)
            line = {"shape": name, "dims": [S, MB, G, rep, D], "fill": fill,
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
    with open(os.path.join(ROOT, "chiprun_out", "paged_read_bench.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
