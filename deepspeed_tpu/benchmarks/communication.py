"""Collective latency/bandwidth microbenchmarks over mesh axes.

Reference: ``benchmarks/communication/{all_reduce,all_gather,all_to_all,
pt2pt,broadcast}.py`` + ``run_all.py`` — the reproduction harness BASELINE.md
lists for the reference's comm numbers. TPU-native re-design: each op is a
jitted ``shard_map`` over a named mesh axis (the compiler lowers to ICI/DCN
collectives); timing is wall-clock around a chained iteration loop with a
device fetch as the completion fence (works through transports where
``block_until_ready`` is advisory).

Bus bandwidth follows the reference's convention (``utils.py`` get_bw): the
algorithmic bytes are scaled by the ring factor 2(n-1)/n for all-reduce and
(n-1)/n for all-gather / reduce-scatter / all-to-all, so numbers are
comparable across world sizes.
"""

import time
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_SIZES = [1 << 14, 1 << 18, 1 << 22, 1 << 24]  # elements (fp32)
OPS = ("psum", "all_gather", "psum_scatter", "all_to_all", "ppermute",
       "compressed_allreduce_1bit")


def _op_fn(op: str, axis: str, mesh: Mesh):
    """Jitted collective over `axis`; input is the per-device shard."""
    n = mesh.shape[axis]
    in_spec = P(axis)
    if op == "psum":
        body = lambda x: jax.lax.psum(x, axis)                 # noqa: E731
        out_spec = P(axis)
    elif op == "all_gather":
        def body(x):
            # slice back to the shard size so iterations chain (the slice is
            # local; the full gather still crossed the wire)
            return jax.lax.all_gather(x, axis, tiled=True)[:x.shape[0]]
        out_spec = P(axis)
    elif op == "psum_scatter":
        def body(x):
            s = jax.lax.psum_scatter(x, axis, tiled=True)
            return jnp.tile(s, n)  # local re-expand to the shard size
        out_spec = P(axis)
    elif op == "all_to_all":
        def body(x):
            r = x.reshape(n, x.shape[0] // n, *x.shape[1:])
            return jax.lax.all_to_all(r, axis, 0, 0, tiled=False).reshape(
                x.shape)
        out_spec = P(axis)
    elif op == "ppermute":
        perm = [(i, (i + 1) % n) for i in range(n)]
        body = lambda x: jax.lax.ppermute(x, axis, perm)       # noqa: E731
        out_spec = P(axis)
    elif op == "compressed_allreduce_1bit":
        from deepspeed_tpu.comm.compressed import compressed_allreduce_1bit
        body = lambda x: compressed_allreduce_1bit(x, axis)    # noqa: E731
        out_spec = P(axis)
    else:
        raise ValueError(f"unknown op {op!r}")

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                       out_specs=out_spec, check_vma=False)

    def chained(x, iters):
        # chain iterations through a data dependency so one dispatch times
        # `iters` executions of the collective
        def step(carry, _):
            y = fn(carry)
            return y.reshape(carry.shape).astype(carry.dtype), None
        y, _ = jax.lax.scan(step, x, None, length=iters)
        return y

    return jax.jit(chained, static_argnums=(1,))


def _bus_factor(op: str, n: int) -> float:
    """Reference convention (benchmarks/communication/utils.py get_bw)."""
    if n <= 1:
        return 1.0
    if op in ("psum", "compressed_allreduce_1bit"):
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "psum_scatter", "all_to_all"):
        return float(n - 1) / n
    return 1.0  # ppermute: point-to-point


def run_comm_bench(mesh: Optional[Mesh] = None, *, axis: Optional[str] = None,
                   sizes: Optional[List[int]] = None, ops=OPS,
                   iters: int = 10, dtype=jnp.float32) -> List[Dict]:
    """One result dict per (op, size): latency, algorithmic and bus BW."""
    if mesh is None:
        devs = jax.devices()
        mesh = Mesh(np.array(devs), ("data",))
    axes = [axis] if axis else list(mesh.axis_names)
    sizes = sizes or DEFAULT_SIZES
    results = []
    for ax in axes:
        n = mesh.shape[ax]
        for op in ops:
            for size in sizes:
                per_dev = max(size // max(n, 1), n)
                per_dev -= per_dev % max(n, 1)  # all_to_all divisibility
                total = per_dev * n
                x = jax.device_put(
                    jnp.arange(total, dtype=dtype) / total,
                    NamedSharding(mesh, P(ax)))
                try:
                    with mesh:
                        fn = _op_fn(op, ax, mesh)
                        # warm with the SAME static iters (separate lengths
                        # would put a fresh compile inside the timed region)
                        np.asarray(jax.device_get(fn(x, iters)))
                        t0 = time.perf_counter()
                        out = fn(x, iters)
                        np.asarray(jax.device_get(out))       # fence
                        dt = (time.perf_counter() - t0) / iters
                except Exception as e:  # noqa: BLE001 — per-op isolation
                    results.append({"op": op, "axis": ax, "world": n,
                                    "elements": total, "error": str(e)[:120]})
                    continue
                # payload convention: per-rank tensor bytes (every rank holds
                # a shard of `per_dev` elements); all_gather's payload is the
                # gathered OUTPUT (n shards) — matching nccl-tests/reference
                shard_bytes = per_dev * jnp.dtype(dtype).itemsize
                nbytes = shard_bytes * (n if op == "all_gather" else 1)
                alg_bw = nbytes / dt / 1e9
                results.append({
                    "op": op, "axis": ax, "world": n, "elements": total,
                    "size_mb": round(nbytes / 1e6, 2),
                    "latency_us": round(dt * 1e6, 1),
                    "alg_bw_gbps": round(alg_bw, 4),
                    "bus_bw_gbps": round(alg_bw * _bus_factor(op, n), 4),
                })
    return results


def main(argv=None):
    import argparse
    import json
    p = argparse.ArgumentParser()
    p.add_argument("--sizes", type=int, nargs="*", default=None)
    p.add_argument("--ops", nargs="*", default=list(OPS))
    p.add_argument("--iters", type=int, default=10)
    a = p.parse_args(argv)
    for row in run_comm_bench(sizes=a.sizes, ops=a.ops, iters=a.iters):
        print(json.dumps(row))


if __name__ == "__main__":
    main()
