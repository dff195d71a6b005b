"""Share of the train step's device time spent in the flash-attention
kernels: self time of the Mosaic custom calls (in a train step the only
Pallas kernels are flash attention's: forward, its remat replay, dQ,
dK/dV) over the busy time of the traced steps."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "model + train kernels (models/transformer.py, ops/flash_attention.py)",
          "unit": "%", "moves": "train_tokens_per_s_per_chip",
          "jobs": ["train"], "source": "device_trace", "better": "lower"}


def read(run):
    t = run["trace"]
    if not t or not t.get("busy_s"):
        return None
    s = trace_reduce.op_seconds(t, trace_reduce.is_mosaic)
    return 100.0 * s / t["busy_s"] if s else None
