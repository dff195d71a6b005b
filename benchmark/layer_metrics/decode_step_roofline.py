"""The decode step's share of its memory roofline: bytes one step must
read (harness/bytes.decode_step_bytes: every layer's weights and the head
once + the live KV rows at the mean of the lengths sampled after each
round) over the chip's HBM bandwidth, over the measured step time.
Memory-bound: a step does ~2 FLOPs per weight byte per sequence."""
from benchmark.harness import bytes as nbytes
from benchmark.layer_metrics import decode_step_device_ms

HEADER = {"layer": "decode step (models/transformer.py decode_step_paged)",
          "unit": "%", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "device_trace", "better": "higher"}


def read(run):
    ms = decode_step_device_ms.read(run)
    if not ms:
        return None
    c = run["counters"]
    need = nbytes.decode_step_bytes(run["hf"], c["kv_cache_bits"], c["mean_live_tokens"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
