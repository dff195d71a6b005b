"""The paged decode kernel against its memory roofline: the bytes the decode
steps of the traced stretch MUST read of the paged planes — per execution of
the step program (``jit_step``, as ``decode_step_device_ms`` counts them) the
LIVE rows of every paged plane, int8 K and V rows and their float32 scales
(the family's ``kv_bytes_per_token`` x the mean of the live rows sampled
after each round: rows, not the whole blocks the kernel fetches, so it cannot
pass 100 %) — over the chip's HBM bandwidth, over the device time of the
kernel, found by the NAME of its Mosaic call (``%paged_decode_int8.N``,
``ops/decode_attention.py``). A program whose decode step reads the pool
through XLA's gather has no such call and reads nothing."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "decode step (models/transformer.py decode_step_paged)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "higher"}
KERNEL = "paged_decode_int8"


def read(run):
    t, fam, c = run["trace"], run["family"], run["counters"]
    if not t or not hasattr(fam, "kv_bytes_per_token"):
        return None
    took = trace_reduce.op_seconds(t, lambda name: KERNEL in name)
    live = c.get("mean_live_tokens")
    if not took or not live:
        return None
    steps, _ = trace_reduce.module_stats(t, "jit_step")
    need = steps * live * fam.kv_bytes_per_token(run["hf"], c["kv_cache_bits"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / took
