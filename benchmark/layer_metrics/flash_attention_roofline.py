"""The flash-attention kernels' share of their compute roofline: FLOPs the
forward and backward need for the traced steps (`flash_flops` of the
configuration's family, families/<model_type>.py, for ONE layer: causal half
counted once, backward = 2.5 x forward, a remat replay counts as time but
not as work; times the layers the program built) over the kernels' summed
device time over the chip's bf16 peak. Compute-bound: at S = 2048, hd = 128
the kernels do ~1000 FLOPs per byte of q/k/v they read."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "model + train kernels (models/transformer.py, ops/flash_attention.py)",
          "unit": "%", "moves": "train_tokens_per_s_per_chip",
          "jobs": ["train"], "source": "device_trace", "better": "higher"}


def read(run):
    t = run["trace"]
    if not t or not t.get("devices"):
        return None
    kernel_s = trace_reduce.op_seconds(t, trace_reduce.is_mosaic)         # per chip
    steps, _ = trace_reduce.module_stats(t, "jit_train_step")
    if not kernel_s or not steps:
        return None
    c = run["counters"]
    need = run["family"].flash_flops(run["hf"], c["sequences_per_step"], c["seq_len"])["total"]
    need *= c["num_layers"] * steps / run["chips"]
    return 100.0 * need / kernel_s / run["peaks"]["bf16_flops_per_s"]
