"""The two banded backward kernels against the chip's matmul peak: the FLOPs
the backward of ONE step's sliding-window layers needs over its VISIBLE
(query, key) pairs — the family's ``flash_band_flops(...)["bwd"]``: five
matmuls a pair, S W - W (W - 1) / 2 pairs a sequence past the window, the
same work whatever tiles a kernel visits — times the sliding blocks, over the
device time of ``flash_bwd_band_dq`` and ``flash_bwd_band_dkv`` in the traced
steps. Compute-bound: at head dim 128 the kernels do hundreds of FLOPs per
byte of q / k / v they read."""
from benchmark.layer_metrics.moe_share_of_step import kernel_seconds

HEADER = {"layer": "window attention (models/hybrid.py, ops/flash_attention.py)",
          "unit": "%", "moves": "train_tokens_per_s_per_chip",
          "jobs": ["train"], "source": "device_trace", "better": "higher"}


def read(run):
    found = kernel_seconds(run, ("flash_bwd_band_dq", "flash_bwd_band_dkv"))
    if not found or not found[1]:
        return None
    (took, steps), fam = found, run["family"]
    c, hf = run["counters"], run["hf"]
    need = fam.flash_band_flops(hf, c["sequences_per_step"], c["seq_len"])["bwd"]
    need *= fam.count(hf, "wattn") * steps / run["chips"]
    return 100.0 * need / took / run["peaks"]["bf16_flops_per_s"]
