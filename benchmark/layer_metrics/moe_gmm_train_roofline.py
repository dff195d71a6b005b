"""The grouped-matmul kernels of a train step against their roofline: the
least time ONE step's expert matmuls can take — the family's
``moe_gmm_train_flops`` over the bf16 peak, or ``moe_gmm_train_bytes`` over
the memory bandwidth where that is longer (it is not, from ~240 rows an
expert), times the expert blocks — over the device time of ``moe_gmm`` and
``moe_gmm_dw`` in the traced steps: forward, the rows' gradient, the experts'
gradient; a remat replay of the forward is time and no work.

The work is that of the rows the held experts are EXPECTED to get, tokens x
experts a token x held / router width (``expected_held_rows``):
``harness/train_job.py`` hands a reader no counter of the program, so the
rows the router really sent (the engine's ``moe_held_rows`` metric) are not
here. A router that favours the held experts makes the kernels' time longer
and this share lower, never higher than what the rows deserve."""
from benchmark.layer_metrics.moe_share_of_step import kernel_seconds

HEADER = {"layer": "expert layer (moe/sharded_moe.py)", "unit": "%",
          "moves": "train_tokens_per_s_per_chip", "jobs": ["train"],
          "source": "device_trace", "better": "higher"}


def read(run):
    found = kernel_seconds(run, ("moe_gmm", "moe_gmm_dw"))
    if not found or not found[1]:
        return None
    (took, steps), fam = found, run["family"]
    hf, pk, tokens = run["hf"], run["peaks"], run["counters"]["tokens_per_step"]
    least = max(fam.moe_gmm_train_flops(hf, tokens) / pk["bf16_flops_per_s"],
                fam.moe_gmm_train_bytes(hf, tokens) / pk["hbm_bytes_per_s"])
    return 100.0 * least * fam.count(hf, "moe") * steps / run["chips"] / took
