"""Share of the train step's device time spent in the expert layers' grouped
matmuls: self time of the ``moe_gmm`` kernels (forward, its remat replay, the
rows' gradient) and of ``moe_gmm_dw`` (the experts' gradient), found by the
family's ``kernel`` (families/mellum.py: a Mosaic call whose instruction name
carries the kernel's), over the busy time of the traced steps. The sort, the
two permutations and the weighted sum around them are XLA fusions and are not
in it. A program without such kernels (another family's, the parent's one-hot
masks) reads nothing. ``only``: the kernels counted."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "expert layer (moe/sharded_moe.py)", "unit": "%",
          "moves": "train_tokens_per_s_per_chip", "jobs": ["train"],
          "source": "device_trace", "better": "lower"}


def kernel_seconds(run, only):
    """(device seconds of the kernels ``only`` names, train steps traced), or
    None where the trace, the family's ``kernel`` or the kernels are not
    there: what the four readers of PR 48 start from."""
    t, fam = run["trace"], run["family"]
    if not t or not t.get("devices") or not hasattr(fam, "kernel"):
        return None
    took = trace_reduce.op_seconds(t, lambda name: fam.kernel(name) in only)
    steps, _ = trace_reduce.module_stats(t, "jit_train_step")
    return (took, steps) if took else None


def read(run, only=("moe_gmm", "moe_gmm_dw")):
    found = kernel_seconds(run, only)
    busy = (run["trace"] or {}).get("busy_s")
    return 100.0 * found[0] / busy if found and busy else None
