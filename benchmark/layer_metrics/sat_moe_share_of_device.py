"""Share of the device's busy time spent in the expert layer's matmuls
(three per expert layer and program execution: up, gate, down), prefill and
decode together: self time of the ops the family's ``expert_matmul``
finds (families/olmoe.py: the grouped-matmul kernel ``%moe_gmm.N`` of a call
of many tokens, the all-experts fusion of a call of few) over the busy time
of the traced stretch. A program without such ops reads nothing.
``grouped_only`` keeps the sorted form's grouped matmuls alone
(``sat_moe_sorted_share_of_device``)."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "expert layer (moe/sharded_moe.py)", "unit": "%",
          "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run, grouped_only: bool = False):
    t, fam, hf = run["trace"], run["family"], run["hf"]
    if not t or not t.get("busy_s") or not hasattr(fam, "expert_matmul"):
        return None
    s = trace_reduce.op_seconds(
        t, lambda name: fam.expert_matmul(name, hf) is not None
        and (not grouped_only or fam.is_grouped_matmul(name)))
    return 100.0 * s / t["busy_s"] if s else None
