"""Share of the device's busy time spent in the HEAD: self time of the ops
the family's ``head_op`` finds — a result or an operand whose last dimension
is the vocabulary: the final projection over the whole vocabulary and the
greedy pick's passes over the logits, in the step and in the prefill — over
the busy time of the traced stretch. A cell whose chip is one pipeline stage
of a deployment holds the whole head so that it has logits, where a stage
pays its share of it: this is the reading to divide by the stages. A family
without ``head_op`` (every other one, the parent's program) reads nothing."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "decode step (models/transformer.py decode_step_paged)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t, fam = run["trace"], run["family"]
    if not t or not t.get("busy_s") or not hasattr(fam, "head_op"):
        return None
    s = trace_reduce.op_seconds(t, lambda name: fam.head_op(name, run["hf"]))
    return 100.0 * s / t["busy_s"] if s else None
