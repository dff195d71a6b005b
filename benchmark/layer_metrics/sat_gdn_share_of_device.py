"""Share of the device's busy time spent in the gated delta rule's two Pallas
kernels, prefill and decode together: self time of the ops the family's
``gdn_kernel`` finds by NAME in the device trace (``%gdn_chunk.N``, the chunk
form over a prompt; ``%gdn_step.N``, the one-step update of every slot's
matrix state) over the busy time of the traced stretch. The projections, the
convolution, the l2 norms and the gated output norm around them are XLA
fusions and are not in it. A program without such kernels (another family's,
the parent's) reads nothing."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "recurrent mixer (models/gated_deltanet.py, ops/gated_delta.py)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def kernel_seconds(run, which=None):
    """Self seconds of the recurrence's kernels in the traced stretch
    (``which``: ``"chunk"`` / ``"step"`` alone); None where the family or
    the trace has none."""
    t, fam = run["trace"], run["family"]
    if not t or not t.get("busy_s") or not hasattr(fam, "gdn_kernel"):
        return None
    s = trace_reduce.op_seconds(
        t, lambda name: fam.gdn_kernel(name) in ((which,) if which
                                                  else ("chunk", "step")))
    return s or None


def read(run):
    s = kernel_seconds(run)
    return 100.0 * s / run["trace"]["busy_s"] if s else None
