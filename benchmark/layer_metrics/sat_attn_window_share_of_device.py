"""Share of the device's busy time spent in the WINDOW blocks' attention,
prefill and decode together: self time of the banded flash forward
(``%flash_fwd_band.N``) and of every op that reads or writes a window ring in
place (the family's ``window_op`` finds both in the device trace, the ring ops
by the ring leaves' shapes) over the busy time of the traced stretch. The
projections, the q/k norms, rotary and the softmax over the gathered scores
are XLA fusions that touch no ring and are not in it. A program without
window blocks (another family's, the parent's) reads nothing."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "window attention (models/hybrid.py, ops/flash_attention.py)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t, fam, hf = run["trace"], run["family"], run["hf"]
    if not t or not t.get("busy_s") or not hasattr(fam, "window_op"):
        return None
    s = trace_reduce.op_seconds(t, lambda name: fam.window_op(name, hf))
    return 100.0 * s / t["busy_s"] if s else None
