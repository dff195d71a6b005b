"""Share of the device's busy time spent READING AND WRITING THE WIDENED
RESIDUAL STREAM, prefill and decode together: self time of the ops the family's
``hc_op`` finds — an operand or result whose last dim is ``hc_mult x
hidden_size`` (14336 at the published widths: the stream is carried flat, and no
other tensor of the model is that wide), or a kernel named ``%hc_read.N`` /
``%hc_write.N`` — over the busy time of the traced stretch. That is the
stream's opening (the embedding repeated), every block's read (the RMS over the
whole stream, the projection onto the 24 mapping logits, ``H_pre X``) and write
(``H_res X + H_post^T y``) and the closing read; the Sinkhorn rounds themselves
work on 16 numbers a token and are in it only where the compiler fused them
into such an op. A family without ``hc_op`` (every other one; the parent's
program cannot run the configuration) reads nothing."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "residual stream (models/hybrid.py hyper-connections)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t, fam, hf = run["trace"], run["family"], run["hf"]
    if not t or not t.get("busy_s") or not hasattr(fam, "hc_op"):
        return None
    s = trace_reduce.op_seconds(t, lambda name: fam.hc_op(name, hf))
    return 100.0 * s / t["busy_s"] if s else None
