"""Share of the device's busy time spent in the TWO mixers of a layer that
runs a Mamba-2 mixer and attention side by side, prefill and decode together:
self time of the ops the family's ``mixer_op`` finds — the recurrence's
kernels by NAME (``%ssm_scan.N``, ``%ssm_step.N``), the prompt's flash forward
(``%flash_fwd.N``), the decode read of the K/V planes (the paged kernel by its
name, or the XLA list read's ops by the gathered blocks' shape), and the
writes of both pools (ops whose result is a pool leaf of the run's own
shapes: the K/V row and block writes, the state and convolution-tail writes)
— over the busy time of the traced stretch. The projections, the convolution,
rotary, the gated norm and the sum of the two branches are XLA fusions that
touch no pool and are NOT in it: this is what the layer's two kinds of state
cost, not the whole block. A family without ``mixer_op`` (every other one,
the parent's program) reads nothing."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "parallel mixer block (models/hybrid.py P blocks)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t, fam, c = run["trace"], run["family"], run["counters"]
    if not t or not t.get("busy_s") or not hasattr(fam, "mixer_op"):
        return None
    s = trace_reduce.op_seconds(t, lambda name: fam.mixer_op(name, c))
    return 100.0 * s / t["busy_s"] if s else None
