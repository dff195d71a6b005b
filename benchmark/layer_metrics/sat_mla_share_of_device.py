"""Share of the device's busy time spent in LATENT attention, both paths
together: self time of the ops that read or write the latent pool — the decode
step's read (the kernel ``%latent_decode.N`` by its name where the step takes
it, else the XLA read's ops by the gathered blocks' shape), the step's row
scatter and the prefill's block scatter (ops whose result is the pool leaf) —
and of the expanded prefill's flash forward (``%flash_fwd.N``), found by the
family's ``latent_op`` from the run's own pool shape, over the busy time of the
traced stretch. The low-rank projections, the norms and rotary are small XLA
fusions that touch no pool and are not in it. A program without a latent pool
(another family's, the parent's) reads nothing."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "latent attention (models/latent_attention.py)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t, fam, c = run["trace"], run["family"], run["counters"]
    if not t or not t.get("busy_s") or not hasattr(fam, "latent_op"):
        return None
    s = trace_reduce.op_seconds(t, lambda name: fam.latent_op(name, c))
    return 100.0 * s / t["busy_s"] if s else None
