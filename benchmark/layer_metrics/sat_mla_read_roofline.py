"""The decode step's read of the latent pool against its memory roofline: the
bytes the decode steps of the traced stretch MUST read of the latent planes —
per execution of the step program (``jit_step``, as ``decode_step_device_ms``
counts them) the LIVE rows of every plane once, a plane being both K and V
(the family's ``latent_bytes_per_token``, the row's bytes and the planes taken
from the run's ``stats``, x the mean of the live rows sampled after each round:
rows, never the whole blocks a read fetches nor the lanes the chip pads a row
to, so it cannot pass 100 %) — over the chip's HBM bandwidth, over the device
time of the read's ops (the family's ``latent_read_op``: the kernel by its
name, else the XLA read's gather and two contractions by the gathered blocks'
shape). A program without a latent pool reads nothing."""
from benchmark.harness import trace_reduce

HEADER = {"layer": "latent attention (models/latent_attention.py)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "higher"}


def read(run):
    t, fam, c = run["trace"], run["family"], run["counters"]
    if not t or not hasattr(fam, "latent_read_op"):
        return None
    took = trace_reduce.op_seconds(t, lambda name: fam.latent_read_op(name, c))
    live = c.get("mean_live_tokens")
    if not took or not live:
        return None
    steps, _ = trace_reduce.module_stats(t, "jit_step")
    need = steps * live * fam.latent_bytes_per_token(run["hf"], c)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / took
