"""The one-step update of the matrix state against its memory roofline: the
bytes the step kernels of the traced stretch MUST move — per execution of the
decode step program (``jit_step``) and per Gated DeltaNet block the state of
the LIVE slots read once and written once, and their convolution tail
likewise (the family's ``gdn_step_bytes`` at the window's mean occupancy;
idle slots' state is traffic, not need) — over the chip's HBM bandwidth, over
the device time of the ``%gdn_step.N`` kernels."""
from benchmark.harness import trace_reduce
from benchmark.layer_metrics import sat_gdn_share_of_device as _gdn

HEADER = dict(_gdn.HEADER, better="higher")


def read(run):
    took = _gdn.kernel_seconds(run, "step")
    if not took:
        return None
    fam, hf, c = run["family"], run["hf"], run["counters"]
    steps, _ = trace_reduce.module_stats(run["trace"], "jit_step")
    need = steps * fam.count(hf, "gdn") * fam.gdn_step_bytes(hf, c["mean_occupancy"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / took
