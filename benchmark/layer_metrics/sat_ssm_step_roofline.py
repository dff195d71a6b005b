"""The one-step update of the recurrent state against its memory roofline:
the bytes the step kernels of the traced stretch MUST move — per execution
of the decode step program (``jit_step``) and per Mamba block the state of
the LIVE slots read once and written once, and their convolution tail
likewise (the family's ``ssm_step_bytes`` at the window's mean occupancy;
idle slots' state is traffic, not need) — over the chip's HBM bandwidth,
over the device time of the ``%ssm_step.N`` kernels."""
from benchmark.harness import trace_reduce
from benchmark.layer_metrics import sat_ssm_share_of_device as _ssm

HEADER = dict(_ssm.HEADER, better="higher")


def read(run):
    took = _ssm.kernel_seconds(run, "step")
    if not took:
        return None
    fam, hf, c = run["family"], run["hf"], run["counters"]
    steps, _ = trace_reduce.module_stats(run["trace"], "jit_step")
    blocks = sum(1 for kind, _ in fam.blocks(hf) if kind == "mamba")
    need = steps * blocks * fam.ssm_step_bytes(hf, c["mean_occupancy"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / took
