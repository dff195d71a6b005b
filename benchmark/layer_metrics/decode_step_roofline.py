"""The decode step's share of its memory roofline: bytes one step must
read (the configuration's family says how many, `decode_step_bytes` of
families/<model_type>.py, from the run's counters: for Mistral every layer's
weights and the head once + the live KV rows at the mean of the lengths
sampled after each round) over the chip's HBM bandwidth, over the measured
step time. Memory-bound: a step does ~2 FLOPs per weight byte per sequence."""
from benchmark.layer_metrics import decode_step_device_ms

HEADER = {"layer": "decode step (models/transformer.py decode_step_paged)",
          "unit": "%", "moves": "tpot_p90_ms", "jobs": ["serve"],
          "source": "device_trace", "better": "higher"}


def read(run):
    ms = decode_step_device_ms.read(run)
    if not ms:
        return None
    need = run["family"].decode_step_bytes(run["hf"], run["counters"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
