"""The chunked scan of a prompt against the peak that binds it: for every
execution of a ``%ssm_scan.N`` kernel in the traced stretch, the larger of
the FLOPs of the scan's four products over 197 TFLOP/s and the bytes it must
move (x, B, C, dt in, y out, the state in and out) over 819 GB/s, by the
family's own count (``ssm_scan_flops``, ``ssm_scan_bytes``) at the positions
the call ran (its result ``[heads, T, head dim]``: the prompt bucket rounded
up to whole chunks), summed, over the device time those executions took.
What the kernel moves beyond that (its decay matrices, float32 outputs) is
time, not need. Reads the raw trace (events, not sums): each bucket has its
own floor."""
import re

from benchmark.harness import program_spans, trace_reduce
from benchmark.layer_metrics import sat_ssm_share_of_device as _ssm

HEADER = dict(_ssm.HEADER, better="higher")
_RESULT = re.compile(r"= \(?f32\[\d+,(\d+),\d+\]")


def read(run):
    t, fam = run["trace"], run["family"]
    if not t or not t.get("devices") or not hasattr(fam, "ssm_kernel"):
        return None
    path = program_spans.find_xplane(run["cell"]["name"])
    if path is None:
        return None
    hf, pk = run["hf"], run["peaks"]
    win = [sp for sp in t["spans"] if sp[0] == trace_reduce.WINDOW_SPAN]
    lo, hi = (win[0][1], win[0][2]) if win else (float("-inf"), float("inf"))
    need = took = 0.0
    for plane in trace_reduce.read_xplane(path)["planes"]:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for name, start, dur in trace_reduce._line(plane, trace_reduce.OPS_LINE):
            if start < lo or start + dur > hi or fam.ssm_kernel(name) != "scan":
                continue
            tokens = int(_RESULT.search(name).group(1))
            need += max(fam.ssm_scan_flops(hf, tokens) / pk["bf16_flops_per_s"],
                        fam.ssm_scan_bytes(hf, tokens) / pk["hbm_bytes_per_s"])
            took += dur / 1e9
    return 100.0 * need / took if took else None
