"""The chunk form over a prompt against the peak that binds it: for every
execution of a ``%gdn_chunk.N`` kernel in the traced stretch, the larger of
the FLOPs the chunk form needs over 197 TFLOP/s and the bytes it must move
(q, k, v, g, beta in, o out, the state in and out) over 819 GB/s, by the
family's own count (``gdn_chunk_flops``, ``gdn_chunk_bytes``) at the
positions the call ran (its result ``[value heads, chunks, chunk, value
dim]``: the prompt bucket rounded up to whole chunks), summed, over the
device time those executions took. What the kernel spends beyond that (the
doublings of its triangular solve at the highest precision, float32 outputs)
is time, not need. Reads the raw trace (events, not sums): each bucket has its
own floor."""
import re

from benchmark.harness import program_spans, trace_reduce
from benchmark.layer_metrics import sat_gdn_share_of_device as _gdn

HEADER = dict(_gdn.HEADER, better="higher")
_RESULT = re.compile(r"= \(?f32\[\d+,(\d+),(\d+),\d+\]")


def read(run):
    t, fam = run["trace"], run["family"]
    if not t or not t.get("devices") or not hasattr(fam, "gdn_kernel"):
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
            if start < lo or start + dur > hi or fam.gdn_kernel(name) != "chunk":
                continue
            chunks, chunk = (int(x) for x in _RESULT.search(name).groups())
            need += max(fam.gdn_chunk_flops(hf, chunks * chunk, chunk)
                        / pk["bf16_flops_per_s"],
                        fam.gdn_chunk_bytes(hf, chunks * chunk)
                        / pk["hbm_bytes_per_s"])
            took += dur / 1e9
    return 100.0 * need / took if took else None
