"""The banded flash forward against the chip's matmul peak: for every
execution of a ``%flash_fwd_band.N`` kernel in the traced stretch, the FLOPs
its VISIBLE (query, key) pairs need (the family's ``flash_band_flops`` at the
positions the call ran, read off its result ``[1, kv heads, group, S, head
dim]``: S W - W (W - 1) / 2 pairs past the window, whatever tiles the kernel
visits) over 197 TFLOP/s, summed, over the device time those executions took.
Reads the raw trace (events, not sums): each prompt bucket has its own
floor."""
from benchmark.harness import program_spans, trace_reduce
from benchmark.layer_metrics import sat_attn_window_share_of_device as _win

HEADER = dict(_win.HEADER, better="higher")


def read(run):
    t, fam = run["trace"], run["family"]
    if not t or not t.get("devices") or not hasattr(fam, "flash_band_kernel"):
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
            if start < lo or start + dur > hi:
                continue
            positions = fam.flash_band_kernel(name)
            if positions is None:
                continue
            need += fam.flash_band_flops(hf, positions) / pk["bf16_flops_per_s"]
            took += dur / 1e9
    return 100.0 * need / took if took else None
