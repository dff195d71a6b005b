"""The widened residual stream's reads and writes against the memory roofline:
the LEAST bytes any implementation moves for the tokens of the traced stretch
(the family's ``hc_bytes``: a token and block ONE read and ONE write of the
``hc_mult x hidden_size`` stream plus the block's H-wide input and output, the
closing read, and every block's mappings once a program run) over the chip's
HBM bandwidth, over the device time of the ops that touch the stream (the
family's ``hc_op``, as ``sat_hc_share_of_device`` finds them). Below 100 % by
construction: whatever an implementation moves beyond one read and one write
(the stream read once for its RMS and projection and again for the mix, a
float32 intermediate) is time, not need.

The tokens are counted per RUN of a program inside the window (the family's
``program_tokens``: every execution of ``jit_step`` x its slots, of
``jit_prefill`` x its padded row, each program's count read once from the
shapes of its own ops), NOT per op event: an implementation that materialises
the stream twice is not credited twice. Reads the raw trace for that. A family
without ``hc_op`` reads nothing."""
from benchmark.harness import program_spans, trace_reduce

HEADER = {"layer": "residual stream (models/hybrid.py hyper-connections)",
          "unit": "%", "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "higher"}


def read(run):
    t, fam, hf = run["trace"], run["family"], run["hf"]
    if not t or not t.get("devices") or not hasattr(fam, "hc_op"):
        return None
    took = trace_reduce.op_seconds(t, lambda name: fam.hc_op(name, hf))
    path = program_spans.find_xplane(run["cell"]["name"])
    if not took or path is None:
        return None
    win = [sp for sp in t["spans"] if sp[0] == trace_reduce.WINDOW_SPAN]
    lo, hi = (win[0][1], win[0][2]) if win else (float("-inf"), float("inf"))
    tokens = runs = planes = 0
    for plane in trace_reduce.read_xplane(path)["planes"]:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        mods = [(n, s, d)
                for n, s, d in trace_reduce._line(plane, trace_reduce.MODULES_LINE)
                if s >= lo and s + d <= hi
                and n.startswith(("jit_step", "jit_prefill"))]
        ops = sorted(trace_reduce._line(plane, trace_reduce.OPS_LINE),
                     key=lambda e: e[1])
        n_tok, n_run = fam.program_tokens(mods, ops, hf)
        tokens, runs, planes = tokens + n_tok, runs + n_run, planes + 1
    if not tokens:
        return None
    need = fam.hc_bytes(hf, tokens / planes, runs / planes, run["counters"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / took
