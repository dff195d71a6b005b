"""The expert layer's matmuls against their roofline: for every execution
of one in the traced stretch, the larger of bytes / 819 GB/s and FLOPs /
197 TFLOP/s it NEEDS, summed, over the device time those executions took.
The ops are found by the family's ``expert_matmul``, which also says how
many of the layer's three matrices an op streams, and the two cost
functions are the family's too (``moe_ffn_bytes``, ``moe_ffn_flops`` of
families/olmoe.py: a third of a layer's cost per matrix): bytes = the
matrices of the experts the call's tokens TOUCHED + the tokens x top-k rows
in and out, FLOPs = 2 per multiply-add over those rows — whatever the op
computed beyond that (every expert over every row, pad rows) is time, not
need.

Tokens and touched experts per execution: a call of at most ``max_seqs``
tokens is a decode step's — its active slots are ``mean_occupancy`` and its
touched experts the engine's counter ``moe_experts_touched_per_step``; a
larger one is a prefill's — its tokens are the prompt bucket's (pad tokens
counted) and it is charged the experts that the counter
``moe_experts_touched_per_prefill`` says a prompt touched. Reads the raw
trace (events, not sums) because each shape has its own floor.
``grouped_only`` keeps the sorted form's grouped matmuls alone
(``sat_moe_sorted_ffn_roofline``)."""
from benchmark.harness import program_spans, trace_reduce

HEADER = {"layer": "expert layer (moe/sharded_moe.py)", "unit": "%",
          "moves": "serve_tokens_per_s", "jobs": ["serve"],
          "source": "device_trace", "better": "higher"}


def read(run, grouped_only: bool = False):
    t, fam = run["trace"], run["family"]
    if not t or not t.get("devices") or not hasattr(fam, "expert_matmul"):
        return None
    path = program_spans.find_xplane(run["cell"]["name"])
    stats = run["counters"].get("stats") or {}
    if path is None or "moe_experts_touched_per_step" not in stats:
        return None
    hf, pk, c = run["hf"], run["peaks"], run["counters"]
    k = hf["num_experts_per_tok"]
    win = [sp for sp in t["spans"] if sp[0] == trace_reduce.WINDOW_SPAN]
    lo, hi = (win[0][1], win[0][2]) if win else (float("-inf"), float("inf"))
    need = took = 0.0
    for plane in trace_reduce.read_xplane(path)["planes"]:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for name, start, dur in trace_reduce._line(plane, trace_reduce.OPS_LINE):
            if start < lo or start + dur > hi:
                continue
            found = fam.expert_matmul(name, hf)
            if found is None or (grouped_only and not fam.is_grouped_matmul(name)):
                continue
            tokens, matrices = found
            if tokens <= c["max_seqs"]:                        # a decode step
                tokens = c["mean_occupancy"]
                touched = stats["moe_experts_touched_per_step"]
            else:
                touched = stats.get("moe_experts_touched_per_prefill",
                                    hf["num_experts"])
            need += matrices / 3 * max(
                fam.moe_ffn_bytes(hf, tokens * k, touched) / pk["hbm_bytes_per_s"],
                fam.moe_ffn_flops(hf, tokens * k) / pk["bf16_flops_per_s"])
            took += dur / 1e9
    return 100.0 * need / took if took else None
