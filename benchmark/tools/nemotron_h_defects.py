#!/usr/bin/env python3
"""What the ``correct`` limits of ``nemotron-3-nano-30b-serve`` tell apart, on
the chip: one run of the cell's engine on one seed — the cell's own window
and its own ``sample_requests`` — then the SAME sampled requests put through
the harness's own comparison (``harness/correct.check_tokens_vs_reference``
under the configuration's ``correct`` limits, as ``harness/serve_job.run``
calls it) against the plain reference and against references that carry one
seeded defect each (``families/nemotron_h.DEFECTS``; the comparison is
symmetric: a defect on either side reads the same).

    python benchmark/tools/nemotron_h_defects.py --seed 3000000401 \
        [--seconds 45] [--requests 12] [--only a,b]

Defects: ``softmax_router`` (softmax in place of the sigmoid scores),
``no_shared_expert``, ``no_routed_scale`` (no x 2.5), ``swiglu_experts`` /
``relu_experts`` (another expert form), ``rotary`` (a positional embedding in
the attention block), ``pad_moves_state`` (the prompt bucket's pad rows
advance the recurrence), ``pad_in_conv_tail`` (the convolution tail handed to
decode is the bucket's last rows, pads included), ``bf16_state`` (the SSM
state rounded to bf16 after every position), ``state_not_zeroed`` (a slot
carries the last request's state), ``kv_4bit`` (K and V of the attention
block rounded to 4 bits per (position, head)), ``precision_below`` (the WHOLE
forward in the precision below the stated one: operands of every matrix
product in ``float8_e5m2``, bf16 state, 4-bit K/V). Prints one line per
variant with the check's
``ok`` (what ``correct`` would be), then one ``DEFECTS`` line of JSON, and
writes it to ``chiprun_out/nemotron_h_defects.<seed>.json``: per variant the
dict the check returns. Not part of a run; the readings go into the
configuration file's ``correct.why`` and PERF.md. ``--rehearsal``: toy widths
on the CPU (~1 min), where the limits are the rehearsal's (all off), as in
``serve_job.run``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "nemotron-3-nano-30b-serve.batch-reasoning"


class PerRequest:
    """The reference as the check calls it (``logits(ids)``), told each
    request's prompt length first: the padding defects lay the prompt out as
    the engine's prefill saw it."""

    def __init__(self, ref, samples):
        import numpy as np
        self.ref = ref
        self.prompt_len = {np.concatenate(s).astype(np.int32).tobytes():
                           len(s[0]) for s in samples}

    def logits(self, ids):
        import numpy as np
        self.ref.prompt_len = self.prompt_len[
            np.asarray(ids, np.int32).tobytes()]
        return self.ref.logits(ids)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window; default BENCHMARK.json's run_seconds")
    ap.add_argument("--requests", type=int, default=None,
                    help="default the configuration's sample_requests")
    ap.add_argument("--only", default="",
                    help="comma-separated defects, judged in this order")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from benchmark.harness import common, correct, loadgen, serve_job
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    if not args.rehearsal:
        enable_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    cfg = common.load_config(cell["config"])
    cc = cfg["correct"]
    if args.rehearsal:             # as serve_job.run: toy logits are near-ties
        cc = dict(cc, margin=0.0, min_judged_share=0.0, min_agreement=0.0,
                  max_mismatch_share=1.0)
    if args.seconds is None:
        args.seconds = 5.0 if args.rehearsal else float(bench["run_seconds"])
    if args.requests is None:
        args.requests = int(cc["sample_requests"])
    fam = loadgen.load_family(cfg)
    traffic = loadgen.load_traffic(cell["traffic"])
    srv, hf, traffic = serve_job.build(cell, cfg, traffic, args.seed, args.rehearsal)
    schedule = loadgen.generate(traffic, args.seed, {
        "vocab_size": hf["vocab_size"], "seconds": args.seconds,
        "max_model_len": srv.max_model_len})
    serve_job.warm(srv, traffic, hf["vocab_size"], args.seed)
    d = serve_job.drive(srv, schedule, args.seconds, float(traffic.get("drain_s", 0.0)))
    finished, rid_of = d["finished"], d["rid_of"]
    rng = np.random.default_rng([args.seed, 0x636865636B])     # run()'s sample
    done = sorted(idx for idx, rid in rid_of.items() if rid in finished)
    pick = rng.permutation(len(done))[:args.requests]
    samples = [(np.asarray(schedule[done[j]]["prompt"], np.int32),
                np.asarray(finished[rid_of[done[j]]].generated, np.int32)) for j in pick]
    names = [None] + (args.only.split(",") if args.only else list(fam.DEFECTS))
    out = {"seed": args.seed, "seconds": args.seconds, "finished": len(done),
           "sampled": len(samples),
           "tokens_per_s": d["tokens_in_window"] / d["window_s"],
           "limits": {k: v for k, v in cc.items() if k != "why"},
           "variants": {}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"nemotron_h_defects.{args.seed}.json")
    for name in names:
        ref = fam.Reference(hf, srv.engine.params, defect=name)
        ref.prompt_bucket = srv.config.prompt_bucket
        chk = correct.check_tokens_vs_reference(
            samples, PerRequest(ref, samples), float(cc["margin"]),
            float(cc["min_judged_share"]), float(cc["min_agreement"]),
            float(cc.get("max_mismatch_share", 0.0)))
        out["variants"][name or "plain"] = chk
        print(f"{name or 'plain'}: correct {str(chk['ok']).lower()}: "
              f"{chk['mismatched']} of {chk['judged']} judged mismatched "
              f"({100 * chk['mismatch_share']:.2f} % against "
              f"{100 * chk['max_mismatch_share']:.2f} %), agreement "
              f"{chk['agreement']:.4f} against {chk['min_agreement']:g}, "
              f"judged share {chk['judged_share']:.3f}", flush=True)
        with open(path, "w") as f:          # after every variant: a cut call
            json.dump(out, f, indent=1)     # keeps what it got
    srv.close()
    print("DEFECTS " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
