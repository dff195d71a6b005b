#!/usr/bin/env python3
"""What the ``correct`` limits of ``xing4.0-29b-a4b-serve`` tell apart, on the
chip: one run of the cell's engine on one seed — the cell's own window and its
own ``sample_requests`` — then the SAME sampled requests put through the
harness's own comparison (``harness/correct.check_tokens_vs_reference`` under
the configuration's ``correct`` limits, as ``harness/serve_job.run`` calls it)
against the plain reference and against references that carry one seeded defect
each (``families/xing4_0.DEFECTS``; the comparison is symmetric: a defect on
either side reads the same). The GLM tool's twin (``glm4_moe_lite_defects.py``).

    python benchmark/tools/xing4_0_defects.py --seed 5900000401 \
        [--seconds 45] [--requests 12] [--only a,b]

Defects: ``precision_below`` (the WHOLE forward one precision below the stated
one: operands of every matrix product and the cached latent row in
``float8_e5m2``, the stream's mappings in bf16) and its parts ``fp8_operands``,
``latent_fp8``, ``bf16_mappings``; ``sinkhorn_one_round`` (1 round of 20);
``no_column_norm`` (the columns never normalised); ``post_without_2`` (``H_post
= sigmoid`` without its 2); ``no_h_res`` (``H_res`` the identity);
``close_by_sum`` (the closing read a plain sum of the rows); ``open_row0_only``
(the stream opened in row 0, the other rows zero); ``no_mscale`` (softmax scale
192^-1/2 without ``mscale^2`` = 2.005); ``plain_rope`` (plain rotary in place of
the YaRN table); ``v_wrong_columns`` (V from a head's FIRST 128 columns of
``W_kvb``, its keys'); ``no_routed_scale`` (``routed_scaling_factor`` left out).
Prints one line per variant with the check's ``ok`` (what ``correct`` would be),
then one ``DEFECTS`` line of JSON, and writes it to
``chiprun_out/xing4_0_defects.<seed>.json``: per variant the dict the check
returns. Not part of a run; the readings go into the configuration file's
``correct.why`` and PERF.md. ``--rehearsal``: toy widths on the CPU, where the
limits are the rehearsal's (all off), as in ``serve_job.run``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "xing4.0-29b-a4b-serve.batch-docqa"


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
        args.seconds = 20.0 if args.rehearsal else float(bench["run_seconds"])
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
           "step_shape_rounds": srv.stats()["step_shape_rounds"],
           "limits": {k: v for k, v in cc.items() if k != "why"},
           "variants": {}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"xing4_0_defects.{args.seed}.json")
    for name in names:
        ref = fam.Reference(hf, srv.engine.params, defect=name)
        chk = correct.check_tokens_vs_reference(
            samples, ref, float(cc["margin"]),
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
