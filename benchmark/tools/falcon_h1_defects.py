#!/usr/bin/env python3
"""What the ``correct`` limits of ``falcon-h1-34b-serve`` tell apart, on the
chip: one run of the cell's engine on one seed — the cell's own window and
its own ``sample_requests`` — then the SAME sampled requests put through the
harness's own comparison (``harness/correct.check_tokens_vs_reference`` under
the configuration's ``correct`` limits, as ``harness/serve_job.run`` calls
it) against the plain reference and against references that carry one seeded
defect each (``families/falcon_h1.DEFECTS``; the comparison is symmetric: a
defect on either side reads the same).

    python benchmark/tools/falcon_h1_defects.py --seed 5700000401 \
        [--seconds 45] [--requests 12] [--only a,b] \
        [--kv-cache-bits 0 [--max-seqs 48]]

Defects: ``precision_below`` (the WHOLE forward in the precision below the
stated one: operands of every matrix product and the convolved ``xBC`` in
``float8_e5m2``, the SSM state in bf16, K and V at 4 bits) and two of its
parts alone, ``bf16_state`` and ``kv_4bit``; ``no_key_multiplier``
(``key_multiplier`` 1); ``no_mup_vector`` (the five ``ssm_multipliers`` 1);
``no_ssm_branch`` / ``no_attn_branch`` (one of the block's two mixers left
out); ``no_rotary``; ``gate_after_norm`` (the gated norm with the gate AFTER
it); ``no_conv_bias``; ``no_D``; ``state_not_zeroed`` (a slot's last state
reaches the next request). WITNESSES (``families/falcon_h1.WITNESSES``, no
defects: the reference in the STATED precision, part by part —
``bf16_operands``, ``int8_read``, ``stated_precision``) are judged the same
way and, beside it, against the PLAIN reference on the same ids: ``floor``,
the share of the plain reference's judged positions at which the witness's
own argmax differs — what the stated precision alone flips, in code that
shares nothing with the program. ``--kv-cache-bits 0`` is the witness on the
program's side: the same engine with a float K/V pool (``--max-seqs`` fewer
slots, so that the pool of twice the bytes fits), its tokens through the same
check. Prints one line per variant with the check's
``ok`` (what ``correct`` would be), then one ``DEFECTS`` line of JSON, and
writes it to ``chiprun_out/falcon_h1_defects.<seed>.json``: per variant the
dict the check returns. Not part of a run; the readings go into the
configuration file's ``correct.why`` and PERF.md. ``--rehearsal``: toy widths
on the CPU, where the limits are the rehearsal's (all off), as in
``serve_job.run``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "falcon-h1-34b-serve.batch-reasoning"


class _Picks:
    """A reference that keeps, for every sequence it is asked for, what it
    picked at each position: (argmax, its lead over the runner-up, its
    logit)."""

    def __init__(self, ref):
        self.ref, self.picks = ref, []

    def logits(self, ids):
        import numpy as np
        lg = self.ref.logits(ids)
        top2 = np.partition(lg, -2, axis=-1)[:, -2:]
        self.picks.append((lg.argmax(axis=-1), top2[:, 1] - top2[:, 0],
                           top2[:, 1]))
        return lg


def _floor(samples, plain, witness, margin: float) -> dict:
    """A witness reference against the plain one on the same ids, at the
    generated positions the plain one judges (its lead over ``margin``): the
    share at which the witness picks another token, and by how much the top
    logit moved."""
    import numpy as np
    n = bad = 0
    moved = []
    for (prompt, gen), (a0, gap0, top0), (a1, _, top1) in zip(samples, plain,
                                                             witness):
        rows = slice(len(prompt) - 1, len(prompt) + len(gen) - 1)
        judged = gap0[rows] > margin
        n += int(judged.sum())
        bad += int((judged & (a0[rows] != a1[rows])).sum())
        moved.append(np.abs(top1[rows] - top0[rows]))
    moved = np.concatenate(moved)
    return {"floor_judged": n, "floor_mismatched": bad,
            "floor": bad / max(1, n),
            "floor_top_moved_p50": float(np.percentile(moved, 50)),
            "floor_top_moved_p99": float(np.percentile(moved, 99))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window; default BENCHMARK.json's run_seconds")
    ap.add_argument("--requests", type=int, default=None,
                    help="default the configuration's sample_requests")
    ap.add_argument("--only", default="",
                    help="comma-separated defects and witnesses, judged in "
                         "this order (default: every witness, every defect)")
    ap.add_argument("--kv-cache-bits", type=int, default=None,
                    help="the engine's K/V pool: 0 a float pool (a witness; "
                         "the configuration states 8)")
    ap.add_argument("--max-seqs", type=int, default=None,
                    help="fewer slots than the configuration's, with "
                         "--kv-cache-bits 0")
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
    tag = ""
    if args.kv_cache_bits is not None:      # the witness on the program's side
        run = cfg["run"] = dict(cfg["run"])
        run["init_serving"] = dict(run.get("init_serving", {}),
                                   kv_cache_bits=args.kv_cache_bits)
        run["expect"] = dict(run["expect"], kv_cache_bits=args.kv_cache_bits)
        if args.max_seqs:
            run["serving"] = dict(run["serving"], max_seqs=args.max_seqs)
        tag = f".kv{args.kv_cache_bits}"
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
    names = [None] + (args.only.split(",") if args.only
                      else list(fam.WITNESSES + fam.DEFECTS))
    out = {"seed": args.seed, "seconds": args.seconds, "finished": len(done),
           "sampled": len(samples),
           "tokens_per_s": d["tokens_in_window"] / d["window_s"],
           "step_shape_rounds": srv.stats()["step_shape_rounds"],
           "kv_cache_bits": int(srv.model.config.kv_cache_bits or 0),
           "max_seqs": srv.config.max_seqs,
           "limits": {k: v for k, v in cc.items() if k != "why"},
           "variants": {}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"falcon_h1_defects.{args.seed}{tag}.json")
    plain_picks = None
    for name in names:
        ref = _Picks(fam.Reference(hf, srv.engine.params, defect=name))
        chk = correct.check_tokens_vs_reference(
            samples, ref, float(cc["margin"]),
            float(cc["min_judged_share"]), float(cc["min_agreement"]),
            float(cc.get("max_mismatch_share", 0.0)))
        line = (f"{name or 'plain'}: correct {str(chk['ok']).lower()}: "
                f"{chk['mismatched']} of {chk['judged']} judged mismatched "
                f"({100 * chk['mismatch_share']:.2f} % against "
                f"{100 * chk['max_mismatch_share']:.2f} %), agreement "
                f"{chk['agreement']:.4f} against {chk['min_agreement']:g}, "
                f"judged share {chk['judged_share']:.3f}")
        if name is None:
            plain_picks = ref.picks
        elif name in fam.WITNESSES:
            chk.update(_floor(samples, plain_picks, ref.picks,
                              float(cc["margin"])))
            line += (f"; against the plain reference itself: "
                     f"{chk['floor_mismatched']} of {chk['floor_judged']} "
                     f"({100 * chk['floor']:.2f} %), its top logit moved by "
                     f"{chk['floor_top_moved_p50']:.4f} (median) / "
                     f"{chk['floor_top_moved_p99']:.4f} (p99)")
        out["variants"][name or "plain"] = chk
        print(line, flush=True)
        with open(path, "w") as f:          # after every variant: a cut call
            json.dump(out, f, indent=1)     # keeps what it got
    srv.close()
    print("DEFECTS " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
