#!/usr/bin/env python3
"""What the ``correct`` limit of ``mellum2-12b-train`` tells apart, on the
chip: the cell's engine built from one seed as ``harness/train_job.run``
builds it, trained ``--steps`` steps over the cell's pool, then the NEXT
batch's loss by the engine against the plain reference's and against
references that carry one seeded defect each (``families/mellum.DEFECTS``),
through the harness's own comparison (``harness/correct.
check_loss_vs_reference`` under the configuration's ``loss_rel_tol``).

    python benchmark/tools/mellum_defects.py --seed 4800000401 \
        [--steps 100] [--only a,b]

``precision_below`` is the WHOLE forward in the precision below the stated
one (both operands of every matrix product in ``float8_e5m2``). Prints one
line per variant with the check's ``ok`` (what ``correct`` would be), then
one ``DEFECTS`` line of JSON, and writes it to ``chiprun_out/
mellum_defects.<seed>.json``. Not part of a run; the readings go into the
configuration file's ``correct.why`` and PERF.md. ``--rehearsal``: toy
widths on the CPU.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "mellum2-12b-train.seq8192"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--only", default="",
                    help="comma-separated defects, judged in this order")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import deepspeed_tpu
    from benchmark.harness import common, correct, loadgen
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    if not args.rehearsal:
        enable_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    cfg = common.load_config(cell["config"])
    fam = loadgen.load_family(cfg)
    hf = common.hf_of(cfg, args.rehearsal)
    traffic = dict(loadgen.load_traffic(cell["traffic"]))
    if args.rehearsal:
        for key in ("seq_len", "tokens_per_step"):
            traffic[key] //= common.REHEARSAL_SHRINK
    sched = loadgen.generate(traffic, args.seed, {"vocab_size": hf["vocab_size"]})
    pool = sched["pool"]
    ds = dict(cfg["run"]["engine"], train_batch_size=sched["sequences_per_step"])
    engine, *_ = deepspeed_tpu.initialize(
        model=make_model(common.model_config(cfg, hf, sched["seq_len"]),
                         name=cell["config"]),
        config=ds, rng=jax.random.PRNGKey(args.seed))
    engine.train_batches(({"input_ids": pool[i % len(pool)]}
                          for i in range(args.steps)), args.steps)
    jax.block_until_ready(engine.state)
    batch = pool[args.steps % len(pool)]
    names = [None] + (args.only.split(",") if args.only else list(fam.DEFECTS))
    # the references first: the engine's step donates the parameters
    refs = {name or "plain": fam.Reference(
        hf, engine.state["params"], defect=name).loss(batch) for name in names}
    m = engine.train_batch({"input_ids": batch})
    eng = float(m["loss"])
    out = {"seed": args.seed, "steps": args.steps, "engine_loss": eng,
           "loss_rel_tol": cfg["correct"]["loss_rel_tol"],
           "step_metrics": {k: float(v) for k, v in m.items()}, "variants": {}}
    for name, ref in refs.items():
        chk = correct.check_loss_vs_reference(
            eng, ref, float(cfg["correct"]["loss_rel_tol"]))
        out["variants"][name] = chk
        print(f"{name}: correct {str(chk['ok']).lower()}: engine {eng:.6f} "
              f"reference {ref:.6f} rel_err {chk['rel_err']:.3e} against "
              f"{chk['rel_tol']:g}", flush=True)
    engine.close()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"mellum_defects.{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print("DEFECTS " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
