#!/usr/bin/env python3
"""What the ``correct`` limits of ``ouro-2.6b-serve`` tell apart, on the
chip: one run of the cell's engine on one seed, then the SAME sampled
requests judged by the plain reference and by references that carry one
seeded defect each (the comparison is symmetric: a defect on either side
reads the same).

    python benchmark/tools/ouro_defects.py --seed 3500000401 \
        [--seconds 20] [--requests 32] [--margins 0.05,0.1,0.15]

Defects (``variants``, which ``tests/unit/test_ouro.py`` holds the program's
logits against too): ``three_passes`` (the stack walked 3 times, not 4),
``no_between_pass_norm`` (the final norm applied once, after the last pass,
as an unlooped model has it), ``no_sandwich_norm`` (a sublayer's output joins
the residual unnormed), ``shared_kv_planes`` (every pass reads the K/V that
pass 0 computed for the layer: one plane a layer), ``kv_4bit`` (K and V
rounded to 4 bits per (position, head): the nearest precision below the int8
pool the configuration states), ``fp8_operands`` (both operands of every
product with a weight matrix rounded to ``float8_e5m2``, the nearest
precision below the bf16 the configuration states, K/V as the plain
reference keeps it: the lower COMPUTE precision alone), ``precision_below``
(the two together, the WHOLE forward one precision below the stated one:
the control that has to come out ``correct: false``). Prints one line per
variant with ``ok`` — what ``harness/correct.check_tokens_vs_reference``
ITSELF returns for the variant's logits under the configuration's own
limits, i.e. what ``correct`` would be — then one ``DEFECTS`` line of JSON,
written to ``chiprun_out/ouro_defects.<seed>.json`` too: per variant that
check's dict and, per margin of ``--margins``, the same numbers over the
first 8, 16, ... sampled requests. Not part of a run; the readings go into
the configuration file's ``correct.why`` and PERF.md. ``--rehearsal``: toy
widths on the CPU, where the limits are the rehearsal's (all off).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "ouro-2.6b-serve.batch-worked-answers"


def variants(fam, hf, params):
    import jax.numpy as jnp

    class NoBetweenPassNorm(fam.Reference):
        def _handed_on(self, s, x):
            return x

    class NoSandwichNorm(fam.Reference):
        def _after(self, y, scale):
            return y

    class SharedKVPlanes(fam.Reference):
        def _reads(self, t, i):
            return 0, i

    class KV4Bit(fam.Reference):
        def _stored(self, rows):                 # symmetric, per (position, head)
            scale = jnp.max(jnp.abs(rows), axis=-1, keepdims=True) / 7.0
            return jnp.round(rows / jnp.where(scale > 0, scale, 1.0)) * scale

    class FP8Operands(fam.Reference):
        def _mm(self, a, w):
            return (a.astype(jnp.float8_e5m2).astype(jnp.float32)
                    @ w.astype(jnp.float8_e5m2).astype(jnp.float32))

    class PrecisionBelow(FP8Operands, KV4Bit):
        pass

    return {
        "plain": fam.Reference(hf, params),
        "three_passes": fam.Reference(
            dict(hf, total_ut_steps=hf["total_ut_steps"] - 1), params),
        "no_between_pass_norm": NoBetweenPassNorm(hf, params),
        "no_sandwich_norm": NoSandwichNorm(hf, params),
        "shared_kv_planes": SharedKVPlanes(hf, params),
        "kv_4bit": KV4Bit(hf, params),
        "fp8_operands": FP8Operands(hf, params),
        "precision_below": PrecisionBelow(hf, params),
    }


def judge(per_request, margin):
    """check_tokens_vs_reference's numbers from (gap, same) per request."""
    import numpy as np
    gap = np.concatenate([g for g, _ in per_request])
    same = np.concatenate([s for _, s in per_request])
    judged = gap > margin
    bad = judged & ~same
    return {"requests": len(per_request), "positions": int(gap.size),
            "judged": int(judged.sum()), "mismatched": int(bad.sum()),
            "judged_share": float(judged.mean()), "agreement": float(same.mean()),
            "mismatch_share": float(bad.sum() / max(1, judged.sum())),
            "worst_mismatch_margin": float(gap[~same].max()) if (~same).any() else 0.0}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--margins", default="0.05,0.1,0.15")
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
        cell = {w["name"]: w for w in json.load(f)["workloads"]}[CELL]
    cfg = common.load_config(cell["config"])
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
    refs = variants(fam, hf, srv.engine.params)
    margins = [float(m) for m in args.margins.split(",")]
    cc = cfg["correct"]
    if args.rehearsal:                           # as serve_job.run: all off
        cc = dict(cc, margin=0.0, min_judged_share=0.0, min_agreement=0.0,
                  max_mismatch_share=1.0)

    class Kept:
        """One variant's logits, computed once, as the check asks for them."""
        def __init__(self, ref):
            self.by_ids = {np.concatenate(s).tobytes(): ref.logits(np.concatenate(s))
                           for s in samples}

        def logits(self, ids):
            return self.by_ids[np.asarray(ids, np.int32).tobytes()]

    out = {"seed": args.seed, "finished": len(done), "sampled": len(samples),
           "tokens_per_s": d["tokens_in_window"] / d["window_s"],
           "limits": {k: cc[k] for k in ("margin", "min_judged_share",
                                         "min_agreement", "max_mismatch_share")},
           "variants": {}}
    for name, ref in refs.items():
        kept = Kept(ref)
        check = correct.check_tokens_vs_reference(
            samples, kept, float(cc["margin"]), float(cc["min_judged_share"]),
            float(cc["min_agreement"]), float(cc["max_mismatch_share"]))
        per_request = []
        for prompt, generated in samples:
            ids = np.concatenate([prompt, generated])
            lg = kept.logits(ids)[prompt.size - 1: ids.size - 1]
            top2 = np.partition(lg, -2, axis=-1)[:, -2:]
            per_request.append((top2[:, 1] - top2[:, 0], lg.argmax(axis=-1) == generated))
        sizes = sorted({n for n in (8, 16, 32, 48, len(samples)) if n <= len(samples)})
        out["variants"][name] = dict(
            {f"{m:g}": {str(n): judge(per_request[:n], m) for n in sizes} for m in margins},
            check=check)
        print(f"{name}: ok={check['ok']} under the configuration's limits: agreement "
              f"{check['agreement']:.4f}, {check['mismatched']} of {check['judged']} judged "
              f"mismatched at margin {check['margin']:g}, worst mismatch margin "
              f"{check['worst_mismatch_margin']:.4f}", flush=True)
    srv.close()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"ouro_defects.{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print("DEFECTS " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
