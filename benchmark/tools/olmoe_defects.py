#!/usr/bin/env python3
"""What the ``correct`` limits of ``olmoe-1b-7b-serve`` tell apart, on the
chip: one run of the cell's engine on one seed, then the SAME sampled
requests judged by the plain reference and by references that carry one
seeded defect each (the comparison is symmetric: a defect on either side
reads the same).

    python benchmark/tools/olmoe_defects.py --seed 2600000401 \
        [--seconds 20] [--requests 32] [--margins 0.05,0.1,0.15]

Defects: ``renormalise`` (top-8 weights divided by their sum: Mixtral's
rule), ``top7`` (every token loses its weakest expert: a dropped
assignment), ``no_qk_norm``, ``bf16_router`` (router logits rounded to
bf16 before the softmax), ``kv_4bit`` (K and V rounded to 4 bits per
(position, head): the nearest precision below the int8 pool the
configuration states). Prints one ``DEFECTS`` line of JSON and writes it to
``chiprun_out/olmoe_defects.<seed>.json``: per variant and margin the
numbers ``harness/correct.check_tokens_vs_reference`` would report over the
first 8, 16, ... sampled requests. Not part of a run; the readings go into
the configuration file's ``correct.why`` and PERF.md.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "olmoe-1b-7b-serve.batch-longprompt"


def variants(fam, hf, params):
    import jax.numpy as jnp
    from benchmark.families.mistral import _at, _rms

    def _attn_without_qk_norm(self, layers, i, x):
        H, nh, nkv, hd = fam.dims(self.hf)
        S, eps = x.shape[0], self.hf["rms_norm_eps"]
        h = _rms(x, _at(layers, "ln1_scale", i), eps)
        q, k, v = (h @ _at(layers, n, i) for n in ("wq", "wk", "wv"))
        theta = float(self.hf.get("rope_theta", 10000.0))
        q = fam._rope(q.reshape(S, nh, hd), theta).reshape(S, nkv, nh // nkv, hd)
        k = fam._rope(k.reshape(S, nkv, hd), theta)
        v = v.reshape(S, nkv, hd)
        return _attend(self, layers, i, x, q, k, v)

    def _attend(self, layers, i, x, q, k, v):
        import jax
        S, hd = x.shape[0], q.shape[-1]
        s = jnp.einsum("sngd,tnd->ngst", q, k) / (hd ** 0.5)
        ok = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("ngst,tnd->sngd", p, v).reshape(S, -1)
        x = x + o @ _at(layers, "wo", i)
        return x, _rms(x, _at(layers, "ln2_scale", i), self.hf["rms_norm_eps"])

    class NoQKNorm(fam.Reference):
        _attn_block = _attn_without_qk_norm

    class KV4Bit(fam.Reference):
        def _attn_block(self, layers, i, x):
            H, nh, nkv, hd = fam.dims(self.hf)
            S, eps = x.shape[0], self.hf["rms_norm_eps"]
            h = _rms(x, _at(layers, "ln1_scale", i), eps)
            q = _rms(h @ _at(layers, "wq", i), _at(layers, "q_norm", i), eps)
            k = _rms(h @ _at(layers, "wk", i), _at(layers, "k_norm", i), eps)
            v = h @ _at(layers, "wv", i)
            theta = float(self.hf.get("rope_theta", 10000.0))
            q = fam._rope(q.reshape(S, nh, hd), theta).reshape(S, nkv, nh // nkv, hd)

            def four_bits(a):                    # symmetric, per (position, head)
                scale = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 7.0
                return jnp.round(a / jnp.where(scale > 0, scale, 1.0)) * scale
            k = four_bits(fam._rope(k.reshape(S, nkv, hd), theta))
            return _attend(self, layers, i, x, q, k, four_bits(v.reshape(S, nkv, hd)))

    class BF16Router(fam.Reference):
        def _router(self, layers, i, h):
            import jax
            logits = (h @ _at(layers, "wg", i)).astype(jnp.bfloat16).astype(jnp.float32)
            top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     self.hf["num_experts_per_tok"])
            onehot = jax.nn.one_hot(idx, logits.shape[-1], dtype=jnp.float32)
            return jnp.einsum("sk,ske->se", top, onehot)

    return {
        "plain": fam.Reference(hf, params),
        "renormalise": fam.Reference(dict(hf, norm_topk_prob=True), params),
        "top7": fam.Reference(dict(hf, num_experts_per_tok=hf["num_experts_per_tok"] - 1), params),
        "no_qk_norm": NoQKNorm(hf, params),
        "bf16_router": BF16Router(hf, params),
        "kv_4bit": KV4Bit(hf, params),
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
    from benchmark.harness import common, loadgen, serve_job
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
    out = {"seed": args.seed, "finished": len(done), "sampled": len(samples),
           "tokens_per_s": d["tokens_in_window"] / d["window_s"], "variants": {}}
    for name, ref in refs.items():
        per_request = []
        for prompt, generated in samples:
            ids = np.concatenate([prompt, generated])
            lg = ref.logits(ids)[prompt.size - 1: ids.size - 1]
            top2 = np.partition(lg, -2, axis=-1)[:, -2:]
            per_request.append((top2[:, 1] - top2[:, 0], lg.argmax(axis=-1) == generated))
        sizes = sorted({n for n in (8, 16, 32, 48, len(samples)) if n <= len(samples)})
        out["variants"][name] = {f"{m:g}": {str(n): judge(per_request[:n], m) for n in sizes}
                                 for m in margins}
        full = judge(per_request, margins[-1])
        print(f"{name}: agreement {full['agreement']:.4f}, {full['mismatched']} of "
              f"{full['judged']} judged mismatched at margin {margins[-1]:g}, worst "
              f"mismatch margin {full['worst_mismatch_margin']:.4f}", flush=True)
    srv.close()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"olmoe_defects.{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print("DEFECTS " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
