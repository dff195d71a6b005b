"""The serving job: one engine, one thread, an open loop on a schedule.

The generator and the server share the thread: requests that are due are
handed to ``add_request`` between rounds (the engine admits at round
boundaries anyway), then ``step()`` runs one round. Times are taken from
when a request was DUE, so a round that makes the generator late is
counted against the server, and how late the generator ran is reported.

``build`` / ``warm`` / ``drive`` are also what ``tools/sweep.py`` uses.
"""
import time

import numpy as np

from . import common, correct, loadgen, metrics
from .common import log


def pad_prompt(n: int, bucket: int, max_len: int) -> int:
    """The prompt bucket a prompt of n tokens is padded to
    (``ServingConfig.prompt_bucket`` granularity, capped at the context)."""
    return max(bucket, min(-(-n // bucket) * bucket, max_len))


def build(cell, cfg, traffic, seed: int, rehearsal: bool):
    """The cell's engine from the seed -> (srv, hf, traffic as run)."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import make_model

    hf = common.hf_of(cfg, rehearsal)
    run_cfg = cfg["run"]
    serving = dict(run_cfg["serving"])
    if rehearsal:
        k = common.REHEARSAL_SHRINK
        serving["max_model_len"] //= k
        traffic = dict(traffic)
        for key in ("prompt", "output"):
            traffic[key] = {n: (max(1, v // k) if n != "sigma" else v)
                            for n, v in traffic[key].items()}
    mcfg = common.model_config(cfg, hf, serving["max_model_len"])
    srv = deepspeed_tpu.init_serving(
        make_model(mcfg, name=cell["config"]), serving=serving,
        rng=jax.random.PRNGKey(seed), **run_cfg.get("init_serving", {}))
    kv_bits = int(srv.model.config.kv_cache_bits or 0)
    log(f"engine: decode_backend={srv.decode_backend} kv_cache_bits={kv_bits} pool "
        + ", ".join(f"{k} {v['shape']} {v['dtype']}" for k, v in pool_leaves(srv).items())
        + f" max_seqs={srv.config.max_seqs} max_model_len={srv.max_model_len}")
    for key, want in ({} if rehearsal else run_cfg.get("expect", {})).items():
        got = engine_attr(srv, key)
        if got != want:
            raise RuntimeError(f"config expects {key}={want!r}, engine has {got!r}")
    return srv, hf, traffic


def pool_leaves(srv) -> dict:
    """Every leaf of the engine's cache pool by its path: shape and dtype."""
    import jax
    return {jax.tree_util.keystr(path, simple=True, separator="/"):
            {"shape": tuple(x.shape), "dtype": str(x.dtype)}
            for path, x in jax.tree_util.tree_leaves_with_path(srv.pools)}


def engine_attr(srv, key: str):
    """An ``expect`` key of a configuration: the attribute of that name on
    the serving engine or, failing that, on its model's config."""
    for owner in (srv, srv.model.config):
        if hasattr(owner, key):
            return getattr(owner, key)
    raise KeyError(f"config expects {key!r}: neither the serving engine nor "
                   "its model config has an attribute of that name")


def warm(srv, traffic, vocab: int, seed: int):
    """One request per prompt bucket THIS mix can hit, ten tokens each, so
    the prefill programs and the quantum step are built (or loaded from the
    compile cache) before the window."""
    p = traffic["prompt"]
    b, m = srv.config.prompt_bucket, srv.max_model_len
    bks = sorted({pad_prompt(n, b, m) for n in range(int(p["min"]), int(p["max"]) + 1)})
    rng = np.random.default_rng([seed, 0x7761726D])
    pending = [loadgen.random_prompt(rng, min(x, int(p["max"])), vocab) for x in bks]
    # ... and one more after the first wave has decoded: an admission that
    # follows a quantum step writes its first token into the step's own
    # output array, a second specialisation of a small scatter program
    pending.append(pending[0])
    done = 0

    def in_system():
        return len(srv.scheduler.running) + srv.scheduler.num_waiting

    while done < len(pending) or in_system():
        while done < len(pending):
            # a slot's worth at a time (the warm-up must not preempt); the
            # last one alone, after everything before it has finished
            limit = 1 if done == len(pending) - 1 else srv.config.max_seqs
            if in_system() >= limit:
                break
            srv.add_request(pending[done], 10)
            done += 1
        srv.step()
    srv.reset_stats()
    return bks


def drive(srv, schedule, seconds: float, drain_s: float, tracer=None,
          trace_len: float = 3.0) -> dict:
    """Offer ``schedule`` for ``seconds``, then let what is in flight finish
    for at most ``drain_s``. A schedule whose requests are all due at 0 is a
    saturating one: the run stops at the window's far edge."""
    import jax
    from deepspeed_tpu.inference.scheduler import AdmissionRejected
    TA = jax.profiler.TraceAnnotation
    n = len(schedule)
    saturating = all(r["due_s"] == 0.0 for r in schedule)
    trace_at = max(0.0, seconds - trace_len - 1.0)
    rid_of = {}                      # schedule index -> engine rid
    finished = {}                    # rid -> Request (as step() returned it)
    late_ms, occupancy, live_tokens, queue = [], [], [], []
    added_at = {}                    # schedule index -> seconds into the window
    trace_span = [None, None]
    refused = 0
    i = 0
    t_end = tokens_end = window_span = None
    tracing = "pending" if tracer else "off"
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if tracing == "pending" and now >= trace_at:
            tracer.start()
            window_span = TA("bench:window")
            window_span.__enter__()
            tracing, trace_span[0] = "on", now
        elif tracing == "on" and now >= min(trace_at + trace_len, seconds):
            window_span.__exit__(None, None, None)
            tracer.stop()
            tracing, trace_span[1] = "done", now
            now = time.perf_counter() - t0
        if now >= seconds and t_end is None:
            # the window's far edge, at a round boundary
            t_end = now
            tokens_end = (sum(len(r.generated) for r in finished.values())
                          + sum(len(r.generated) for r in srv.scheduler.running))
            if saturating:
                break
        if now >= seconds + drain_s:
            break
        while i < n and schedule[i]["due_s"] <= now:
            with TA("bench:add_request"):
                r = schedule[i]
                try:
                    rid_of[i] = srv.add_request(r["prompt"], r["max_new_tokens"])
                except (AdmissionRejected, ValueError) as e:   # refused: a failed request
                    refused += 1
                    log(f"add_request refused request {i}: {e!r}")
            added_at[i] = time.perf_counter() - t0
            late_ms.append((added_at[i] - schedule[i]["due_s"]) * 1e3)
            i += 1
        if srv.scheduler.running or srv.scheduler.num_waiting:
            with TA("bench:step"):
                done = srv.step()
            for r in done:
                finished[r.rid] = r
            occupancy.append(len(srv.scheduler.running))
            live_tokens.append(sum(r.cached_rows for r in srv.scheduler.running))
            queue.append((time.perf_counter() - t0, srv.scheduler.num_waiting))
        elif i >= n:
            break
        else:
            with TA("bench:sleep"):
                time.sleep(min(0.002, max(0.0, schedule[i]["due_s"] - now)))
    if tracing == "on":
        window_span.__exit__(None, None, None)
        tracer.stop()
        trace_span[1] = time.perf_counter() - t0
    if t_end is None:                       # everything finished early
        t_end = time.perf_counter() - t0
        tokens_end = sum(len(r.generated) for r in finished.values())
    if saturating and i >= n and not srv.scheduler.num_waiting:
        raise RuntimeError(f"the {n} queued requests ran out before the window "
                           "ended: raise `requests` in the traffic file")

    # latencies, from when each request was due
    ttft, tpot = [], []
    for idx in range(i):
        r = finished.get(rid_of.get(idx, -1))
        if r is None or r.first_token_t is None or r.finish_t is None:
            continue
        ttft.append((r.first_token_t - (t0 + schedule[idx]["due_s"])) * 1e3)
        if len(r.generated) > 1:
            tpot.append((r.finish_t - r.first_token_t) * 1e3 / (len(r.generated) - 1))
    if saturating:
        # the queue is the workload: only requests that got a slot were
        # attempted, and those still running at the edge are not misses
        attempted, misses = len(finished) + len(srv.scheduler.running), 0
    else:
        attempted, misses = i, i - len(ttft)
    pb, ml = srv.config.prompt_bucket, srv.max_model_len
    in_trace = [pad_prompt(schedule[k]["prompt"].size, pb, ml)
                for k, t in added_at.items()
                if trace_span[0] is not None and trace_span[0] <= t <= trace_span[1]]
    return {
        "saturating": saturating, "t0": t0, "window_s": t_end,
        "tokens_in_window": tokens_end, "attempted": attempted,
        "misses": misses, "refused": refused, "offered": i,
        "rid_of": rid_of, "finished": finished,
        "ttft_ms": ttft, "tpot_ms": tpot, "late_ms": late_ms,
        "miss_ms": (seconds + drain_s) * 1e3, "queue": queue,
        "rounds": len(occupancy),
        "mean_occupancy": float(np.mean(occupancy)) if occupancy else 0.0,
        "mean_live_tokens": float(np.mean(live_tokens)) if live_tokens else 0.0,
        "mean_padded_prompt": float(np.mean(in_trace)) if in_trace else None,
        "preemptions": sum(r.preemptions for r in finished.values())
        + sum(r.preemptions for r in srv.scheduler.running),
        "queue_at_end": srv.scheduler.num_waiting,
        "running_at_end": len(srv.scheduler.running),
    }


def run(cell, cfg, traffic, args, env) -> dict:
    import jax
    t_build = time.perf_counter()
    srv, hf, traffic = build(cell, cfg, traffic, args.seed, args.rehearsal)
    t_warm = time.perf_counter()
    seconds = float(args.seconds)
    drain_s = float(traffic.get("drain_s", 0.0))
    ctx = {"vocab_size": hf["vocab_size"], "seconds": seconds,
           "max_model_len": srv.max_model_len}
    schedule = loadgen.generate(traffic, args.seed, ctx)
    buckets = warm(srv, traffic, hf["vocab_size"], args.seed)
    log(f"warmed {len(buckets)} prompt buckets {buckets[0]}..{buckets[-1]} + the "
        f"quantum step; {len(schedule)} requests scheduled. set-up so far: "
        f"program imports {t_build - env['t_start']:.1f} s, init_serving "
        f"{t_warm - t_build:.1f} s, warm-up {time.perf_counter() - t_warm:.1f} s")
    tracer = common.TraceSession(f"{cell['name']}.seed{args.seed}") if args.trace else None

    compiles0 = env["compiles"].n
    setup_s = time.perf_counter() - env["t_start"]
    setup_parts = {"program_imports_s": t_build - env["t_start"],
                   "init_serving_s": t_warm - t_build,
                   "warm_up_s": setup_s - (t_warm - env["t_start"])}
    d = drive(srv, schedule, seconds, drain_s, tracer,
              float(cfg["run"].get("trace_seconds", 3.0)))
    compiles_in_window = env["compiles"].n - compiles0
    if compiles_in_window:
        log(f"COMPILED IN THE WINDOW: {env['compiles'].names[-compiles_in_window:]}")

    mem = common.memory(jax.devices())
    kv_bits = int(srv.model.config.kv_cache_bits or 0)
    finished, rid_of = d.pop("finished"), d.pop("rid_of")
    counters = dict(d, compiles_in_window=compiles_in_window,
                    max_seqs=srv.config.max_seqs,
                    decode_quantum=srv.config.decode_quantum,
                    kv_cache_bits=kv_bits, pool=pool_leaves(srv), stats=srv.stats(),
                    phases=srv.phase_decomposition(),
                    bytes_in_use=mem["bytes_in_use"], setup_parts=setup_parts)
    host = {k: counters.pop(k) for k in ("ttft_ms", "tpot_ms", "late_ms", "miss_ms")}
    counters.pop("queue")
    e2e = {"setup_s": setup_s}
    if d["saturating"]:
        e2e["serve_tokens_per_s"] = d["tokens_in_window"] / d["window_s"]
        log(f"samples: {d['tokens_in_window']} tokens in a {d['window_s']:.3f} s "
            f"window, {len(finished)} requests finished, "
            f"{d['running_at_end']} running")
    else:
        n = len(host["ttft_ms"]) + d["misses"]
        e2e["ttft_p90_ms"] = metrics.percentile(host["ttft_ms"], 90, d["misses"], host["miss_ms"])
        e2e["tpot_p90_ms"] = metrics.percentile(host["tpot_ms"], 90, d["misses"], host["miss_ms"])
        log(f"samples: ttft n={len(host['ttft_ms'])} + {d['misses']} misses "
            f"({metrics.samples_beyond(n, 90)} beyond p90), tpot "
            f"n={len(host['tpot_ms'])}; queue at end {d['queue_at_end']}")
    log(f"gen_late p95 {metrics.percentile(host['late_ms'], 95):.1f} ms; rounds "
        f"{d['rounds']}, mean occupancy {d['mean_occupancy']:.1f} of "
        f"{srv.config.max_seqs}, preemptions {d['preemptions']}, compiles in "
        f"window {compiles_in_window}")

    # ---- correctness, outside the window ----------------------------------
    cc = cfg["correct"]
    if args.rehearsal:
        # toy logits are all near-ties: the rehearsal runs the comparison,
        # the calibrated tolerances belong to the published widths
        cc = dict(cc, margin=0.0, min_judged_share=0.0, min_agreement=0.0,
                  max_mismatch_share=1.0)
    rng = np.random.default_rng([args.seed, 0x636865636B])
    done_idx = sorted(idx for idx, rid in rid_of.items() if rid in finished)
    pick = rng.permutation(len(done_idx))[:int(cc["sample_requests"])]
    samples = [(schedule[done_idx[j]]["prompt"],
                finished[rid_of[done_idx[j]]].generated) for j in pick]
    bad_len = [idx for idx in done_idx
               if len(finished[rid_of[idx]].generated) != schedule[idx]["max_new_tokens"]]
    checks = [{"name": "finished_requests_have_their_length",
               "wrong": len(bad_len), "ok": not bad_len and bool(done_idx)}]
    # env["family"] is not bound to a name of its own: every local variable of
    # this frame, which is live during the warm-up, costs a serve cell ~0.25 s
    # of setup_s on the chip's host (PERF.md section 6, PR 25)
    ref = env["family"].Reference(hf, srv.engine.params)
    t_ref = time.perf_counter()
    checks.append(correct.check_tokens_vs_reference(
        samples, ref, float(cc["margin"]), float(cc["min_judged_share"]),
        float(cc["min_agreement"]), float(cc.get("max_mismatch_share", 0.0))))
    checks[-1]["family"] = env["family"].__name__
    log(f"reference forward over {len(samples)} requests took "
        f"{time.perf_counter() - t_ref:.1f} s (after the window, not in setup_s)")
    checks.append({"name": "no_compile_in_window", "count": compiles_in_window,
                   "ok": compiles_in_window == 0})
    srv.close()
    return {"job": "serve", "e2e": e2e, "attempted": d["attempted"],
            "failed": d["refused"] + d["misses"], "checks": checks,
            "counters": counters, "host": host, "tracer": tracer, "hf": hf,
            "memory": mem}
