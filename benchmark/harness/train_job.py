"""The training job: a seeded pool of token batches cycled through the
engine's own async loop (``train_batches``: prefetch + in-flight steps).

The window is ONE ``train_batches`` call, fed batches until the time is up
(the engine bounds its own run-ahead), and ends in
``jax.block_until_ready(engine.state)``: every token counted was trained.
"""
import time

import numpy as np

from . import common, correct, loadgen
from .common import log


def run(cell, cfg, traffic, args, env) -> dict:
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import make_model

    hf = common.hf_of(cfg, args.rehearsal)
    family = env["family"]
    run_cfg = cfg["run"]
    if args.rehearsal:
        traffic = dict(traffic)
        for key in ("seq_len", "tokens_per_step"):
            traffic[key] //= common.REHEARSAL_SHRINK
    sched = loadgen.generate(traffic, args.seed, {"vocab_size": hf["vocab_size"]})
    pool, seq = sched["pool"], sched["seq_len"]
    mcfg = common.model_config(cfg, hf, seq)
    ds = dict(run_cfg["engine"])
    ds["train_batch_size"] = sched["sequences_per_step"]
    t_build = time.perf_counter()
    engine, *_ = deepspeed_tpu.initialize(
        model=make_model(mcfg, name=cell["config"]), config=ds,
        rng=jax.random.PRNGKey(args.seed))
    chips = len(jax.devices())
    t_warm = time.perf_counter()

    # every step's loss, as the device array the step returned (no sync)
    step_losses = []
    inner = engine.train_batch

    def train_batch(batch):
        m = inner(batch)
        step_losses.append(m["loss"])
        return m
    engine.train_batch = train_batch

    TA = jax.profiler.TraceAnnotation
    fed = 0                                     # batches handed to the engine
    marks = []                                  # when each was asked for

    def batches(n=None, deadline=None):
        """The pool in order: n batches, or as many as are asked for
        before the deadline."""
        nonlocal fed
        while (n is None or n > 0) and (deadline is None
                                        or time.perf_counter() < deadline):
            marks.append(time.perf_counter())
            yield {"input_ids": pool[fed % len(pool)]}
            fed += 1
            n = None if n is None else n - 1

    with TA("bench:train_batches"):
        engine.train_batches(batches(n=2), 2)  # warm-up: compile or cache load
    jax.block_until_ready(engine.state)
    warm = len(step_losses)
    log(f"warmed the train step ({warm} steps); {sched['sequences_per_step']} x "
        f"{seq} tokens per step on {chips} chip(s). set-up so far: program imports "
        f"{t_build - env['t_start']:.1f} s, initialize {t_warm - t_build:.1f} s, "
        f"warm-up {time.perf_counter() - t_warm:.1f} s")

    # ---- the window: ONE train_batches call fed until the time is up, so
    # the engine's own in-flight bound paces the host all the way through --
    seconds = float(args.seconds)
    compiles0 = env["compiles"].n
    setup_s = time.perf_counter() - env["t_start"]
    del marks[:]
    t0 = time.perf_counter()
    with TA("bench:train_batches"):
        engine.train_batches(batches(deadline=t0 + seconds), 10**9)
    jax.block_until_ready(engine.state)
    t_end = time.perf_counter() - t0
    steps = len(step_losses) - warm
    window_marks = list(marks)
    compiles_in_window = env["compiles"].n - compiles0
    mem = common.memory(jax.devices())

    tracer = None
    if args.trace:
        tracer = common.TraceSession(f"{cell['name']}.seed{args.seed}")
        tracer.start()
        k = int(run_cfg.get("trace_steps", 5))
        with TA("bench:window"):
            with TA("bench:train_batches"):
                engine.train_batches(batches(n=k), k)
            jax.block_until_ready(engine.state)
        tracer.stop()

    losses = [float(x) for x in jax.device_get(step_losses)]
    window_losses = losses[warm:warm + steps]
    tokens = steps * sched["tokens_per_step"]
    rate = tokens / t_end / chips
    fpt = family.train_flops_per_token(hf, seq)
    log(f"samples: {steps} steps = {tokens} tokens in a {t_end:.3f} s window; "
        f"{fpt / 1e9:.3f} GFLOP/token (matmul params, causal half) -> MFU "
        f"{100 * rate * fpt / env['peaks']['bf16_flops_per_s']:.1f} % of "
        f"{env['peaks']['bf16_flops_per_s'] / 1e12:.0f} TFLOP/s; loss "
        f"{window_losses[0]:.4f} -> {window_losses[-1]:.4f}; compiles in window "
        f"{compiles_in_window}")
    # time between two requests for a batch = one step, once the in-flight
    # steps and the two prefetch buffers are full
    fill = 8
    gaps = (np.diff(window_marks)[fill:] * 1e3 if len(window_marks) > fill + 2
            else np.array([t_end / max(1, steps) * 1e3]))

    # ---- correctness, outside the window ----------------------------------
    cc = cfg["correct"]
    checks = [correct.check_losses(window_losses)]
    batch = pool[fed % len(pool)]
    ref = family.Reference(hf, engine.state["params"])
    ref_loss = ref.loss(batch)
    eng_loss = float(inner({"input_ids": batch})["loss"])
    checks.append(dict(correct.check_loss_vs_reference(
        eng_loss, ref_loss, float(cc["loss_rel_tol"])), family=family.__name__))
    checks.append({"name": "no_compile_in_window", "count": compiles_in_window,
                   "ok": compiles_in_window == 0})
    bad = sum(1 for x in window_losses if not np.isfinite(x))
    engine.close()
    counters = {"steps": steps, "tokens_in_window": tokens, "window_s": t_end,
                "step_ms_groups": [float(x) for x in gaps],
                "sequences_per_step": sched["sequences_per_step"],
                "seq_len": seq, "tokens_per_step": sched["tokens_per_step"],
                "num_layers": mcfg.num_layers,
                "flops_per_token": fpt, "losses": losses, "warm_steps": warm,
                "compiles_in_window": compiles_in_window,
                "bytes_in_use": mem["bytes_in_use"]}
    return {"job": "train",
            "e2e": {"setup_s": setup_s, "train_tokens_per_s_per_chip": rate},
            "attempted": steps, "failed": bad, "checks": checks,
            "counters": counters, "host": {}, "tracer": tracer, "hf": hf,
            "memory": mem}
