"""Benchmark: tokens/sec/chip + MFU on the flagship training step.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Baseline anchor (BASELINE.md): the reference's headline is 45% MFU for
Llama-2-7B ZeRO-3 on v5p; on one chip we measure the largest Llama-family
model that FITS and report MFU as value, vs_baseline = MFU / 0.45.

Fit logic (round-1 postmortem: a blind llama-1b/seq-2048/bs-8 pick OOM'd the
v5e and the whole round produced no number): we estimate the resident bytes of
each ladder rung from first principles, skip rungs that can't fit the probed
HBM, and still wrap each attempt in an OOM catch-and-step-down so a bad
estimate degrades to a smaller config instead of rc=1.
"""

import argparse
import gc
import json
import math
import os
import sys
import time

import numpy as np

GiB = 1 << 30

# (model size, seq len, global batch) from most to least ambitious.
LADDER = [
    ("7b", 2048, 8),
    ("3b", 2048, 8),
    ("1b", 2048, 8),
    ("1b", 2048, 4),
    # bs4 beats bs8/16 on the v5e for 350m (measured: 0.419 vs 0.401 MFU —
    # larger batches push the activation working set past what fits beside
    # the ZeRO-1 state and XLA schedules more HBM traffic)
    ("350m", 2048, 4),
    ("350m", 2048, 8),
    ("tiny", 1024, 8),
    ("tiny", 512, 4),
]

# chunked CE: fp32 logits materialize per chunk only. 2048 (= the bench seq,
# i.e. one chunk per micro-batch) measured fastest on v5e at bs4: 0.4712 MFU
# vs 0.4669 @512 / 0.4599 @1024 — fewer scan steps, and the 3 GB fp32 logits
# transient still fits beside the ZeRO-1 state. The fit estimator accounts
# for it per rung, so memory-tight rungs still step down.
LOSS_CHUNK = 2048


def estimate_resident_bytes(cfg, n_params: int, batch: int, seq: int,
                            chunk: int = None, remat: str = "dots_saveable"
                            ) -> int:
    """Single-chip ZeRO-1 resident bytes: bf16 params (2) + bf16 grads (2) +
    fp32 master/m/v (12) per param, plus saved activations under the given
    remat policy, plus fp32 logits + softmax workspace (chunked CE bounds
    them to one chunk). Must mirror the --chunk/--remat flags _try_rung
    actually uses."""
    state = 16 * n_params
    c = LOSS_CHUNK if chunk is None else chunk
    logits = 12 * batch * (min(seq, c) if c else seq) * cfg.vocab_size
    # saved activation bytes/position/layer by remat policy
    acts_factor = {"none": 40, "dots_saveable": 14, "save_nothing": 6}.get(
        remat, 14)
    acts = acts_factor * batch * seq * cfg.hidden_size * cfg.num_layers
    workspace = 1 * GiB  # compiler temps, infeed, fragmentation headroom
    return state + logits + acts + workspace


def _mfu(cfg, n_params: int, B: int, S: int, nsteps: int, dt: float,
         n_devices: int = None) -> float:
    """MFU from wall time vs chip peak, PaLM-convention model FLOPs:
    6N + 12*L*H*S per token, with NO causal discount (the standard MFU
    definition — PaLM App. B / nanoGPT — counts full-S attention even though
    a causal kernel executes ~half; every rung here uses the same convention,
    so rungs are comparable to each other and to published MFU numbers).
    n_devices: override for deliberately single-chip rungs (capacity)."""
    import jax
    from deepspeed_tpu.accelerator import get_accelerator
    tok_per_sec = B * S * nsteps / dt
    flops_per_token = 6.0 * n_params + 12.0 * cfg.num_layers * cfg.hidden_size * S
    peak = (get_accelerator().peak_flops_per_device("bf16")
            * (n_devices if n_devices else max(1, jax.device_count())))
    return tok_per_sec * flops_per_token / peak


def _is_oom(err: BaseException) -> bool:
    s = str(err)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "OOM" in s or "Allocator" in s)


def _count_params(cfg) -> int:
    """Closed-form param count — avoids materializing weights just to size."""
    h, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    inter = cfg.intermediate_size
    kvh = (cfg.num_kv_heads or cfg.num_heads)
    head_dim = h // cfg.num_heads
    attn = h * h + 2 * h * kvh * head_dim + h * h  # q, k+v, o
    mlp = 3 * h * inter if cfg.activation == "silu_glu" else 2 * h * inter
    norms = 2 * h
    embed = V * h * (1 if cfg.tie_embeddings else 2)
    return L * (attn + mlp + norms) + embed + h


def _try_rung(size, S, B, nsteps, chunk=None, remat="dots_saveable",
              fused_backward=False, fuse_steps=1):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import llama_config, make_model
    from deepspeed_tpu.parallel import num_params

    chunk = LOSS_CHUNK if chunk is None else chunk
    cfg = llama_config(size, max_seq_len=S, remat=remat != "none",
                       remat_policy=remat, loss_chunk=chunk)
    model = make_model(cfg, name=f"llama-{size}")
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": B,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        # async step pipeline: bounded dispatch window + input prefetch
        "pipeline": {"in_flight": 4, "prefetch": True,
                     **({"fuse_steps": fuse_steps} if fuse_steps > 1 else {})},
        # fused attention backward (delta epilogue inside the Pallas grids)
        "transformer": {"fused_backward": bool(fused_backward)},
        "steps_per_print": 1000000,
    })

    import itertools
    rng = np.random.default_rng(0)
    # pre-generate: host RNG inside the timed loop would dominate small models
    batches = itertools.cycle(
        [{"input_ids": rng.integers(0, cfg.vocab_size, size=(B, S), dtype=np.int32)}
         for _ in range(min(nsteps, 8))])
    make_batch = lambda: next(batches)

    # warmup (compile); every timed window ends in block_until_ready
    def sync():
        jax.block_until_ready(engine.state)

    engine.train_batch(make_batch())
    if fuse_steps > 1:
        # the fused K-step program is a SECOND jit the timed loop will
        # dispatch — compile it outside the window too
        engine.train_batches((make_batch() for _ in range(fuse_steps)),
                             fuse_steps)
    sync()

    # async path (the headline step_ms): train_batches keeps
    # pipeline.in_flight steps dispatched ahead with prefetched inputs; the
    # trailing sync() makes the timing honest (blocked, not dispatch-only)
    t0 = time.perf_counter()
    engine.train_batches((make_batch() for _ in range(nsteps)), nsteps)
    sync()
    dt = time.perf_counter() - t0

    # per-step sync path (the pre-async behavior): fetch a metric after
    # every step so each dispatch stalls on the previous step's round trip.
    # step_ms_sync - step_ms is the dispatch stall the pipeline removed.
    nsync = min(nsteps, 10)
    t0 = time.perf_counter()
    for _ in range(nsync):
        m = engine.train_batch(make_batch())
        float(np.asarray(jax.device_get(m["loss"])))
    dt_sync = (time.perf_counter() - t0) / nsync
    extras = {
        "step_ms_sync": round(dt_sync * 1000, 2),
        "dispatch_stall_ms": round((dt_sync - dt / nsteps) * 1000, 2),
    }
    n = num_params(engine.state["params"])
    return cfg, engine, n, dt, extras


def run_bench(quick: bool = False, model_size: str = None, seq: int = None,
              batch: int = None, steps: int = None, chunk: int = None,
              remat: str = "auto"):
    import jax
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models import llama_config

    accel = get_accelerator()
    on_tpu = accel.platform not in ("cpu",)
    hbm = accel.hbm_bytes()

    # the levers this round ships (ISSUE 8): fused attention backward is on
    # for every headline rung; the remat policy (and fused multi-step K)
    # comes from the measured in-bench sweep when --remat auto (default).
    fused_backward = True
    fuse_steps = 1
    sweep_fields = {}
    est_remat = remat if remat != "auto" else "dots_saveable"

    if model_size:  # explicit override: single rung, no ladder
        ladder = [(model_size, seq or 2048, batch or 8)]
    elif quick or not on_tpu:
        ladder = [("tiny", 512, 8)]
    else:
        ladder = []
        for size, S, B in LADDER:
            cfg = llama_config(size, max_seq_len=S)
            est = estimate_resident_bytes(cfg, _count_params(cfg), B, S,
                                          chunk=chunk, remat=est_remat)
            if est <= 0.90 * hbm:
                ladder.append((size, S, B))
        if not ladder:
            ladder = [LADDER[-1]]
    nsteps = steps or (10 if (quick or not on_tpu) else 20)

    if remat == "auto":
        remat = "dots_saveable"
        if not model_size and not quick:
            # measured remat-policy x fuse_steps sweep on the rung the
            # ladder picked (statically pruned by RematAudit + MemoryLint
            # before any candidate runs); the winner becomes the headline
            # policy and is recorded in the JSON
            try:
                size0, S0, B0 = ladder[0]
                if not on_tpu:   # CPU smoke: tiny shapes, same code path
                    size0, S0, B0 = "tiny", 512, 4
                sweep_fields, win_policy, win_fuse = _remat_sweep_bench(
                    size0, S0, B0, hbm, small=not on_tpu)
                if on_tpu:
                    # the sweep timed the REAL headline rung — ship its
                    # winner. The CPU smoke sweeps a tiny proxy model whose
                    # winner does not transfer across shapes (observed:
                    # proxy save_nothing/fuse2 degrading the real rung), so
                    # there it only records the table.
                    remat, fuse_steps = win_policy, win_fuse
                # whether the headline number was produced UNDER the winner
                # (flipped off by the OOM-retry below) — applied_levers is
                # always authoritative for what actually ran
                sweep_fields["remat_sweep_winner_applied"] = on_tpu
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: remat sweep failed: {e}", file=sys.stderr)

    last_err = None
    for size, S, B in ladder:
        try:
            try:
                cfg, engine, n_params, dt, extras = _try_rung(
                    size, S, B, nsteps, chunk=chunk, remat=remat,
                    fused_backward=fused_backward, fuse_steps=fuse_steps)
            except Exception as e:  # noqa: BLE001 — sweep-winner OOM
                # an OOM the sweep's 92% modeled-HBM prune missed must cost
                # the optional lever, not a model-size rung: retry the SAME
                # shape on the safe policy before stepping down the ladder
                if not _is_oom(e) or (remat == "dots_saveable"
                                      and fuse_steps == 1):
                    raise
                print(f"bench: llama-{size} seq={S} bs={B} OOM'd with "
                      f"remat={remat}/fuse{fuse_steps}; retrying with "
                      "dots_saveable/fuse1", file=sys.stderr)
                gc.collect()
                remat, fuse_steps = "dots_saveable", 1
                sweep_fields["remat_sweep_winner_applied"] = False
                cfg, engine, n_params, dt, extras = _try_rung(
                    size, S, B, nsteps, chunk=chunk, remat=remat,
                    fused_backward=fused_backward, fuse_steps=fuse_steps)
        except Exception as e:  # noqa: BLE001 — OOM ladder fallback
            if _is_oom(e):
                print(f"bench: llama-{size} seq={S} bs={B} OOM'd; stepping down",
                      file=sys.stderr)
                last_err = e
                gc.collect()
                continue
            raise
        tok_per_sec = B * S * nsteps / dt
        mfu = _mfu(cfg, n_params, B, S, nsteps, dt)
        result = {
            "metric": f"llama-{size} bf16 zero1 train MFU (seq={S}, bs={B}, "
                      f"{n_params/1e6:.0f}M params, {accel.device_kind()})",
            "value": round(mfu, 4),
            "unit": "MFU",
            "vs_baseline": round(mfu / 0.45, 4),
            "tokens_per_sec_per_chip": round(tok_per_sec / max(1, jax.device_count()), 1),
            "step_ms": round(dt / nsteps * 1000, 2),
            # the perf levers actually applied to this headline number —
            # the acceptance contract names them next to the MFU they moved
            "applied_levers": (["fused_backward", f"remat:{remat}"]
                               + ([f"fuse_steps:{fuse_steps}"]
                                  if fuse_steps > 1 else [])),
            **sweep_fields,
            **extras,
        }
        if on_tpu and not (quick or model_size):
            # the training engine (~90% of HBM with ZeRO state) must go
            # before a second model of the same size can be built
            del engine
            gc.collect()
            try:
                result.update(_telemetry_bench(size, S, B,
                                               result["step_ms"] / 1000.0))
            except AssertionError as e:
                # the <1% overhead gate: LOUD and visible in the JSON line
                # (telemetry_overhead_ok=false), not swallowed as a rung skip
                print(f"bench: TELEMETRY OVERHEAD GATE FAILED: {e}",
                      file=sys.stderr)
                result.update(getattr(e, "metrics", None)
                              or {"telemetry_overhead_ok": False})
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: telemetry bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_kernel_parity_matrix())
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: kernel parity smoke failed: {e}", file=sys.stderr)
            try:
                result["seq8k_mfu"] = _long_seq_bench(
                    size, remat=remat, fused_backward=fused_backward)
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: seq-8k bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_stall_attribution_bench(size))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: stall attribution failed: {e}",
                      file=sys.stderr)
            try:
                result.update(_sparse_kernel_bench())
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: sparse bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                sweep = _decode_bench(size)
                result.update(sweep)
                if "decode_bs8_ctx256_bf16" in sweep:
                    result["decode_tok_per_sec"] = sweep["decode_bs8_ctx256_bf16"]
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: decode bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_serving_bench(size))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: serving bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_latency_bench(size))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: latency bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_lora_bench(size))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: lora bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_router_bench(size))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: router bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_disagg_bench(size))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: disagg bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_capacity_bench())
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: capacity bench failed: {e}", file=sys.stderr)
            gc.collect()
            try:
                result.update(_offload_bench(size, S, B,
                                             result["step_ms"] / 1000.0))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: offload bench failed: {e}", file=sys.stderr)
        elif not on_tpu and not quick and not model_size:
            # CPU smoke of the stall-attribution rung (true seq lengths,
            # CPU-sized vocab): keeps the traced-capture path exercised on
            # boxes without a TPU
            try:
                result.update(_stall_attribution_bench(size, small=True))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: stall attribution failed: {e}",
                      file=sys.stderr)
            # CPU smoke of the serving rung: tiny model, same engine/
            # scheduler/pool code path incl. the SLO fields + the one-shot
            # comparison, so the rung can't rot on boxes without a TPU
            try:
                result.update(_serving_bench(size, n_requests=4, max_new=8,
                                             small=True))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: serving bench failed: {e}", file=sys.stderr)
            # CPU smoke of the latency-frontier rungs: tiny model, same
            # prefix-cache/chunked-prefill/speculation paths incl. the
            # warm-vs-cold equal-output assertion, so the hit-rate and
            # ITL fields can't rot on boxes without a TPU
            try:
                result.update(_latency_bench(size, small=True))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: latency bench failed: {e}", file=sys.stderr)
            # CPU smoke of the multi-tenancy rungs: tiny model, same
            # adapter slot-pool / gathered-einsum / int8-weight paths
            # incl. the mixed-vs-merged-serial parity assertion and the
            # >=0.9 greedy-agreement bar, so serve_lora_* and
            # serve_int8w_* can't rot on boxes without a TPU
            try:
                result.update(_lora_bench(size, small=True))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: lora bench failed: {e}", file=sys.stderr)
            # CPU smoke of the 2-replica router rung: tiny model, same
            # router/registry/failover code path incl. the mid-run kill,
            # so serve_failover_ms / serve_lost_requests can't rot on
            # boxes without a TPU
            try:
                result.update(_router_bench(size, n_requests=12, max_new=8,
                                            small=True))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: router bench failed: {e}", file=sys.stderr)
            # CPU smoke of the disaggregated rung: tiny model, same KV
            # handoff / role-routing / autoscale code path incl. the
            # handoff-vs-reprefill pricing and the TTFT + zero-lost
            # gates, so serve_handoff_ms / serve_autoscale_* can't rot
            # on boxes without a TPU
            try:
                result.update(_disagg_bench(size, n_requests=8, max_new=6,
                                            small=True))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: disagg bench failed: {e}", file=sys.stderr)
            # CPU smoke of the capacity rung: tiny model over the NVMe
            # io_uring tier — the overlapped offload pipeline, its measured
            # decomposition + doctor overlap pricing, and the drained-twin
            # direction proof (offload_pipeline_speedup), so the offload
            # fields can't rot on boxes without a TPU
            try:
                result.update(_capacity_bench(small=True))
            except OffloadGateError as e:
                # the overlap/direction gate: LOUD and visible in the JSON
                # line (offload_overlap_ok=false), never swallowed as a
                # rung skip (same contract as the telemetry overhead gate)
                print(f"bench: OFFLOAD OVERLAP GATE FAILED: {e}",
                      file=sys.stderr)
                result["offload_overlap_ok"] = False
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: capacity bench failed: {e}", file=sys.stderr)
            gc.collect()
            # CPU smoke of the optimizer-offload tiers (pipelined swapper +
            # native host-Adam) with an inline no-offload baseline
            try:
                result.update(_offload_bench(size, 0, 0, small=True))
            except Exception as e:  # noqa: BLE001 — secondary metric
                print(f"bench: offload bench failed: {e}", file=sys.stderr)
        return result
    raise RuntimeError(f"every bench rung OOM'd; last error: {last_err}")


def _stall_attribution_bench(size: str, bench_dir: str = None,
                             small: bool = False) -> dict:
    """Traced-step capture + device-time stall attribution at seq 2048 and
    8k (ROADMAP item 1's evidence gate: name the top two stall sources in
    the bench JSON before shipping any perf lever).

    One step per rung runs under ``jax.profiler``; the trace artifact lands
    in the bench dir (rotated — see profiling/capture.py caps) and the
    perf doctor's attribution produces ``stall_top2_<suffix>`` = the two
    largest non-compute-bound buckets with ms + fraction of the step span.
    The modeled ``exposed_comm_ms`` from the telemetry overlap join rides
    along so modeled-vs-measured divergence is visible in the same JSON.

    small=True (CPU smoke): same sequence lengths, but a 2-layer/128-hidden
    f32 model with a 2k vocab — the O(S^2) XLA attention and the logits
    stay CPU-sized while the capture/attribution path is fully real."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama_config, make_model
    from deepspeed_tpu.profiling.capture import capture_traced_step
    from deepspeed_tpu.profiling.doctor import diagnose, stall_fields

    bench_dir = bench_dir or os.environ.get("DSTPU_BENCH_DIR",
                                            "bench_artifacts")
    out = {}
    rungs = [("seq2048", 2048, 4 if not small else 1, LOSS_CHUNK),
             ("seq8k", 8192, 2 if not small else 1, 1024)]
    for suffix, S, B, chunk in rungs:
        # per-rung isolation: a seq-8k OOM must not throw away the seq-2048
        # fields already gathered (same degradation contract as the other
        # secondary benches)
        try:
            overrides = dict(vocab_size=2048, num_layers=2, hidden_size=128,
                             num_heads=4, num_kv_heads=2,
                             intermediate_size=384) if small else {}
            cfg = llama_config(size, max_seq_len=S, remat=not small,
                               remat_policy="dots_saveable" if not small
                               else "none",
                               loss_chunk=min(chunk, S), **overrides)
            model = make_model(cfg, name=f"llama-{size}")
            engine, *_ = deepspeed_tpu.initialize(model=model, config={
                "train_batch_size": B,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": not small},
                "zero_optimization": {"stage": 1},
                # static_join: the modeled exposed_comm_ms the measured
                # attribution cross-checks comes from the same overlap
                # audit the MFU rung reports
                "telemetry": {"enabled": True},
                "steps_per_print": 1000000})
            rng = np.random.default_rng(0)
            b = {"input_ids": rng.integers(0, cfg.vocab_size, (B, S),
                                           dtype=np.int32)}
            res = capture_traced_step(engine, b, bench_dir, tag=suffix,
                                      steps=1)
            win = engine.drain_telemetry() or {}
            modeled = win.get("exposed_comm_ms")
            del engine
            gc.collect()
            if res is None:
                print(f"bench: stall attribution {suffix}: no trace "
                      "produced", file=sys.stderr)
                continue
            d = diagnose(res.trace, res.hlo_text, cost=res.cost,
                         steps=res.steps, modeled_exposed_comm_ms=modeled)
        except Exception as e:  # noqa: BLE001 — keep completed rungs
            print(f"bench: stall attribution {suffix} failed: {e}",
                  file=sys.stderr)
            gc.collect()
            continue
        out.update(stall_fields(d, suffix))
        out[f"trace_artifact_{suffix}"] = res.artifact_path
        out[f"step_span_ms_{suffix}"] = d["step_span_ms"]
        out[f"exposed_comm_ms_{suffix}"] = d["exposed_comm_ms"]
        if d.get("exposed_comm_divergence") is not None:
            out[f"exposed_comm_divergence_{suffix}"] = \
                d["exposed_comm_divergence"]
        # refresh the doctor baseline from THIS (post-optimization) trace:
        # the next `doctor --trace T --baseline <path>` gates stall-
        # regression against the fractions the shipped levers produce, not
        # a stale pre-lever attribution. Ratchet, don't clobber: when a
        # previous baseline exists and the new attribution REGRESSES
        # against it, the old baseline is kept (refreshing from the very
        # trace a later doctor run gates would let every regression
        # silently re-baseline itself) — accept a known regression
        # explicitly with `doctor --trace T --write-baseline <path>`.
        try:
            from deepspeed_tpu.profiling.doctor import baseline_dict, gate
            bpath = os.path.join(bench_dir, f"doctor_baseline_{suffix}.json")
            refreshed = True
            if os.path.exists(bpath):
                # only a stall-REGRESSION vs the old baseline blocks the
                # refresh — gate().ok would also veto on the absolute
                # exposed-collective budget, freezing the baseline even
                # when the attribution improved
                with open(bpath) as f:
                    report = gate(d, baseline=json.load(f), program=suffix)
                refreshed = not any(f.rule == "stall-regression"
                                    for f in report.findings)
            if refreshed:
                with open(bpath, "w") as f:
                    json.dump(baseline_dict(d), f, indent=2)
            else:
                print(f"bench: doctor baseline {suffix} NOT refreshed — "
                      "attribution regressed vs the existing baseline",
                      file=sys.stderr)
            out[f"doctor_baseline_{suffix}"] = bpath
            out[f"doctor_baseline_refreshed_{suffix}"] = refreshed
        except Exception as e:  # noqa: BLE001 — baseline is advisory
            print(f"bench: doctor baseline {suffix} failed: {e}",
                  file=sys.stderr)
    return out


def _telemetry_bench(size: str, S: int, B: int, base_step_s: float,
                     nsteps: int = 20) -> dict:
    """Telemetry overhead + telemetry-derived window MFU at the main rung:
    the same model/config with the full observability stack on (in-graph
    accumulators incl. update-ratio norms, step tracer, anomaly detector,
    static x runtime join). Asserts the steady-state overhead stays < 1% of
    step_ms — the zero-added-sync design goal (PR 3 acceptance). The window
    drain (one batched device_get + the one-time static-join lower/compile)
    is forced AFTER the timed loop, exactly where a production run pays it:
    off the hot path."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import llama_config, make_model

    cfg = llama_config(size, max_seq_len=S, remat=True,
                       remat_policy="dots_saveable", loss_chunk=LOSS_CHUNK)
    model = make_model(cfg, name=f"llama-{size}")
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": B,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "pipeline": {"in_flight": 4, "prefetch": True},
        "telemetry": {"enabled": True},
        "steps_per_print": 1000000,   # no boundary inside the timed loop
    })
    rng = np.random.default_rng(0)
    import itertools
    batches = itertools.cycle(
        [{"input_ids": rng.integers(0, cfg.vocab_size, size=(B, S),
                                    dtype=np.int32)}
         for _ in range(min(nsteps, 8))])

    def sync():
        jax.block_until_ready(engine.state)

    engine.train_batch(next(batches))
    sync()
    t0 = time.perf_counter()
    engine.train_batches((next(batches) for _ in range(nsteps)), nsteps)
    sync()
    tel_step_s = (time.perf_counter() - t0) / nsteps
    win = engine.drain_telemetry() or {}
    ok = tel_step_s < 1.01 * base_step_s
    out = {
        "telemetry_step_ms": round(tel_step_s * 1000, 2),
        "telemetry_overhead_pct": round(
            max(0.0, tel_step_s / base_step_s - 1.0) * 100, 2),
        "telemetry_overhead_ok": bool(ok),
    }
    if win.get("window_mfu") is not None:
        out["telemetry_window_mfu"] = round(win["window_mfu"], 4)
    if win.get("modeled_comm_bytes_per_sec") is not None:
        out["telemetry_comm_bytes_per_sec"] = round(
            win["modeled_comm_bytes_per_sec"], 1)
    # overlap-audit join (scheduled-HLO census priced at the observed rate):
    # exposed_comm_ms = modeled serial wire time the scheduler is NOT
    # hiding; overlap_efficiency = overlapped bytes / total collective bytes
    if win.get("exposed_comm_ms") is not None:
        out["exposed_comm_ms"] = round(win["exposed_comm_ms"], 3)
    if win.get("overlap_efficiency") is not None:
        out["overlap_efficiency"] = round(win["overlap_efficiency"], 4)
    # memory-lint join: statically modeled peak HBM of the compiled step
    # (liveness over the scheduled HLO) next to the allocator's measured
    # high-water mark — a modeled/measured gap is a liveness-model bug or
    # an allocator surprise, both worth a look before a real pod OOMs
    if win.get("modeled_peak_hbm") is not None:
        out["modeled_peak_hbm"] = int(win["modeled_peak_hbm"])
    if win.get("measured_peak_hbm") is not None:
        out["measured_peak_hbm"] = int(win["measured_peak_hbm"])
    del engine
    gc.collect()
    if not ok:
        # the gate must survive run_bench's blanket except: carry the
        # metrics on the error so the caller reports them either way
        err = AssertionError(
            f"telemetry overhead {tel_step_s / base_step_s - 1.0:.2%} >= 1% "
            f"of step_ms ({tel_step_s * 1e3:.2f} vs "
            f"{base_step_s * 1e3:.2f} ms)")
        err.metrics = out
        raise err
    return out


def _long_seq_bench(size: str, S: int = 8192, B: int = 2,
                    nsteps: int = 8, remat: str = "dots_saveable",
                    fused_backward: bool = True) -> float:
    """Long-context rung: same model trained at seq 8k (the blocked-KV flash
    kernel's VMEM residency is O(block), so sequence length is HBM-bound —
    the round-2 kernel capped out below this). Runs with the same levers as
    the headline (fused backward + the sweep's remat policy); a
    policy-induced OOM at 8k falls back to dots_saveable so the rung still
    reports."""
    try:
        cfg, engine, n_params, dt, _ = _try_rung(
            size, S, B, nsteps, chunk=1024, remat=remat,
            fused_backward=fused_backward)
    except Exception as e:  # noqa: BLE001 — OOM fallback to the safe policy
        if not _is_oom(e) or remat == "dots_saveable":
            raise
        gc.collect()
        cfg, engine, n_params, dt, _ = _try_rung(
            size, S, B, nsteps, chunk=1024, remat="dots_saveable",
            fused_backward=fused_backward)
    mfu = _mfu(cfg, n_params, B, S, nsteps, dt)
    del engine
    gc.collect()
    return round(mfu, 4)


def _remat_sweep_bench(size: str, S: int, B: int, hbm: int,
                       small: bool = False, tsteps: int = 4):
    """Measured remat-policy sweep on the bench rung, statically pruned.

    Candidates are remat policies (none / dots_saveable / dots_and_attn /
    save_nothing), then the winning policy x pipeline.fuse_steps. Before a
    candidate ever runs, the engine's own static analyzers price it:
    MemoryLint's modeled peak HBM (``memory-peak`` at 92% of the chip) and
    RematAudit (``involuntary-remat`` / ``remat-policy-inert``) prune
    predicted-OOM or inert configs for the cost of one AOT compile — the
    jit cache then reuses that compile when the surviving candidate is
    timed. Returns (json_fields, winner_policy, winner_fuse_steps)."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.analysis import AnalysisSettings
    from deepspeed_tpu.models import llama_config, make_model

    budget = int(0.92 * hbm) if hbm else None
    table = {}

    def candidate(policy, fuse):
        key = f"{policy}/fuse{fuse}"
        overrides = dict(vocab_size=2048, num_layers=2, hidden_size=128,
                         num_heads=4, num_kv_heads=2,
                         intermediate_size=384) if small else {}
        cfg = llama_config(size, max_seq_len=S, remat=policy != "none",
                           remat_policy=policy,
                           loss_chunk=min(LOSS_CHUNK, S), **overrides)
        model = make_model(cfg, name=f"llama-{size}")
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": B,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "bf16": {"enabled": not small},
            "zero_optimization": {"stage": 1},
            "pipeline": {"in_flight": 4, "prefetch": True,
                         **({"fuse_steps": fuse} if fuse > 1 else {})},
            "transformer": {"fused_backward": True},
            "steps_per_print": 1000000})
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, cfg.vocab_size, (B, S),
                                           dtype=np.int32)}
        entry = {}
        try:
            # static pruning BEFORE the candidate executes a single step
            report = engine.audit(batch=batch, settings=AnalysisSettings(
                max_hbm_bytes=budget))
            mem = report.memory.get("train_step", {})
            if mem.get("peak_hbm_bytes"):
                entry["modeled_peak_hbm"] = int(mem["peak_hbm_bytes"])
            pruned = sorted({f.rule for f in report.findings
                             if f.rule in ("memory-peak", "involuntary-remat",
                                           "remat-policy-inert")})
            if pruned:
                entry["pruned"] = ",".join(pruned)
                table[key] = entry
                return None
        except Exception as e:  # noqa: BLE001 — audit is advisory here
            print(f"bench: remat sweep audit {key} failed: {e}",
                  file=sys.stderr)
        try:
            # warmup compiles BOTH programs the timed loop will dispatch:
            # the single step and (fuse>1) the fused K-step program
            engine.train_batch(batch)
            if fuse > 1:
                engine.train_batches((dict(batch) for _ in range(fuse)), fuse)
            int(np.asarray(jax.device_get(engine.state["step"])))
            t0 = time.perf_counter()
            engine.train_batches((dict(batch) for _ in range(tsteps)), tsteps)
            int(np.asarray(jax.device_get(engine.state["step"])))
            entry["step_ms"] = round(
                (time.perf_counter() - t0) / tsteps * 1000, 2)
        except Exception as e:  # noqa: BLE001 — an OOM the lint missed
            entry["pruned"] = f"runtime:{type(e).__name__}"
            if not _is_oom(e):
                print(f"bench: remat sweep {key} failed: {e}",
                      file=sys.stderr)
        finally:
            table[key] = entry
        return entry.get("step_ms")

    def close(engine=None):
        gc.collect()

    winner, winner_ms = "dots_saveable", None
    for policy in ("none", "dots_saveable", "dots_and_attn", "save_nothing"):
        ms = candidate(policy, 1)
        close()
        if ms is not None and (winner_ms is None or ms < winner_ms):
            winner, winner_ms = policy, ms
    winner_fuse = 1
    for fuse in ((2,) if small else (4,)):
        ms = candidate(winner, fuse)
        close()
        if ms is not None and winner_ms is not None and ms < winner_ms:
            winner_ms, winner_fuse = ms, fuse
    fields = {"remat_sweep": table,
              "remat_sweep_winner": f"{winner}/fuse{winner_fuse}"}
    return fields, winner, winner_fuse


def _rel_err(a, b):
    """Relative L2 error in fp32 (scale-free: valid across S/D/GQA shapes)."""
    import jax.numpy as jnp
    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a32 - b32).reshape(-1))
                 / (jnp.linalg.norm(b32.reshape(-1)) + 1e-20))


def _kernel_parity_matrix() -> dict:
    """On-hardware Pallas parity MATRIX (flash fwd+bwd + decode kernel vs
    XLA references): catches Mosaic lowering bugs at D=128, non-pow2 seq,
    high GQA ratios, and long-seq accumulation drift that CPU
    interpret-mode tests can't (VERDICT r3 weakness #4). Relative-L2
    tolerances — absolute thresholds are meaningless across shapes."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.flash_attention import (flash_attention,
                                                   reference_attention)

    REL_TOL = 2e-2  # bf16 inputs: ~8e-3 observed; 2e-2 headroom for drift
    worst, cases, ok = 0.0, 0, True

    # (B, S, Nkv, rep, D) — D in {64, 128}, rep in {1, 4, 8}, S incl. 8k
    # and a non-pow2 multiple of the 512 q-block
    flash_shapes = [(2, 1024, 4, 2, 64),
                    (1, 8192, 4, 4, 64),
                    (2, 1024, 1, 8, 128),
                    (1, 1536, 8, 1, 128),
                    (2, 2048, 2, 4, 64)]
    for B, S, Nkv, rep, D in flash_shapes:
        ks = jax.random.split(jax.random.PRNGKey(B * S + D), 3)
        q = jax.random.normal(ks[0], (B, S, Nkv * rep, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, Nkv, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, Nkv, D), jnp.bfloat16)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v, causal=True)
                                    .astype(jnp.float32) ** 2).sum()

        of = flash_attention(q, k, v, causal=True)
        orf = reference_attention(q, k, v, causal=True)
        gf = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss(reference_attention),
                              argnums=(0, 1, 2)))(q, k, v)
        errs = [_rel_err(of, orf)] + [_rel_err(a, b) for a, b in zip(gf, gr)]
        worst = max(worst, max(errs))
        ok = ok and max(errs) < REL_TOL
        cases += 1

        # fused backward (delta epilogue inside the Pallas grids, ISSUE 8):
        # ON HARDWARE vs the unfused kernel path. The fused grids compute
        # delta = rowsum(dO*O) in f32 on-chip exactly like the XLA delta
        # pass, so the tolerance is an order tighter than the
        # vs-XLA-reference bar — a Mosaic lowering bug in the fused
        # epilogue shows up here before it shows up against the reference.
        def fused(qa, ka, va, causal=True):
            return flash_attention(qa, ka, va, causal=causal,
                                   fused_backward=True)
        gff = jax.jit(jax.grad(loss(fused), argnums=(0, 1, 2)))(q, k, v)
        errs_f = [_rel_err(a, b) for a, b in zip(gff, gf)]
        worst = max(worst, max(errs_f))
        ok = ok and max(errs_f) < 2e-3
        cases += 1

    # paged decode kernel (block-table gather resolved in the index maps)
    # vs the XLA gather path through models/transformer._paged_attention
    # (the token-major read, _paged_token_attention) so the masking contract
    # lives in ONE place instead of a re-implemented reference drifting here.
    # Mixed per-slot lengths incl. 0 (fresh slot) and a full table.
    from deepspeed_tpu.models.transformer import _paged_attention
    for S, NB, MB, Nkv, rep, bs, D in [(8, 33, 4, 8, 1, 64, 64),
                                       (4, 17, 4, 2, 4, 128, 128),
                                       (2, 9, 4, 4, 2, 256, 64)]:
        ks = jax.random.split(jax.random.PRNGKey(NB * bs + D), 5)
        q = jax.random.normal(ks[0], (S, 1, Nkv * rep, D), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (NB, bs, Nkv, D), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (NB, bs, Nkv, D), jnp.bfloat16)
        kr = jax.random.normal(ks[3], (S, Nkv, 1, D), jnp.bfloat16)
        vr = jax.random.normal(ks[4], (S, Nkv, 1, D), jnp.bfloat16)
        rng_t = np.random.default_rng(S + D)
        tabs = jnp.asarray(rng_t.permutation(np.arange(1, NB))[:S * MB]
                           .reshape(S, MB), jnp.int32)
        lens = jnp.asarray(
            np.concatenate([[0], rng_t.integers(1, MB * bs, size=S - 1)])
            if S > 1 else [MB * bs], jnp.int32)
        o_p = _paged_attention(q, kp, vp, tabs, lens, None,
                               kv_row=(kr, vr), backend="pallas")
        o_x = _paged_attention(q, kp, vp, tabs, lens, None,
                               kv_row=(kr, vr), backend="xla")
        err = _rel_err(o_p, o_x)
        worst = max(worst, err)
        ok = ok and err < REL_TOL
        cases += 1

    # sparse layouts ON HARDWARE (VERDICT r4 weakness #6: the 2.63x
    # headline kernels were parity-checked only in CPU interpret mode —
    # exactly the Mosaic-lowering blind spot r3 flagged for flash). A full
    # dense reference at 32k needs a [S, S] fp32 score plane (4.3GB/head),
    # so the reference is ROW-SLICED: exact softmax rows for sampled query
    # blocks (first, middle, last — covers global, sliding and random
    # regions of the layout).
    from deepspeed_tpu.ops.sparse_attention import (get_sparsity_config,
                                                    sparse_attention)

    def sparse_rows_ref(q, k, v, cfgS, qpos):
        S, D = q.shape[1], q.shape[3]
        layout = cfgS.make_layout(S)
        # expand only the sampled query rows' block-rows: the full dense
        # [S, S] mask would be ~1GB at 32k
        mask = np.repeat(layout[np.asarray(qpos) // cfgS.block],
                         cfgS.block, axis=1)
        mask = mask & (np.arange(S)[None] <= np.asarray(qpos)[:, None])
        s = jnp.einsum("brnd,btnd->bnrt", q[:, qpos].astype(jnp.float32),
                       k.astype(jnp.float32)) / math.sqrt(D)
        s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.asarray(mask)[None, None], p, 0.0)
        return jnp.einsum("bnrt,btnd->brnd", p, v.astype(jnp.float32))

    sparse_cases = [
        ("bigbird", dict(block=128, num_random_blocks=1,
                         num_sliding_window_blocks=3, num_global_blocks=1),
         1, 32768, 4, 64),
        ("fixed", dict(block=128, num_local_blocks=4, num_global_blocks=1),
         2, 4096, 4, 64),
        ("bslongformer", dict(block=128, num_sliding_window_blocks=3),
         1, 8192, 4, 128),
    ]
    for mode, kw, B, S, N, D in sparse_cases:
        cfgS = get_sparsity_config(mode, **kw)
        ks = jax.random.split(jax.random.PRNGKey(S + D + 7), 3)
        q = jax.random.normal(ks[0], (B, S, N, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, N, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, N, D), jnp.bfloat16)
        out = sparse_attention(q, k, v, cfgS, causal=True)
        nblk = S // cfgS.block
        qpos = np.concatenate([
            np.arange(cfgS.block),                                 # global
            (nblk // 2) * cfgS.block + np.arange(cfgS.block),      # middle
            (nblk - 1) * cfgS.block + np.arange(cfgS.block)])      # tail
        ref = sparse_rows_ref(q, k, v, cfgS, qpos)
        err = _rel_err(out[:, qpos], ref)
        worst = max(worst, err)
        ok = ok and err < REL_TOL
        cases += 1

    # ring attention's compute path on hardware: a 1-device ("seq",) mesh
    # executes the real shard_map + online-softmax accumulation + ppermute
    # program on the chip (degenerate ring — the multi-device collective
    # semantics are covered by the 8-device CPU-mesh suite).
    from jax.sharding import Mesh
    from deepspeed_tpu.ops.ring_attention import ring_attention
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("seq",))
    ks = jax.random.split(jax.random.PRNGKey(99), 3)
    q = jax.random.normal(ks[0], (2, 2048, 8, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 2048, 8, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 2048, 8, 64), jnp.bfloat16)
    out = ring_attention(q, k, v, mesh1, causal=True, batch_axes=(),
                         heads_axis=None)
    ref = reference_attention(q, k, v, causal=True)
    err = _rel_err(out, ref)
    worst = max(worst, err)
    ok = ok and err < REL_TOL
    cases += 1

    return {"kernel_parity_ok": bool(ok),
            "kernel_parity_worst_rel": round(worst, 5),
            "kernel_parity_cases": cases}


def _offload_bench(size: str, S: int, B: int, hbm_step_s: float = None,
                   nsteps: int = 3, small: bool = False) -> dict:
    """Optimizer-offload overhead at the main rung, BOTH tiers (VERDICT r4
    weakness #2: the use_cpu_adam tier was claimed but never measured).
    Same model/config as the MFU rung plus offload_optimizer.device=cpu:
      - chunk-streamed pinned tier: 24 bytes/param/step cross the
        host<->HBM link -> ratio bound by the link (not measured on
        the current chip host)
      - use_cpu_adam tier (XlaHostAdamSwapper): Adam runs ON the TPU host
        via compute_on over pinned-resident fp32 state; only ~4
        bytes/param/step cross (bf16 grads down, bf16 params up).
    small=True (CPU smoke): a tiny model through the SAME swapper tiers
    (chunk-streamed host buffers + the native HostAdamSwapper), with the
    no-offload baseline measured inline — the ratio fields track the
    pipelined swapper's trend on boxes without a TPU."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama_config, make_model

    if small:
        size, S, B = "tiny", 256, 4

    def one(offload: bool, use_cpu_adam: bool = False) -> float:
        cfg = llama_config(size, max_seq_len=S, remat=True,
                           remat_policy="dots_saveable",
                           loss_chunk=min(S, LOSS_CHUNK))
        model = make_model(cfg, name=f"llama-{size}")
        zero = {"stage": 1}
        if offload:
            zero["offload_optimizer"] = {"device": "cpu",
                                         "use_cpu_adam": use_cpu_adam}
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": B,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": zero,
            "steps_per_print": 1000000})
        rng = np.random.default_rng(0)
        b = {"input_ids": rng.integers(0, cfg.vocab_size, (B, S),
                                       dtype=np.int32)}
        m = engine.train_batch(b)
        float(np.asarray(m["loss"]))
        t0 = time.perf_counter()
        for _ in range(nsteps):
            m = engine.train_batch(b)
        float(np.asarray(m["loss"]))
        dt = (time.perf_counter() - t0) / nsteps
        if engine._swapper is not None:
            engine._swapper.close()   # release the pinned buffers promptly
        del engine
        gc.collect()
        return dt

    if hbm_step_s is None:
        hbm_step_s = one(False)   # no-offload baseline on the same shapes
    dt_stream = one(True, use_cpu_adam=False)
    dt_cpu_adam = one(True, use_cpu_adam=True)
    return {"offload_step_s": round(dt_stream, 3),
            "offload_overhead_ratio": round(dt_stream / hbm_step_s, 2),
            "offload_cpu_adam_step_s": round(dt_cpu_adam, 3),
            "offload_cpu_adam_ratio": round(dt_cpu_adam / hbm_step_s, 2)}


class OffloadGateError(AssertionError):
    """The capacity smoke's overlap/direction gate failed — distinct from
    any other AssertionError inside the rung, so the caller's gate handler
    never mislabels a numerics failure as an overlap regression."""


def _capacity_bench(size: str = "3b", S: int = 1024, nsteps: int = 2,
                    small: bool = False) -> dict:
    """Max trainable params per chip (BASELINE.json metric #2): train the
    ZeRO-Infinity layer-streamed path — params + Adam state on the host/NVMe
    tier, HBM holds one layer's working set — and report the param count
    that actually stepped. llama-3b (3.0B) is the in-bench rung for time
    budget; llama-7b (6.74B, 4.2x HBM) steps by the same path (verified
    manually before this round of work; not re-measured on the current
    chip host).

    small=True (CPU smoke): a tiny model over the NVMe chunk-file tier
    (real io_uring AIO on local disk) — the same overlapped-pipeline code
    path incl. the measured decomposition, the doctor's offload-overlap
    pricing, and a fully-drained twin for the direction proof, so the
    offload fields can't rot on boxes without a TPU."""
    import gc as _gc
    import tempfile
    import shutil as _shutil
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import llama_config
    from deepspeed_tpu.models.transformer import make_model
    from deepspeed_tpu.profiling.doctor import (diagnose_offload,
                                                gate_offload, offload_fields)

    if small:
        size, S, nsteps = "tiny", 256, 4
    tmp = tempfile.mkdtemp(prefix="dstpu-bench-offload-") if small else None
    off_cfg = ({"device": "nvme", "nvme_path": tmp} if small
               else {"device": "cpu"})

    def build(pipeline: bool):
        cfg = llama_config(size, max_seq_len=S, loss_chunk=min(512, S))
        model = make_model(cfg, name=f"llama-{size}")
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {
                "stage": 3,
                "offload_param": {**off_cfg, "pipeline_read": pipeline,
                                  "pipeline_write": pipeline},
                # optimizer ON the TPU host (compute_on over pinned-resident
                # fp32 state on hardware; the native fused cpu_adam in the
                # CPU smoke): the opt chunks stop crossing the host<->HBM
                # bus (r4 verdict item #1)
                "offload_optimizer": {**off_cfg, "use_cpu_adam": True,
                                      "pipeline_read": pipeline,
                                      "pipeline_write": pipeline}},
            "steps_per_print": 1000000})
        return cfg, engine

    try:
        cfg, engine = build(pipeline=True)
        rng = np.random.default_rng(0)
        b = {"input_ids": rng.integers(0, cfg.vocab_size, (1, S),
                                       dtype=np.int32)}
        engine.train_batch(b)  # compile + first step
        t0 = time.perf_counter()
        losses = [float(engine.train_batch(b)["loss"])
                  for _ in range(nsteps - 1)]
        dt = (time.perf_counter() - t0) / max(1, nsteps - 1)
        n = engine._infinity_exec.num_params + sum(
            int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(engine._infinity_exec.nl_params))
        assert all(np.isfinite(losses)), losses
    except BaseException:
        # the engine-build / timed-step segment runs outside the metric
        # try-blocks below — the smoke's NVMe tempdir must not outlive a
        # failed rung
        if tmp:
            _shutil.rmtree(tmp, ignore_errors=True)
        raise
    # measured transfer-vs-compute decomposition (VERDICT Weak #2: the 7x
    # offload ratio was attributed only in prose): chunk DMA, layer fwd+bwd,
    # the chunk-Adam update, the embed/CE top and the opt-chunk round-trip
    # are timed directly on the live executor; the doctor prices how much
    # of the step's storage IO the pipeline hid under compute
    # (offload_overlap_fraction: 0 = fully exposed wire, 1 = fully hidden)
    decomp = {}
    try:
        decomp = engine._infinity_exec.measure_decomposition(b)
        if not small:
            # hardware pricing: the measured step against the measured
            # compute + io probes (the 0.8 production bar)
            diag = diagnose_offload(decomp, step_ms=dt * 1000)
            decomp.update(offload_fields(diag))
            gate = gate_offload(diag, program=f"capacity-{size}")
            decomp["offload_overlap_ok"] = bool(gate.ok)
            if not gate.ok:
                print(f"bench: {gate.summary()}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — secondary metric
        print(f"bench: capacity decomposition failed: {e}", file=sys.stderr)
    engine._infinity_exec.close()
    del engine
    _gc.collect()
    if small:
        # mechanism + direction proof: the tiny rung's real storage IO is
        # page-cache fast (~30 ms under ~100 ms of host jitter), so raw
        # step pricing would just report noise. The offload_lint audit
        # injects a CALIBRATED per-fetch latency into the REAL executor
        # and measures what the schedule hid: the pipelined executor must
        # clear the 0.8 bar, the fully-drained twin must expose ~all of it
        # (the offload-serial-pipeline corpus defect), and the audited
        # step-time ratio is the direction proof.
        try:
            from deepspeed_tpu.analysis.offload_lint import simulate_offload
            # ONE pair run measures both twins with the same injected
            # latency (cross-twin pricing — robust in a loaded process)
            diag_p, _rep = simulate_offload(pipeline=True)
            decomp["offload_overlap_fraction"] = \
                diag_p["offload_overlap_fraction"]
            decomp["offload_overlap_ok"] = \
                diag_p["offload_overlap_fraction"] >= 0.8
            decomp["offload_pipeline_speedup"] = round(
                diag_p["offload_step_ms_serial"]
                / diag_p["offload_step_ms_pipelined"], 2)
        except Exception as e:  # noqa: BLE001 — secondary metric
            print(f"bench: offload overlap audit failed: {e}",
                  file=sys.stderr)
        finally:
            _shutil.rmtree(tmp, ignore_errors=True)
        # the gate checks live OUTSIDE the measurement try: an overlap or
        # direction regression must fail the capacity rung LOUDLY, not
        # degrade into a stderr line (the audit-crashed case above leaves
        # the fields absent, which the gate reads as a failure too). The
        # dedicated exception type keeps the caller's gate handler from
        # mislabeling unrelated assertion failures as overlap regressions.
        if not decomp.get("offload_overlap_ok") \
                or decomp.get("offload_pipeline_speedup", 0) <= 1.2:
            raise OffloadGateError(f"overlap/direction gate failed: "
                                   f"{decomp}")
    # effective MFU of the streamed step (VERDICT r3 weakness #6: the rung
    # reported step time only, hiding round-over-round regressions). The
    # host<->HBM link bounds this: the metric tracks the TREND, the note
    # carries the caveat.
    tok_per_sec = S / dt
    cap_mfu = _mfu(cfg, n, 1, S, 1, dt, n_devices=1)
    note = ("CPU smoke: tiny model over the NVMe io_uring tier — the "
            "pipelined executor, decomposition and drained-twin direction "
            "proof on the real code path; capacity/MFU numbers are not "
            "hardware claims" if small else
            "llama-7b (6.74B) steps on one 16GB chip via "
            "the same layer-streamed offload path; 3b is "
            "the timed in-bench rung. Adam runs on the "
            "TPU host (compute_on, opt state never "
            "crosses the bus). offload_io_ms vs the compute probes + the "
            "overlap fraction attribute the remaining ratio: the "
            "host<->HBM DMA bounds the wire term (link rate not "
            "measured on the current chip host)")
    return {"max_params_per_chip": int(n),
            "capacity_step_s": round(dt, 1 if not small else 3),
            "capacity_tokens_per_sec": round(tok_per_sec, 1),
            "capacity_mfu": round(cap_mfu, 4),
            **decomp,
            "capacity_note": note}


def _sparse_kernel_bench(S: int = 32768, iters: int = 5) -> dict:
    """Block-sparse vs dense flash at long context (fwd+bwd wall time).
    The sparse kernels' DMA pipelines read only listed blocks, so they
    scale ~linearly in S where dense attention is quadratic."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.flash_attention import flash_attention
    from deepspeed_tpu.ops.sparse_attention import (get_sparsity_config,
                                                    sparse_attention)
    cfg = get_sparsity_config("bigbird", block=128, num_random_blocks=1,
                              num_sliding_window_blocks=3,
                              num_global_blocks=1)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, S, 8, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, 8, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, 8, 64), jnp.bfloat16)

    def timed(fn):
        f = jax.jit(jax.value_and_grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        r = f(q, k, v)
        np.asarray(jax.device_get(r[0]))
        t0 = time.perf_counter()
        for _ in range(iters):
            r = f(q, k, v)
        np.asarray(jax.device_get(r[0]))
        return (time.perf_counter() - t0) / iters * 1000

    sp = timed(lambda q, k, v: sparse_attention(q, k, v, cfg, causal=True))
    de = timed(lambda q, k, v: flash_attention(q, k, v, causal=True))
    tag = f"{S // 1024}k"
    return {f"sparse_{tag}_ms": round(sp, 1),
            f"dense_flash_{tag}_ms": round(de, 1),
            f"sparse_{tag}_speedup": round(de / sp, 2)}


# r4's measured decode_bs8_ctx256_bf16 — the floor the rung must never
# silently sink below again (the r5 regression: a blanket int8-KV default
# quietly flipped the "bf16" rung to a quantized cache; rungs now pin their
# cache dtype explicitly and the floor assertion makes any regression LOUD)
DECODE_CTX256_FLOOR = 2853.0


def _decode_bench(size: str) -> dict:
    """KV-cache decode throughput sweep (generated tokens/sec across the
    batch): batch x context x weight/cache-dtype rungs via the jitted
    windowed scan decode loop. Decode at short context is weight/op-latency
    bound (int8 WEIGHTS and batch scaling are the levers — an int8 CACHE
    only adds quantize overhead there); long context adds the cache-read
    term, where int8 KV halves the bytes. Every rung pins kv_cache_bits +
    max_tokens so its name tells the truth about what it measures."""
    import gc as _gc
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import llama_config, make_model

    cfg = llama_config(size, max_seq_len=4096)
    rng = np.random.default_rng(0)
    out = {}
    # (key, batch, prompt, new, quantize_weights, kv_bits, max_tokens)
    rungs = [("decode_bs8_ctx256_bf16", 8, 128, 128, False, 0, 256),
             ("decode_bs8_ctx2048_bf16", 8, 1920, 128, False, 0, 2048),
             ("decode_bs8_ctx2048_int8kv", 8, 1920, 128, False, 8, 2048),
             ("decode_bs32_ctx256_int8", 32, 128, 128, True, 8, 256)]
    for key, B, prompt, new, int8w, kvb, mt in rungs:
        try:
            model = make_model(cfg, name=f"llama-{size}")
            eng = deepspeed_tpu.init_inference(model, config={
                "train_batch_size": 1,
                "kv_cache_bits": kvb, "max_tokens": mt,
                **({"quantize_bits": 8} if int8w else {})})
            ids = rng.integers(0, cfg.vocab_size, size=(B, prompt),
                               dtype=np.int32)
            np.asarray(jax.device_get(eng.generate(ids, max_new_tokens=new)))
            t0 = time.perf_counter()
            o = eng.generate(ids, max_new_tokens=new)
            np.asarray(jax.device_get(o))
            out[key] = round(B * new / (time.perf_counter() - t0), 1)
            del eng
        except Exception as e:  # noqa: BLE001 — keep completed rungs
            print(f"bench: decode rung {key} failed: {e}", file=sys.stderr)
        _gc.collect()
    if "decode_bs8_ctx256_bf16" in out:
        ok = out["decode_bs8_ctx256_bf16"] >= DECODE_CTX256_FLOOR
        out["decode_floor_ok"] = bool(ok)
        if not ok:
            print("bench: DECODE FLOOR FAILED: decode_bs8_ctx256_bf16 "
                  f"{out['decode_bs8_ctx256_bf16']} < {DECODE_CTX256_FLOOR} "
                  "(r4 measured floor — see ISSUE 9 satellite 1)",
                  file=sys.stderr)
    return out


def _paged_backend_microbench(cfg, n_slots: int, num_blocks: int,
                              block_size: int, MB: int,
                              iters: int = 10) -> dict:
    """Time the paged Pallas decode kernel vs the XLA gather on a bf16
    pool with the serving rung's geometry. Delegates to the SAME
    representative-load recipe ServingEngine._select_backend measures at
    init (inference/serving.measure_paged_backends) — the bench's
    serve_backend_* evidence can't desynchronize from the engine's."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import measure_paged_backends

    nkv, hd = cfg.kv_heads, cfg.dim_per_head
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    kp = jax.random.normal(ks[0], (num_blocks, block_size, nkv, hd),
                           jnp.bfloat16)
    vp = jax.random.normal(ks[1], (num_blocks, block_size, nkv, hd),
                           jnp.bfloat16)
    xla_ms, pallas_ms = measure_paged_backends(
        cfg, kp, vp, max_seqs=n_slots, MB=MB, block_size=block_size,
        num_blocks=num_blocks, dtype=jnp.bfloat16, iters=iters)
    return {"serve_backend_xla_ms": round(xla_ms, 3),
            "serve_backend_pallas_ms": round(pallas_ms, 3),
            "serve_backend_pallas_speedup": round(xla_ms / pallas_ms, 3),
            "serve_backend_note": "bf16-pool microbench (headline pool "
                                  "is int8 -> engine auto-selects XLA)"}


def _serving_bench(size: str, n_requests: int = 32,
                   max_new: int = 64, small: bool = False) -> dict:
    """Multi-tenant serving SLO rung: continuous batching + paged KV cache
    + quantized decode at bs=32 over MIXED context lengths (64..1024 token
    prompts). Emits time-to-first-token p50/p99 and aggregate generated
    tok/s, plus the measured paged-kernel-vs-XLA micro-bench the engine's
    backend auto-select ran at init.

    The one-shot comparison serves the SAME requests sequentially through
    the engine's generate() loop — `serve_vs_oneshot_speedup` > 1 is the
    continuous-batching win the acceptance bar names (shared pool + slot
    interleaving vs per-request batch-1 decode)."""
    import gc as _gc
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import llama_config, make_model

    overrides = dict(vocab_size=2048, num_layers=2, hidden_size=128,
                     num_heads=4, num_kv_heads=2,
                     intermediate_size=384) if small else {}
    cfg = llama_config(size, max_seq_len=4096, **overrides)
    rng = np.random.default_rng(0)
    model = make_model(cfg, name=f"llama-{size}")
    srv = deepspeed_tpu.init_serving(
        model, config={"train_batch_size": 1},
        serving=(dict(max_seqs=n_requests, block_size=16,
                      max_model_len=128, decode_quantum=4,
                      prompt_bucket=16) if small else
                 # 640 blocks = the 32-request mixed load's ~544-block peak
                 # + headroom, NOT full residency (32 slots x 2048 tokens
                 # would pin 1025 blocks ~3GB int8 on a 7b rung); the
                 # scheduler queues/preempts if the load runs hotter —
                 # serve_preemptions in the JSON makes that visible
                 dict(max_seqs=n_requests, block_size=64,
                      max_model_len=2048, decode_quantum=8,
                      num_blocks=640)))
    prompts = [16, 32, 48] if small else [64, 128, 256, 512, 1024]
    reqs = [(rng.integers(0, cfg.vocab_size,
                          size=(prompts[i % len(prompts)],),
                          ).astype(np.int32), max_new)
            for i in range(n_requests)]
    # warm the compiles outside the timed window (one prefill per prompt
    # bucket + the shared quantum step), then serve the real load fresh
    srv.run([(rng.integers(0, cfg.vocab_size, size=(p,)).astype(np.int32),
              8) for p in prompts])
    srv.reset_stats()
    t0 = time.perf_counter()
    srv.run(reqs)
    serve_dt = time.perf_counter() - t0
    st = srv.stats()
    out = {
        "serve_p50_ttft_ms": round(st.get("p50_ttft_ms", 0.0), 1),
        "serve_p99_ttft_ms": round(st.get("p99_ttft_ms", 0.0), 1),
        "serve_tok_per_sec_bs32_mixed": round(
            st.get("generated_tokens", 0.0) / serve_dt, 1),
        "serve_preemptions": int(st.get("preemptions", 0)),
        # PER-DEVICE pool shard (ISSUE 15 fix: the old number was the
        # logical pool — on a tp-sharded engine that overstated HBM by
        # the tp degree); the logical size rides alongside, and the
        # active mesh is recorded so the SLO numbers say what they ran on
        "serve_pool_bytes": int(st.get("pool_bytes", 0)),
        "serve_pool_bytes_logical": int(st.get("pool_bytes_logical", 0)),
        "serve_mesh": srv.mesh_desc,
        "serve_decode_backend": srv.decode_backend,
    }
    # tracing-overhead rung (ISSUE 18): the SAME warm engine serves the
    # SAME load with per-request tracing armed — host-clock spans only,
    # so like _telemetry_bench's gate the steady-state cost must stay
    # < 1% (the zero-added-sync design goal; the tracing-sync-leak
    # corpus twin is the seeded violation). The traced window also
    # feeds the serving doctor's phase decomposition, so the bench
    # carries the "what is the round bound on" evidence next to the
    # SLO numbers. decode_floor_ok is untouched: tracing never rides
    # the decode floor rung.
    try:
        from deepspeed_tpu.profiling.doctor import (diagnose_serving,
                                                    serving_fields)
        srv.enable_request_trace(replica="bench")
        srv.reset_stats()
        t0 = time.perf_counter()
        srv.run([(p.copy(), n) for p, n in reqs])
        traced_dt = time.perf_counter() - t0
        decomp = srv.phase_decomposition()
        srv.disable_request_trace()
        srv.reset_stats()
        pct = max(0.0, traced_dt / serve_dt - 1.0) * 100
        decomp["serve_trace_overhead_pct"] = pct
        out["serve_trace_overhead_pct"] = round(pct, 2)
        out["serve_trace_overhead_ok"] = bool(traced_dt < 1.01 * serve_dt)
        out.update(serving_fields(diagnose_serving(decomp)))
        if not out["serve_trace_overhead_ok"]:
            print("bench: TRACE OVERHEAD FAILED: traced serving "
                  f"{traced_dt:.3f}s vs untraced {serve_dt:.3f}s "
                  "(>= 1% — the host-clock-only contract; see "
                  "tracing-sync-leak corpus)", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — gate reports, never crashes
        print(f"bench: tracing-overhead rung failed: {e}", file=sys.stderr)
        out["serve_trace_overhead_ok"] = False
    for k, v in srv.backend_bench.items():
        if k != "backend":
            out[f"serve_backend_{k}"] = v
    # the acceptance bar wants the paged kernel MEASURED in-bench. The
    # quantized headline pool is int8, which short-circuits the engine's
    # auto-select to XLA without timing — so time both backends on a
    # bf16 pool of the same geometry here (the layout the kernel exists
    # for; if it keeps losing this micro-bench on real hardware, delete
    # it like its contiguous predecessor).
    if srv.backend_bench.get("reason", "").startswith("int8"):
        try:
            out.update(_paged_backend_microbench(
                cfg, n_slots=n_requests, num_blocks=srv.num_blocks,
                block_size=srv.config.block_size, MB=srv.MB))
        except Exception as e:  # noqa: BLE001 — evidence rung, not gate
            print(f"bench: paged-kernel microbench failed: {e}",
                  file=sys.stderr)
    # one-shot same-load comparison (sequential batch-1 generate through
    # the same params/int8-KV config the serving engine runs)
    try:
        eng = srv.engine
        total = 0
        # warm the generate compiles for every prompt bucket in the load
        for p in prompts:
            np.asarray(jax.device_get(eng.generate(
                rng.integers(0, cfg.vocab_size, size=(1, p)).astype(
                    np.int32), max_new_tokens=max_new)))
        t0 = time.perf_counter()
        for p, n in reqs:
            np.asarray(jax.device_get(
                eng.generate(p[None], max_new_tokens=n)))
            total += n
        dt = time.perf_counter() - t0
        out["oneshot_tok_per_sec_same_load"] = round(total / dt, 1)
        out["serve_vs_oneshot_speedup"] = round(
            out["serve_tok_per_sec_bs32_mixed"] / (total / dt), 2)
    except Exception as e:  # noqa: BLE001 — comparison is secondary
        print(f"bench: one-shot comparison failed: {e}", file=sys.stderr)
    # faulted rung: the reliability layer armed on the SAME engine + a
    # seeded fault storm over the same mixed load — SLO-under-fault
    # evidence next to the clean numbers. (The decode floor rung is
    # untouched by the reliability layer: decode_floor_ok stays asserted
    # against the same 2853 tok/s ctx-256 bf16 bar.)
    try:
        out.update(_serving_faulted_bench(srv, reqs, max_new=max_new))
    except Exception as e:  # noqa: BLE001 — evidence rung, not gate
        print(f"bench: faulted serving rung failed: {e}", file=sys.stderr)
    del srv
    _gc.collect()
    return out


def _latency_bench(size: str, small: bool = False) -> dict:
    """Latency-frontier rungs (ISSUE 12): the copy-on-write prefix cache,
    token-budget chunked prefill and speculative decoding, measured.

    * ``serve_prefix_hit_tok_per_sec`` vs ``serve_prefix_cold_tok_per_sec``
      — an 80%-shared-prefix load served warm (cache populated by an
      untimed pass) vs cold through identical engines, greedy outputs
      asserted EQUAL; ``serve_prefix_hit_rate`` is recorded so a silent
      cache miss reads as a miss, never as a regression in disguise.
    * ``serve_p99_itl_ms`` — inter-token latency p99 under an adversarial
      prompt mix (long prompts landing mid-decode) with the chunked
      token budget on, next to the unchunked number.
    * ``serve_spec_accept_rate`` / ``serve_spec_tok_per_sec`` — the
      n-gram self-drafting proposer over repetitive prompts.

    The quantized-decode floor rung (``decode_floor_ok``) is untouched:
    these engines pin ``kv_cache_bits=0`` so the greedy-parity contract
    stays strict."""
    import gc as _gc
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import llama_config, make_model

    overrides = dict(vocab_size=2048, num_layers=4, hidden_size=256,
                     num_heads=4, num_kv_heads=2,
                     intermediate_size=512) if small else {}
    # f32 compute: the warm-vs-cold assertion is EXACT token equality, and
    # bf16's ~1e-3 logit noise between the span-computed residual rows and
    # the whole-prompt prefill flips near-tied argmaxes (the same reason
    # the int8 parity tests carry a weaker bar). The speedup ratio is
    # dtype-independent; the bf16 serving SLOs live in _serving_bench.
    cfg = llama_config(size, max_seq_len=4096, dtype=jnp.float32,
                       **overrides)
    model = make_model(cfg, name=f"llama-{size}")
    rng = np.random.default_rng(0)
    if small:
        # prefill-dominant shape: the CPU smoke must still show the
        # cache's mechanism (a ~440-token shared prefix skipped, 4 decode
        # steps paid either way), not just dispatch overhead
        geom = dict(max_seqs=4, block_size=16, max_model_len=512,
                    decode_quantum=4, prompt_bucket=16)
        n_req, prefix_len, tail_len, max_new = 5, 440, 15, 4
        long_prompt, budget, short_len, short_new = 448, 64, 24, 48
    else:
        geom = dict(max_seqs=16, block_size=64, max_model_len=2048,
                    decode_quantum=8, num_blocks=640)
        n_req, prefix_len, tail_len, max_new = 16, 1024, 63, 32
        long_prompt, budget, short_len, short_new = 1792, 512, 128, 96

    def serve(extra, params=None):
        return deepspeed_tpu.init_serving(
            model, config={"train_batch_size": 1, "kv_cache_bits": 0},
            serving=dict(geom, **extra), params=params,
            dtype=jnp.float32)

    def timed_run(srv, reqs, warmup=1):
        # cache-armed engines warm TWICE: the first pass populates the
        # cache on the cold path, the second compiles the hit path's
        # chunk/fork programs — only then is the timed pass steady-state
        for _ in range(warmup):
            srv.run(list(reqs))
        srv.reset_stats()
        t0 = time.perf_counter()
        outs = srv.run(list(reqs))
        return outs, time.perf_counter() - t0, srv.stats()

    out = {}
    shared = rng.integers(0, cfg.vocab_size, size=(prefix_len,)
                          ).astype(np.int32)
    # the 80%-shared load: tails CYCLE over two values, so identical
    # prompts recur (retried/duplicate queries) — those hits reach into
    # the donor's partially-filled boundary block and exercise the
    # copy-on-write fork, not just full-block referencing
    tails = [rng.integers(0, cfg.vocab_size, size=(tail_len,)
                          ).astype(np.int32) for _ in range(2)]
    sreqs = []
    for i in range(n_req):
        if i < max(1, int(0.8 * n_req)):
            p = np.concatenate([shared, tails[i % 2]])
        else:
            p = rng.integers(0, cfg.vocab_size,
                             size=(prefix_len + tail_len,)).astype(np.int32)
        sreqs.append((p, max_new))
    cold_srv = serve({})
    cold_outs, cold_dt, cold_st = timed_run(cold_srv, sreqs)
    params = jax.device_get(cold_srv.engine.params)
    warm_srv = serve(dict(enable_prefix_cache=True), params=params)
    warm_outs, warm_dt, warm_st = timed_run(warm_srv, sreqs, warmup=2)
    # greedy bit-parity pinned (rids differ across engines/warmups —
    # compare in submission order)
    for i, (c, w) in enumerate(zip(
            (cold_outs[k] for k in sorted(cold_outs)),
            (warm_outs[k] for k in sorted(warm_outs)))):
        np.testing.assert_array_equal(
            c, w, err_msg=f"prefix-cache rung: request {i} diverged")
    gen = warm_st.get("generated_tokens", 0.0)
    out.update({
        "serve_prefix_hit_tok_per_sec": round(gen / warm_dt, 1),
        "serve_prefix_cold_tok_per_sec": round(
            cold_st.get("generated_tokens", 0.0) / cold_dt, 1),
        "serve_prefix_speedup": round(cold_dt / warm_dt, 2),
        "serve_prefix_hit_rate": warm_st.get("prefix_hit_rate", 0.0),
        "serve_prefix_hit_rows": int(warm_st.get("prefix_hit_rows", 0)),
        "serve_cow_forks": int(warm_st.get("cow_forks", 0)),
    })
    del cold_srv, warm_srv
    _gc.collect()

    # adversarial prompt mix: short requests decode MANY rounds while
    # long-prompt admissions land mid-serve (slots > requests, so the
    # second long prompt admits into a decoding batch) — p99 ITL with
    # the token budget on, unchunked alongside
    mreqs = [(rng.integers(0, cfg.vocab_size, size=(short_len,))
              .astype(np.int32), short_new) for _ in range(n_req - 2)]
    mreqs += [(rng.integers(0, cfg.vocab_size, size=(long_prompt,))
               .astype(np.int32), max_new) for _ in range(2)]
    for key, extra in (("serve_p99_itl_ms",
                        dict(prefill_token_budget=budget)),
                       ("serve_p99_itl_ms_unchunked", {})):
        srv = serve(extra, params=params)
        _, _, st = timed_run(srv, mreqs)
        out[key] = round(st.get("p99_itl_ms", 0.0), 2)
        if key == "serve_p99_itl_ms":
            out["serve_p50_itl_ms"] = round(st.get("p50_itl_ms", 0.0), 2)
            out["serve_prefill_chunks"] = int(st.get("prefill_chunks", 0))
        del srv
        _gc.collect()

    # speculation: repetitive prompts + LONG generations (greedy decode
    # settles into loops the n-gram lookup then rides), acceptance rate
    # in the JSON
    motif = rng.integers(0, cfg.vocab_size, size=(max(4, tail_len // 4),)
                         ).astype(np.int32)
    vreqs = [(np.concatenate([np.tile(motif, 4), rng.integers(
        0, cfg.vocab_size, size=(3,)).astype(np.int32)]), max_new * 8)
        for _ in range(n_req)]
    srv = serve(dict(spec_tokens=4), params=params)
    _, spec_dt, st = timed_run(srv, vreqs)
    out.update({
        "serve_spec_accept_rate": st.get("spec_accept_rate", 0.0),
        "serve_spec_tok_per_sec": round(
            st.get("generated_tokens", 0.0) / spec_dt, 1),
        "serve_spec_steps": int(st.get("spec_steps", 0)),
    })
    del srv
    _gc.collect()
    return out


def _lora_bench(size: str, small: bool = False) -> dict:
    """Massive-multi-tenancy rungs (ISSUE 17): paged multi-LoRA serving
    and weight-only int8 decode matmuls, measured WITH their parity bars.

    * ``serve_lora_tok_per_sec`` — a mixed load (every decode quantum
      batches requests of DIFFERENT adapters plus base-model traffic)
      through the device adapter slot pool, next to
      ``serve_lora_base_tok_per_sec`` (the same load with no adapters
      armed); ``serve_lora_floor_ok`` pins the >=0.8x SLO bar. The
      parity bar is asserted, not just recorded: the mixed batch's
      greedy outputs must EQUAL serving each adapter serially through
      an engine with that adapter's delta merged into the dense weights
      (``apply_lora_dense``) — the gathered-einsum path vs the offline
      single-tenant merge.
    * ``serve_int8w_tok_per_sec`` / ``serve_int8w_hbm_bytes`` — the same
      load through ``weight_bits=8`` (per-channel scales, dequant fused
      into the matmul epilogue, weights RESIDENT int8 in HBM), with the
      weights-at-rest byte count next to the unquantized engine's and
      ``serve_int8w_greedy_agreement`` >= 0.9 as the accuracy bar.

    f32 compute + ``kv_cache_bits=0`` so the mixed-vs-serial comparison
    is EXACT token equality (same reasoning as the prefix-cache rung);
    the quantized-decode floor rung (``decode_floor_ok``) is untouched.
    """
    import gc as _gc
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.inference.lora import (apply_lora_dense,
                                              make_random_adapter)
    from deepspeed_tpu.models import llama_config, make_model
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.parallel.partitioning import sharded_bytes

    overrides = dict(vocab_size=2048, num_layers=2, hidden_size=128,
                     num_heads=4, num_kv_heads=2,
                     intermediate_size=384) if small else {}
    cfg = llama_config(size, max_seq_len=4096, dtype=jnp.float32,
                       **overrides)
    model = make_model(cfg, name=f"llama-{size}-lora")
    rng = np.random.default_rng(0)
    if small:
        geom = dict(max_seqs=4, block_size=16, max_model_len=128,
                    decode_quantum=4, prompt_bucket=16)
        # 4 slots (incl. the reserved null) for 4 tenants: the timed load
        # EXERCISES eviction/re-page, not just warm hits
        n_req, n_adapters, rank, slots, max_new = 8, 4, 4, 4, 8
        plens = (16, 24, 32)
    else:
        geom = dict(max_seqs=16, block_size=64, max_model_len=2048,
                    decode_quantum=8, num_blocks=640)
        n_req, n_adapters, rank, slots, max_new = 32, 8, 8, 6, 32
        plens = (64, 128, 256)
    # the parity oracle folds A@B into the DENSE weights, so every engine
    # must share one raw (unfused) param tree — init_serving fuses wqkv
    # internally either way
    raw = jax.device_get(init_params(jax.random.PRNGKey(0), cfg))
    adapters = {a: make_random_adapter(cfg, rank, seed=a)
                for a in range(1, n_adapters + 1)}
    # round-robin over {base, adapter 1..N}: every quantum mixes tenants
    aids = [i % (n_adapters + 1) for i in range(n_req)]
    prompts = [rng.integers(0, cfg.vocab_size, size=(plens[i % len(plens)],)
                            ).astype(np.int32) for i in range(n_req)]

    def serve(extra, params, config_extra=None):
        return deepspeed_tpu.init_serving(
            model, config=dict({"train_batch_size": 1, "kv_cache_bits": 0},
                               **(config_extra or {})),
            serving=dict(geom, **extra), params=params,
            dtype=jnp.float32)

    def timed_run(srv, reqs, warmup=1):
        for _ in range(warmup):
            srv.run(list(reqs))
        srv.reset_stats()
        t0 = time.perf_counter()
        outs = srv.run(list(reqs))
        return outs, time.perf_counter() - t0, srv.stats()

    out = {}
    base_reqs = [(prompts[i], max_new) for i in range(n_req)]
    base_srv = serve({}, params=raw)
    base_outs, base_dt, base_st = timed_run(base_srv, base_reqs)
    del base_srv
    _gc.collect()

    lora_srv = serve(dict(adapter_slots=slots, lora_rank=rank), params=raw)
    for a, tabs in adapters.items():
        lora_srv.register_adapter(a, tabs)
    lora_reqs = [(prompts[i], max_new, aids[i]) for i in range(n_req)]
    lora_outs, lora_dt, lora_st = timed_run(lora_srv, lora_reqs)
    mixed = [lora_outs[k] for k in sorted(lora_outs)]
    del lora_srv
    _gc.collect()

    # the parity bar: serial per-adapter serving through MERGED dense
    # weights must reproduce the mixed batch token-for-token (small mode
    # covers every tenant; full mode a 3-tenant sample — the exhaustive
    # sweep lives in tests/unit/test_lora_serving.py)
    check = sorted(set(aids)) if small else sorted(set(aids))[:3]
    for a in check:
        sp = apply_lora_dense(raw, cfg, adapters[a]) if a else raw
        ssrv = serve({}, params=sp)
        idxs = [i for i in range(n_req) if aids[i] == a]
        souts = ssrv.run([(prompts[i], max_new) for i in idxs])
        for i, o in zip(idxs, (souts[k] for k in sorted(souts))):
            np.testing.assert_array_equal(
                mixed[i], o, err_msg=f"lora rung: request {i} (adapter "
                f"{a}) diverged from the merged-dense serial oracle")
        del ssrv
        _gc.collect()

    base_tps = base_st.get("generated_tokens", 0.0) / base_dt
    lora_tps = lora_st.get("generated_tokens", 0.0) / lora_dt
    ratio = lora_tps / base_tps if base_tps else 0.0
    # the >=0.8x bar is the TPU SLO; the CPU smoke is dispatch-overhead
    # dominated (tiny model, deliberate slot thrash) so its floor only
    # guards against pathological regressions
    floor = 0.4 if small else 0.8
    out.update({
        "serve_lora_tok_per_sec": round(lora_tps, 1),
        "serve_lora_base_tok_per_sec": round(base_tps, 1),
        "serve_lora_ratio": round(ratio, 3),
        "serve_lora_floor_ok": bool(ratio >= floor),
        "serve_adapter_hits": int(lora_st.get("adapter_hits", 0)),
        "serve_adapter_page_ins": int(lora_st.get("adapter_page_ins", 0)),
        "serve_adapter_evictions": int(lora_st.get("adapter_evictions", 0)),
    })

    # weight-only int8 rung: same load, weights at rest int8 + f32
    # per-channel scales, dequant in the matmul epilogue; agreement is
    # per-token greedy match vs the unquantized engine
    i8_srv = serve({}, params=raw, config_extra={"weight_bits": 8})
    i8_outs, i8_dt, i8_st = timed_run(i8_srv, base_reqs)
    i8_bytes = int(sharded_bytes(i8_srv.engine.params))
    base_bytes = int(sum(int(np.prod(a.shape)) * a.dtype.itemsize
                         for a in jax.tree.leaves(raw)))
    agree = tot = 0
    for b, q in zip((base_outs[k] for k in sorted(base_outs)),
                    (i8_outs[k] for k in sorted(i8_outs))):
        n = min(len(b), len(q))
        agree += int(np.sum(np.asarray(b[:n]) == np.asarray(q[:n])))
        tot += max(len(b), len(q))
    agreement = agree / tot if tot else 0.0
    out.update({
        "serve_int8w_tok_per_sec": round(
            i8_st.get("generated_tokens", 0.0) / i8_dt, 1),
        "serve_int8w_hbm_bytes": i8_bytes,
        "serve_int8w_hbm_bytes_f32": base_bytes,
        "serve_int8w_hbm_ratio": round(i8_bytes / base_bytes, 3),
        "serve_int8w_greedy_agreement": round(agreement, 4),
        "serve_int8w_agreement_ok": bool(agreement >= 0.9),
        "serve_int8w_weight_bits": int(i8_st.get("weight_bits", 0)),
    })
    del i8_srv
    _gc.collect()
    return out


def _router_bench(size: str, n_requests: int = 24, max_new: int = 16,
                  small: bool = False) -> dict:
    """Multi-replica routing rung (ISSUE 11): a 2-replica mixed load with
    a mid-run replica kill, served through the rendezvous-backed
    ``ServingRouter``. Emits the failover unavailability window
    (``serve_failover_ms`` = kill to last in-flight request re-placed on a
    survivor), the spill rate (admissions that shed on their first-choice
    replica and landed on a sibling instead), the lost-request count
    (MUST be 0 — failover migrates the drained snapshot), and the
    2-replica p99 TTFT next to the single-engine SLO rungs. The existing
    single-engine rungs (incl. ``decode_floor_ok``) are untouched.

    The registry clock is simulated (1 s per routing round) so heartbeat
    staleness — the detection path — advances deterministically; the
    failover window itself is real wall time."""
    import collections
    import gc as _gc
    import shutil
    import tempfile
    import deepspeed_tpu
    from deepspeed_tpu.inference.router import RouterConfig, ServingRouter
    from deepspeed_tpu.inference.scheduler import AdmissionRejected
    from deepspeed_tpu.models import llama_config, make_model
    from deepspeed_tpu.robustness import faults as rb_faults
    from deepspeed_tpu.robustness.faults import FaultInjector, FaultSchedule

    overrides = dict(vocab_size=2048, num_layers=2, hidden_size=128,
                     num_heads=4, num_kv_heads=2,
                     intermediate_size=384) if small else {}
    cfg = llama_config(size, max_seq_len=4096, **overrides)
    model = make_model(cfg, name=f"llama-{size}-router")
    rng = np.random.default_rng(0)
    serving_kw = (dict(max_seqs=4, block_size=16, max_model_len=128,
                       decode_quantum=4, prompt_bucket=16, max_queue=6)
                  if small else
                  # per-replica pools sized like the serving rung's but
                  # halved (two engines share the chip); tight queue
                  # watermark so the overload burst actually spills
                  dict(max_seqs=16, block_size=64, max_model_len=2048,
                       decode_quantum=8, num_blocks=320, max_queue=8))
    srv0 = deepspeed_tpu.init_serving(
        model, config={"train_batch_size": 1}, serving=dict(serving_kw))
    # the second replica shares the first's params — replicas replicate
    # compute, not weights-at-rest
    srv1 = deepspeed_tpu.init_serving(
        model, config={"train_batch_size": 1}, serving=dict(serving_kw),
        params=srv0.engine.params)
    prompts = [16, 32, 48] if small else [64, 128, 256, 512]
    reqs = [(rng.integers(0, cfg.vocab_size,
                          size=(prompts[i % len(prompts)],),
                          ).astype(np.int32), max_new)
            for i in range(n_requests)]
    # warm each replica's compiles (per-bucket prefill + quantum step)
    # outside the timed window
    for srv in (srv0, srv1):
        srv.run([(rng.integers(0, cfg.vocab_size, size=(p,)
                               ).astype(np.int32), 4) for p in prompts])
        srv.reset_stats()
    tmp = tempfile.mkdtemp(prefix="router_bench_")
    t = [0.0]
    rcfg = RouterConfig(store_dir=os.path.join(tmp, "store"),
                        drain_dir=os.path.join(tmp, "drains"),
                        dead_after_s=2.5, breaker_faults=2,
                        breaker_probe_after=1, clock=lambda: t[0])
    router = ServingRouter(rcfg)
    router.register("r0", srv0)
    router.register("r1", srv1)
    prev = rb_faults.active()
    # the kill lands right after the round-1 overload burst, while both
    # replicas hold in-flight work — killing later risks an empty drain
    # on fast rungs (nothing left to migrate = no failover evidence)
    rb_faults.install(FaultInjector(FaultSchedule([
        {"kind": "replica_kill", "at": 2, "replica": 1},
    ], seed=0)))
    pending = collections.deque(reqs)
    arrive = max(2, n_requests // 8)
    rounds = 0
    t0 = time.perf_counter()
    try:
        while pending or not router.done:
            # steady arrivals with one overload burst at round 1: the
            # first-choice replica's queue watermark sheds the tail and
            # the router spills it to the sibling (typed, counted)
            feed = min(len(pending),
                       max(arrive, 10) if rounds == 1 else arrive)
            for _ in range(feed):
                try:
                    router.add_request(*pending[0])
                except AdmissionRejected:
                    break            # all saturated: retry next round
                pending.popleft()
            router.step()
            t[0] += 1.0
            rounds += 1
            if rounds > 100000:
                raise RuntimeError("router rung did not converge")
    finally:
        rb_faults.install(prev)
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.perf_counter() - t0
    st = router.stats()
    if st["lost_requests"]:
        print(f"bench: ROUTER LOST REQUESTS: {st['lost_requests']} "
              "(failover must migrate every in-flight request — see "
              "ISSUE 11 acceptance)", file=sys.stderr)
    out = {
        "serve_failover_ms": st["failover_ms"],
        "serve_router_spill_rate": st["spill_rate"],
        "serve_lost_requests": int(st["lost_requests"]),
        "serve_p99_ttft_ms_2replica": round(st.get("p99_ttft_ms", 0.0), 1),
        "serve_router_migrated": int(st["migrated"]),
        "serve_router_rounds": rounds,
        "serve_router_completed": int(st["completed"]),
        "serve_router_tok_per_sec": round(
            (int(st["completed"]) * max_new) / dt, 1),
    }
    del router, srv0, srv1
    _gc.collect()
    return out


def _disagg_bench(size: str, n_requests: int = 16, max_new: int = 8,
                  small: bool = False) -> dict:
    """Disaggregated prefill/decode rung (ISSUE 19), three measurements:

    1. **Handoff pricing** — engine-level: the KV-byte handoff (export
       gather -> release -> accept(kv) -> one tail-span step on the
       decode engine, ``serve_handoff_ms``) against the re-prefill
       fallback (same hop, record only — the decode engine re-pays the
       whole prompt, ``serve_handoff_reprefill_ms``). Both are
       time-to-next-token on the receiving engine, warm compiles.
    2. **Topology** — the prefill=1 + decode=2 fleet vs the colocated
       2-replica router on the adversarial prompt mix:
       ``serve_p99_ttft_ms_disagg`` vs ``serve_p99_ttft_ms_coloc`` and
       the ``serve_disagg_ttft_ok`` gate (p99 TTFT must beat colocated —
       a dedicated prefill tier never makes a new prompt wait behind a
       stranger's decode quanta). Continuations stay token-identical
       either way (pinned in tests/unit/test_disagg.py, not re-proved
       here).
    3. **Autoscale soak** — one replica + the FleetController under a
       burst-then-lull load: the burst must at least double the tier,
       the lull must drain it back, and ``serve_autoscale_lost`` MUST
       be 0 throughout (scale-downs drain through the integrity chain)."""
    import gc as _gc
    import shutil
    import statistics
    import tempfile
    import deepspeed_tpu
    from deepspeed_tpu.inference.fleet import FleetConfig, FleetController
    from deepspeed_tpu.inference.router import RouterConfig, ServingRouter
    from deepspeed_tpu.models import llama_config, make_model

    overrides = dict(vocab_size=2048, num_layers=2, hidden_size=128,
                     num_heads=4, num_kv_heads=2,
                     intermediate_size=384) if small else {}
    cfg = llama_config(size, max_seq_len=4096, **overrides)
    model = make_model(cfg, name=f"llama-{size}-disagg")
    rng = np.random.default_rng(0)
    serving_kw = (dict(max_seqs=4, block_size=16, max_model_len=128,
                       decode_quantum=4, prompt_bucket=16, max_queue=8)
                  if small else
                  dict(max_seqs=16, block_size=64, max_model_len=2048,
                       decode_quantum=8, num_blocks=320, max_queue=8))

    def _make(role=None, params=None, **extra):
        kw = dict(serving_kw, **extra)
        if role:
            kw["role"] = role
        return deepspeed_tpu.init_serving(
            model, config={"train_batch_size": 1}, serving=kw,
            params=params)

    # ---- 1) handoff pricing (engine level) ---------------------------
    # chunked prefill on (the production posture): the re-prefill
    # fallback pays prompt/budget rounds on the receiver, the KV path
    # pays one gather/scatter round-trip + a single tail-span chunk
    budget = 32 if small else 128
    pre = _make("prefill", prefill_token_budget=budget)
    params = pre.engine.params
    dec = _make("decode", params, prefill_token_budget=budget)
    # the re-prefill fallback pays O(prompt); price the hop at the longest
    # prompt the geometry admits so the gap is the one operators see
    plen = 112 if small else 512

    def _prefill_one(eng, prompt):
        rid = eng.add_request(prompt, max_new_tokens=max_new)
        for _ in range(200):
            eng.step()
            req = eng._requests.get(rid)
            if req is not None and req.prefill_done and req.generated:
                return rid
        raise RuntimeError("prefill never completed")

    def _next_token_ms(eng, rid):
        """Steps until the request emits its next token (or finishes)."""
        base = len(eng._requests[rid].generated)
        t0 = time.perf_counter()
        for _ in range(400):
            eng.step()
            req = eng._requests.get(rid)
            if req is None or len(req.generated) > base:
                return (time.perf_counter() - t0) * 1e3
        raise RuntimeError("handed-off request never advanced")

    def _drain(eng):
        for _ in range(400):
            if eng.scheduler.done:
                return
            eng.step()

    kv_ms, reprefill_ms = [], []
    samples = 3 if small else 5
    for i in range(samples + 1):       # sample 0 warms both paths' compiles
        prompt = rng.integers(0, cfg.vocab_size, size=(plen,)
                              ).astype(np.int32)
        # KV path: export gather + release + accept(kv) + tail-span step
        rid = _prefill_one(pre, prompt)
        t0 = time.perf_counter()
        payloads = pre.export_kv([rid])
        recs = pre.release_requests([rid])
        dec.accept_migration(recs, source="pre", kv=payloads)
        hand = (time.perf_counter() - t0) * 1e3
        hand += _next_token_ms(dec, rid)
        _drain(dec)
        # fallback path: same hop, record only — full re-prefill on dec
        rid = _prefill_one(pre, prompt)
        t0 = time.perf_counter()
        recs = pre.release_requests([rid])
        dec.accept_migration(recs, source="pre")
        fall = (time.perf_counter() - t0) * 1e3
        fall += _next_token_ms(dec, rid)
        _drain(dec)
        if i > 0:
            kv_ms.append(hand)
            reprefill_ms.append(fall)
    out = {
        "serve_handoff_ms": round(statistics.median(kv_ms), 2),
        "serve_handoff_reprefill_ms": round(
            statistics.median(reprefill_ms), 2),
        "serve_handoff_bytes": int(
            pre.stats()["handoff_bytes"] / max(1, samples + 1)),
    }
    del pre, dec
    _gc.collect()

    # ---- 2) topology: disagg vs colocated p99 TTFT -------------------
    # the adversarial mix: decode tails long enough that a colocated
    # replica's seats stay pinned by strangers' decode quanta while new
    # prompts queue; the disagg prefill tier recycles its seats at
    # handoff time instead, so queued prompts reach first token sooner
    prompts = [32, 48, 96] if small else [256, 512, 1024]
    t_new = max_new * 4
    reqs = [(rng.integers(0, cfg.vocab_size,
                          size=(prompts[i % len(prompts)],),
                          ).astype(np.int32), t_new)
            for i in range(2 * n_requests)]

    def _fleet_p99(roles):
        tmp = tempfile.mkdtemp(prefix="disagg_bench_")
        engines = []
        try:
            router = ServingRouter(RouterConfig(
                store_dir=os.path.join(tmp, "store"),
                drain_dir=os.path.join(tmp, "drains")))
            for i, role in enumerate(roles):
                eng = _make(role, params)
                # warm the per-bucket prefill/decode compiles outside the
                # timed window (decode-role engines still prefill on the
                # fallback path; warming keeps the comparison about
                # routing, not compile order). A prefill-role engine
                # never decodes, so its requests never FINISH — warm it
                # by prefilling to first token, then release.
                # the short prompt warms the smallest prefill bucket —
                # the one a handed-off tail span (1 pending token) lands
                # in on the decode side
                warm = [(rng.integers(0, cfg.vocab_size, size=(p,)
                                      ).astype(np.int32), 4)
                        for p in prompts + [8]]
                if role == "prefill":
                    rids = [eng.add_request(p, m) for p, m in warm]
                    for _ in range(10000):
                        eng.step()
                        live = {r.rid: r for r in eng.scheduler.running}
                        if all(rid in live and live[rid].prefill_done
                               and live[rid].generated
                               for rid in rids):
                            break
                    eng.release_requests(rids)
                else:
                    eng.run(warm)
                eng.reset_stats()
                engines.append(eng)
                router.register(f"{role}{i}", eng)
            # warm the handoff path itself (gather on the source, scatter
            # + tail-span on each sink) — first-import compiles otherwise
            # land inside the timed window and swamp the p99
            if roles[0] == "prefill":
                src = engines[0]
                for dst in engines[1:]:
                    prompt = rng.integers(0, cfg.vocab_size,
                                          size=(prompts[0],)
                                          ).astype(np.int32)
                    rid = _prefill_one(src, prompt)
                    payloads = src.export_kv([rid])
                    recs = src.release_requests([rid])
                    dst.accept_migration(recs, source="warm", kv=payloads)
                    _drain(dst)
                for eng in engines:
                    eng.reset_stats()
            router.run(list(reqs), max_rounds=100000)
            st = router.stats()
            return st, router
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    st_disagg, r_disagg = _fleet_p99(["prefill", "decode", "decode"])
    st_coloc, _ = _fleet_p99(["both", "both"])
    p99_d = st_disagg.get("p99_ttft_ms", 0.0)
    p99_c = st_coloc.get("p99_ttft_ms", 0.0)
    ok = bool(p99_d and p99_c and p99_d < p99_c)
    if not ok:
        print(f"bench: DISAGG TTFT GATE: p99 {p99_d:.1f} ms (disagg) vs "
              f"{p99_c:.1f} ms (colocated) — the dedicated prefill tier "
              "should win under the adversarial mix (see ISSUE 19)",
              file=sys.stderr)
    out.update({
        "serve_p99_ttft_ms_disagg": round(p99_d, 1),
        "serve_p99_ttft_ms_coloc": round(p99_c, 1),
        "serve_disagg_ttft_ok": ok,
        "serve_disagg_handoffs": int(st_disagg["handoffs"]),
        "serve_disagg_handoff_fallbacks": int(
            st_disagg["handoff_fallbacks"]),
        "serve_disagg_lost": int(st_disagg["lost_requests"]),
    })
    del r_disagg
    _gc.collect()

    # ---- 3) autoscale soak: burst doubles, lull drains, zero lost ----
    tmp = tempfile.mkdtemp(prefix="autoscale_bench_")
    try:
        router = ServingRouter(RouterConfig(
            store_dir=os.path.join(tmp, "store"),
            drain_dir=os.path.join(tmp, "drains")))
        router.register("r0", _make(None, params))
        ctl = FleetController(
            router, lambda name, role: _make(role, params),
            FleetConfig(role="both", min_replicas=1, max_replicas=3,
                        scale_up_load=1.0, scale_up_after=2,
                        scale_down_load=0.05, scale_down_after=3,
                        cooldown_ticks=1))
        burst = [(rng.integers(0, cfg.vocab_size,
                               size=(prompts[0],)).astype(np.int32),
                  max_new)
                 for _ in range(3 * serving_kw["max_seqs"])]
        outs = {}
        peak = 1
        from deepspeed_tpu.inference.scheduler import AdmissionRejected
        pending = list(burst)
        for _ in range(600):
            while pending:
                try:
                    router.add_request(*pending[0])
                except AdmissionRejected:
                    break
                pending.pop(0)
            for r in router.step():
                outs[r.rid] = r.output
            ctl.tick()
            peak = max(peak, int(router.fleet_stats()["fleet_live"]))
            if not pending and router.done:
                break
        for _ in range(12):            # the lull: load gone, tier drains
            router.step()
            ctl.tick()
        fs = router.fleet_stats()
        st = router.stats()
        lost = int(st["lost_requests"]) + (len(burst) - len(outs))
        if lost or peak < 2 or fs["fleet_live"] != 1:
            print(f"bench: AUTOSCALE GATE: peak={peak} final="
                  f"{fs['fleet_live']} lost={lost} (burst must double the "
                  "tier, the lull must drain it, nothing may be lost)",
                  file=sys.stderr)
        out.update({
            "serve_autoscale_peak_replicas": peak,
            "serve_autoscale_final_replicas": int(fs["fleet_live"]),
            "serve_autoscale_scale_ups": int(ctl.stats()["scale_ups"]),
            "serve_autoscale_lost": lost,
        })
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    _gc.collect()
    return out


def _serving_faulted_bench(srv, reqs, max_new: int = 64) -> dict:
    """SLO-under-fault rung: arm deadlines + admission watermarks on the
    live serving engine, install a seeded fault schedule (failed decode
    dispatch at round 2, a 2-round pool-exhaustion storm at round 5), and
    serve the same mixed load. Emits p99 TTFT under fault, the shed and
    deadline-miss rates, and the measured recovery cost — the numbers the
    README's reliability section tells operators to watch."""
    import time as _time
    from deepspeed_tpu.robustness import faults as rb_faults
    from deepspeed_tpu.robustness.faults import FaultInjector, FaultSchedule

    from deepspeed_tpu.inference.scheduler import AdmissionRejected

    n = len(reqs)
    prev = rb_faults.active()
    c = srv.config
    prev_cfg = (c.ttft_deadline_ms, c.deadline_ms,
                srv.scheduler.max_queue, c.dispatch_timeout_s)
    clean_p99 = srv.stats().get("p99_ttft_ms", 0.0)
    srv.reset_stats()
    try:
        # tight queue watermark + an overload burst timed into the
        # exhaustion storm: the burst tail sheds (typed, counted); TTFT
        # budget keyed off the CLEAN p99 so only fault-induced delay
        # misses; the watchdog bounds a genuinely hung dispatch
        srv.scheduler.max_queue = max(2, n // 8)
        c.ttft_deadline_ms = max(4.0 * clean_p99, 250.0)
        c.deadline_ms = None
        c.dispatch_timeout_s = 30.0
        rb_faults.install(FaultInjector(FaultSchedule([
            {"kind": "decode_dispatch", "at": 1},
            {"kind": "pool_exhaust", "at": 3, "times": 2},
        ], seed=0)))
        arrivals = list(reqs)
        burst = [reqs[i % n] for i in range(max(4, n // 2))]
        arrive = max(1, n // 6)
        attempted = len(arrivals) + len(burst)
        rounds = 0
        t0 = _time.perf_counter()
        while arrivals or burst or not srv.scheduler.done:
            feed = arrivals[:arrive]
            del arrivals[:arrive]
            if rounds == 3:          # overload burst INTO the storm round
                feed += burst
                burst = []
            for p, k in feed:
                try:
                    srv.add_request(p, k)
                except AdmissionRejected:
                    pass             # counted + evented by the engine
            srv.step()
            rounds += 1
            if rounds > 100000:
                raise RuntimeError("faulted serving rung did not converge")
        dt = _time.perf_counter() - t0
        st = srv.stats()
        admitted = attempted - int(st["shed"])
        recov = int(st["recoveries"])
        return {
            "serve_p99_ttft_ms_under_fault": round(
                st.get("p99_ttft_ms", 0.0), 1),
            "serve_shed_rate": round(st["shed"] / attempted, 3),
            "serve_deadline_miss_rate": round(
                st["deadline_misses"] / max(1, admitted), 3),
            "serve_recovery_ms": round(
                st["recovery_ms"] / max(1, recov), 2),
            "serve_recoveries": recov,
            "serve_tok_per_sec_under_fault": round(
                st.get("generated_tokens", 0.0) / dt, 1),
        }
    finally:
        rb_faults.install(prev)
        (c.ttft_deadline_ms, c.deadline_ms,
         srv.scheduler.max_queue, c.dispatch_timeout_s) = prev_cfg


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--comm", action="store_true",
                   help="collective latency/BW sweep instead of training "
                        "(reference: benchmarks/communication/run_all.py)")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--size", default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--chunk", type=int, default=None)
    p.add_argument("--remat", default="auto",
                   help="remat policy for the headline rung; 'auto' runs "
                        "the measured in-bench policy x fuse_steps sweep "
                        "(statically pruned) and ships the winner")
    a = p.parse_args()
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if a.comm:
        from deepspeed_tpu.benchmarks.communication import run_comm_bench
        for row in run_comm_bench():
            print(json.dumps(row))
        sys.exit(0)
    result = run_bench(quick=a.quick, model_size=a.size, seq=a.seq,
                       batch=a.batch, steps=a.steps, chunk=a.chunk,
                       remat=a.remat)
    print(json.dumps(result))
