"""graft-lint: static analysis of compiled step programs.

Runner + CLI. The pass lowers an engine's own jitted step functions on
abstract shapes (no execution, any backend) and runs the four analyzers
(analysis/analyzers.py) against the config's expectations.

CLI::

    python -m deepspeed_tpu.analysis.lint --config ds_config.json
    python -m deepspeed_tpu.analysis.lint --config '{"zero_optimization":...}'
    python -m deepspeed_tpu.analysis.lint --corpus undonated-state

Emits a human summary on stderr and (with --json) a JSON report with the
full collective census; exits non-zero when any error finding survives
suppression/baseline — the CI gate (tests/unit/test_analysis.py runs it).
"""

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

from deepspeed_tpu.analysis.analyzers import (AnalysisSettings,
                                              CollectiveAudit, MemoryLint,
                                              OverlapAudit, RematAudit,
                                              default_analyzers)
from deepspeed_tpu.analysis.expectations import (expected_collectives,
                                                 expected_memory_law)
from deepspeed_tpu.analysis.hlo_parse import (collective_census,
                                              overlap_summary,
                                              parse_overlap)
from deepspeed_tpu.analysis.program import (ProgramArtifacts, abstractify,
                                            lower_program)
from deepspeed_tpu.analysis.report import (Report, compare_census,
                                           load_baseline, save_baseline)
from deepspeed_tpu.utils.logging import logger


def _dtype_tag(dtype) -> str:
    name = getattr(dtype, "__name__", str(dtype))
    return {"bfloat16": "bf16", "float16": "f16"}.get(name, "f32")


def analyze_programs(artifacts: List[ProgramArtifacts], config, plan,
                     settings: Optional[AnalysisSettings] = None) -> Report:
    """Run every analyzer over every lowered program and assemble the
    report (suppression + baseline applied)."""
    import jax
    settings = settings or AnalysisSettings.from_config(config)
    report = Report(meta={
        "jax": jax.__version__,
        "mesh": plan.describe() if plan is not None else "",
        "zero_stage": config.zero_optimization.stage,
        "compute_dtype": _dtype_tag(config.compute_dtype),
        "programs": [a.name for a in artifacts],
    })
    baseline = None
    if settings.baseline:
        baseline = load_baseline(settings.baseline)
    law = expected_memory_law(config, plan) if plan is not None else None
    for art in artifacts:
        policy = expected_collectives(
            config, plan, onebit_phase=art.meta.get("onebit_phase"))
        # parsed ONCE per program: OverlapOp carries kind/nbytes/is_async (a
        # superset of CollectiveOp), so the same pass feeds the collective
        # census, the kind policy, and the overlap classification
        overlap_ops = parse_overlap(art.optimized_hlo)
        ops = overlap_ops
        # the memory summary is likewise computed once: MemoryLint,
        # RematAudit and the report all read the same measurement
        memory = MemoryLint.measure(art)
        for analyzer in default_analyzers(policy, law=law):
            if isinstance(analyzer, CollectiveAudit):
                report.extend(analyzer.analyze(art, settings, ops=ops))
            elif isinstance(analyzer, OverlapAudit):
                report.extend(analyzer.analyze(art, settings,
                                               overlap_ops=overlap_ops))
            elif isinstance(analyzer, (MemoryLint, RematAudit)):
                report.extend(analyzer.analyze(art, settings, memory=memory))
            else:
                report.extend(analyzer.analyze(art, settings))
        report.census[art.name] = collective_census(ops)
        report.memory[art.name] = memory
        # UNFILTERED overlap census: min_exposed_bytes only exempts
        # control-plane ops from the OverlapAudit gate — the recorded
        # census must match the telemetry join's (min_bytes=0) so
        # dryrun_multichip and the join report comparable numbers
        report.overlap[art.name] = overlap_summary(overlap_ops)
        if baseline and art.name in baseline.get("census", {}):
            report.extend(compare_census(
                report.census[art.name], baseline["census"][art.name],
                art.name, source=f"baseline {settings.baseline}"))
    report.suppress(settings.suppress)
    if baseline:
        report.apply_baseline(baseline)
    return report


# --------------------------------------------------------------------------
# Engine hook
# --------------------------------------------------------------------------

def lower_engine_programs(engine, batch=None) -> List[ProgramArtifacts]:
    """Lower the engine's own compiled step functions on abstract shapes.

    Covers the dense GSPMD step, the NVMe-swapper grad program, and both
    1-bit shard_map phases. The ZeRO-Infinity layer-streamed executor has no
    single step program to lower and is rejected with a clear error.
    """
    import jax
    if engine._infinity:
        raise ValueError(
            "audit: the layer-streamed (ZeRO-Infinity) executor compiles "
            "per-layer programs on demand and cannot be audited as one step "
            "program; audit the same config without offload_param instead")
    if batch is None:
        batch = synth_batch(engine)
    batch_abs = abstractify(engine._device_batch(batch))
    state_abs = abstractify(engine.state)
    rng_abs = jax.ShapeDtypeStruct(engine._rng.shape, engine._rng.dtype)
    dtag = _dtype_tag(engine.compute_dtype)
    stage = engine.config.zero_optimization.stage
    # the effective remat policy (for RematAudit's inert-policy check):
    # transformer.py wraps the layer body in jax.checkpoint whenever remat
    # is on or a named policy is set ("none"+remat=True = full checkpoint)
    mcfg = getattr(engine.model, "config", None)
    remat_policy = None
    if mcfg is not None and (getattr(mcfg, "remat", False)
                             or getattr(mcfg, "remat_policy", "none")
                             not in ("none", None)):
        rp = getattr(mcfg, "remat_policy", "none")
        remat_policy = rp if rp not in ("none", None) else "full"
    meta = {"params_replicated_by_design": stage < 3,
            "world_size": engine.plan.world_size,
            "remat_policy": remat_policy}
    arts = []
    if engine._onebit_comm:
        for phase in ("warm", "comp"):
            fn = engine._get_onebit_step(phase, batch_abs)
            arts.append(lower_program(
                fn, state_abs, batch_abs, rng_abs,
                name=f"onebit_{phase}_step", mesh=engine.mesh,
                donatable=state_abs, compute_dtype=dtag,
                meta={**meta, "onebit_phase": phase}))
    elif engine._nvme_opt:
        # state persists host/NVMe-side across steps by design: the grad
        # program does not own (or donate) the optimizer state
        arts.append(lower_program(
            engine._batch_grads, state_abs, batch_abs, rng_abs,
            name="batch_grads", mesh=engine.mesh,
            donatable=None, donation_expected=False,
            compute_dtype=dtag, meta=meta))
    else:
        arts.append(lower_program(
            engine._train_step, state_abs, batch_abs, rng_abs,
            name="train_step", mesh=engine.mesh,
            donatable=state_abs, compute_dtype=dtag, meta=meta))
        k = int(getattr(engine.config.pipeline, "fuse_steps", 1) or 1)
        if k > 1 and engine._can_fuse():
            # same predicate train_batches uses: don't gate CI on a fused
            # program the engine would refuse to dispatch (curriculum/LTD/
            # PLD/MoQ configs fall back to single-step)
            # the fused K-step program is a distinct compiled artifact: its
            # census must be exactly Kx the single step's (a collective
            # hoisted out of — or duplicated into — the unrolled loop is
            # drift). CollectiveAudit scales exact pins by meta fuse_steps.
            import numpy as np
            stacked = jax.tree.map(
                lambda x: np.stack([np.asarray(x)] * k), batch)
            batches_abs = abstractify(engine._device_batches(stacked))
            rngs_abs = jax.ShapeDtypeStruct(
                (k,) + tuple(engine._rng.shape), engine._rng.dtype)
            arts.append(lower_program(
                engine._get_fused_step(k), state_abs, batches_abs, rngs_abs,
                name="train_step_fused", mesh=engine.mesh,
                donatable=state_abs, compute_dtype=dtag,
                meta={**meta, "fuse_steps": k}))
    return arts


def audit_engine(engine, batch=None,
                 settings: Optional[AnalysisSettings] = None) -> Report:
    """The ``engine.audit()`` implementation: lower the engine's compiled
    steps and lint them. Returns a Report; raises nothing on findings —
    callers decide (the CLI exits non-zero, tests assert)."""
    arts = lower_engine_programs(engine, batch=batch)
    return analyze_programs(arts, engine.config, engine.plan,
                            settings=settings)


def synth_batch(engine, seq_len: Optional[int] = None) -> Dict[str, Any]:
    """A shape-only batch for lowering when the caller has none handy."""
    import numpy as np
    model_cfg = getattr(engine.model, "config", None)
    if model_cfg is None or not hasattr(model_cfg, "max_seq_len"):
        raise ValueError("audit: pass batch= for non-transformer models "
                         "(cannot synthesize input shapes)")
    s = seq_len or min(model_cfg.max_seq_len, 128)
    b = engine.config.train_batch_size
    return {"input_ids": np.zeros((b, s), np.int32)}


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

_DEMO_MODEL = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                   max_seq_len=128, attention_impl="xla")


def run_lint(config, *, model=None, devices=None, batch=None,
             settings: Optional[AnalysisSettings] = None) -> Report:
    """Build an engine for `config` (demo transformer unless `model` given)
    and audit its compiled step programs."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.config import Config
    cfg = Config.load(config)
    if model is None:
        from deepspeed_tpu.models import TransformerConfig, make_model
        model = make_model(
            TransformerConfig(dtype=cfg.compute_dtype, **_DEMO_MODEL),
            name="lint-demo")
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=cfg, devices=devices)
    return audit_engine(engine, batch=batch, settings=settings)


def _ensure_cpu_devices(n: int):
    """Force an n-virtual-device CPU backend for the lint process. Must run
    before jax initializes its backend (importing jax is fine — backends are
    lazy); errors out loudly if some earlier code already initialized one."""
    import jax
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # pragma: no cover - config key drift
        pass
    ndev = len(jax.devices())
    if ndev < n:
        raise SystemExit(
            f"lint: wanted {n} CPU devices but the jax backend initialized "
            f"with {ndev} — run with XLA_FLAGS="
            f"'--xla_force_host_platform_device_count={n}' in the "
            "environment (the backend was created before the flag applied)")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.analysis.lint",
        description="Static analysis (collectives/donation/dtype/replication)"
                    " of the compiled train step for a config.")
    p.add_argument("--config", help="engine config: JSON file path or an "
                                    "inline JSON object")
    p.add_argument("--corpus", help="lint a seeded known-bad corpus entry "
                                    "instead of a config (see --list-corpus)")
    p.add_argument("--list-corpus", action="store_true",
                   help="list seeded corpus entries and exit")
    p.add_argument("--devices", type=int, default=2,
                   help="virtual CPU device count for the mesh (default 2)")
    p.add_argument("--json", dest="json_out", metavar="PATH",
                   help="write the JSON report to PATH ('-' for stdout)")
    p.add_argument("--baseline", help="baseline JSON: suppress known "
                                      "findings and pin the census")
    p.add_argument("--write-baseline", metavar="PATH",
                   help="accept the current state: write findings+census "
                        "digest to PATH and exit 0")
    args = p.parse_args(argv)

    if args.list_corpus:
        from deepspeed_tpu.analysis.corpus import CORPUS
        for name, fn in sorted(CORPUS.items()):
            print(f"{name:24s} {fn.__doc__.strip().splitlines()[0]}")
        return 0
    if not args.config and not args.corpus:
        p.error("one of --config / --corpus / --list-corpus is required")
    if args.corpus and (args.baseline or args.write_baseline):
        # corpus entries carry their own seeded expectations; silently
        # ignoring a baseline here would let a pipeline author believe one
        # is gating the run
        p.error("--baseline/--write-baseline do not apply to --corpus runs")

    _ensure_cpu_devices(args.devices)

    if args.corpus:
        from deepspeed_tpu.analysis.corpus import run_corpus
        report = run_corpus(args.corpus)
    else:
        from deepspeed_tpu.config import Config
        src = args.config
        if src.strip().startswith("{"):
            src = json.loads(src)
        cfg = Config.load(src)
        settings = None
        if args.baseline:
            settings = AnalysisSettings.from_config(cfg)
            settings.baseline = args.baseline
        # honor --devices even when the backend has more (a pre-existing
        # XLA_FLAGS device count is preserved by _ensure_cpu_devices):
        # baselines/pins are per mesh size
        import jax
        report = run_lint(cfg, settings=settings,
                          devices=list(jax.devices())[:args.devices])

    print(report.summary(), file=sys.stderr)
    if args.json_out:
        text = report.to_json()
        if args.json_out == "-":
            print(text)
        else:
            with open(args.json_out, "w") as f:
                f.write(text + "\n")
    if args.write_baseline:
        save_baseline(report, args.write_baseline)
        logger.info(f"baseline written to {args.write_baseline}")
        return 0
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
