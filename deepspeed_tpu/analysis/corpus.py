"""Seeded known-bad programs/configs the lint MUST flag.

Each entry builds a program with exactly one planted defect and returns the
lint Report; tests assert the right rule fires (and the CLI exposes them via
``--corpus`` so the gate itself can be exercised end-to-end). This is the
regression floor for the analyzers: a parser change that stops flagging any
of these is a lint escape, not a cleanup.
"""

from typing import Dict, List, Optional

from deepspeed_tpu.analysis.analyzers import AnalysisSettings
from deepspeed_tpu.analysis.lint import analyze_programs, run_lint
from deepspeed_tpu.analysis.program import abstractify, lower_program


def _mesh2(devices=None):
    import jax
    from jax.sharding import Mesh
    devs = devices or jax.devices()[:2]
    if len(devs) < 2:
        raise SystemExit("corpus: needs >= 2 devices "
                         "(--xla_force_host_platform_device_count)")
    return Mesh(list(devs)[:2], ("data",))


class _FakePlan:
    """Just enough MeshPlan surface for expectations/report metadata."""
    data, fsdp, tensor, pipe, expert, seq = 2, 1, 1, 1, 1, 1
    world_size = 2

    def describe(self):
        return "corpus[data=2]"


def _stage0_config():
    from deepspeed_tpu.config import Config
    return Config.load({"train_batch_size": 4,
                        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                        "bf16": {"enabled": False}})


def undonated_state(devices=None):
    """Donation lint: an optimizer-like step compiled WITHOUT donating its
    state — every big state buffer held live twice."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh2(devices)
    repl = NamedSharding(mesh, P())
    state = {"params": {"w": jax.ShapeDtypeStruct((256, 256), jnp.float32,
                                                  sharding=repl)},
             "opt": {"m": jax.ShapeDtypeStruct((256, 256), jnp.float32,
                                               sharding=repl)}}

    def step(state, lr):
        w, m = state["params"]["w"], state["opt"]["m"]
        m2 = 0.9 * m + w
        return {"params": {"w": w - lr * m2}, "opt": {"m": m2}}

    # the defect: no donate_argnums — the reference equivalent is an fp16
    # optimizer that keeps both param copies resident
    jitted = jax.jit(step)
    art = lower_program(jitted, state, jax.ShapeDtypeStruct((), jnp.float32),
                        name="undonated_step", mesh=mesh, donatable=state,
                        meta={"skip_required": True})
    return analyze_programs([art], _stage0_config(), _FakePlan(),
                            settings=AnalysisSettings())


def extra_collective(devices=None, seeded=True):
    """Collective audit: a data-parallel grad step with ONE gratuitous extra
    all-reduce (a replicated batch statistic nobody asked for) — the census
    pin catches what no structural rule can. XLA combines the extra
    reduction into the grad's all-reduce (one tuple-shaped op), so the pin
    holds the BYTES beside the count. ``seeded=False`` is the defect-free
    twin: the same step without the statistic."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh2(devices)
    repl = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("data"))
    w_abs = jax.ShapeDtypeStruct((128, 128), jnp.float32, sharding=repl)
    x_abs = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=row)

    def grads(w, x):
        loss = lambda w_: jnp.sum((x @ w_) ** 2)
        g = jax.grad(loss)(w)          # batch-sharded x -> one all-reduce
        if not seeded:
            return g, g[0, 0]
        extra = jnp.sum(x, axis=0)     # the silent extra: replicated [128]
        return g, g[0, 0] + 1e-12 * jnp.sum(extra)

    jitted = jax.jit(grads, out_shardings=(repl, repl))
    art = lower_program(jitted, w_abs, x_abs, name="grad_step", mesh=mesh,
                        donatable=None, donation_expected=False,
                        meta={"skip_required": True})
    # the clean program compiles to exactly one all-reduce, of the [128,128]
    # f32 gradient; pin it
    return analyze_programs(
        [art], _stage0_config(), _FakePlan(),
        settings=AnalysisSettings(expect_collectives={
            "all-reduce": {"count": 1, "bytes": 128 * 128 * 4}}))


def f32_upcast(devices=None, seeded=True):
    """Dtype lint: a bf16 program that MATERIALIZES a >=1MiB f32 widening
    of an activation (a fused elementwise convert would be fine — the lint
    only flags converts whose result is a buffer). ``seeded=False`` is the
    defect-free twin: the same loss with the widening left inside its
    fusion."""
    import jax
    import jax.numpy as jnp

    def loss(x):
        big = x.astype(jnp.float32)    # the defect: 512*512*4 = 1 MiB copy
        if not seeded:
            return jnp.sum(big * big)
        return jnp.sum(big * big), big  # returning it forces materialization

    x_abs = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    art = lower_program(jax.jit(loss), x_abs, name="bf16_loss",
                        donatable=None, donation_expected=False,
                        compute_dtype="bf16", meta={"skip_required": True})
    return analyze_programs([art], _stage0_config(), _FakePlan(),
                            settings=AnalysisSettings())


def replicated_budget(devices=None):
    """Replication budget: a >=1MiB tensor pinned to a replicated sharding
    on a 2-device mesh (the double-memory mistake the old
    replicated_tensor_bytes scan caught)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh2(devices)
    row = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())

    def f(x):
        y = x * 2.0
        # the defect: force full replication of an activation-sized tensor
        return jax.lax.with_sharding_constraint(y, repl)

    x_abs = jax.ShapeDtypeStruct((512, 512), jnp.float32, sharding=row)
    art = lower_program(jax.jit(f), x_abs, name="replicated_step", mesh=mesh,
                        donatable=None, donation_expected=False,
                        meta={"skip_required": True})
    return analyze_programs([art], _stage0_config(), _FakePlan(),
                            settings=AnalysisSettings())


def census_drift(devices=None):
    """Config-level: a real ZeRO-2 engine audited against a census pin that
    doesn't match it (the 'somebody changed the program' CI failure)."""
    config = {
        "train_batch_size": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "bf16": {"enabled": False},
        "zero_optimization": {"stage": 2},
        "mesh": {"axes": {"data": 2}},
        # seeded defect: the pin claims stage-0 shape (all-reduce only)
        "analysis": {"expect_collectives": {"all-reduce": 23}},
    }
    import jax
    return run_lint(config, devices=list(jax.devices())[:2])


def fused_loop_hoist(devices=None):
    """Collective audit: a fused K-step loop whose per-step grad all-reduce
    was hoisted OUT of the unrolled loop — the K local updates diverge per
    rank and only the final reduce papers over it. The per-step census pin
    (scaled by meta fuse_steps=K, the same mechanics engine.train_batches'
    fused program is audited with) expects K all-reduces and sees 1."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    K = 4
    mesh = _mesh2(devices)
    repl = NamedSharding(mesh, P())
    w_abs = jax.ShapeDtypeStruct((128, 128), jnp.float32, sharding=repl)
    xs_abs = jax.ShapeDtypeStruct((K, 8, 128), jnp.float32,
                                  sharding=NamedSharding(mesh, P(None, "data")))

    def per_device(w, xs):
        # the defect: each unrolled step updates with the LOCAL gradient;
        # the cross-replica mean runs once at the end instead of per step
        for i in range(K):
            g = jax.grad(lambda w_: jnp.sum((xs[i] @ w_) ** 2))(w)
            w = w - 1e-3 * g
        return lax.pmean(w, "data")   # 1 all-reduce where K belong

    from deepspeed_tpu.comm.schedule import shard_map_compat
    fn = shard_map_compat(per_device, mesh,
                          in_specs=(P(), P(None, "data")), out_specs=P(),
                          manual_axes=("data",))
    art = lower_program(jax.jit(fn), w_abs, xs_abs, name="fused_step",
                        mesh=mesh, donatable=None, donation_expected=False,
                        meta={"skip_required": True, "fuse_steps": K})
    # pin is PER STEP (one grad all-reduce); the audit scales it by K
    return analyze_programs(
        [art], _stage0_config(), _FakePlan(),
        settings=AnalysisSettings(expect_collectives={"all-reduce": 1}))


def telemetry_leak(devices=None, seeded=True):
    """Telemetry done WRONG, both ways the real accumulators must never be:
    (a) the stats buffer is NOT donated — every step holds the old and new
    [256,256] window plane live at once (the real leaf rides the donated
    state); (b) the per-step update all-reduces a batch statistic across
    `data` instead of accumulating device-locally (the real leaf adds one
    dense collective: zero). The donation lint must flag the un-donated
    buffer and the census pin must flag the extra reduction — by its
    BYTES: XLA combines it into the grad's all-reduce. ``seeded=False`` is
    the defect-free twin: stats donated and accumulated locally."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh2(devices)
    repl = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("data"))
    params_abs = {"w": jax.ShapeDtypeStruct((128, 128), jnp.float32,
                                            sharding=repl)}
    tel_abs = {"stats": jax.ShapeDtypeStruct((256, 256), jnp.float32,
                                             sharding=repl)}
    x_abs = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=row)

    def step(params, telemetry, x):
        loss = lambda w_: jnp.sum((x @ w_) ** 2)
        g = jax.grad(loss)(params["w"])  # batch-sharded x -> one all-reduce
        if seeded:
            # defect (b): a replicated batch statistic folded into the stats
            # plane — GSPMD must reduce it across `data` every step
            batch_mean = jnp.mean(x, axis=0)
            stats = telemetry["stats"] + jnp.tile(batch_mean, 2)[None, :]
        else:
            stats = telemetry["stats"] + 1.0
        return {"w": params["w"] - 1e-3 * g}, {"stats": stats}

    # defect (a): only the params are donated; the telemetry arg is not
    jitted = jax.jit(step, donate_argnums=(0,) if seeded else (0, 1),
                     out_shardings=({"w": repl}, {"stats": repl}))
    art = lower_program(
        jitted, params_abs, tel_abs, x_abs, name="telemetry_step", mesh=mesh,
        donatable={"params": params_abs, "telemetry": tel_abs},
        meta={"skip_required": True})
    # the clean program compiles to exactly the one grad all-reduce, of the
    # [128,128] f32 gradient; pin it
    return analyze_programs(
        [art], _stage0_config(), _FakePlan(),
        settings=AnalysisSettings(expect_collectives={
            "all-reduce": {"count": 1, "bytes": 128 * 128 * 4}}))


def deferred_sync_regression(devices=None):
    """Deferred-sync regression: a stage-2-style gas=4 microbatch loop whose
    accumulator spec forces a reduce-scatter EVERY microbatch — the per-
    microbatch sync `comm.deferred_grad_sync` exists to remove. The census
    pin expects the deferred shape (ONE boundary reduce-scatter per step),
    so the audit must flag the gas x collective inflation; and because the
    per-microbatch reductions are synchronous, the overlap audit (gated at
    max_exposed_collectives=0) must report them as exposed."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    GAS = 4
    mesh = _mesh2(devices)
    repl = NamedSharding(mesh, P())
    w_abs = jax.ShapeDtypeStruct((256, 128), jnp.float32, sharding=repl)
    xs_abs = jax.ShapeDtypeStruct((GAS, 8, 128), jnp.float32,
                                  sharding=NamedSharding(mesh,
                                                         P(None, "data")))

    def per_device(w, xs):
        # the defect: the dp-sharded accumulator spec makes every unrolled
        # microbatch reduce-scatter its grads; the deferred path accumulates
        # locally and scatters ONCE at the boundary
        acc = jnp.zeros((w.shape[0] // 2, w.shape[1]), jnp.float32)
        for i in range(GAS):
            g = jax.grad(lambda w_: jnp.sum((xs[i] @ w_.T) ** 2))(w)
            acc = acc + lax.psum_scatter(g, "data", scatter_dimension=0,
                                         tiled=True) / GAS
        return acc

    from deepspeed_tpu.comm.schedule import shard_map_compat
    fn = shard_map_compat(per_device, mesh,
                          in_specs=(P(), P(None, "data")),
                          out_specs=P("data"), manual_axes=("data",))
    art = lower_program(jax.jit(fn), w_abs, xs_abs, name="deferred_step",
                        mesh=mesh, donatable=None, donation_expected=False,
                        meta={"skip_required": True})
    from deepspeed_tpu.config import Config
    cfg = Config.load({"train_batch_size": 4,
                       "optimizer": {"type": "adamw",
                                     "params": {"lr": 1e-3}},
                       "bf16": {"enabled": False},
                       "zero_optimization": {"stage": 2}})
    # the deferred shape is ONE boundary reduce-scatter per step; the audit
    # sees GAS of them (+ the overlap gate sees them all exposed)
    return analyze_programs(
        [art], cfg, _FakePlan(),
        settings=AnalysisSettings(
            expect_collectives={"reduce-scatter": 1},
            max_exposed_collectives=0, min_exposed_bytes=1))


def _long_scan_program(remat: bool, devices=None):
    """A 16-deep scanned residual stack with a fat intermediate per layer —
    the shape whose activation liveness blows up without checkpointing.
    Shared weights keep params/grads small so the fwd/bwd activation carry
    dominates the peak: ~24 MiB modeled without remat (the stacked
    [L,64,2048] residuals live across the whole backward) vs ~12 MiB with
    the body checkpointed — the 18 MiB budget sits between the two, so
    only the missing-checkpoint variant fires (measured on jax 0.4.37;
    re-measure BOTH variants before retuning the budget)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    L = 16

    def layer(h, w1, w2):
        mid = jnp.tanh(h @ w1)           # [64,2048] — the fat intermediate
        return h + jnp.tanh(mid @ w2)    # back to [64,256]

    def loss(ws, x):
        body = lambda h, _: (layer(h, ws[0], ws[1]), None)
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        h, _ = lax.scan(body, x, None, length=L)
        return jnp.sum(h ** 2)

    ws = (jax.ShapeDtypeStruct((256, 2048), jnp.float32),
          jax.ShapeDtypeStruct((2048, 256), jnp.float32))
    x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    return lower_program(
        jax.jit(jax.grad(loss)), ws, x,
        name="long_scan_step", donatable=None, donation_expected=False,
        meta={"skip_required": True})


def remat_missing(devices=None):
    """Memory lint: the long-scan config with its remat policy OFF — every
    layer's fat intermediate is saved across the fwd/bwd boundary and the
    static activation liveness blows past the budget (`memory-peak` must
    fire). The same program WITH jax.checkpoint on the body stays under
    the identical budget (tests assert both directions)."""
    art = _long_scan_program(remat=False, devices=devices)
    return analyze_programs(
        [art], _stage0_config(), _FakePlan(),
        settings=AnalysisSettings(max_hbm_bytes=18 << 20))


def stage3_replicated_opt(devices=None):
    """Memory law: a stage-3-style step whose params shard over dp but
    whose Adam moments were left REPLICATED — per-device opt bytes are 2x
    what the ZeRO memory law allows on the 2-device mesh. `memory-law`
    must fire, and the explicit replicated shardings also blow the
    replication budget (`replication-budget`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh2(devices)
    repl = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("data"))
    state = {
        "opt": {   # the defect: moments pinned to a replicated sharding
            "m": jax.ShapeDtypeStruct((1024, 1024), jnp.float32,
                                      sharding=repl),
            "v": jax.ShapeDtypeStruct((1024, 1024), jnp.float32,
                                      sharding=repl)},
        "params": {"w": jax.ShapeDtypeStruct((1024, 1024), jnp.float32,
                                             sharding=row)}}

    def step(state, lr):
        w, m, v = state["params"]["w"], state["opt"]["m"], state["opt"]["v"]
        g = w * 2.0
        m2 = 0.9 * m + 0.1 * g
        v2 = 0.99 * v + 0.01 * g * g
        w2 = w - lr * m2 / (jnp.sqrt(v2) + 1e-8)
        return {"opt": {"m": m2, "v": v2}, "params": {"w": w2}}

    # donation_expected=False: this entry plants exactly ONE defect (the
    # replicated moments); whether XLA honors the donation of a replicated
    # buffer on this backend is not the seeded failure. The memory-law
    # check reads donatable_paths (the state-class map) either way.
    jitted = jax.jit(step, donate_argnums=(0,))
    art = lower_program(jitted, state,
                        jax.ShapeDtypeStruct((), jnp.float32),
                        name="stage3_step", mesh=mesh, donatable=state,
                        donation_expected=False,
                        meta={"skip_required": True, "world_size": 2})
    from deepspeed_tpu.config import Config
    cfg = Config.load({"train_batch_size": 4,
                       "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                       "bf16": {"enabled": False},
                       "zero_optimization": {"stage": 3}})
    return analyze_programs([art], cfg, _FakePlan(),
                            settings=AnalysisSettings())


class NoisyLossModel:
    """A model wrapper whose loss adds a term that forces one extra dense
    cross-replica reduction — the classic silently-added allreduce, planted
    at the model level so the full engine pipeline compiles it."""

    def __init__(self, inner):
        self._inner = inner
        self.name = inner.name + "-noisy"
        self.config = getattr(inner, "config", None)
        self.init = inner.init
        self.logical_axes = inner.logical_axes

    def loss_fn(self, params, batch, rng, deterministic):
        import jax.numpy as jnp
        loss = self._inner.loss_fn(params, batch, rng, deterministic)
        # mean over the (data-sharded) batch dim -> replicated [S] result:
        # GSPMD must insert an extra all-reduce to materialize it
        extra = jnp.mean(batch["input_ids"].astype(jnp.float32), axis=0)
        return loss + 1e-12 * jnp.sum(extra)


def _paged_decode_program(num_blocks: int, devices=None):
    """The serving tier's paged decode step (models/transformer
    decode_step_paged) lowered on abstract shapes: a tiny transformer, 4
    slots, a block pool of `num_blocks` 32-token blocks. The pool enters as
    donated state, so MemoryLint's liveness model prices it like any other
    resident buffer."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  make_model)
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=256,
                            dtype=jnp.float32, attention_impl="xla")
    model = make_model(cfg, name="tiny-serve")
    S, MB, bs = 4, 8, 32
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pools = jax.eval_shape(
        lambda: model.init_paged_cache(num_blocks, bs))
    toks = jax.ShapeDtypeStruct((S,), jnp.int32)
    tables = jax.ShapeDtypeStruct((S, MB), jnp.int32)
    lens = jax.ShapeDtypeStruct((S,), jnp.int32)

    def step(params, pools, tokens, tables, lens):
        logits, pools = model.decode_step_paged(params, tokens, pools,
                                                tables, lens, backend="xla")
        return jnp.argmax(logits, -1).astype(jnp.int32), pools

    jitted = jax.jit(step, donate_argnums=(1,))
    return lower_program(
        jitted, abstractify(params), pools, toks, tables, lens,
        name="serve_decode_step", donatable={"pools": pools},
        donation_expected=False, meta={"skip_required": True})


# between the two pool sizings: measured modeled peaks 1.90 MiB (33-block
# pool, correctly freed) vs 4.20 MiB (96-block leak) on jax 0.9.0 (PR 21;
# 1.18 vs 2.21 MiB on the previous stack, budget 1.5 MiB) — re-measure
# BOTH variants before retuning (same protocol as remat-missing)
PAGED_LEAK_BUDGET = 3 << 20      # 3 MiB


def paged_cache_leak(devices=None):
    """Memory lint: a serving block pool sized as if FINISHED requests'
    blocks were never freed — the classic paged-cache leak (an eviction
    path that forgets allocator.free). Peak concurrency on this toy rung
    is 4 slots x 8 blocks (+ trash) = 33 blocks; the leaked variant holds
    the whole request history's 96 blocks resident, and the static peak
    blows the budget (`memory-peak` must fire). The CORRECTLY-freed twin
    (33 blocks, same program otherwise) stays under the identical budget —
    tests assert both directions."""
    art = _paged_decode_program(num_blocks=96, devices=devices)
    return analyze_programs(
        [art], _stage0_config(), _FakePlan(),
        settings=AnalysisSettings(max_hbm_bytes=PAGED_LEAK_BUDGET))


# Exact census of the tp=2 paged decode quantum step (the ISSUE 15 pin,
# measured on jax 0.4.37 — re-measure BOTH twins before retuning):
#   all-reduce x3, 1024 B each: the scanned layer body's TWO row-parallel
#     out-projections (attn wo + MLP w_out — the only per-layer cross-chip
#     reductions) + ONE for the token-embedding gather over the
#     vocab-sharded table;
#   all-gather x2, 32 B each: the greedy argmax's cross-shard
#     (value, index) exchange at the vocab-sharded lm head.
# The POOL SCATTER contributes ZERO collectives: each chip writes its own
# kv-head slice of the fresh rows in place. A pool accidentally replicated
# across `tensor` shows up as census DRIFT (the fresh rows all-gather
# before the scatter) on top of the replication/memory findings.
TP_SERVE_CENSUS = {"all-reduce": 3, "all-gather": 2}
# between the twins: modeled per-device peaks ~583 KiB (head-sharded pool)
# vs ~1.72 MiB (replicated pool) on jax 0.4.37 — the 1 MiB budget sits
# between (same re-measure protocol as remat-missing)
TP_SERVE_POOL_BUDGET = 1 << 20


class _FakeTPPlan(_FakePlan):
    data, tensor = 1, 2

    def describe(self):
        return "corpus[tensor=2]"


def tp_serving_pool_report(shard_pool: bool, devices=None):
    """Lower the serving tier's tp=2 paged decode step (decode_step_paged
    + greedy argmax) over a 2-device `tensor` mesh — weights in the
    Megatron col/row layout (make_rules), the KV block pool either
    head-sharded per ``paged_cache_logical_axes`` (the correct twin) or
    REPLICATED across `tensor` (the planted defect) — and audit it with
    the exact ISSUE-15 census pin + replication/memory budgets."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  make_model)
    from deepspeed_tpu.parallel import make_rules, spec_tree

    devs = devices or jax.devices()[:2]
    if len(devs) < 2:
        raise SystemExit("corpus: needs >= 2 devices "
                         "(--xla_force_host_platform_device_count)")
    mesh = Mesh(list(devs)[:2], ("tensor",))
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=256,
                            dtype=jnp.float32, attention_impl="xla")
    model = make_model(cfg, name="tiny-serve-tp")
    S, MB, bs, NB = 4, 4, 32, 33
    rules = make_rules(zero_stage=0, tp=True)

    def with_specs(tree, spec_t):
        flat, treedef = jax.tree_util.tree_flatten(tree)
        specs = treedef.flatten_up_to(spec_t)
        return treedef.unflatten([
            jax.ShapeDtypeStruct(l.shape, l.dtype,
                                 sharding=NamedSharding(mesh, s))
            for l, s in zip(flat, specs)])

    params = with_specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                        spec_tree(model.logical_axes, rules))
    pools_a = jax.eval_shape(lambda: model.init_paged_cache(NB, bs))
    pool_spec = (spec_tree(model.paged_cache_axes(), rules) if shard_pool
                 else jax.tree.map(lambda _: P(), pools_a))
    pools = with_specs(pools_a, pool_spec)
    toks = jax.ShapeDtypeStruct((S,), jnp.int32)
    tables = jax.ShapeDtypeStruct((S, MB), jnp.int32)
    lens = jax.ShapeDtypeStruct((S,), jnp.int32)

    def step(params, pools, tokens, tables, lens):
        logits, pools = model.decode_step_paged(params, tokens, pools,
                                                tables, lens, backend="xla")
        return jnp.argmax(logits, -1).astype(jnp.int32), pools

    name = ("serve_decode_step_tp2" if shard_pool
            else "serve_decode_step_tp2_replpool")
    art = lower_program(
        jax.jit(step, donate_argnums=(1,)), params, pools, toks, tables,
        lens, name=name, mesh=mesh, donatable={"pools": pools},
        donation_expected=False,
        meta={"skip_required": True, "world_size": 2})
    return analyze_programs(
        [art], _stage0_config(), _FakeTPPlan(),
        settings=AnalysisSettings(
            expect_collectives=dict(TP_SERVE_CENSUS),
            # the pool tensors are ~270 KiB each on this toy rung: drop the
            # replication floor below them so the replicated twin's pool
            # (540 KiB across k+v) is in scope
            min_replicated_bytes=256 << 10,
            max_hbm_bytes=TP_SERVE_POOL_BUDGET))


def tp_serving_replicated_pool(devices=None):
    """Pod-serving audit: the tp=2 paged decode step whose KV block pool
    was accidentally REPLICATED across the `tensor` axis — each chip pays
    the full logical pool (the per-device peak blows the budget:
    `memory-peak`), the replicated pool tensors blow the replication
    budget (`replication-over-budget`), and the fresh-row scatter now
    all-gathers the head-sharded rows before writing (census drift against
    the exact TP_SERVE_CENSUS pin). The correctly head-sharded twin
    (``tp_serving_pool_report(shard_pool=True)``) passes the identical
    settings — tests assert both directions; both CLI-runnable
    (``lint --corpus tp-serving-replicated-pool``)."""
    return tp_serving_pool_report(shard_pool=False, devices=devices)


# the int8 layer stack of the toy rung is ~88 KiB total (smallest matmul
# payload 4 KiB): a 4 KiB floor puts every quantized weight in scope while
# the correctly-sharded twin's explicitly-replicated tensors (norm scales,
# per-channel dequant scales) all sit below it
INT8W_REPL_FLOOR = 4 << 10


def int8_weight_pool_report(shard_weights: bool, devices=None):
    """Lower the weight-only int8 tp=2 decode step (decode_step_paged over
    ``{"q": s8, "scale": f32}`` layer weights, dequant fused into the
    matmul epilogue) over a 2-device `tensor` mesh — the quantized stack
    either sharded per ``quantized_logical_axes`` (int8 payload columns
    with the projection, scales riding the same out-channel axis: the
    correct twin) or REPLICATED across `tensor` (the planted defect) — and
    audit the replication budget. The whole point of weight-only int8 is
    halving what HBM holds; a replicated quantized stack pays full bytes
    per chip and quietly gives the win back."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  make_model,
                                                  quantize_layer_stack,
                                                  quantized_logical_axes)
    from deepspeed_tpu.parallel import make_rules, spec_tree

    devs = devices or jax.devices()[:2]
    if len(devs) < 2:
        raise SystemExit("corpus: needs >= 2 devices "
                         "(--xla_force_host_platform_device_count)")
    mesh = Mesh(list(devs)[:2], ("tensor",))
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=256,
                            dtype=jnp.float32, attention_impl="xla",
                            # rotary: no learned position table (a 64 KiB
                            # replicated-by-design f32 param that would sit
                            # above the 4 KiB scan floor in BOTH twins)
                            position_type="rotary",
                            quantized_weights=True, weight_only_bits=8)
    model = make_model(cfg, name="tiny-serve-int8w")
    S, MB, bs, NB = 4, 4, 32, 33
    rules = make_rules(zero_stage=0, tp=True)

    def with_specs(tree, spec_t):
        flat, treedef = jax.tree_util.tree_flatten(tree)
        specs = treedef.flatten_up_to(spec_t)
        return treedef.unflatten([
            jax.ShapeDtypeStruct(l.shape, l.dtype,
                                 sharding=NamedSharding(mesh, s))
            for l, s in zip(flat, specs)])

    raw = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    qparams = jax.eval_shape(lambda p: quantize_layer_stack(p, bits=8), raw)
    qspec = spec_tree(quantized_logical_axes(cfg), rules)
    if not shard_weights:
        # the defect: the quantized stack (s8 payloads + f32 scales) lands
        # replicated on every chip; everything else keeps its layout
        qspec = dict(qspec)
        qspec["layers"] = jax.tree.map(lambda _: P(), qparams["layers"])
    params = with_specs(qparams, qspec)
    pools = with_specs(jax.eval_shape(lambda: model.init_paged_cache(NB, bs)),
                       spec_tree(model.paged_cache_axes(), rules))
    toks = jax.ShapeDtypeStruct((S,), jnp.int32)
    tables = jax.ShapeDtypeStruct((S, MB), jnp.int32)
    lens = jax.ShapeDtypeStruct((S,), jnp.int32)

    def step(params, pools, tokens, tables, lens):
        logits, pools = model.decode_step_paged(params, tokens, pools,
                                                tables, lens, backend="xla")
        return jnp.argmax(logits, -1).astype(jnp.int32), pools

    name = ("serve_decode_step_int8w_tp2" if shard_weights
            else "serve_decode_step_int8w_tp2_repl")
    art = lower_program(
        jax.jit(step, donate_argnums=(1,)), params, pools, toks, tables,
        lens, name=name, mesh=mesh, donatable={"pools": pools},
        donation_expected=False,
        meta={"skip_required": True, "world_size": 2})
    return analyze_programs(
        [art], _stage0_config(), _FakeTPPlan(),
        settings=AnalysisSettings(min_replicated_bytes=INT8W_REPL_FLOOR))


def quantized_weight_replicated(devices=None):
    """Weight-only-quantization audit: the tp=2 int8-weight decode step
    whose quantized layer stack was accidentally REPLICATED across the
    `tensor` axis — each chip holds the full s8 payload + scales, so the
    HBM halving that justified weight-only int8 is silently returned.
    ``replication-over-budget`` must fire (the int8 payloads are in scope:
    the replication scanner prices s8 tensors alongside floats). The
    correctly-sharded twin (``int8_weight_pool_report(shard_weights=True)``
    — payload columns with the projection, scales on the same out-channel
    axis) passes the identical settings — tests assert both directions;
    CLI-runnable (``lint --corpus quantized-weight-replicated``)."""
    return int8_weight_pool_report(shard_weights=False, devices=devices)


def adapter_slot_leak(devices=None):
    """Multi-tenancy audit: a serving request path that never releases its
    LoRA adapter-slot pin under churned multi-tenant load. Refcounts only
    climb, refcount-0 residents never reach the LRU queue, and the slot
    pool exhausts even though every request that pinned it has long
    finished. ``pool-growth`` must fire. The correctly-releasing twin
    (same churn, every finish drops its pin) cycles the load through LRU
    eviction forever and passes — tests assert both directions; the twin
    is also CLI-runnable (``serving_lint --adapters --correct``)."""
    from deepspeed_tpu.analysis.serving_lint import audit_adapters
    return audit_adapters(correct=False)


def serving_unbounded_queue(devices=None):
    """Admission audit: the serving scheduler configured with NO admission
    watermark under a sustained exhaustion storm — every arrival queues,
    the queue grows monotonically without bound, nothing is shed.
    ``queue-growth`` must fire. The correctly-watermarked twin (same
    overload, ``max_queue=8``) sheds typed ``AdmissionRejected``s, keeps
    the queue bounded, and passes — tests assert both directions; the twin
    is also CLI-runnable (``serving_lint --max-queue 8``)."""
    from deepspeed_tpu.analysis.serving_lint import audit_admission
    return audit_admission(max_queue=None)


def router_blackhole(devices=None):
    """Routing audit: a multi-replica serving router with NO circuit
    breaker, fed a steady arrival stream while one replica dies silently
    mid-run. The dead replica's registry meta froze at low load, so the
    breaker-less router keeps winning ties toward the corpse — its
    attributed in-flight count grows monotonically and nothing completes.
    ``inflight-growth`` must fire. The breaker-enabled twin (same load,
    same kill, ``RouterConfig.breaker=True``) detects the stale heartbeat,
    fails over from the drain snapshot, and passes — tests assert both
    directions; the twin is also CLI-runnable
    (``serving_lint --router --breaker``)."""
    from deepspeed_tpu.analysis.serving_lint import audit_router
    return audit_router(breaker=False)


def prefix_refcount_leak(devices=None):
    """Prefix-sharing audit: a copy-on-write fork path that never
    decrements shared-block refcounts under a churned shared-prefix load.
    The LRU cache keeps evicting stale entries, but evicted blocks hold
    stuck references and never rejoin the free list — the held-block
    count grows monotonically until the pool exhausts. ``pool-growth``
    must fire. The correctly-decrementing twin (same churn, fork drops
    its pin and finish frees every mapped block) stays bounded at the
    cache cap and passes — tests assert both directions; the twin is
    also CLI-runnable (``serving_lint --prefix --correct``)."""
    from deepspeed_tpu.analysis.serving_lint import audit_prefix
    return audit_prefix(correct=False)


def handoff_recompute(devices=None):
    """Disaggregated-serving audit: a prefill tier feeding a decode tier
    whose handoffs silently fall back to re-prefill
    (``RouterConfig.handoff_kv`` off) under a steady long-prompt load.
    Every request still completes, but the decode tier re-pays every
    stranger's prompt — re-prefill debt outruns the decode budget and
    decode-tier TTFT grows monotonically. ``ttft-growth`` must fire. The
    KV twin (same load, same tiers, the bytes actually travel) stays
    flat and passes — tests assert both directions; the twin is also
    CLI-runnable (``serving_lint --handoff --kv``)."""
    from deepspeed_tpu.analysis.serving_lint import audit_handoff
    return audit_handoff(kv=False)


def offload_serial_pipeline(devices=None):
    """Offload pipeline audit: a layer-streamed executor whose overlap
    pipeline was silently disabled — every param fetch resolves
    synchronously on the critical path and every write drains before the
    next layer runs, so the step pays the full storage latency on top of
    compute.
    ``audit_offload`` drives the REAL InfinityExecutor with calibrated
    injected fetch latency; the drained defect exposes ~the whole injected
    budget and ``offload-overlap`` must fire (host-stall dominant). The
    pipelined twin (same executor, same latency,
    ``pipeline_read/pipeline_write`` on) hides it under layer compute and
    passes — tests assert both directions; the twin is also CLI-runnable
    (``python -m deepspeed_tpu.analysis.offload_lint --pipelined``)."""
    from deepspeed_tpu.analysis.offload_lint import audit_offload
    return audit_offload(pipeline=False)


def exposed_collective_trace(devices=None):
    """Perf doctor gate: a TRACED step (not a compiled program) whose
    all-reduce runs with nothing scheduled under it — 8 ms of measured
    exposed wire in an 18 ms step. The doctor's attribution must price the
    full collective as exposed and ``exposed-collective-measured`` must
    fire. This is the measured counterpart of ``deferred-sync-regression``
    (whose exposure is modeled from the scheduled HLO)."""
    from deepspeed_tpu.profiling.doctor import run_corpus_entry
    return run_corpus_entry()


def serving_blind_stall(devices=None):
    """Serving doctor gate (synthetic decomposition, not a compiled
    program): a round-phase ring where adapter paging/CoW housekeeping
    blows up every other round — an injected paging stall that flat
    counters would average away. ``diagnose_serving`` must attribute the
    per-token bound to the housekeeping phase and ``serving-phase-stall``
    must fire naming it (paging-bound, with the adapter_slots knob). The
    instrumented twin (same synthetic fleet, stall removed) passes —
    tests assert both directions; the twin is also CLI-runnable
    (``python -m deepspeed_tpu.profiling.doctor --corpus
    serving-blind-stall``)."""
    from deepspeed_tpu.profiling.doctor import run_corpus_entry
    return run_corpus_entry("serving-blind-stall")


def tracing_sync_leak(devices=None):
    """Serving doctor gate: the REAL ``RequestTracer`` armed with an
    ``on_span`` hook that performs a ``device_get`` per span — the
    documented defect seam of the zero-sync tracing contract. The hook
    self-reports through ``tracer.device_syncs``; ``tracing-sync-leak``
    must fire (device-syncs). The host-clock twin (same span load, no
    hook) reports zero syncs and passes — both directions CLI-runnable
    (``doctor --corpus tracing-sync-leak``). The measured span overhead
    is a reported field; it gates nothing."""
    from deepspeed_tpu.profiling.doctor import run_corpus_entry
    return run_corpus_entry("tracing-sync-leak")


def staging_buffer_alias(devices=None):
    """Race corpus (deterministic interleaving explorer, not a compiled
    program): the REAL ``StagingRing`` with the write-behind fence skipped
    — the sweep refills a staging buffer before its drain copied it. The
    explorer must find an interleaving where a drained chunk carries the
    next chunk's bytes and report ``buffer-alias`` with a replayable
    schedule id. Corrected twin (``acquire`` through the busy-future
    fence): race_lint --corpus staging-buffer-alias --correct."""
    from deepspeed_tpu.analysis.race_lint import audit_schedules
    return audit_schedules("staging-buffer-alias", correct=False)


def allocator_unlocked_share(devices=None):
    """Race corpus: an unsynchronized check-then-share against the REAL
    ``BlockAllocator`` racing a concurrent free + fresh allocation — the
    explorer must find a schedule where the share hits a freed/recycled
    block (``refcount-race``), with a replayable schedule id. Corrected
    twin holds the share and the invalidating free atomic."""
    from deepspeed_tpu.analysis.race_lint import audit_schedules
    return audit_schedules("allocator-unlocked-share", correct=False)


def drain_schema_skew(devices=None):
    """Proto corpus (wire-schema lint, not a compiled program): a v3
    drain-state writer that persists an UNREGISTERED ``sampler_state``
    field, read back bare (no ``.get``/membership guard) by a reader
    that still sees v2 tags on disk — the reader/writer skew a rolling
    fleet upgrade turns into a crash loop. ``proto_lint`` must flag the
    writer (``schema-breaking-change``, file:line) and the reader
    (``reader-writer-skew``). Corrected twin (field registered, read
    guarded): ``proto_lint --corpus``."""
    from deepspeed_tpu.analysis.proto_lint import audit_drain_schema_skew
    return audit_drain_schema_skew(correct=False)


def fenceless_failover(devices=None):
    """Model-check corpus (exhaustive bounded explorer over the REAL
    ``ServingRouter``, not a compiled program): a router that treats
    heartbeat silence ALONE as death evidence. The explorer must find an
    event sequence (probe -> stale -> probe -> probe) where the muted
    but alive replica completes a request the fenceless sweep already
    resubmitted — ``double-serve``, with a replayable event-trace id.
    Corrected twin (the shipped fencing rule: migrate only on
    in-process death or a committed drain snapshot) holds over the full
    bounded space: ``modelcheck --corpus``."""
    from deepspeed_tpu.robustness.modelcheck import audit_events
    return audit_events("fenceless-failover", correct=False)


CORPUS = {
    "undonated-state": undonated_state,
    "extra-collective": extra_collective,
    "f32-upcast": f32_upcast,
    "replicated-budget": replicated_budget,
    "census-drift": census_drift,
    "fused-hoist": fused_loop_hoist,
    "telemetry-leak": telemetry_leak,
    "deferred-sync-regression": deferred_sync_regression,
    "remat-missing": remat_missing,
    "stage3-replicated-opt": stage3_replicated_opt,
    "paged-cache-leak": paged_cache_leak,
    "tp-serving-replicated-pool": tp_serving_replicated_pool,
    "quantized-weight-replicated": quantized_weight_replicated,
    "adapter-slot-leak": adapter_slot_leak,
    "serving-unbounded-queue": serving_unbounded_queue,
    "router-blackhole": router_blackhole,
    "prefix-refcount-leak": prefix_refcount_leak,
    "handoff-recompute": handoff_recompute,
    "offload-serial-pipeline": offload_serial_pipeline,
    "exposed-collective-trace": exposed_collective_trace,
    "serving-blind-stall": serving_blind_stall,
    "tracing-sync-leak": tracing_sync_leak,
    "staging-buffer-alias": staging_buffer_alias,
    "allocator-unlocked-share": allocator_unlocked_share,
    "drain-schema-skew": drain_schema_skew,
    "fenceless-failover": fenceless_failover,
}


def run_corpus(name: str, devices=None):
    """Run one seeded entry; the returned Report must NOT be ok."""
    try:
        fn = CORPUS[name]
    except KeyError:
        raise SystemExit(f"unknown corpus entry '{name}' — one of "
                         f"{sorted(CORPUS)}")
    return fn(devices)
