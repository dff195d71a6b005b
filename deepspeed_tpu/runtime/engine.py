"""The training engine.

Reference: ``deepspeed/runtime/engine.py:183`` (DeepSpeedEngine) and
``deepspeed/__init__.py:52`` (initialize). The reference engine is a hook
machine: it wraps an eager nn.Module, intercepts forward/backward, buckets
grads, and drives partitioned optimizers. Here the engine is a *compiler
front-end*: it resolves config -> mesh plan -> sharding specs, builds ONE
jitted train_step (forward + backward + grad-accum + optimizer + loss-scale
update, with buffer donation), and XLA performs what stage_1_and_2.py /
stage3.py do by hand (reduce-scatter of grads, partitioned optimizer step,
all-gather of updated params, overlap of comm with compute).

API parity:
  initialize(...) -> (engine, optimizer, dataloader, lr_scheduler)
  engine.train_batch(batch)            — pipe-engine-style one-call step
  engine.forward / backward / step     — eager-style 3-call loop (grad
                                          accumulation across calls, like the
                                          reference's micro-batch loop)
  engine.save_checkpoint / load_checkpoint
  engine.global_steps, get_lr, get_loss_scale, ...
"""

import contextlib
import dataclasses
import json
import math
import os
import time
from functools import partial
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.config import Config
from deepspeed_tpu.ops.registry import get_optimizer_builder
from deepspeed_tpu.ops.optimizers import Optimizer, global_grad_norm
from deepspeed_tpu.parallel import (
    MeshPlan, build_mesh, make_rules, plan_from_config, spec_tree, num_params)
from deepspeed_tpu.runtime import fp16 as fp16_mod
from deepspeed_tpu.runtime import zero as zero_mod
from deepspeed_tpu.runtime import checkpointing as ckpt_mod
from deepspeed_tpu.runtime.lr_schedules import get_scheduler
from deepspeed_tpu.telemetry import accumulators as tel_acc
from deepspeed_tpu.telemetry.tracing import build_clock, build_log
from deepspeed_tpu.telemetry.tracing import span as _span
from deepspeed_tpu.utils import logging as log_mod
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer

logger = log_mod.logger

# optimizers the flat-chunk swap kernels implement (reference: the cpu-adam
# restriction on the swap_tensor path)
_ADAM_FAMILY = ("adam", "adamw", "cpuadam", "fusedadam")


def _opt_name(config) -> str:
    return (config.optimizer.name if config.optimizer else "adamw").lower()



def initialize(args=None, model=None, config=None, config_params=None,
               optimizer=None, lr_scheduler=None, mesh=None, rng=None,
               model_parameters=None, dist_init_required=None, mpu=None,
               **kwargs):
    """Build an Engine (reference: ``deepspeed/__init__.py:52``).

    `model` is a ModelSpec (deepspeed_tpu.models) or any object with
    .init/.loss_fn/.logical_axes. Returns (engine, optimizer, dataloader,
    lr_scheduler) for signature parity — dataloader is None unless
    training_data is passed via kwargs.
    """
    cfg = Config.load(config if config is not None else config_params)
    if args is not None and getattr(args, "deepspeed_config", None):
        cfg = Config.load(args.deepspeed_config)
    if cfg.autotuning.enabled:
        # reference: autotuning/autotuner.py:39 — search mesh/zero/microbatch/
        # remat before building the real engine, then build with the winner
        from deepspeed_tpu.autotuning import autotune_config
        src = config if config is not None else config_params
        if src is None and args is not None:
            src = getattr(args, "deepspeed_config", None)
        if isinstance(src, dict):
            raw = json.loads(json.dumps(src))
        else:
            with open(src) as f:
                raw = json.load(f)
        raw, model = autotune_config(model, raw,
                                     devices=kwargs.get("devices"))
        cfg = Config.load(raw)
    if cfg.elasticity.enabled:
        # reference: elasticity/elasticity.py:231 — pin a batch size
        # compatible with the widest device-count range, then derive the
        # micro/gas split for THIS world size
        from deepspeed_tpu.elasticity import compute_elastic_config
        devs = kwargs.get("devices")
        ws = len(devs) if devs else jax.device_count()
        if not cfg.elasticity.ignore_non_elastic_batch_info and any(
                v is not None for v in (cfg.train_batch_size,
                                        cfg.train_micro_batch_size_per_gpu,
                                        cfg.gradient_accumulation_steps)):
            raise ValueError(
                "elasticity sets the batch triad itself; remove "
                "train_batch_size/train_micro_batch_size_per_gpu/"
                "gradient_accumulation_steps or set "
                "ignore_non_elastic_batch_info")
        # the batch triad is per DATA-parallel replica, not per chip: a
        # tensor/pipe-parallel mesh divides the chips among model shards
        dp = plan_from_config(cfg, ws).dp_world_size
        fb, _valid, micro = compute_elastic_config(
            dataclasses.asdict(cfg.elasticity), world_size=dp)
        cfg.train_batch_size = fb
        cfg.train_micro_batch_size_per_gpu = micro
        cfg.gradient_accumulation_steps = fb // (micro * dp)
    engine = Engine(model=model, config=cfg, optimizer=optimizer,
                    lr_scheduler=lr_scheduler, mesh=mesh, rng=rng,
                    devices=kwargs.get("devices"))
    training_data = kwargs.get("training_data")
    dataloader = None
    if training_data is not None:
        from deepspeed_tpu.runtime.dataloader import DataLoader
        # train_batch() consumes GLOBAL batches (train_batch_size rows)
        dataloader = DataLoader(training_data,
                                batch_size=engine.config.train_batch_size)
        # checkpoints carry the loader's position (epoch/batch/seed) so an
        # elastic resume neither replays nor skips data
        engine.attach_dataloader(dataloader)
    return engine, engine.optimizer, dataloader, engine.lr_scheduler


class Engine:
    def __init__(self, model, config: Config, optimizer: Optional[Optimizer] = None,
                 lr_scheduler=None, mesh: Optional[Mesh] = None, rng=None,
                 devices=None):
        from deepspeed_tpu import comm
        comm.init_distributed()

        self.model = model
        self.config = config
        self.accelerator = get_accelerator()

        # --- mesh plan (reference: _configure_distributed_model:1052 + groups)
        n_devices = len(devices) if devices is not None else jax.device_count()
        self.plan: MeshPlan = plan_from_config(config, n_devices)
        self.mesh: Mesh = mesh if mesh is not None else build_mesh(self.plan, devices)
        from deepspeed_tpu.parallel.context import set_parallel_context
        set_parallel_context(self.mesh, self.plan)
        # ZeRO-Infinity layer streaming: with an explicit mesh it composes
        # with data/fsdp parallelism (batch triad resolves against the full
        # dp degree); with no mesh config it stays the legacy single-device
        # capacity executor regardless of the harness's device count
        self._infinity_multi = (_infinity_mode(config)
                                and bool(config.mesh.axes)
                                and self.plan.world_size > 1)
        config.resolve_batch_size(
            self.plan.dp_world_size
            if (not _infinity_mode(config) or self._infinity_multi) else 1)
        logger.info(zero_mod.describe(config.zero_optimization, self.plan))
        logger.info(f"batch: train={config.train_batch_size} "
                    f"micro={config.train_micro_batch_size_per_gpu} "
                    f"gas={config.gradient_accumulation_steps} "
                    f"dp={self.plan.dp_world_size}")
        if config.sparse_gradients:
            # reference: engine.py:2302-2369 sparse_allreduce_list. N/A by
            # design here — see sparse_gradients_enabled() and
            # benchmarks/embedding_grad.py for the byte math
            logger.warning(
                "sparse_gradients=true is a no-op on TPU: embedding "
                "cotangents are fused scatter-adds reduce-scattered over "
                "ICI with the other grads (V*H/dp bytes/chip); a "
                "(values, indices) wire would need dynamic shapes and "
                "moves more bytes at realistic vocab/batch sizes")

        # --- model-level perf levers (`transformer` config section):
        # applied with the act-quant rebuild idiom — dataclasses.replace +
        # make_model keeps the param structure identical; only the compute
        # path (fused attention backward) changes. Runs BEFORE pipeline
        # wrapping so staged models get the same levers.
        tcfg = config.transformer
        if tcfg.fused_backward:
            from deepspeed_tpu.models.transformer import (
                TransformerConfig as _TC)
            if isinstance(getattr(model, "config", None), _TC):
                from deepspeed_tpu.models import make_model as _mk
                model = _mk(dataclasses.replace(
                    model.config, fused_backward=tcfg.fused_backward),
                    name=model.name)
                self.model = model
                logger.info("transformer tuning: fused_backward="
                            f"{tcfg.fused_backward}")
            else:
                logger.warning("`transformer` config section ignored: model "
                               "is not a transformer ModelSpec")

        # --- pipeline wrapping (reference: PipelineEngine construction)
        self._pp_mode = self.plan.pipe > 1
        if self._pp_mode and self.plan.seq > 1:
            raise ValueError("pipe>1 with seq>1 is not supported: ring "
                             "attention cannot nest inside the pipelined "
                             "manual mesh region")
        if self._pp_mode:
            from deepspeed_tpu.models.transformer import TransformerConfig
            from deepspeed_tpu.models.pipeline_wrapper import make_pipelined_model
            if not isinstance(getattr(model, "config", None), TransformerConfig):
                raise ValueError("pipeline parallelism requires a transformer "
                                 "ModelSpec (stacked-layer params)")
            model = make_pipelined_model(
                model.config, self.mesh,
                num_microbatches=config.gradient_accumulation_steps,
                name=f"{model.name}-pp{self.plan.pipe}")
            self.model = model
            logger.info(f"pipeline mode: {self.plan.pipe} stages, "
                        f"{config.gradient_accumulation_steps} microbatches")

        # --- sharding rules
        zero_cfg = config.zero_optimization
        self.rules = make_rules(zero_cfg.stage, tp=self.plan.tensor > 1,
                                pipe=self._pp_mode)
        laxes = model.logical_axes
        base_specs = spec_tree(laxes, self.rules)
        # shapes via eval_shape (no memory)
        self._rng = rng if rng is not None else jax.random.PRNGKey(config.seed)
        param_shapes = jax.eval_shape(model.init, self._rng)
        shape_tree = jax.tree.map(lambda s: s.shape, param_shapes)
        self.param_specs = jax.tree.map(
            lambda spec, sh: zero_mod.zero_param_spec(spec, sh, self.plan, zero_cfg),
            base_specs, shape_tree, is_leaf=lambda x: isinstance(x, P))
        self.grad_specs = zero_mod.tree_grad_spec(
            self.param_specs, shape_tree, self.plan, zero_cfg)
        self.opt_specs = zero_mod.tree_opt_spec(
            self.param_specs, shape_tree, self.plan, zero_cfg)
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.param_specs,
            is_leaf=lambda x: isinstance(x, P))

        # --- precision (reference: _configure_distributed_model dtype + fp16 wrap)
        self.compute_dtype = config.compute_dtype
        self._fp16 = config.fp16.enabled
        use_master = self.compute_dtype != jnp.float32

        # --- optimizer-state offload (ZeRO-Offload / ZeRO-Infinity; reference:
        # runtime/zero/offload_config.py + swap_tensor/*). device=cpu keeps
        # states in pinned host DRAM; device=nvme streams fp32 state through
        # HBM from NVMe chunk files (swap_tensor.NVMeOptimizerSwapper).
        off_opt_cfg = config.zero_optimization.offload_optimizer
        self._nvme_opt = off_opt_cfg.enabled and off_opt_cfg.device == "nvme"
        self._offload_opt = off_opt_cfg.enabled and off_opt_cfg.device == "cpu"
        self._swapper = None
        if self._nvme_opt and not _infinity_mode(config):
            if not off_opt_cfg.nvme_path:
                raise ValueError("offload_optimizer.device=nvme requires "
                                 "offload_optimizer.nvme_path")
            if _opt_name(config) not in _ADAM_FAMILY:
                raise ValueError(
                    f"offload_optimizer.device=nvme supports the Adam family "
                    f"only (got '{_opt_name(config)}') — the flat-chunk swap "
                    f"kernel is Adam; reference has the same restriction")
            if optimizer is not None:
                raise ValueError("offload_optimizer.device=nvme requires a "
                                 "config-built optimizer, not a client one")
        self._swap_storage = "nvme"
        if self._offload_opt and not _infinity_mode(config):
            kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
            has_pinned = "pinned_host" in kinds
            on_cpu = get_accelerator().platform == "cpu"
            if off_opt_cfg.use_cpu_adam:
                if (_opt_name(config) not in _ADAM_FAMILY
                        and _opt_name(config) != "adagrad") or \
                        optimizer is not None:
                    # same contract as the nvme swapper: the fused host
                    # kernels cover the Adam family + Adagrad (reference:
                    # csrc/{adam,adagrad}/cpu_*.cpp), config-built only
                    raise ValueError(
                        "offload_optimizer.use_cpu_adam requires a config-"
                        f"built Adam-family or Adagrad optimizer (got "
                        f"'{_opt_name(config)}'"
                        f"{', client-supplied' if optimizer else ''})")
                if _opt_name(config) == "adagrad":
                    from deepspeed_tpu.ops.cpu_adagrad import (
                        cpu_adagrad_available as cpu_adam_available)
                else:
                    from deepspeed_tpu.ops.cpu_adam import cpu_adam_available
                if cpu_adam_available():
                    # the optimizer runs ON the host (native fused CPU-Adam)
                    # over host-resident fp32 state: 4 bytes/param/step on
                    # the bus instead of 28 (reference: DeepSpeedCPUAdam)
                    self._nvme_opt = True
                    self._offload_opt = False
                    self._swap_storage = "cpu_adam"
                    logger.info("optimizer state offload: host CPU-Adam "
                                "(fp32 state host-resident)")
                else:
                    logger.warning("use_cpu_adam requested but the native "
                                   "library failed to build; falling back "
                                   "to the chunk-streamed tier")
            if self._swap_storage == "cpu_adam":
                pass  # routed above
            elif _opt_name(config) in _ADAM_FAMILY and optimizer is None:
                # device=cpu rides the same chunked double-buffered swapper
                # as NVMe, with host-tier buffers instead of files — the
                # round trip streams per chunk and overlaps with compute
                # (round-2 verdict: the old path moved the WHOLE opt tree
                # to device and back eagerly every step)
                self._nvme_opt = True
                self._offload_opt = False
                self._swap_storage = "host" if (on_cpu or not has_pinned) \
                    else "pinned"
                logger.info("optimizer state offload: chunk-streamed "
                            f"{self._swap_storage} tier (pipelined swapper)")
            elif not has_pinned:
                # the eager fallback needs real pinned_host memory
                logger.warning("offload_optimizer requested but pinned_host "
                               "memory unavailable; disabling")
                self._offload_opt = False
            else:
                logger.info("optimizer state offload: pinned_host DRAM "
                            "(eager round-trip; non-Adam or client "
                            "optimizer cannot use the flat-chunk swapper)")

        # --- param offload (ZeRO-Infinity param path; reference:
        # swap_tensor/partitioned_param_swapper.py). Stacked layer weights
        # live in pinned host DRAM; the forward scan streams one layer at a
        # time into HBM (models/transformer.py body device_put).
        off_p_cfg = config.zero_optimization.offload_param
        # ZeRO-Infinity layer-streamed executor: owns BOTH the param chunks
        # and the optimizer chunks (reference: partitioned_param_swapper.py:35
        # + stage3.py:1735 sub-group loop). Two tiers:
        #   device=nvme          -> AIO chunk files (local-NVMe deployments)
        #   device=cpu (+opt cpu)-> TPU-host pinned DRAM (ZeRO-Offload tier)
        self._infinity = _infinity_mode(config)
        self._infinity_exec = None
        self._infinity_backend = None
        if self._infinity:
            if off_p_cfg.device == "nvme" or off_opt_cfg.device == "nvme":
                # the LayerStore is one tier for param AND opt chunks: a
                # mixed cpu/nvme request collapses to nvme as the system of
                # record — the executor's full host bf16-bits param cache
                # (offload_param.max_in_cpu, default all layers) gives the
                # cpu-tier refetch speed on top
                self._infinity_backend = "nvme"
                if off_p_cfg.device == "cpu":
                    logger.info(
                        "offload_param.device=cpu + offload_optimizer."
                        "device=nvme: chunks persist on nvme; the host "
                        "param cache keeps params cpu-resident for refetch")
            elif get_accelerator().platform == "cpu":
                self._infinity_backend = "host"  # CPU tests: plain buffers
            else:
                self._infinity_backend = "pinned"
            if not off_opt_cfg.enabled:
                # reference ZeRO-3 can offload params while keeping the
                # optimizer in HBM; the layer-streamed executor owns both —
                # opt chunks ride the same tier as the params
                logger.info("offload_param without offload_optimizer: "
                            "optimizer chunks ride the param tier (the "
                            "executor streams both per layer)")
            from deepspeed_tpu.models.transformer import TransformerConfig
            if not isinstance(getattr(model, "config", None), TransformerConfig):
                raise ValueError("offload_param requires a transformer "
                                 "ModelSpec (layer streaming)")
            if self._infinity_backend == "nvme":
                if not (off_p_cfg.nvme_path or off_opt_cfg.nvme_path):
                    raise ValueError("offload_param.device=nvme requires "
                                     "nvme_path")
                if off_opt_cfg.enabled and off_opt_cfg.device == "cpu" \
                        and off_p_cfg.device == "nvme":
                    logger.info(
                        "offload_param.device=nvme + offload_optimizer."
                        "device=cpu: opt chunks persist on nvme with the "
                        "params (one LayerStore tier)")
            if self._infinity_multi:
                # offload composed with data/fsdp/tensor parallelism
                # (reference: ZeRO-3 + NVMe under a Megatron TP mpu,
                # engine.py:1088-1100 + stage3.py:65): layer chunks shard
                # over fsdp x tensor, batch over (data, fsdp), and the
                # per-layer jits re-shard the unflattened weights to
                # Megatron col/row specs
                if (self.plan.pipe > 1 or self.plan.seq > 1
                        or self.plan.expert > 1):
                    raise ValueError(
                        "layer-streamed offload shards over "
                        "data/fsdp/tensor (pipe/seq/expert must be 1)")
            elif self.plan.world_size > 1:
                if get_accelerator().platform == "cpu":
                    # CPU test harness: single-device executor is fine
                    logger.warning(
                        "the layer-streamed executor runs single-device "
                        "without an explicit mesh config; set mesh.axes "
                        "{data/fsdp} to shard it")
                else:
                    # on real multi-chip hardware silently training on one
                    # chip (with 7 idle) is never what the user configured
                    raise ValueError(
                        "multi-device layer-streamed offload requires an "
                        "explicit mesh config: set mesh.axes {'data': N} "
                        "and/or {'fsdp': N}")
            if self._pp_mode:
                raise ValueError("layer-streamed offload with pipeline "
                                 "parallelism is not supported")
            # fp16 composes: the executor carries host-side dynamic loss
            # scaling (storage bits stay bf16; the fp32 master in the opt
            # chunks carries precision)
            if _opt_name(config) not in ("adam", "adamw"):
                raise ValueError("layer-streamed offload supports the "
                                 f"Adam family only (got "
                                 f"'{_opt_name(config)}')")
            if optimizer is not None:
                raise ValueError("layer-streamed offload requires a "
                                 "config-built optimizer, not a client one")
            # the executor replaces the swapper AND the jitted train step
            self._nvme_opt = False
        # every offload_param configuration routes through the layer-streamed
        # executor above (round-5: the old non-streamed scan-fetch train path
        # was single-device-only — an in-graph host writeback this runtime
        # rejects — and is deleted; cfg.offload_params scan-fetch remains for
        # INFERENCE capacity, models/transformer.py:1089)

        # --- optimizer (reference: _configure_optimizer:1175)
        self.lr_scheduler = lr_scheduler
        self._schedule = None
        if lr_scheduler is None and config.scheduler is not None:
            self._schedule = get_scheduler(config.scheduler.name,
                                           config.scheduler.params)
            self.lr_scheduler = self._schedule
        elif callable(lr_scheduler):
            self._schedule = lr_scheduler
        if optimizer is not None:
            from deepspeed_tpu.ops.optimizers import from_optax, is_optax_transform
            self.optimizer = from_optax(optimizer) if is_optax_transform(optimizer) \
                else optimizer
        else:
            opt_cfg = config.optimizer
            name = opt_cfg.name if opt_cfg else "adamw"
            params = dict(opt_cfg.params) if opt_cfg else {}
            if self._schedule is not None:
                params["lr"] = self._schedule
            params.setdefault("use_master_weights", use_master)
            builder = get_optimizer_builder(name)
            self.optimizer = builder(**params)
        self._base_lr = None
        if config.optimizer and "lr" in config.optimizer.params:
            self._base_lr = config.optimizer.params["lr"]

        # --- 1-bit compressed communication path (reference: the NCCL/MPI
        # compressed_allreduce backends, runtime/comm/nccl.py:53). Grads stay
        # per-device local inside a shard_map over `data`; only packed sign
        # bits cross the wire in the compressed phase.
        from deepspeed_tpu.ops.onebit import PhasedOptimizer
        self._onebit_comm = False
        if isinstance(self.optimizer, PhasedOptimizer) and self.plan.data > 1:
            pure_dp = (self.plan.tensor == 1 and self.plan.pipe == 1
                       and self.plan.fsdp == 1 and self.plan.expert == 1
                       and self.plan.seq == 1)
            # ZeRO stays off by design: the 1-bit algorithm keeps FULL
            # momentum + master per rank (local momentum accumulates the
            # full local gradient before compression), so optimizer-state
            # sharding cannot compose — the reference's 1-bit optimizers
            # carry the same ZeRO restriction.
            ok = (pure_dp and zero_cfg.stage == 0
                  and not self._offload_opt and not self._nvme_opt)
            if ok:
                self._onebit_comm = True
                extras = []
                if self._fp16:
                    extras.append("fp16 loss scaling in-step")
                if config.gradient_clipping:
                    extras.append("synchronized norm-proxy clipping")
                logger.info("1-bit optimizer: compressed communication over "
                            f"data axis ({self.plan.data} ranks), packed "
                            "sign all-gather in the compressed phase"
                            + (f" ({', '.join(extras)})" if extras else ""))
            else:
                logger.warning(
                    "1-bit optimizer: compressed communication requires a "
                    "pure data-parallel mesh, zero stage 0, and no "
                    "offload — falling back to dense (error-feedback "
                    "sign update semantics are preserved, bytes are not "
                    "reduced)")

        # --- compression (reference: compression/compress.py:92) — a traced
        # param transform inside the step; masters stay full precision
        self._compression = None
        comp_cfg = dataclasses.asdict(config.compression_training)
        if any(((comp_cfg.get(k) or {}).get("shared_parameters", {})
                .get("enabled") or (comp_cfg.get(k) or {}).get("enabled"))
               for k in ("weight_quantization", "sparse_pruning",
                         "row_pruning", "head_pruning",
                         "activation_quantization", "channel_pruning",
                         "layer_reduction")):
            from deepspeed_tpu.compression import init_compression
            self._compression = init_compression(comp_cfg)
            # composes with the 1-bit compressed-comm path: the shard_map
            # step applies the same traced param transform inside its
            # per-device loss (see _get_onebit_step)
            # activation quantization / layer reduction reshape the MODEL,
            # not the params (reference: QuantAct wraps forward;
            # student_initialization builds a shallower net)
            self._act_quant = self._compression.activation_quant
            self._act_quant_on = False
            lr = self._compression.layer_reduction
            if self._act_quant or lr:
                from deepspeed_tpu.models.transformer import TransformerConfig
                if not isinstance(getattr(model, "config", None),
                                  TransformerConfig):
                    raise ValueError("activation_quantization/layer_reduction "
                                     "require a transformer ModelSpec")
            if lr is not None:
                import dataclasses as _dc
                from deepspeed_tpu.models import make_model as _mk
                keep = lr["keep_number"]
                model = _mk(_dc.replace(model.config, num_layers=keep),
                            name=f"{model.name}-student{keep}")
                self.model = model
                logger.info(f"layer reduction: student keeps {keep} layers")
                if lr["teacher_layer"]:
                    # the engine has no teacher weights to copy from —
                    # teacher init is an explicit user step, as in the
                    # reference's student_initialization utility
                    logger.warning(
                        "layer_reduction.teacher_layer is informational "
                        "here: initialize the student from a trained "
                        "teacher with compression.student_params_from_"
                        "teacher(...) and assign engine.state['params']")
            if self._act_quant and self._act_quant[1] <= 0:
                # no schedule offset: bake quantized activations in now
                model = self._rebuild_act_quant(model)
        else:
            self._act_quant = None
            self._act_quant_on = False

        # --- MoQ (reference: runtime/quantize.py + engine eigenvalue
        # events): eigenvalue-scheduled quantization of the layer stack
        from deepspeed_tpu.runtime.quantize import build_moq
        self._moq = None
        if config.quantize_training.get("enabled"):
            from deepspeed_tpu.models.transformer import TransformerConfig
            if not isinstance(getattr(model, "config", None),
                              TransformerConfig):
                raise ValueError("quantize_training (MoQ) requires a "
                                 "transformer ModelSpec (stacked layers)")
            if self._pp_mode:
                raise ValueError("quantize_training (MoQ) with pipeline "
                                 "parallelism is not supported")
            if _infinity_mode(config) and \
                    (config.quantize_training.get("eigenvalue") or {}) \
                    .get("enabled"):
                # the blockwise-Rayleigh curvature probe needs the resident
                # stacked-layer tree; streamed layers fall back to the
                # uniform quantize_period schedule
                logger.warning(
                    "MoQ eigenvalue scheduling requires resident params; "
                    "layer-streamed offload uses the uniform "
                    "quantize_period for every layer")
            # composes with the 1-bit compressed-comm path: the shard_map
            # step applies the same traced _moq_bits transform inside its
            # per-device loss (see _get_onebit_step)
            self._moq = build_moq(config.quantize_training,
                                  model.config.num_layers)

        # --- telemetry (deepspeed_tpu/telemetry): the accumulator leaf lives
        # in the donated jitted state so the jitted paths advance it in-graph;
        # host-driven optimizer paths (NVMe swapper, layer-streamed executor)
        # mirror it host-side — their metrics are host-resident by design
        tcfg = config.telemetry
        self._tel_cfg = tcfg if tcfg.enabled else None
        self._tel_in_graph = (tcfg.enabled and not self._nvme_opt
                              and not self._infinity)

        # --- state init (sharded at creation; reference: zero.Init equivalent)
        self.state_shardings = None
        if self._infinity:
            self.state = None  # streamed: the full tree never materializes
            self._infinity_exec = self._build_infinity()
        else:
            # set-up in the build log (telemetry.tracing.build_log): the
            # state's initialisation and sharding here, each step program at
            # its first call (``_building``)
            builds = build_clock()
            build_s = builds.seconds
            with _span("ds:setup.state") as sp_state:
                self.state = self._init_state()
            self._setup_state_s = (sp_state.seconds,
                                   builds.seconds - build_s)
            # --- jitted step functions
            self._compile_steps()

        # --- bookkeeping (reference: engine timers/monitor wiring)
        self.global_steps = 0
        # host-side part of the skip counter: the jitted paths account
        # skips in-graph (state["skipped"]); host-driven paths (NVMe
        # swapper, layer-streamed executor) bump this offset directly
        self._skipped_offset = 0
        self._ckpt_engine = None  # persistent async checkpoint engine
        self._last_grad_norm = None
        self._last_log_window = 0
        self.micro_steps = 0
        # --- robustness (deepspeed_tpu/robustness): deterministic fault
        # injection armed from config; the injector is PROCESS-global so an
        # elastic rebuild mid-run keeps the schedule's counters
        self._dataloader = None  # attach_dataloader: data position in ckpts
        self.fault_injector = None
        if config.robustness.faults.enabled:
            from deepspeed_tpu.robustness import faults as rb_faults
            self.fault_injector = rb_faults.install_from_config(
                config.robustness.faults)
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print)
        self._grad_buffer = None  # for forward/backward/step API
        self._accum_count = 0
        self.monitor = self._build_monitor()
        self.losses = None
        # --- telemetry host-side pieces (tracer, anomaly, window bookkeeping)
        self._tracer = None
        self._anomaly = None
        self._tel_host = None
        self._tel_prev = None        # last drained cumulative snapshot
        self._tel_wall = None        # perf_counter at the last drain
        self._tel_wall_steps = 0     # global_steps at the last drain
        self._tel_last_window = None  # last drained window stats (host dict)
        self._tel_static = None      # cached static-join cost ({} = failed)
        self._tel_static_thread = None  # background lower/compile worker
        import threading
        self._tel_lock = threading.Lock()  # guards _tel_static (worker
        # thread publishes the compiled cost; boundary drains poll it)
        self._tel_abs = None         # (jitted fn, abstract args, divisor)
        if self._tel_cfg is not None:
            from deepspeed_tpu.telemetry import (AnomalyDetector, HostWindow,
                                                 StepTracer)
            self._tracer = StepTracer(trace_cfg=self._tel_cfg.trace,
                                      max_events=self._tel_cfg.max_trace_events)
            if self._tel_cfg.anomaly.enabled:
                self._anomaly = AnomalyDetector(self._tel_cfg.anomaly)
            if not self._tel_in_graph:
                self._tel_host = HostWindow(self._tel_cfg.gnorm_hist_buckets)
        # comms-logger wiring (reference: the comms_logger config section
        # configures the logger at engine init; its totals reach the monitor
        # as comm/* events at steps_per_print boundaries — see _log_step)
        if config.comms_logger.enabled:
            from deepspeed_tpu.comm import comms_logger
            comms_logger.configure(
                enabled=True, verbose=config.comms_logger.verbose,
                prof_ops=(() if config.comms_logger.prof_all
                          else config.comms_logger.prof_ops))
        # --- data efficiency (reference: runtime/data_pipeline/*)
        self._curriculum = None
        if config.curriculum_learning.enabled:
            from deepspeed_tpu.runtime.data_pipeline import CurriculumScheduler
            if config.curriculum_learning.curriculum_type != "seqlen":
                raise ValueError("curriculum_type must be 'seqlen' (the "
                                 "reference's only in-engine curriculum)")
            self._curriculum = CurriculumScheduler(dataclasses.asdict(
                config.curriculum_learning))
            logger.info("curriculum learning: seqlen "
                        f"{self._curriculum.min_difficulty} -> "
                        f"{self._curriculum.max_difficulty} over "
                        f"{self._curriculum.total_step} steps")
        # progressive layer drop (reference: runtime/progressive_layer_drop.py
        # ProgressiveLayerDrop — theta(t) = (1-theta)*exp(-gamma*t) + theta)
        self._pld = None
        if config.progressive_layer_drop.enabled:
            from deepspeed_tpu.models.transformer import TransformerConfig
            if not isinstance(getattr(model, "config", None), TransformerConfig):
                raise ValueError("progressive_layer_drop requires a "
                                 "transformer ModelSpec")
            if self._pp_mode:
                raise ValueError("progressive_layer_drop with pipeline "
                                 "parallelism is not supported")
            if not model.config.scan_layers:
                raise ValueError("progressive_layer_drop requires "
                                 "scan_layers=True (the drop cond lives in "
                                 "the layer scan)")
            if not model.config.progressive_layer_drop:
                import dataclasses as _dc
                from deepspeed_tpu.models import make_model as _mk
                model = _mk(_dc.replace(model.config,
                                        progressive_layer_drop=True),
                            name=model.name)
                self.model = model
            self._pld = (config.progressive_layer_drop.theta,
                         config.progressive_layer_drop.gamma)
            logger.info(f"progressive layer drop: theta_floor={self._pld[0]} "
                        f"gamma={self._pld[1]}")
        self._ltd = None
        self._ltd_keep = None
        routing = config.data_efficiency.data_routing or {}
        if config.data_efficiency.enabled and \
                routing.get("random_ltd", {}).get("enabled"):
            from deepspeed_tpu.runtime.data_pipeline import RandomLTDScheduler
            from deepspeed_tpu.models.transformer import TransformerConfig
            if not isinstance(getattr(model, "config", None), TransformerConfig):
                raise ValueError("random_ltd requires a transformer ModelSpec")
            if self._pp_mode:
                raise ValueError("random_ltd with pipeline parallelism is not "
                                 "supported")
            self._ltd = RandomLTDScheduler(routing)
            self._ltd_orig_scan = model.config.scan_layers
            logger.info(f"random-ltd: kept tokens "
                        f"{self._ltd.min_value} -> {self._ltd.max_value}")
        n = num_params(param_shapes)
        state_s = getattr(self, "_setup_state_s", None)
        logger.info(f"engine ready: {model.name if hasattr(model, 'name') else 'model'} "
                    f"{n / 1e6:.1f}M params, dtype={self.compute_dtype.__name__}, "
                    f"mesh={self.plan.describe()}"
                    + (f", state set up in {state_s[0]:.2f} s "
                       f"({state_s[1]:.2f} s building its programs)"
                       if state_s else ""))

    # ------------------------------------------------------------------
    def _build_monitor(self):
        try:
            from deepspeed_tpu.monitor import MonitorMaster
            return MonitorMaster(self.config)
        except Exception as e:
            # a typo'd W&B/TB config must not silently disable monitoring
            logger.warning(f"monitor disabled — backend init failed: {e!r}")
            return None

    def _init_state(self):
        cfg = self.config
        zero_cfg = cfg.zero_optimization
        mesh = self.mesh

        param_sh = self.param_shardings

        def make_state(key):
            params32 = self.model.init(key)
            # nvme offload: fp32 state lives on NVMe chunks, never in HBM
            opt_state = None if self._nvme_opt else self.optimizer.init(params32)
            params = jax.tree.map(
                lambda p: p.astype(self.compute_dtype), params32)
            state = {"params": params, "opt": opt_state,
                     "step": jnp.zeros((), jnp.int32)}
            if self._fp16:
                if cfg.fp16.dynamic:
                    ls = fp16_mod.init_loss_scale(cfg.fp16.initial_scale_power,
                                                  hysteresis=cfg.fp16.hysteresis)
                else:
                    ls = fp16_mod.static_loss_scale(cfg.fp16.loss_scale)
                state["loss_scale"] = {"scale": ls.scale,
                                       "good_steps": ls.good_steps,
                                       "hysteresis": ls.hysteresis}
                # device-resident skip accounting: the jitted step advances
                # this on overflow so the host never fetches the overflow
                # flag in the hot loop (engine.skipped_steps reads it lazily)
                state["skipped"] = jnp.zeros((), jnp.int32)
            if self._tel_in_graph:
                # telemetry accumulators ride the donated state the same way:
                # advanced in-graph, drained by _log_step's one batched fetch
                state["telemetry"] = tel_acc.init_leaf(
                    cfg.telemetry.gnorm_hist_buckets)
            return state

        # Determine opt-state sharding by matching leaves against params:
        # per-param tensors (same shape as a param) use opt_specs; scalars replicate.
        state_shapes = jax.eval_shape(make_state, self._rng)
        self.state_shardings = self._state_shardings_from(state_shapes)
        init_fn = jax.jit(make_state, out_shardings=self.state_shardings)
        with self.mesh:
            state = init_fn(self._rng)
        if self._onebit_comm:
            state = self._expand_rank_varying(state)
        if self._offload_opt:
            state["opt"] = self._opt_to_host(state["opt"])
        if self._nvme_opt:
            self._swapper = self._build_swapper(state_shapes["params"])
            self._swapper.initialize(state["params"])
        return state

    def _expand_rank_varying(self, state):
        """Give each rank-varying optimizer-state subtree (1-bit error
        feedback buffers, 0/1-Adam local momentum) a leading [dp] dim sharded
        over `data` — per-worker values that are explicit and checkpointable
        instead of silently divergent 'replicated' shards."""
        dp = self.plan.data
        mesh = self.mesh
        rv = set(self.optimizer.rank_varying)

        def expand_tree(tree, spec_tree_):
            sh = jax.tree.map(
                lambda s: NamedSharding(mesh, P("data", *s.spec)), spec_tree_,
                is_leaf=lambda x: isinstance(x, NamedSharding))
            fn = jax.jit(
                lambda t: jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (dp,) + a.shape), t),
                out_shardings=sh)
            with mesh:
                out = fn(tree)
            return out, sh

        for k in list(state["opt"].keys()):
            if k in rv and state["opt"][k] is not None:
                state["opt"][k], sh = expand_tree(
                    state["opt"][k], self.state_shardings["opt"][k])
                self.state_shardings["opt"][k] = sh
        return state

    def _build_swapper(self, param_shapes):
        from deepspeed_tpu.runtime.swap_tensor import (HostAdamSwapper,
                                                       NVMeOptimizerSwapper)
        cfg = self.config
        off = cfg.zero_optimization.offload_optimizer
        p = dict(cfg.optimizer.params) if cfg.optimizer else {}
        name = _opt_name(cfg)
        if self._swap_storage == "cpu_adam":
            kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
            kw = dict(
                betas=tuple(p.get("betas", (0.9, 0.999))),
                eps=p.get("eps", 1e-10 if name == "adagrad" else 1e-8),
                weight_decay=p.get("weight_decay",
                                   0.01 if name == "adamw" else 0.0),
                adam_w_mode=(name == "adamw" or p.get("adam_w_mode", False)),
                bias_correction=p.get("bias_correction", True),
                param_shardings=self.param_shardings,
                compute_dtype=self.compute_dtype)
            if name == "adagrad":
                # host Adagrad tier rides the native swapper (the compute_on
                # flavor's tree update is Adam-only for now)
                return HostAdamSwapper(param_shapes, mesh=self.mesh,
                                       optim="adagrad", **kw)
            if (get_accelerator().platform != "cpu"
                    and "pinned_host" in kinds):
                # TPU-native flavor: Adam runs on the TPU host INSIDE the
                # XLA program (compute_on) over pinned-resident state — no
                # process-side grad fetch, so it's fast even when this
                # process is remote from the TPU host
                from deepspeed_tpu.runtime.swap_tensor import \
                    XlaHostAdamSwapper
                return XlaHostAdamSwapper(param_shapes, mesh=self.mesh, **kw)
            return HostAdamSwapper(param_shapes, mesh=self.mesh, **kw)
        grad_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.grad_specs,
            is_leaf=lambda x: isinstance(x, P))
        return NVMeOptimizerSwapper(
            param_shapes, mesh=self.mesh, nvme_path=off.nvme_path,
            storage=self._swap_storage,
            betas=tuple(p.get("betas", (0.9, 0.999))), eps=p.get("eps", 1e-8),
            weight_decay=p.get("weight_decay",
                               0.01 if name == "adamw" else 0.0),
            adam_w_mode=(name == "adamw" or p.get("adam_w_mode", False)),
            bias_correction=p.get("bias_correction", True),
            chunk_elems=max(1, off.buffer_size // 4),  # buffer_size is bytes
            param_shardings=self.param_shardings,
            grad_shardings=grad_shardings,
            compute_dtype=self.compute_dtype,
            # both pipeline knobs off = the fully-drained swapper (the old
            # `... or True` ignored an explicit opt-out)
            pipeline=bool(off.pipeline_read or off.pipeline_write),
            aio_config=cfg.aio)

    def _build_infinity(self):
        from deepspeed_tpu.runtime.infinity import InfinityExecutor
        cfg = self.config
        off_p = cfg.zero_optimization.offload_param
        off_o = cfg.zero_optimization.offload_optimizer
        p = dict(cfg.optimizer.params) if cfg.optimizer else {}
        name = _opt_name(cfg)
        lr = self._schedule if self._schedule is not None else p.get("lr", 1e-3)
        import dataclasses as _dc
        model_cfg = _dc.replace(self.model.config, dtype=self.compute_dtype)
        return InfinityExecutor(
            model_cfg, rng=self._rng,
            backend=self._infinity_backend,
            nvme_path=off_p.nvme_path or off_o.nvme_path,
            lr=lr, betas=tuple(p.get("betas", (0.9, 0.999))),
            eps=p.get("eps", 1e-8),
            weight_decay=p.get("weight_decay",
                               0.01 if name == "adamw" else 0.0),
            adam_w_mode=(name == "adamw" or p.get("adam_w_mode", False)),
            bias_correction=p.get("bias_correction", True),
            grad_clip=cfg.gradient_clipping or 0.0,
            param_cache_bytes=off_p.max_in_cpu,
            gas=cfg.gradient_accumulation_steps,
            mesh=self.mesh if self._infinity_multi else None,
            fp16=(dataclasses.asdict(cfg.fp16) if cfg.fp16.enabled else None),
            compression=self._compression,
            use_cpu_adam=off_o.use_cpu_adam,
            moq=self._moq is not None,
            # live cache only when the user set the knob: the reference
            # default (1e9) silently pinning ~2GB of bits in HBM could OOM
            # workloads sized without it
            max_live_params=(
                cfg.zero_optimization.stage3_max_live_parameters
                if cfg.zero_optimization.was_set("stage3_max_live_parameters")
                else 0),
            # overlapped offload pipeline: double-buffered layer streaming +
            # the three-way update sweep. The executor has ONE switch, so
            # turning BOTH knobs of EITHER offload section off drains it
            # (the offload-serial-pipeline corpus twin) — an explicit
            # opt-out on just offload_param must not be vetoed by
            # offload_optimizer's defaults
            pipeline=bool((off_p.pipeline_read or off_p.pipeline_write)
                          and (off_o.pipeline_read or off_o.pipeline_write)),
            aio_config=cfg.aio)

    def _state_shardings_from(self, state_shapes):
        """Build shardings for the full train-state pytree: params use
        param_specs, optimizer per-param tensors use opt_specs (ZeRO
        partitioning of master/moments), scalars replicate."""
        mesh = self.mesh
        param_leaves, param_treedef = jax.tree.flatten(
            jax.tree.map(lambda s: s, self.param_specs,
                         is_leaf=lambda x: isinstance(x, P)))
        opt_spec_tree = self.opt_specs

        def shard_like_params(subtree_shapes, specs):
            return jax.tree.map(
                lambda sh, sp: NamedSharding(mesh, sp),
                subtree_shapes, specs, is_leaf=lambda x: hasattr(x, "shape"))

        params_shapes = state_shapes["params"]

        def assign(sub):
            """Recursively walk the optimizer state: any subtree whose pytree
            structure matches the params tree gets the ZeRO opt-state specs
            (covers our dict optimizers AND optax NamedTuple states); scalars
            and everything else replicate."""
            if sub is None:
                return None
            if _same_structure(sub, params_shapes):
                return shard_like_params(sub, opt_spec_tree)
            if hasattr(sub, "shape"):  # leaf
                return NamedSharding(mesh, P())
            if isinstance(sub, dict):
                return {k: assign(v) for k, v in sub.items()}
            if isinstance(sub, tuple) and hasattr(sub, "_fields"):  # namedtuple
                return type(sub)(*[assign(v) for v in sub])
            if isinstance(sub, (tuple, list)):
                return type(sub)(assign(v) for v in sub)
            return jax.tree.map(lambda s: NamedSharding(mesh, P()), sub)

        out = {}
        # reuse the prebuilt param shardings (they may carry memory kinds,
        # e.g. pinned_host layer stacks under offload_param)
        out["params"] = self.param_shardings
        out["opt"] = assign(state_shapes["opt"])
        if self._offload_opt:
            # the jitted step stays memory-kind-free (XLA SPMD drops sharding
            # attributes on placement custom-calls for replicated tensors);
            # host residency is managed EAGERLY at step boundaries instead
            self._opt_host_shardings = jax.tree.map(
                lambda s: NamedSharding(s.mesh, s.spec, memory_kind="pinned_host")
                if s is not None else None,
                out["opt"], is_leaf=lambda x: x is None or isinstance(x, NamedSharding))
        out["step"] = NamedSharding(mesh, P())
        if "loss_scale" in state_shapes:
            out["loss_scale"] = jax.tree.map(
                lambda s: NamedSharding(mesh, P()), state_shapes["loss_scale"])
        if "skipped" in state_shapes:
            out["skipped"] = NamedSharding(mesh, P())
        if "telemetry" in state_shapes:
            out["telemetry"] = jax.tree.map(
                lambda s: NamedSharding(mesh, P()), state_shapes["telemetry"])
        return out

    # ------------------------------------------------------------------
    def _batch_spec(self):
        # expert groups consume distinct data (expert-data-parallelism);
        # sequence dim shards over `seq` when sequence parallelism is on
        from deepspeed_tpu.parallel.mesh import BATCH_AXES
        if self.plan.seq > 1:
            return P(BATCH_AXES, "seq")
        return P(BATCH_AXES)

    @staticmethod
    def _accum_micro_grads(micro_fn, params, batch, gas: int, rng,
                           postprocess=None, unroll: int = 0):
        """Gradient accumulation over `gas` microbatches, shared by the dense
        GSPMD step, the deferred-sync shard_map body, and the 1-bit shard_map
        step. micro_fn(params, mb, rng) -> (loss, grads); postprocess (e.g. a
        sharding constraint) is applied to the running accumulator. The 1/gas
        mean scaling is FOLDED into the accumulator update (one fused
        multiply-add inside the loop) instead of a separate post-scan sweep
        over the full grad tree. unroll >= gas fully unrolls the microbatch
        loop (comm.microbatch_unroll: per-microbatch collectives become
        distinct schedulable sites). Returns (summed grads / gas, mean
        loss)."""
        if gas == 1:
            loss, grads = micro_fn(params, batch, rng)
            return grads, loss

        def split(x):
            if getattr(x, "ndim", 0) == 0:  # scalar side-channel (e.g.
                return jnp.broadcast_to(x, (gas,))  # _pld_theta): replicate
            return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

        if isinstance(batch, dict):
            mbs = {k: (jnp.broadcast_to(v, (gas,) + jnp.shape(v))
                       if _is_side_channel(k) else split(v))
                   for k, v in batch.items()}
        else:
            mbs = jax.tree.map(split, batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if postprocess is not None:
            zeros = postprocess(zeros)
        inv_gas = np.float32(1.0 / gas)

        def body(acc, mb_rng):
            mb, r = mb_rng
            loss, g = micro_fn(params, mb, r)
            acc = jax.tree.map(lambda a, gg: a + gg * inv_gas, acc, g)
            if postprocess is not None:
                acc = postprocess(acc)
            return acc, loss

        rngs = jax.random.split(rng, gas)
        grads, losses = jax.lax.scan(
            body, zeros, (mbs, rngs),
            unroll=True if unroll >= gas else max(1, int(unroll)))
        return grads, jnp.mean(losses)

    def _compile_steps(self):
        cfg = self.config
        # (step function, batch shape) built so far: ``_building``
        self._steps_built = set()
        # in pipeline mode grad accumulation IS the microbatch rotation inside
        # the pipelined loss; the outer step consumes the whole global batch
        gas = 1 if self._pp_mode else cfg.gradient_accumulation_steps
        mesh = self.mesh
        grad_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                      self.grad_specs,
                                      is_leaf=lambda x: isinstance(x, P))
        model = self.model
        fp16 = self._fp16
        fp16_cfg = cfg.fp16
        clip = cfg.gradient_clipping
        compute_dtype = self.compute_dtype

        compression = self._compression

        moq = self._moq

        tel_on = self._tel_in_graph
        tel_ratio = tel_on and cfg.telemetry.update_ratio

        # --- communication scheduling (comm.schedule: deferred grad sync;
        # reference: overlap_comm / contiguous_gradients / no_sync in
        # runtime/zero/stage_1_and_2.py)
        from deepspeed_tpu.comm import schedule as comm_sched
        ccfg = cfg.comm
        unroll = max(0, int(ccfg.microbatch_unroll))
        self._microbatch_unroll = unroll  # one derivation; onebit reads it
        self._deferred_sync = False
        if ccfg.deferred_grad_sync:
            if self._onebit_comm:
                logger.info(
                    "comm.deferred_grad_sync: the 1-bit shard_map step is "
                    "already deferred by construction (grads accumulate "
                    "per-device local; only the phase collective crosses "
                    "the wire at the boundary)")
            elif self._nvme_opt or self._infinity or self._pp_mode:
                logger.warning(
                    "comm.deferred_grad_sync ignored: host-driven optimizer "
                    "paths and pipeline mode keep their own step structure")
            else:
                ok, why = comm_sched.deferred_supported(self.plan)
                if not ok:
                    logger.warning(f"comm.deferred_grad_sync ignored: {why}")
                elif self.plan.data <= 1:
                    logger.info(
                        "comm.deferred_grad_sync: no `data` axis to defer "
                        "over (dp rides fsdp; per-use reductions are ZeRO-3 "
                        "semantics) — eager path unchanged")
                else:
                    self._deferred_sync = True
                    logger.info(
                        "comm.deferred_grad_sync: microbatch grads "
                        "accumulate in a per-device local buffer; ONE "
                        f"data-axis sync per step (gas={gas})")
        deferred = self._deferred_sync
        plan = self.plan
        local_acc_specs = None
        deferred_unroll = unroll
        if deferred:
            local_ = comm_sched.local_tree(self.grad_specs)
            if any(len(s) for s in jax.tree.leaves(
                    local_, is_leaf=lambda x: isinstance(x, P))):
                local_acc_specs = local_
            # a lax.scan INSIDE the manual-over-data region trips an XLA
            # SPMD check (hlo_sharding_util IsManualSubgroup) whenever a
            # size>1 AUTO axis exists (fsdp/tensor 2D meshes) — unroll the
            # microbatch loop there; pure-data meshes keep the scan
            if any(v > 1 for a, v in plan.axis_sizes().items()
                   if a != "data"):
                deferred_unroll = max(unroll, gas)

        moe_model = getattr(getattr(model, "config", None),
                            "num_experts", 1) > 1

        def micro_grads(params, mb, rng, scale, step=None, specs="grad",
                        load=None):
            """``load``: a list that takes the expert layers' load rows of
            this microbatch ([layers, E + 1], ``moe.sharded_moe._LoadTap``),
            where the caller's trace is the one this runs in."""
            from deepspeed_tpu.moe.sharded_moe import expert_load_tap

            def loss_fn(p):
                if compression is not None:
                    p = compression.apply(p, step if step is not None else 0)
                if moq is not None and "_moq_bits" in mb:
                    p = moq.apply(p, mb["_moq_bits"])
                if load is None:
                    loss, rows = model.loss_fn(p, mb, rng, False), None
                else:
                    with expert_load_tap() as tap:
                        loss = model.loss_fn(p, mb, rng, False)
                    rows = tap.stacked()
                if fp16:
                    loss = loss * scale.astype(loss.dtype)
                return loss, rows
            (loss, rows), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if rows is not None:
                load.append(rows)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            if specs == "grad":
                specs = self.grad_specs
            if specs is not None:
                grads = jax.lax.with_sharding_constraint(grads, specs)
            return loss, grads

        def apply_grads(state, grads, mean_loss):
            """Unscale, clip, optimizer, loss-scale update, overflow skip."""
            params, opt = state["params"], state["opt"]
            if fp16:
                ls = fp16_mod.LossScaleState(**state["loss_scale"])
                grads = fp16_mod.unscale_grads(grads, ls)
                overflow = fp16_mod.has_overflow(grads)
            else:
                overflow = jnp.zeros((), jnp.bool_)
            gnorm = global_grad_norm(grads)
            if clip and clip > 0:
                scale_c = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * scale_c, grads)
            new_params, new_opt = self.optimizer.update(grads, opt, params)
            if fp16:
                # skip the step on overflow (reference: step:1635 overflow path)
                # (both trees are in device memory here — where() before the
                # host writeback)
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n), new_params, params)
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n), new_opt, opt)
            if fp16:
                new_ls = fp16_mod.update_loss_scale(
                    ls, overflow, dynamic=fp16_cfg.dynamic,
                    scale_window=fp16_cfg.loss_scale_window,
                    min_scale=fp16_cfg.min_loss_scale,
                    max_hysteresis=fp16_cfg.hysteresis,
                    consecutive_hysteresis=fp16_cfg.consecutive_hysteresis)
                loss_scale_state = {"scale": new_ls.scale,
                                    "good_steps": new_ls.good_steps,
                                    "hysteresis": new_ls.hysteresis}
            else:
                loss_scale_state = None
            # applied-update counter: does not advance on a skipped (overflow)
            # step, mirroring the reference's optimizer-step accounting
            new_step = jnp.where(overflow, state["step"], state["step"] + 1)
            new_state = {"params": new_params, "opt": new_opt, "step": new_step}
            if loss_scale_state is not None:
                new_state["loss_scale"] = loss_scale_state
            if fp16:
                # in-graph skip counter: no per-step bool(overflow) fetch on
                # the host — skipped_steps/get_lr read this lazily at
                # steps_per_print boundaries
                new_state["skipped"] = (state["skipped"]
                                        + overflow.astype(jnp.int32))
            if tel_on:
                # in-graph telemetry accumulators: scalar ops over values the
                # step already computed (zero added syncs; the update/param
                # norms are the only extra reductions, and only when
                # telemetry.update_ratio is on)
                ratio = (tel_acc.update_to_param_ratio(new_params, params)
                         if tel_ratio else None)
                new_state["telemetry"] = tel_acc.accumulate(
                    state["telemetry"], loss=mean_loss, gnorm=gnorm,
                    overflow=overflow, update_ratio=ratio)
            metrics = {"loss": mean_loss, "grad_norm": gnorm,
                       "overflow": overflow}
            if fp16:
                metrics["loss_scale"] = state["loss_scale"]["scale"]
            return new_state, metrics

        def moe_load_metrics(rows):
            """What the expert layers did this step, from their load rows
            ([layers, E + 1]: rows kept per expert HELD, then the assignments
            the router made), as means over the layers: the rows the held
            experts multiplied, the fullest expert's over the mean, and the
            rows that found no place — counted where every expert is held
            (elsewhere an assignment not kept may be another chip's; a
            dropless model's are 0 by construction)."""
            kept = rows[:, :-1].astype(jnp.float32)
            held = jnp.sum(kept, axis=1)
            cfg_m = model.config
            whole = cfg_m.moe_router_width == cfg_m.num_experts
            dropped = (jnp.mean(rows[:, -1].astype(jnp.float32) - held)
                       if whole and cfg_m.drop_tokens else jnp.float32(0.0))
            fullest = jnp.mean(jnp.max(kept, axis=1)
                               / jnp.maximum(jnp.mean(kept, axis=1), 1.0))
            return dict(zip(_MOE_METRICS, (jnp.mean(held), fullest, dropped)))

        def deferred_batch_grads(params, batch, rng, scale, step):
            """Deferred sync: grad accumulation runs manual over `data`
            (everything else stays auto/GSPMD). Each device accumulates the
            LOCAL (unreduced) grad sum across all `gas` microbatches — no
            data-axis collective can exist inside the scan — and
            comm.schedule.boundary_reduce issues the ONE reduction at the
            step boundary (psum_scatter onto dp-sharded grad specs, psum
            for replicated leaves). DeepSpeed no_sync semantics: dp-sync
            collective counts are independent of gas."""
            def local_body(params, batch, rng, scale, step):
                grads, mean_loss = self._accum_micro_grads(
                    lambda p, mb, r: micro_grads(p, mb, r, scale, step=step,
                                                 specs=local_acc_specs),
                    params, batch, gas, rng,
                    postprocess=(None if local_acc_specs is None else
                                 lambda t: jax.lax.with_sharding_constraint(
                                     t, local_acc_specs)),
                    unroll=deferred_unroll)
                grads = comm_sched.boundary_reduce(grads, self.grad_specs,
                                                   plan)
                mean_loss = jax.lax.pmean(mean_loss, "data")
                return grads, mean_loss

            fn = comm_sched.shard_map_compat(
                local_body, mesh,
                in_specs=(jax.tree.map(lambda _: P(), params),
                          _manual_batch_specs(batch), P(), P(), P()),
                out_specs=(comm_sched.manual_out_spec(self.grad_specs), P()),
                manual_axes=("data",))
            grads, mean_loss = fn(params, batch, rng, scale, step)
            # pin the final placement: the scattered data dim plus whatever
            # auto-axis sharding rode out of the region lands on grad_specs
            grads = jax.lax.with_sharding_constraint(grads, self.grad_specs)
            return grads, mean_loss

        def batch_grads(state, batch, rng, load=None):
            """Averaged grads + mean loss over `gas` microbatches.
            batch leaves: [global_batch, ...], sharded over (data, fsdp).
            ``load``: ``micro_grads``'s."""
            params = state["params"]
            scale = state["loss_scale"]["scale"] if fp16 else jnp.float32(1.0)
            if deferred:
                grads, mean_loss = deferred_batch_grads(
                    params, batch, rng, scale, state["step"])
            else:
                grads, mean_loss = self._accum_micro_grads(
                    lambda p, mb, r: micro_grads(p, mb, r, scale,
                                                 step=state["step"],
                                                 load=load),
                    params, batch, gas, rng,
                    postprocess=lambda t: jax.lax.with_sharding_constraint(
                        t, self.grad_specs),
                    unroll=unroll)
            if fp16:
                mean_loss = mean_loss / scale
            return mean_loss, grads

        def train_step(state, batch, rng):
            """One full optimizer step over `gas` microbatches. The named
            scopes land in the compiled program's op_name metadata — the
            perf doctor's trace join reads them to split device time into
            grad-compute vs optimizer phases."""
            # the expert load rides out of the ONE microbatch that runs in
            # this trace; a scan over several, or the deferred region, keeps
            # its rows to itself
            load = [] if moe_model and gas == 1 and not deferred else None
            with jax.named_scope("grads"):
                mean_loss, grads = batch_grads(state, batch, rng, load)
            with jax.named_scope("optimizer"):
                new_state, metrics = apply_grads(state, grads, mean_loss)
            if load:
                metrics.update(moe_load_metrics(load[0]))
            return new_state, metrics

        # raw (unjitted) step for the fused K-step program; recompiles
        # (Random-LTD/act-quant rebuilds) invalidate any cached fusions
        self._train_step_fn = train_step
        self._fused_steps = {}

        if self._nvme_opt:
            # optimizer apply happens chunk-wise through the NVMe swapper;
            # only the grad computation is a monolithic jitted program
            self._batch_grads = jax.jit(
                batch_grads,
                in_shardings=(self.state_shardings, None, None),
                out_shardings=(None, grad_shardings))
            self._train_step = None
        else:
            self._train_step = jax.jit(
                train_step,
                in_shardings=(self.state_shardings, None, None),
                out_shardings=(self.state_shardings, None),
                donate_argnums=(0,))

        if self._onebit_comm:
            # phase-compiled shard_map steps replace the GSPMD train step:
            # dense pmean in the warm program, 1-bit packed all-gather in the
            # compressed program, no collective at all in a local program
            self._train_step = None
            self._onebit_steps = {}
            # host mirror of opt["step"] driving phase selection; synced from
            # device state so mid-run recompiles (e.g. Random-LTD rebuilds)
            # and load_checkpoint cannot restart the warmup phase
            if getattr(self, "state", None) is not None:
                self._onebit_applied = int(np.asarray(jax.device_get(
                    self.state["opt"]["step"]))[0])
            else:
                self._onebit_applied = 0

        def eval_step(state, batch):
            p = state["params"]
            if compression is not None:
                p = compression.apply(p, state["step"])
            loss = model.loss_fn(p, batch, None, True)
            return loss

        self._eval_step = jax.jit(
            eval_step, in_shardings=(self.state_shardings, None))

        # --- 3-call API pieces (forward/backward/step)
        def grad_only(state, batch, rng):
            scale = state["loss_scale"]["scale"] if fp16 else jnp.float32(1.0)
            loss, grads = micro_grads(state["params"], batch, rng, scale,
                                      step=state["step"])
            return (loss / scale if fp16 else loss), grads

        self._grad_only = jax.jit(
            grad_only, in_shardings=(self.state_shardings, None, None),
            out_shardings=(None, grad_shardings))
        self._accum = jax.jit(
            lambda acc, g: jax.tree.map(jnp.add, acc, g),
            in_shardings=(grad_shardings, grad_shardings),
            out_shardings=grad_shardings, donate_argnums=(0,))
        if self._nvme_opt:
            self._apply = None  # step() routes through _nvme_apply
        else:
            self._apply = jax.jit(
                lambda state, grads, loss: apply_grads(
                    state, jax.tree.map(lambda g: g / gas, grads), loss),
                in_shardings=(self.state_shardings, grad_shardings, None),
                out_shardings=(self.state_shardings, None), donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    # 1-bit compressed step (shard_map over data; grads never dense-reduced
    # in the compressed phase — reference: runtime/comm/nccl.py:53)
    # ------------------------------------------------------------------
    def _get_onebit_step(self, phase: str, batch=None):
        if phase in self._onebit_steps:
            return self._onebit_steps[phase]
        cfg = self.config
        gas = cfg.gradient_accumulation_steps
        mesh = self.mesh
        model = self.model
        opt = self.optimizer
        rv = set(opt.rank_varying)
        from jax import lax

        fp16 = self._fp16
        fp16_cfg = cfg.fp16
        clip = cfg.gradient_clipping
        compression = self._compression
        moq = self._moq
        tel_on = self._tel_in_graph
        tel_ratio = tel_on and cfg.telemetry.update_ratio

        def per_device(state, batch, rng):
            params = state["params"]
            step = state["step"]
            opt_local = {
                k: (jax.tree.map(lambda a: jnp.squeeze(a, 0), v)
                    if k in rv and v is not None else v)
                for k, v in state["opt"].items()}
            rng = jax.random.fold_in(rng, lax.axis_index("data"))
            scale = (state["loss_scale"]["scale"] if fp16
                     else jnp.float32(1.0))

            def micro(p, mb, r):
                def loss_fn(q):
                    if compression is not None:
                        # same traced param transform the GSPMD step
                        # applies (micro_grads above); masks/quant see the
                        # per-device replicated params, schedule driven by
                        # the traced step
                        q = compression.apply(q, step)
                    if moq is not None and "_moq_bits" in mb:
                        q = moq.apply(q, mb["_moq_bits"])
                    loss = model.loss_fn(q, mb, r, False)
                    return loss * scale.astype(loss.dtype) if fp16 else loss
                return jax.value_and_grad(loss_fn)(p)

            # already deferred by construction: grads stay per-device local
            # across the whole accumulation; comm.microbatch_unroll still
            # applies (schedulable per-microbatch compute sites)
            grads, loss = self._accum_micro_grads(
                lambda p, mb, r: micro(p, mb, r), params, batch, gas, rng,
                unroll=self._microbatch_unroll)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            if fp16:
                grads = fp16_mod.unscale_grads(
                    grads, fp16_mod.LossScaleState(**state["loss_scale"]))
                loss = loss / scale
                # ANY rank overflowing must skip the step on EVERY rank —
                # divergent skips would desynchronize the replicated params
                overflow = lax.pmax(
                    fp16_mod.has_overflow(grads).astype(jnp.float32),
                    "data") > 0
            else:
                overflow = jnp.zeros((), jnp.bool_)

            # RMS of the per-rank local grad norms — an UPPER bound on the
            # true norm of the averaged gradient (computing that exactly
            # would need the dense all-reduce this path avoids). The scalar
            # psum makes it IDENTICAL on every rank, so clipping by it
            # cannot desynchronize parameters.
            gsq = sum(jnp.sum(jnp.square(g))
                      for g in jax.tree.leaves(grads))
            gnorm = jnp.sqrt(lax.pmean(gsq, "data"))
            if clip and clip > 0:
                coef = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * coef, grads)

            new_params, new_opt = opt.update_phase(
                grads, opt_local, params, phase=phase, axis="data")
            if fp16:
                # freeze EVERYTHING on overflow (params, moments, error
                # feedback) — reference: step:1635 overflow path
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n),
                    new_params, params)
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n),
                    new_opt, opt_local)
            new_opt = {
                k: (jax.tree.map(lambda a: a[None], v)
                    if k in rv and v is not None else v)
                for k, v in new_opt.items()}
            mean_loss = lax.pmean(loss, "data")
            new_state = {"params": new_params, "opt": new_opt,
                         "step": jnp.where(overflow, state["step"],
                                           state["step"] + 1)}
            if fp16:
                new_ls = fp16_mod.update_loss_scale(
                    fp16_mod.LossScaleState(**state["loss_scale"]), overflow,
                    dynamic=fp16_cfg.dynamic,
                    scale_window=fp16_cfg.loss_scale_window,
                    min_scale=fp16_cfg.min_loss_scale,
                    max_hysteresis=fp16_cfg.hysteresis,
                    consecutive_hysteresis=fp16_cfg.consecutive_hysteresis)
                new_state["loss_scale"] = {"scale": new_ls.scale,
                                           "good_steps": new_ls.good_steps,
                                           "hysteresis": new_ls.hysteresis}
                new_state["skipped"] = (state["skipped"]
                                        + overflow.astype(jnp.int32))
            if tel_on:
                # inputs (pmean'd loss/gnorm, pmax'd overflow) and the
                # replicated params are rank-identical, so the accumulated
                # leaf stays rank-identical — its out_spec is P()
                ratio = (tel_acc.update_to_param_ratio(new_params, params)
                         if tel_ratio else None)
                new_state["telemetry"] = tel_acc.accumulate(
                    state["telemetry"], loss=mean_loss, gnorm=gnorm,
                    overflow=overflow, update_ratio=ratio)
            metrics = {"loss": mean_loss, "grad_norm": gnorm,
                       "overflow": overflow}
            if fp16:
                metrics["loss_scale"] = state["loss_scale"]["scale"]
            return new_state, metrics

        def spec_of(tree, varying_keys=()):
            return {k: (P("data") if k in varying_keys else P())
                    for k in tree}

        state_spec = {"params": P(),
                      "opt": spec_of(self.state["opt"], rv),
                      "step": P()}
        if fp16:
            state_spec["loss_scale"] = {k: P() for k in
                                        self.state["loss_scale"]}
            state_spec["skipped"] = P()
        if tel_on:
            state_spec["telemetry"] = {k: P() for k in
                                       self.state["telemetry"]}
        out_metrics_spec = {"loss": P(), "grad_norm": P(), "overflow": P()}
        if fp16:
            out_metrics_spec["loss_scale"] = P()
        batch_spec = _manual_batch_specs(batch)
        fn = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(state_spec, batch_spec, P()),
            out_specs=(state_spec, out_metrics_spec),
            axis_names={"data"}, check_vma=False)
        step_fn = jax.jit(fn, in_shardings=(self.state_shardings, None, None),
                          out_shardings=(self.state_shardings, None),
                          donate_argnums=(0,))
        self._onebit_steps[phase] = step_fn
        return step_fn

    # ------------------------------------------------------------------
    # primary API
    # ------------------------------------------------------------------
    def train_batch(self, batch) -> Dict[str, Any]:
        """Consume one *global* batch (train_batch_size rows) and take one
        optimizer step (reference: PipelineEngine.train_batch:282 semantics,
        also covers engine fwd/bwd/step loop for non-pipe)."""
        self._activate_context()
        self.tput_timer.start()
        if self._tracer is not None:
            # windowed jax.profiler capture (telemetry.trace) — a no-op
            # outside the configured window
            self._tracer.maybe_profile(self.global_steps)
        self._rng, sub = jax.random.split(self._rng)
        if self._act_quant and not self._act_quant_on and \
                self.global_steps + 1 >= self._act_quant[1]:
            self._rebuild_act_quant(self.model)
            self._compile_steps()
        if self._curriculum is not None:
            from deepspeed_tpu.runtime.data_pipeline import (
                apply_seqlen_curriculum)
            d = self._curriculum.update_difficulty(self.global_steps + 1)
            batch = apply_seqlen_curriculum(batch, d)
        if self._ltd is not None:
            self._maybe_rebuild_ltd(batch)
        if self._pld is not None:
            theta_min, gamma = self._pld
            theta = ((1.0 - theta_min) * math.exp(-gamma * self.global_steps)
                     + theta_min)
            batch = dict(batch)
            batch["_pld_theta"] = np.float32(theta)  # traced input: the
            # continuously-decaying theta must not retrigger compilation
        if self._moq is not None:
            if self._moq.wants_eigenvalues(self.global_steps) \
                    and self.state is not None:
                evs = self._moq.layer_eigenvalues(
                    self.model.loss_fn, self.state["params"],
                    self._device_batch(batch), rng=sub)
                self._moq.update_eigenvalues(evs, self.global_steps)
            batch = dict(batch)
            # traced [L] side-channel: schedule/eigenvalue updates must not
            # retrigger compilation
            batch["_moq_bits"] = self._moq.bits(self.global_steps)
        if self._infinity:
            # unsharded single-device executor: no mesh batch placement.
            # The executor is host-driven per step, so overflow is already
            # a host value — account it on the host offset directly
            metrics = self._infinity_exec.train_batch(batch)
            self.global_steps += 1
            self.micro_steps += self.config.gradient_accumulation_steps
            if self._fp16 and bool(metrics.get("overflow")):
                self._skipped_offset += 1
            self._tel_anchor()
            self.tput_timer.stop(output=metrics)
            self._log_step(dict(metrics))
            return metrics
        batch = self._device_batch(batch)
        with self._tel_span("dispatch"):
            if self._nvme_opt:
                with self.mesh, self._building(self._batch_grads, batch):
                    mean_loss, grads = self._batch_grads(self.state, batch,
                                                         sub)
                metrics = self._nvme_apply(grads, mean_loss)
            elif self._onebit_comm:
                phase = self.optimizer.phase_for(self._onebit_applied)
                step_fn = self._get_onebit_step(phase, batch)
                self._capture_static_args(step_fn, (self.state, batch, sub), 1)
                with self.mesh, self._building(step_fn, batch):
                    self.state, metrics = step_fn(self.state, batch, sub)
                # EXPLICIT sync point: the warm->compressed phase switch is a
                # host decision keyed on the applied-update count, so this
                # path pays one overflow fetch per step by design (skip
                # accounting itself stays in-graph — state["skipped"])
                if not (self._fp16 and bool(metrics["overflow"])):
                    self._onebit_applied += 1  # overflow steps don't advance
            else:
                if self._offload_opt:
                    self.state["opt"] = self._opt_to_device(self.state["opt"])
                self._capture_static_args(
                    self._train_step, (self.state, batch, sub), 1)
                with self.mesh, self._building(self._train_step, batch):
                    self.state, metrics = self._train_step(self.state, batch,
                                                           sub)
                if self._offload_opt:
                    self.state["opt"] = self._opt_to_host(self.state["opt"])
        self.global_steps += 1
        self.micro_steps += self.config.gradient_accumulation_steps
        self._tel_anchor()
        # no host overflow fetch here: skip accounting is in-graph for the
        # jitted paths (reference step:1635 does it eagerly; the eager bool()
        # was the per-step stall this engine removes), and _nvme_apply
        # already accounted its host-side overflow
        self.tput_timer.stop(output=metrics)
        metrics = {k: v for k, v in metrics.items()}
        self._log_step(metrics)
        fp_cfg = self.config.flops_profiler
        if (fp_cfg.enabled and not getattr(self, "_profiling", False)
                and self.global_steps == fp_cfg.profile_step):
            from deepspeed_tpu.profiling import FlopsProfiler
            self._profiling = True  # run() drives train_batch to time steps
            try:
                self.flops_profile = FlopsProfiler(fp_cfg).run(self, batch)
            finally:
                self._profiling = False
        return metrics

    # ------------------------------------------------------------------
    # async multi-step pipeline (train_batches)
    # ------------------------------------------------------------------
    def train_batches(self, data_iter, num_steps: int) -> Dict[str, Any]:
        """Async multi-step train loop: consume `num_steps` global batches
        from `data_iter` keeping up to ``pipeline.in_flight`` dispatched
        steps in flight.

        Because overflow/skip accounting lives in the donated jitted state,
        the host never waits on step N to decide step N+1: each iteration
        dispatches and moves on, bounded by blocking on the (i-in_flight)'th
        step's output so dispatch can't run away from execution. With
        ``pipeline.prefetch`` the sharding-aware device_put of batch N+1
        overlaps step N; with ``pipeline.fuse_steps`` K>1 (plain dense path
        only) K sequential optimizer steps compile into ONE dispatch.
        Metric fetches happen only at steps_per_print boundaries
        (_log_step). Returns the LAST step's metrics — device arrays;
        float() them to force the final sync.

        The reference has no equivalent single call: its train loop hides
        Python overhead behind CUDA streams but still reads the overflow
        flag every step (engine step:1635)."""
        import collections
        import itertools
        self._activate_context()
        pcfg = self.config.pipeline
        in_flight = max(1, int(pcfg.in_flight))
        k = max(1, int(pcfg.fuse_steps))
        use_fused = k > 1 and self._can_fuse()
        if k > 1 and not use_fused:
            logger.warning(
                "pipeline.fuse_steps ignored: the fused program needs the "
                "plain dense jitted path (no 1-bit/NVMe/infinity executor, "
                "no per-step batch rewrites)")
        it = itertools.islice(iter(data_iter), num_steps)
        if not use_fused and pcfg.prefetch and not self._infinity:
            from deepspeed_tpu.runtime.dataloader import PrefetchLoader
            it = iter(PrefetchLoader(it, put_fn=self._device_batch,
                                     tracer=self._tracer))
        _span = self._tel_span
        window = collections.deque()
        metrics = None
        done = 0
        while done < num_steps:
            if use_fused and num_steps - done >= k:
                with _span("data_wait"):
                    chunk = list(itertools.islice(it, k))
                if not chunk:
                    break
                if len(chunk) < k:
                    # short read: run the tail through the single-step path
                    # below rather than jit-compiling a one-off smaller
                    # fused program
                    for batch in chunk:
                        metrics = self.train_batch(batch)
                        done += 1
                    break
                metrics = self._train_batch_fused(chunk)
                done += k
            else:
                try:
                    with _span("data_wait"):
                        batch = next(it)
                except StopIteration:
                    break
                metrics = self.train_batch(batch)
                done += 1
            window.append(metrics["loss"])
            if len(window) > in_flight:
                # bound host run-ahead: wait for the oldest in-flight step
                # before dispatching further (backpressure, not a stall —
                # in_flight-1 steps are still queued behind it). The tracer's
                # "block" span is the dispatch-stall signal the anomaly
                # detector watches.
                with _span("block"):
                    jax.block_until_ready(window.popleft())
        if done < num_steps:
            logger.warning(f"train_batches: iterator exhausted after {done} "
                           f"of {num_steps} steps")
        return metrics

    def _can_fuse(self) -> bool:
        """The fused K-step program covers the plain dense jitted path only:
        host-driven executors (1-bit phase switch, NVMe swapper, infinity)
        and per-step host batch rewrites (curriculum/LTD/PLD/MoQ, a pending
        act-quant rebuild) need step granularity."""
        return (self._train_step is not None and not self._onebit_comm
                and not self._nvme_opt and not self._infinity
                and not self._offload_opt
                and self._curriculum is None and self._ltd is None
                and self._pld is None and self._moq is None
                and (not self._act_quant or self._act_quant_on)
                and not self.config.flops_profiler.enabled)

    def _get_fused_step(self, k: int):
        """Jitted K-step program: the train state threads through K
        sequential (unrolled) optimizer steps in ONE dispatch, donated
        end-to-end. Per-step collectives scale exactly Kx — the analysis
        census pins that (a collective hoisted out of or duplicated into
        the unrolled loop is census drift)."""
        fn = self._fused_steps.get(k)
        if fn is not None:
            return fn
        step_fn = self._train_step_fn
        state_sh = self.state_shardings

        def fused(state, batches, rngs):
            out = []
            for i in range(k):
                mb = jax.tree.map(lambda x: x[i], batches)
                state, m = step_fn(state, mb, rngs[i])
                # pin the inter-step state to the program-boundary shardings:
                # without this GSPMD reshards the unrolled interior freely
                # and the collective census stops being Kx the single step
                state = jax.tree.map(
                    lambda x, s: jax.lax.with_sharding_constraint(x, s)
                    if s is not None else x,
                    state, state_sh,
                    is_leaf=lambda x: x is None)
                out.append(m)
            metrics = jax.tree.map(lambda *xs: jnp.stack(xs), *out)
            return state, metrics

        fn = jax.jit(fused,
                     in_shardings=(self.state_shardings, None, None),
                     out_shardings=(self.state_shardings, None),
                     donate_argnums=(0,))
        self._fused_steps[k] = fn
        return fn

    def _train_batch_fused(self, batches) -> Dict[str, Any]:
        """Dispatch ONE jitted program covering len(batches) sequential
        optimizer steps (host batches stacked on a leading step dim).
        Bookkeeping matches that many train_batch calls; the returned
        metrics are the last sub-step's, still device-resident."""
        k = len(batches)
        self.tput_timer.start()
        if self._tracer is not None:
            self._tracer.maybe_profile(self.global_steps)
        self._rng, sub = jax.random.split(self._rng)
        rngs = jax.random.split(sub, k)
        placed = self._device_batches(_stack_batches(batches))
        fused_fn = self._get_fused_step(k)
        self._capture_static_args(fused_fn, (self.state, placed, rngs), k)
        with self._tel_span("dispatch"):
            with self.mesh, self._building(fused_fn, placed):
                self.state, metrics_k = fused_fn(self.state, placed, rngs)
        self.global_steps += k
        self.micro_steps += k * self.config.gradient_accumulation_steps
        self._tel_anchor()
        metrics = jax.tree.map(lambda v: v[-1], metrics_k)  # lazy slice
        self.tput_timer.stop(output=metrics, steps=k)
        self._log_step(dict(metrics))
        return metrics

    def _rebuild_act_quant(self, model):
        """Swap in the activation-quantized model config (one recompile —
        the traced alternative would carry a dead branch every step)."""
        import dataclasses as _dc
        from deepspeed_tpu.models import make_model as _mk
        bits = self._act_quant[0]
        model = _mk(_dc.replace(model.config, activation_quant_bits=bits),
                    name=model.name)
        self.model = model
        self._act_quant_on = True
        logger.info(f"activation quantization active: {bits}-bit STE on "
                    "post-norm activations")
        return model

    def _maybe_rebuild_ltd(self, batch):
        """Random-LTD: the kept-token count is a SHAPE, so when the schedule
        crosses a bucket boundary the model + step programs are rebuilt (jit
        caches the old buckets; a handful of compiles per run)."""
        seq_leaves = [v for v in batch.values()
                      if hasattr(v, "ndim") and v.ndim >= 2]
        if not seq_leaves:
            return
        S = seq_leaves[0].shape[1]
        k = self._ltd.kept_tokens(self.global_steps + 1, S)
        if k == self._ltd_keep:
            return
        import dataclasses as _dc
        from deepspeed_tpu.models import make_model
        base = self.model.config
        active = k < S
        # saturated schedule -> back to the dense scanned stack (unrolled
        # layers are only needed while LTD wraps individual layers)
        self.model = make_model(_dc.replace(
            base, random_ltd=active, random_ltd_keep=k,
            scan_layers=self._ltd_orig_scan if not active else False),
            name=self.model.name)
        self._ltd_keep = k
        logger.info(f"random-ltd: kept tokens -> {k} (of {S})")
        self._compile_steps()

    def _nvme_apply(self, grads, mean_loss) -> Dict[str, Any]:
        """Optimizer apply through the NVMe swapper (ZeRO-Infinity path).
        Grad scale/overflow handling happens host-side: on overflow the NVMe
        state is untouched and only the loss scale shrinks."""
        scale = float(self.state["loss_scale"]["scale"]) if self._fp16 else 1.0
        applied = int(np.asarray(jax.device_get(self.state["step"]))) + 1
        new_params, gnorm, overflow = self._swapper.step(
            grads, lr=self.get_lr(), step_num=applied,
            clip=self.config.gradient_clipping, grad_scale=scale)
        if not overflow:
            self.state["params"] = new_params
            self.state["step"] = jax.tree.map(lambda s: s + 1, self.state["step"])
        elif self._fp16:
            # host-driven path: overflow is already a host bool here, so the
            # skip lands on the host offset (the device counter stays 0)
            self._skipped_offset += 1
        if self._fp16:
            ls = fp16_mod.LossScaleState(
                scale=jnp.asarray(scale, jnp.float32),
                good_steps=self.state["loss_scale"]["good_steps"],
                hysteresis=self.state["loss_scale"]["hysteresis"])
            cfgf = self.config.fp16
            new_ls = fp16_mod.update_loss_scale(
                ls, jnp.asarray(overflow), dynamic=cfgf.dynamic,
                scale_window=cfgf.loss_scale_window,
                min_scale=cfgf.min_loss_scale, max_hysteresis=cfgf.hysteresis,
                consecutive_hysteresis=cfgf.consecutive_hysteresis)
            self.state["loss_scale"] = {"scale": new_ls.scale,
                                        "good_steps": new_ls.good_steps,
                                        "hysteresis": new_ls.hysteresis}
        metrics = {"loss": mean_loss, "grad_norm": jnp.asarray(gnorm),
                   "overflow": jnp.asarray(overflow)}
        if self._fp16:
            metrics["loss_scale"] = jnp.asarray(scale)
        return metrics

    def _opt_to_host(self, opt):
        """Move optimizer state to pinned host DRAM (ZeRO-Offload residency)."""
        return jax.tree.map(
            lambda x, s: jax.device_put(x, s) if x is not None and s is not None
            else x,
            opt, self._opt_host_shardings, is_leaf=lambda x: x is None)

    def _opt_to_device(self, opt):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(s.mesh, s.spec))
            if x is not None and s is not None else x,
            opt, self._opt_host_shardings, is_leaf=lambda x: x is None)

    def _activate_context(self):
        """Republish this engine's mesh/plan as the ambient parallel context
        (another Engine/InferenceEngine in the same process may have
        overwritten it)."""
        from deepspeed_tpu.parallel.context import set_parallel_context
        set_parallel_context(self.mesh, self.plan)

    def eval_batch(self, batch):
        self._activate_context()
        if self._infinity:
            return self._infinity_exec.eval_batch(batch)
        batch = self._device_batch(batch)
        with self.mesh:
            return self._eval_step(self.state, batch)

    def audit(self, batch=None, *, settings=None, raise_on_findings=False):
        """Static analysis of this engine's own compiled step programs
        (graft-lint, ``deepspeed_tpu/analysis``): lower the jitted steps on
        abstract shapes — nothing executes — and check the collective
        census, buffer donation, dtype promotion, and replication budget
        against this config's expectations.

        Reference analogue: none — DeepSpeed can only discover an extra
        allreduce by watching the wire (comms_logger); here the compiled
        program is inspected before a single step runs. Returns an
        ``analysis.Report``; with raise_on_findings=True, raises
        RuntimeError when any error-severity finding survives
        suppression/baseline."""
        self._activate_context()
        from deepspeed_tpu.analysis import audit_engine
        report = audit_engine(self, batch=batch, settings=settings)
        if raise_on_findings and not report.ok:
            raise RuntimeError("engine.audit found problems:\n"
                               + report.summary())
        return report

    # --- 3-call compatibility API (reference: forward:1652/backward:1794/step:1990)
    def forward(self, batch):
        """Compute loss+grads for one microbatch; grads are buffered until
        step(). Returns the (unscaled) loss."""
        if self._onebit_comm:
            raise RuntimeError(
                "the 3-call forward/backward/step API is not available with "
                "the 1-bit compressed path (grads must stay per-device local "
                "inside one compiled step) — use train_batch()")
        self._activate_context()
        self._rng, sub = jax.random.split(self._rng)
        batch = self._device_batch(batch)
        with self.mesh:
            loss, grads = self._grad_only(self.state, batch, sub)
        self._pending = (loss, grads)
        return loss

    def backward(self, loss=None):
        """Accumulate the pending grads (the jitted fwd already differentiated;
        this keeps the reference's call order meaningful)."""
        if getattr(self, "_pending", None) is None:
            raise RuntimeError("backward() called without forward()")
        loss, grads = self._pending
        self._pending = None
        with self.mesh:
            if self._grad_buffer is None:
                self._grad_buffer = grads
                self._loss_sum = loss
            else:
                self._grad_buffer = self._accum(self._grad_buffer, grads)
                self._loss_sum = self._loss_sum + loss
        self._accum_count += 1
        self.micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        # pp mode: the pipelined loss consumes all microbatches in one call
        needed = 1 if self._pp_mode else self.config.gradient_accumulation_steps
        return self._accum_count >= needed

    def step(self):
        """Apply the optimizer if at a grad-accum boundary (reference:
        is_gradient_accumulation_boundary:1875 + _take_model_step:1925)."""
        if not self.is_gradient_accumulation_boundary():
            return None
        mean_loss = self._loss_sum / self._accum_count
        if self._nvme_opt:
            gas = self.config.gradient_accumulation_steps
            grads = jax.tree.map(lambda g: g / gas, self._grad_buffer)
            metrics = self._nvme_apply(grads, mean_loss)  # accounts skips
            self._grad_buffer = None
            self._accum_count = 0
            self.global_steps += 1
            self._log_step(metrics)
            return metrics
        if self._offload_opt:
            self.state["opt"] = self._opt_to_device(self.state["opt"])
        with self.mesh:
            self.state, metrics = self._apply(
                self.state, self._grad_buffer, mean_loss)
        if self._offload_opt:
            self.state["opt"] = self._opt_to_host(self.state["opt"])
        self._grad_buffer = None
        self._accum_count = 0
        self.global_steps += 1
        # skip accounting is in-graph (state["skipped"]) — no overflow fetch
        self._log_step(metrics)
        return metrics

    # ------------------------------------------------------------------
    def _device_batch(self, batch):
        """Sharding-aware batch placement. IDEMPOTENT: a leaf already placed
        with the target sharding passes through untouched, so the
        PrefetchLoader can run this ahead of time and curriculum/LTD/PLD
        rewrites (which slice or extend the batch) are simply re-placed at
        consume time."""
        spec = self._batch_spec()
        def place(x, sh):
            if isinstance(x, jax.Array) and x.sharding == sh:
                return x  # already resident (prefetch path): no dispatch
            x = jnp.asarray(x) if not isinstance(x, jax.Array) else x
            return jax.device_put(x, sh)
        def put(x):
            x = jnp.asarray(x) if not isinstance(x, jax.Array) else x
            s = P(*spec[:min(x.ndim, len(spec))])  # 0-d leaves → replicated
            return place(x, NamedSharding(self.mesh, s))
        repl = NamedSharding(self.mesh, P())
        if isinstance(batch, dict):
            return {k: (place(jnp.asarray(v) if not isinstance(v, jax.Array)
                              else v, repl)
                        if _is_side_channel(k) else put(v))
                    for k, v in batch.items()}
        return jax.tree.map(put, batch)

    def _device_batches(self, stacked):
        """Place a K-stacked batch (leaves ``[K, global_batch, ...]``) for
        the fused multi-step program: the leading step dim is replicated
        (each unrolled step slices its own row), the rest shards exactly
        like _device_batch."""
        spec = self._batch_spec()
        def put(key, x):
            x = jnp.asarray(x) if not isinstance(x, jax.Array) else x
            if _is_side_channel(key) or x.ndim <= 1:
                s = P()  # replicated: [K] side-channels / scalars
            else:
                s = P(None, *spec[:min(x.ndim - 1, len(spec))])
            return jax.device_put(x, NamedSharding(self.mesh, s))
        if isinstance(stacked, dict):
            return {k: put(k, v) for k, v in stacked.items()}
        return jax.tree.map(lambda x: put(None, x), stacked)

    def _log_step(self, metrics):
        # keep the device array; get_global_grad_norm() fetches on demand
        if "grad_norm" in metrics:
            self._last_grad_norm = metrics["grad_norm"]
        cfg = self.config
        if self._tel_host is not None:
            # host-driven optimizer paths: queue the step's metric scalars
            # UN-fetched; the boundary drain below folds them in with the
            # same single device_get
            self._tel_host.add(metrics)
        # window-crossing check, not `% == 0`: a fused K-step dispatch
        # advances global_steps by K and can stride over the exact multiple
        window = self.global_steps // max(1, cfg.steps_per_print)
        if window == self._last_log_window:
            return
        self._last_log_window = window
        # the ONE steady-state sync point of the hot loop: every logged
        # metric AND the telemetry accumulator leaf come back in a single
        # device_get instead of one blocking float() per metric
        extra = {k: metrics[k] for k in ("loss", "grad_norm", "loss_scale",
                                         *_MOE_METRICS) if k in metrics}
        need_skipped = (self._schedule is not None
                        and isinstance(self.state, dict)
                        and "skipped" in self.state)
        if need_skipped:
            # the LR schedule evaluates at the applied-update count, which
            # needs the device skip counter — ride the same batched fetch
            # instead of a second round trip through get_lr()
            extra["_skipped"] = self.state["skipped"]
        tel_cur, fetched = self._fetch_telemetry(extra=extra)
        skipped_dev = fetched.pop("_skipped", None)
        vals = {k: float(np.asarray(v)) for k, v in fetched.items()}
        if self._schedule is not None:
            skipped = self._skipped_offset + (
                int(np.asarray(skipped_dev)) if skipped_dev is not None
                else self._device_skipped())
            lr = float(self._schedule(self.global_steps - skipped + 1))
        else:
            lr = self.get_lr()
        msg = (f"step={self.global_steps} loss={vals['loss']:.4f} "
               f"lr={lr:.3e} gnorm={vals.get('grad_norm', 0.0):.3f}")
        if "loss_scale" in vals:
            msg += f" scale={vals['loss_scale']:.0f}"
        logger.info(msg)
        events = [("Train/loss", vals["loss"], self.global_steps),
                  ("Train/lr", lr, self.global_steps)]
        if "grad_norm" in vals:
            events.append(("Train/grad_norm", vals["grad_norm"],
                           self.global_steps))
        if "loss_scale" in vals:
            events.append(("Train/loss_scale", vals["loss_scale"],
                           self.global_steps))
        events += [(f"Train/{k}", vals[k], self.global_steps)
                   for k in _MOE_METRICS if k in vals]
        records = []
        if self._tel_cfg is not None and tel_cur is not None:
            tel_events, records = self._drain_telemetry(tel_cur)
            events += tel_events
        from deepspeed_tpu.comm import comms_logger
        if comms_logger.enabled:
            # CommsLogger totals reach the monitor as comm/* events instead
            # of log-only text (trace-time counts/bytes + host_ms)
            events += comms_logger.events(self.global_steps)
        # robustness events (ckpt_fallback / fault_recovered / preempted /
        # fault_injected) ride the same window-boundary record stream
        from deepspeed_tpu.robustness import events as rb_events
        for rec in rb_events.drain():
            rec.setdefault("step", self.global_steps)
            records.append(rec)
        if self.monitor is not None and self.monitor.enabled:
            self.monitor.write_events(events)  # one batched write
            if records:
                self.monitor.write_records(records)

    # ------------------------------------------------------------------
    # telemetry plumbing (deepspeed_tpu/telemetry)
    # ------------------------------------------------------------------
    def _tel_anchor(self):
        """Anchor the first telemetry window AFTER the compile-bearing first
        dispatch so window rates aren't compile-polluted. One place — every
        dispatch path (dense/onebit/nvme, fused, infinity) calls it."""
        if self._tel_cfg is not None and self._tel_wall is None:
            self._tel_wall = time.perf_counter()
            self._tel_wall_steps = self.global_steps

    @contextlib.contextmanager
    def _building(self, fn, batch):
        """Around a call of the step function ``fn``: its FIRST call at a
        batch shape traces, lowers and compiles (or loads) the program, so
        that call runs under ``ds:setup.program`` (``kind`` ``train_step``,
        ``shape`` the batch's) and leaves its record in the process's build
        log and one line in this engine's; every later call is just the
        call."""
        shape = np.shape(jax.tree.leaves(batch)[0])
        if (id(fn), shape) in self._steps_built:
            yield
            return
        self._steps_built.add((id(fn), shape))
        with build_log().program("train_step",
                                 "x".join(map(str, shape))) as sp:
            yield
        r = sp.record
        logger.info(
            f"built train step {r['shape']}: traced {r['trace_s']:.2f} s, "
            f"lowered {r['lower_s']:.2f} s, "
            f"{'loaded' if r['cache_hit'] else 'compiled'} "
            f"{r['compile_or_load_s']:.2f} s, first call {sp.seconds:.2f} s")

    def _tel_span(self, name: str):
        """The host phase ``ds:train.<name>``, always: a profiler session
        sees it with or without telemetry. With telemetry on it goes
        through the StepTracer, which also feeds its ring and window sums."""
        return (self._tracer.span(name) if self._tracer is not None
                else _span(f"ds:train.{name}"))

    def _capture_static_args(self, fn, args, divisor: int):
        """Remember the jitted step + abstract arg shapes ONCE so the lazy
        static x runtime join can lower the same program off the hot path.
        Abstractify BEFORE dispatch: donation invalidates the state arrays."""
        if (self._tel_cfg is None or not self._tel_cfg.static_join
                or self._tel_abs is not None):
            return
        try:
            from deepspeed_tpu.utils.memory import abstractify
            self._tel_abs = (fn, abstractify(args), divisor)
        except Exception as e:  # noqa: BLE001 - telemetry never kills a run
            logger.debug(f"telemetry: static arg capture failed: {e!r}")
            self._tel_abs = ()   # falsy sentinel: don't retry every step

    def _tel_static_cost(self, wait: bool = False):
        """Cached per-step compiled costs (flops, modeled comm bytes) from
        the static join. The AOT lower+compile does NOT reuse the jit
        dispatch cache, so it runs in a daemon thread kicked off at the
        first window boundary — the training thread never stalls on it.
        Boundary drains poll (windows before it lands just lack the joined
        rates); an explicit drain_telemetry passes wait=True and joins."""
        if self._tel_static is not None:
            return self._tel_static or None
        if not self._tel_abs:
            return None
        if self._tel_static_thread is None:
            import threading

            def work():
                from deepspeed_tpu.telemetry import static_step_cost
                fn, abs_args, divisor = self._tel_abs
                cost = static_step_cost(fn, abs_args, mesh=self.mesh,
                                        divisor=divisor)
                with self._tel_lock:
                    self._tel_static = cost or {}

            self._tel_static_thread = threading.Thread(
                target=work, name="telemetry-static-join", daemon=True)
            self._tel_static_thread.start()
        if wait:
            self._tel_static_thread.join()
        elif self._tel_static_thread.is_alive():
            return None
        with self._tel_lock:
            if self._tel_static is None:  # worker died without a result
                self._tel_static = {}
        return self._tel_static or None

    def _fetch_telemetry(self, extra=None):
        """ONE batched device_get covering the caller's metric scalars, the
        in-graph accumulator leaf, and any pending host-window scalars.
        Returns (cumulative telemetry snapshot | None, fetched extras)."""
        fetch = dict(extra or {})
        if self._tel_in_graph and isinstance(self.state, dict) \
                and "telemetry" in self.state:
            fetch["_telemetry"] = self.state["telemetry"]
        if self._tel_host is not None:
            fetch["_tel_pending"] = self._tel_host.pending()
        fetched = jax.device_get(fetch)
        tel_cur = fetched.pop("_telemetry", None)
        pending = fetched.pop("_tel_pending", None)
        if self._tel_host is not None:
            tel_cur = self._tel_host.drain(pending)
        return tel_cur, fetched

    def _drain_telemetry(self, tel_cur, wait_static: bool = False):
        """Window statistics + events + structured records from one drained
        cumulative snapshot. Pure host work — the device fetch already
        happened in the caller's batched device_get."""
        from deepspeed_tpu.telemetry import joined_rates, window_stats
        now = time.perf_counter()
        wall = (now - self._tel_wall) if self._tel_wall is not None else None
        steps_in_window = self.global_steps - self._tel_wall_steps
        self._tel_wall, self._tel_wall_steps = now, self.global_steps
        win = window_stats(tel_cur, self._tel_prev)
        self._tel_prev = tel_cur
        if not (self._tel_in_graph and self._tel_cfg.update_ratio):
            # no ratio data on this path (disabled, or a host-driven
            # executor whose metrics carry no update norms) — a constant-0
            # series would read as "updates stopped"
            win.pop("update_ratio_mean", None)
            win.pop("update_ratio_max", None)
        if self._tracer is not None:
            win.update(self._tracer.drain_window())
            if "data_wait_ms" in win and "prefetch_ms" in win:
                # the prefetch device_put runs INSIDE the data_wait span
                # (PrefetchLoader tops up during next()); keep the nested
                # spans in the Chrome trace but un-double-count the window
                # total so data_wait_ms means "blocked on data, not placing"
                win["data_wait_ms"] = max(
                    0.0, win["data_wait_ms"] - win["prefetch_ms"])
            if win["steps"]:
                win["stall_ms_per_step"] = (win.get("block_ms", 0.0)
                                            / win["steps"])
        if wall and wall > 0 and steps_in_window > 0:
            win["wall_s"] = wall
            win["steps_per_sec"] = steps_in_window / wall
            static = self._tel_static_cost(wait=wait_static)
            if static is not None:
                from deepspeed_tpu.accelerator import get_accelerator
                accel = get_accelerator()
                peak = (accel.peak_flops_per_device("bf16")
                        * max(1, jax.device_count()))
                win.update(joined_rates(
                    static, win["steps_per_sec"], peak,
                    interconnect_bytes_per_sec=
                    accel.interconnect_bytes_per_sec()))
                if win.get("modeled_peak_hbm"):
                    # measured allocator high-water next to the static
                    # model (a cheap host call; 0 on transports that
                    # expose no memory_stats)
                    measured = accel.max_memory_allocated()
                    if measured:
                        win["measured_peak_hbm"] = float(measured)
        self._tel_last_window = win
        step = self.global_steps
        events = [(f"telemetry/{k}", float(win[k]), step)
                  for k in ("loss_mean", "loss_max", "gnorm_mean",
                            "gnorm_max", "overflow_rate",
                            "update_ratio_mean", "steps_per_sec",
                            "window_mfu", "modeled_comm_bytes_per_sec",
                            "exposed_comm_ms", "overlap_efficiency",
                            "modeled_peak_hbm", "measured_peak_hbm",
                            "stall_ms_per_step")
                  if win.get(k) is not None]
        records = [{"type": "telemetry_window", "step": step, **win}]
        if self._anomaly is not None:
            anomalies = self._anomaly.observe(win, step=step)
            for a in anomalies:
                logger.warning(f"anomaly[{a['severity']}] {a['rule']}: "
                               f"{a['message']}")
                if self._tracer is not None:
                    self._tracer.instant(f"anomaly:{a['rule']}",
                                         args={"severity": a["severity"]})
            # anomalies travel as records ONLY: scalar sinks get their
            # anomaly/<rule> projection from write_records (adding them to
            # `events` too would double-write every scalar sink)
            records += anomalies
        return events, records

    def drain_telemetry(self):
        """Force a window drain outside a steps_per_print boundary (one
        batched device fetch; events/records still fan out). Returns the
        window stats dict, or None when telemetry is off."""
        if self._tel_cfg is None:
            return None
        tel_cur, _ = self._fetch_telemetry()
        if tel_cur is None:
            return None
        events, records = self._drain_telemetry(tel_cur, wait_static=True)
        if self.monitor is not None and self.monitor.enabled:
            if events:
                self.monitor.write_events(events)
            if records:
                self.monitor.write_records(records)
        return self._tel_last_window

    def telemetry_window(self):
        """Last drained telemetry window stats (None before the first
        drain). Host dict — reading it costs nothing."""
        return self._tel_last_window

    def close(self, timeout: float = 5.0) -> bool:
        """Join background host threads with a bounded timeout. Today
        that is the telemetry static-join worker — daemon, so it never
        blocks interpreter exit, but a harness that builds many engines
        in one process wants the compile worker gone before the next
        engine starts. Returns False when the worker outlived the budget
        (its handle is kept so a later close can retry)."""
        t = self._tel_static_thread
        if t is not None and t.is_alive():
            t.join(timeout)
            if t.is_alive():
                return False
        self._tel_static_thread = None
        return True

    def export_trace(self, path: Optional[str] = None) -> str:
        """Write the host step-phase spans (dispatch/prefetch/data_wait/
        block) as Chrome-trace JSON loadable by chrome://tracing or
        Perfetto. Requires telemetry.enabled."""
        if self._tracer is None:
            raise RuntimeError("step tracing requires config "
                               '{"telemetry": {"enabled": true}}')
        if path is None:
            out = self.config.telemetry.trace.output_dir
            os.makedirs(out, exist_ok=True)
            path = os.path.join(out, f"step_trace_{self.global_steps}.json")
        return self._tracer.export_chrome_trace(path)

    # ------------------------------------------------------------------
    # info API (reference parity helpers)
    # ------------------------------------------------------------------
    def get_lr(self) -> float:
        if self._schedule is not None:
            # evaluate at the APPLIED update count (+1 = the lr the next
            # update will use); overflow-skipped steps don't advance it.
            # Plain Python int -> the schedule's numpy path: no device
            # program is built or run for a log-boundary call
            applied = self.global_steps - self.skipped_steps
            return float(self._schedule(applied + 1))
        if isinstance(self._base_lr, (int, float)):
            return float(self._base_lr)
        return 0.0

    @property
    def skipped_steps(self) -> int:
        """Overflow-skipped optimizer steps. The jitted paths account skips
        in-graph (state["skipped"]) so reading this is a LAZY device fetch —
        call it at steps_per_print boundaries, not per step; host-driven
        paths (NVMe swapper, layer-streamed executor) land on the host
        offset and cost nothing."""
        return self._skipped_offset + self._device_skipped()

    @skipped_steps.setter
    def skipped_steps(self, value: int):
        # checkpoint restore: reconcile the host offset against whatever the
        # (just-loaded) device counter says
        self._skipped_offset = int(value) - self._device_skipped()

    def _device_skipped(self) -> int:
        state = getattr(self, "state", None)
        if isinstance(state, dict) and "skipped" in state:
            return int(np.asarray(jax.device_get(state["skipped"])))
        return 0

    def get_loss_scale(self) -> float:
        if self._fp16:
            return float(self.state["loss_scale"]["scale"])
        return 1.0

    def get_global_grad_norm(self) -> Optional[float]:
        """Pre-clip global grad norm of the last applied step (reference:
        engine.get_global_grad_norm). None before the first step."""
        if self._last_grad_norm is None:
            return None
        return float(np.asarray(jax.device_get(self._last_grad_norm)))

    def sparse_gradients_enabled(self) -> bool:
        """API parity with the reference's sparse-embedding-grad switch
        (``engine.py:2302-2369`` sparse_allreduce_list). Always False on
        TPU — BY DESIGN, not omission: under jit+GSPMD the embedding
        cotangent is a fused scatter-add reduce-scattered over ICI like
        every other gradient (V*H/dp bytes/chip), a (values, indices)
        wire would need dynamic shapes, and the static-shape alternative
        moves more bytes at every realistic (vocab, batch). Evidence:
        ``benchmarks/embedding_grad.py``."""
        return False

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def get_mesh(self) -> Mesh:
        return self.mesh

    @property
    def params(self):
        return self.state["params"]

    # ------------------------------------------------------------------
    # checkpointing (reference: save_checkpoint:2817 / load_checkpoint:2512)
    # ------------------------------------------------------------------
    def attach_dataloader(self, loader) -> None:
        """Register the training loader so checkpoints carry its position
        (epoch, batch-in-epoch, seed) and an elastic resume neither replays
        nor skips data. Any object with state_dict/load_state_dict works
        (DataLoader and RepeatingLoader both do)."""
        self._dataloader = loader

    def _rng_key_data(self):
        """Host uint32 view of the engine rng chain (typed or legacy key)."""
        key = self._rng
        try:
            key = jax.random.key_data(key)
        except Exception:  # noqa: BLE001 - already a legacy uint32 key
            pass
        return np.asarray(jax.device_get(key))

    def _restore_rng(self, key_data) -> None:
        arr = np.asarray(key_data, dtype=np.uint32)
        try:
            if jnp.issubdtype(self._rng.dtype, jax.dtypes.prng_key):
                impl = jax.random.key_impl(self._rng)
                self._rng = jax.random.wrap_key_data(jnp.asarray(arr),
                                                     impl=impl)
                return
        except Exception:  # noqa: BLE001 - legacy raw-key path below
            pass
        self._rng = jnp.asarray(arr)

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None,
                        save_latest: bool = True) -> str:
        tag = tag if tag is not None else f"global_step{self.global_steps}"
        client_state = dict(client_state or {})
        client_state.update({
            "global_steps": self.global_steps,
            "skipped_steps": self.skipped_steps,
            "micro_steps": self.micro_steps,
            # the rng split chain: restoring it makes replayed steps after a
            # fault recovery bit-identical to the uninterrupted run
            "rng_key": self._rng_key_data().tolist(),
        })
        if self._dataloader is not None and \
                hasattr(self._dataloader, "state_dict"):
            client_state.setdefault("data_position",
                                    self._dataloader.state_dict())
        if self._infinity:
            return self._save_infinity_checkpoint(save_dir, tag, client_state,
                                                  save_latest)
        engine = None
        if self.config.checkpoint.async_save:
            if self._ckpt_engine is None:
                self._ckpt_engine = ckpt_mod.OrbaxCheckpointEngine(async_save=True)
            engine = self._ckpt_engine  # .save() finalizes any in-flight save
        ck = self.config.checkpoint
        if self._nvme_opt:
            # fp32 optimizer chunks live on NVMe, not in self.state — persist
            # them alongside the Orbax state (reference: optimizer swap files
            # are re-read into the checkpoint, optimizer_utils.py). Written
            # BEFORE the save finalizes so the integrity manifest covers
            # them: a truncated optswap.npz must fail validation too.
            path = os.path.join(save_dir, str(tag))
            os.makedirs(path, exist_ok=True)
            np.savez(os.path.join(path, "optswap.npz"),
                     **self._swapper.export_state())
        return ckpt_mod.save_checkpoint(
            save_dir, tag, self.state, client_state=client_state,
            config_dict=self.config.to_dict(), save_latest=save_latest,
            engine=engine, write_integrity=ck.integrity,
            checksums=ck.integrity_checksums, keep_last_k=ck.keep_last_k)

    def wait_checkpoint(self):
        """Block until an in-flight async checkpoint is durable (and its
        `latest` pointer written). No-op when async_save is off."""
        if self._ckpt_engine is not None:
            self._ckpt_engine.wait()

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True):
        self.wait_checkpoint()
        if tag is not None:
            # an explicit tag is honored verbatim — the caller asked for
            # exactly that save, so a failure there must surface
            return self._load_resolved(load_dir, str(tag),
                                       load_optimizer_states,
                                       load_lr_scheduler_states)
        # tag=None: resolve + integrity-validate, then load; if a VALIDATED
        # tag still fails to load (shallow validation with checksums off, a
        # payload-format error), keep walking back — the elastic rebuild
        # must land on SOME loadable save while one exists
        tried = set()
        last_err = None
        while True:
            try:
                resolved, _fell_back = ckpt_mod.resolve_load_tag(
                    load_dir, exclude=tried)
            except FileNotFoundError:
                if last_err is not None:
                    raise last_err
                raise
            try:
                return self._load_resolved(load_dir, resolved,
                                           load_optimizer_states,
                                           load_lr_scheduler_states)
            except Exception as e:  # noqa: BLE001 - walk back on any failure
                tried.add(resolved)
                last_err = e
                logger.warning(f"checkpoint tag '{resolved}' validated but "
                               f"failed to load ({e!r}); walking back")
                from deepspeed_tpu.robustness import events as rb_events
                rb_events.emit("ckpt_fallback", dir=load_dir,
                               requested=resolved, resolved=None,
                               reason=f"load-error: {e}")

    def _load_resolved(self, load_dir: str, tag: str,
                       load_optimizer_states: bool,
                       load_lr_scheduler_states: bool):
        """Load one specific, already-resolved tag. Every sub-path (Orbax
        state, optional-leaf retries, optswap.npz, infinity) reads the SAME
        tag; the walk-back policy lives in load_checkpoint above."""
        if self._infinity:
            return self._load_infinity_checkpoint(load_dir, tag)
        try:
            state, client_state = ckpt_mod.load_checkpoint(
                load_dir, tag, template=self.state,
                shardings=self.state_shardings)
        except Exception as orig:
            optional = [k for k in ("skipped", "telemetry")
                        if isinstance(self.state, dict) and k in self.state]
            if not optional:
                raise
            # checkpoints written before the device-resident skip counter /
            # telemetry accumulators lack those leaves: retry without each
            # combination, rebuild the dropped leaves fresh (the
            # skipped_steps setter reconciles the host offset against
            # client_state below). If every retry fails, the failure wasn't
            # the missing leaves: surface the ORIGINAL error, not a retry's
            import itertools as _it
            state = None
            dropped = ()
            for r in range(1, len(optional) + 1):
                for drop in _it.combinations(optional, r):
                    tmpl = {k: v for k, v in self.state.items()
                            if k not in drop}
                    sh = {k: v for k, v in self.state_shardings.items()
                          if k not in drop}
                    try:
                        state, client_state = ckpt_mod.load_checkpoint(
                            load_dir, tag, template=tmpl, shardings=sh)
                        dropped = drop
                        break
                    except Exception:
                        continue
                if state is not None:
                    break
            if state is None:
                raise orig
            if "skipped" in dropped:
                state["skipped"] = jax.device_put(
                    jnp.zeros((), jnp.int32), self.state_shardings["skipped"])
            if "telemetry" in dropped:
                state["telemetry"] = jax.device_put(
                    tel_acc.init_leaf(
                        self.config.telemetry.gnorm_hist_buckets),
                    self.state_shardings["telemetry"])
        if not load_optimizer_states:
            state["opt"] = self.state["opt"]
        if self._offload_opt:
            state["opt"] = self._opt_to_host(state["opt"])
        if self._nvme_opt and load_optimizer_states:
            swap_file = os.path.join(load_dir, str(tag), "optswap.npz")
            with np.load(swap_file) as z:
                self._swapper.import_state({k: z[k] for k in z.files})
        self.state = state
        self.global_steps = int(client_state.get("global_steps", 0))
        self.skipped_steps = int(client_state.get("skipped_steps", 0))
        self.micro_steps = int(client_state.get("micro_steps", 0))
        if "rng_key" in client_state:
            self._restore_rng(client_state["rng_key"])
        if self._dataloader is not None and "data_position" in client_state \
                and hasattr(self._dataloader, "load_state_dict"):
            self._dataloader.load_state_dict(client_state["data_position"])
        # restored cumulative telemetry counters: restart the window diff
        # baseline so the first post-restore window isn't a cross-run delta
        self._tel_prev = None
        self._tel_wall = None
        self._tel_wall_steps = self.global_steps
        if self._onebit_comm:
            # phase selection must track the OPTIMIZER's applied count, which
            # resets when load_optimizer_states=False while global_steps
            # doesn't — re-sync the host mirror from device state
            self._onebit_applied = int(np.asarray(jax.device_get(
                self.state["opt"]["step"]))[0])
        return load_dir, client_state

    def _save_infinity_checkpoint(self, save_dir, tag, client_state,
                                  save_latest):
        """Infinity mode: chunk files are copied verbatim; the small
        HBM-resident (non-layer) state goes into an npz with a dtype
        manifest (the same bf16-as-uint16 scheme as save_16bit_model)."""
        path = os.path.join(save_dir, str(tag))
        os.makedirs(path, exist_ok=True)
        from deepspeed_tpu.robustness import integrity as rb_integrity
        rb_integrity.invalidate(path)  # in-place overwrite reads as torn
        small = self._infinity_exec.save_checkpoint(path)
        client_state["applied_steps"] = small.pop("applied_steps")
        if "loss_scale" in small:
            client_state["loss_scale"] = small.pop("loss_scale")
        flat = _flatten_dict({"nl_params": small["nl_params"],
                              "nl_opt": small["nl_opt"]})
        dtypes, arrays = {}, {}
        for key, arr in flat.items():
            arr = np.asarray(arr)
            dtypes[key] = str(arr.dtype)
            if "bfloat16" in str(arr.dtype):
                arr = arr.view(np.uint16)
            arrays[key.replace("/", "__")] = arr
        np.savez(os.path.join(path, "infinity_small.npz"), **arrays)
        with open(os.path.join(path, "infinity_meta.json"), "w") as f:
            json.dump({"dtypes": dtypes, "client_state": client_state}, f)
        ck = self.config.checkpoint
        ckpt_mod.finalize_tag(save_dir, tag, save_latest=save_latest,
                              write_integrity=ck.integrity,
                              checksums=ck.integrity_checksums,
                              keep_last_k=ck.keep_last_k)
        logger.info(f"saved infinity checkpoint {path}")
        return path

    def _load_infinity_checkpoint(self, load_dir, tag):
        import ml_dtypes
        if tag is None:
            tag, _fell_back = ckpt_mod.resolve_load_tag(load_dir)
        path = os.path.join(load_dir, str(tag))
        with open(os.path.join(path, "infinity_meta.json")) as f:
            meta = json.load(f)
        flat = {}
        with np.load(os.path.join(path, "infinity_small.npz")) as z:
            for k in z.files:
                key = k.replace("__", "/")
                arr = z[k]
                if "bfloat16" in meta["dtypes"][key]:
                    arr = arr.view(ml_dtypes.bfloat16)
                flat[key] = arr
        tree = _unflatten_dict(flat)
        client_state = meta["client_state"]
        small = {"nl_params": tree["nl_params"], "nl_opt": tree["nl_opt"],
                 "applied_steps": client_state.get("applied_steps", 0)}
        if "loss_scale" in client_state:
            small["loss_scale"] = client_state["loss_scale"]
        self._infinity_exec.load_checkpoint(path, small)
        self.global_steps = int(client_state.get("global_steps", 0))
        self.skipped_steps = int(client_state.get("skipped_steps", 0))
        self.micro_steps = int(client_state.get("micro_steps", 0))
        if "rng_key" in client_state:
            self._restore_rng(client_state["rng_key"])
        if self._dataloader is not None and "data_position" in client_state \
                and hasattr(self._dataloader, "load_state_dict"):
            self._dataloader.load_state_dict(client_state["data_position"])
        logger.info(f"loaded infinity checkpoint {path}")
        return load_dir, client_state

    def save_16bit_model(self, save_dir: str, name: str = "model_fp16.ckpt"):
        """Gathered 16-bit weights export (reference:
        _zero3_consolidated_16bit_state_dict:3146 / save_16bit_model:3213).

        bf16 has no native npz dtype, so bf16 arrays are stored as uint16
        views plus a dtype manifest; `load_16bit_model` restores them."""
        gathered = jax.tree.map(
            lambda p: np.asarray(jax.device_get(p)), self.state["params"])
        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, name)
        if not path.endswith(".npz"):
            path += ".npz"
        flat = _flatten_dict(gathered)
        dtypes = {}
        arrays = {}
        for key, arr in flat.items():
            dtypes[key] = str(arr.dtype)
            if arr.dtype.kind == "V" or "bfloat16" in str(arr.dtype):
                arr = arr.view(np.uint16)
            arrays[key] = arr
        np.savez(path, **arrays)
        with open(path + ".dtypes.json", "w") as f:
            json.dump(dtypes, f)
        return path


def load_16bit_model(path: str):
    """Restore a save_16bit_model export as {name: np.ndarray} (bf16 arrays
    come back as ml_dtypes.bfloat16)."""
    if not path.endswith(".npz"):
        path += ".npz"
    data = dict(np.load(path))
    manifest = path + ".dtypes.json"
    if os.path.exists(manifest):
        import ml_dtypes
        with open(manifest) as f:
            dtypes = json.load(f)
        for key, dt in dtypes.items():
            if "bfloat16" in dt and key in data:
                data[key] = data[key].view(ml_dtypes.bfloat16)
    return data


def _stack_batches(batches):
    """Stack K host batches on a new leading step dim for the fused
    program. Host-side np.stack by design: the fused path consumes raw
    loader output (one device_put moves the whole K-chunk)."""
    if isinstance(batches[0], dict):
        return {k: np.stack([np.asarray(b[k]) for b in batches])
                for k in batches[0]}
    return jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *batches)


def _flatten_dict(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        key = f"{prefix}{k}" if not prefix else f"{prefix}/{k}"
        if isinstance(v, dict):
            out.update(_flatten_dict(v, key))
        elif v is not None:
            out[key] = v
    return out


def _manual_batch_specs(batch):
    """Per-leaf shard_map in_specs for a batch tree entering a region that
    is manual over `data`: side-channels and scalars replicate, data rows
    shard. The ONE place the rule lives — the deferred-sync region and the
    1-bit step both consult it."""
    if batch is None:
        return P("data")
    if isinstance(batch, dict):
        return {k: (P() if _is_side_channel(k)
                    or getattr(v, "ndim", 0) < 1 else P("data"))
                for k, v in batch.items()}
    return jax.tree.map(
        lambda x: P("data") if getattr(x, "ndim", 0) >= 1 else P(), batch)


# what a step over expert layers reports beside its loss (`train_step`)
_MOE_METRICS = ("moe_held_rows", "moe_load_max_over_mean", "moe_dropped_rows")


def _is_side_channel(key) -> bool:
    """Batch-dict keys starting with "_" are per-step side-channels
    (_pld_theta, _moq_bits): replicated across microbatches and devices —
    their leading dim (if any) is NOT the batch dim. The ONE place the
    convention lives; _accum_micro_grads, _device_batch and the 1-bit
    batch specs all consult it."""
    return isinstance(key, str) and key.startswith("_")


def _infinity_mode(config) -> bool:
    """Whether the config selects the ZeRO-Infinity layer-streamed executor.
    Round 5: EVERY enabled offload_param routes here — the executor is the
    one param-offload train path (mixed cpu/nvme tiers collapse onto the
    nvme store with the host param cache on top; see Engine.__init__)."""
    return config.zero_optimization.offload_param.enabled


def _unflatten_dict(flat):
    out = {}
    for key, v in flat.items():
        node = out
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def _same_structure(a, b) -> bool:
    try:
        return jax.tree.structure(a) == jax.tree.structure(b)
    except Exception:
        return False
