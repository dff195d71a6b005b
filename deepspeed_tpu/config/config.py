"""The framework config tree.

Reference: ``deepspeed/runtime/config.py:658`` (``DeepSpeedConfig``) plus the
pydantic sub-configs (zero ``runtime/zero/config.py:76``, offload
``offload_config.py:20,51``, fp16/bf16 getters ``runtime/config.py:118-640``,
monitor ``monitor/config.py``, comms ``comm/config.py``, aio/flops-profiler
sections). Same JSON key surface where the concept survives on TPU; new
TPU-only keys (mesh/tensor_parallel/sequence_parallel/remat) are additive.

The batch triad solve (train_batch = micro_batch × grad_accum × dp_world) is
preserved exactly (reference: ``runtime/config.py`` batch reconciliation).
"""

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

from deepspeed_tpu.config.config_utils import ConfigModel, ConfigError, config_field
from deepspeed_tpu.utils.logging import logger


# --------------------------------------------------------------------------
# Sub-sections
# --------------------------------------------------------------------------

@dataclasses.dataclass
class OptimizerConfig(ConfigModel):
    ALIASES = {"type": "name"}
    name: str = "adamw"
    params: Dict[str, Any] = config_field({})

    def validate(self):
        from deepspeed_tpu.ops.registry import SUPPORTED_OPTIMIZERS
        if self.name.lower() not in SUPPORTED_OPTIMIZERS:
            raise ConfigError(f"optimizer '{self.name}' not supported; "
                              f"choose from {sorted(SUPPORTED_OPTIMIZERS)}")


@dataclasses.dataclass
class SchedulerConfig(ConfigModel):
    ALIASES = {"type": "name"}
    name: Optional[str] = None
    params: Dict[str, Any] = config_field({})


@dataclasses.dataclass
class FP16Config(ConfigModel):
    """Reference keys: ``runtime/config.py`` fp16 section + ``fp16/loss_scaler.py:84``."""
    enabled: bool = False
    loss_scale: float = 0.0            # 0 = dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0
    auto_cast: bool = True

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == 0.0


@dataclasses.dataclass
class BF16Config(ConfigModel):
    enabled: bool = True  # TPU-first default: bf16 on


@dataclasses.dataclass
class OffloadDeviceConfig(ConfigModel):
    """Reference: ``runtime/zero/offload_config.py:20,51`` (DeepSpeedZeroOffload{Param,Optimizer}Config)."""
    device: str = "none"              # none | cpu | nvme  (cpu == TPU-VM host DRAM)
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    pin_memory: bool = False
    # overlapped offload pipeline (the reference's pipelined optimizer
    # swapper defaults these OFF; here the double-buffered layer streaming /
    # three-way read(i+1) || update(i) || write(i-1) schedule IS the
    # supported fast path, so both default ON — setting BOTH knobs of an
    # offload section to False gets the fully-drained executor/swapper,
    # e.g. for bit-for-bit pipeline bisection)
    pipeline_read: bool = True
    pipeline_write: bool = True
    fast_init: bool = False
    max_in_cpu: int = 1_000_000_000
    ratio: float = 1.0
    # run the optimizer ON the host over host-resident fp32 state (native
    # fused CPU-Adam, the reference's DeepSpeedCPUAdam design): per step only
    # compute-dtype grads/params cross the bus. Opt-in; not yet compared
    # against the streamed tiers on the chip (ROADMAP S2).
    use_cpu_adam: bool = False

    @property
    def enabled(self) -> bool:
        return self.device not in ("none", None)


@dataclasses.dataclass
class ZeroConfig(ConfigModel):
    """Reference: ``runtime/zero/config.py:76`` (DeepSpeedZeroConfig).

    On TPU, stages are realized as sharding rules over the mesh's data/fsdp
    axes rather than a partitioned-tensor runtime:
      stage 0 — pure DP (replicated params/grads/opt, psum grads)
      stage 1 — optimizer states sharded over data axis
      stage 2 — + gradients reduce-scattered (psum_scatter)
      stage 3 — + parameters sharded, all-gathered on use by GSPMD
    """
    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = True
    offload_param: OffloadDeviceConfig = config_field(OffloadDeviceConfig)
    offload_optimizer: OffloadDeviceConfig = config_field(OffloadDeviceConfig)
    sub_group_size: int = 1_000_000_000
    stage3_max_live_parameters: int = 1_000_000_000
    stage3_max_reuse_distance: int = 1_000_000_000
    stage3_prefetch_bucket_size: int = 50_000_000
    stage3_param_persistence_threshold: int = 100_000
    stage3_gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    round_robin_gradients: bool = False
    zero_hpz_partition_size: int = 1
    elastic_checkpoint: bool = False

    def validate(self):
        if self.stage not in (0, 1, 2, 3):
            raise ConfigError(f"zero_optimization.stage must be 0..3, got {self.stage}")


@dataclasses.dataclass
class ActivationCheckpointingConfig(ConfigModel):
    """Reference: ``runtime/activation_checkpointing/checkpointing.py:789``
    (configure). On TPU this maps to jax.checkpoint/remat policies;
    partition_activations maps to saving activations sharded over the tensor
    axis (GSPMD keeps them sharded when the policy saves them)."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False        # offload saved activations to host
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native additions
    policy: str = "none"   # none | full | dots_saveable | save_nothing | offload_dots


@dataclasses.dataclass
class PipelineConfig(ConfigModel):
    stages: int = 1                      # pipeline-parallel degree
    partition_method: str = "parameters"  # parameters | uniform | type:<regex>
    micro_batches: Optional[int] = None   # defaults to gradient_accumulation_steps
    activation_checkpoint_interval: int = 0
    schedule: str = "1f1b"                # 1f1b | gpipe | interleaved
    # --- async STEP pipeline (engine.train_batches; orthogonal to the
    # stage-parallel knobs above). The reference hides dispatch behind CUDA
    # streams; here XLA async dispatch does it — these bound/amplify it.
    in_flight: int = 2       # dispatched-steps window train_batches keeps open
    prefetch: bool = True    # double-buffered device_put of batch N+1
    fuse_steps: int = 1      # K>1: unroll K optimizer steps into ONE dispatch

    def validate(self):
        if self.in_flight < 1:
            raise ConfigError("pipeline.in_flight must be >= 1")
        if self.fuse_steps < 1:
            raise ConfigError("pipeline.fuse_steps must be >= 1")


@dataclasses.dataclass
class TensorParallelConfig(ConfigModel):
    ALIASES = {"size": "tp_size", "tp": "tp_size"}
    tp_size: int = 1
    seq_parallel: bool = False  # shard activations along sequence on the tensor axis


@dataclasses.dataclass
class SequenceParallelConfig(ConfigModel):
    """Context/sequence parallelism (absent in reference v0.8.3 — SURVEY §2.7;
    first-class here): ring attention over the 'seq' mesh axis."""
    ALIASES = {"size": "sp_size"}
    sp_size: int = 1
    mode: str = "ring"  # ring | allgather


@dataclasses.dataclass
class MoEConfig(ConfigModel):
    enabled: bool = False
    expert_parallel_size: int = 1
    num_experts: int = 1
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None   # None | 'Jitter' | 'RSample'
    drop_tokens: bool = True
    use_residual: bool = False                # PR-MoE
    aux_loss_weight: float = 0.01


@dataclasses.dataclass
class MonitorSinkConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"
    # wandb extras
    team: Optional[str] = None
    group: Optional[str] = None
    project: Optional[str] = None


@dataclasses.dataclass
class FlopsProfilerConfig(ConfigModel):
    """Reference: ``profiling/flops_profiler`` config keys, plus the
    TPU-native measured tier: ``measure_trace`` joins a real
    ``jax.profiler`` traced step (profiling/capture.py) against the
    analytic per-module FLOPs so the report's latency column is device
    time, not host-side module timers."""
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None
    measure_trace: bool = False
    trace_dir: str = ""               # "" = no artifact written


@dataclasses.dataclass
class CommConfig(ConfigModel):
    """Collective *scheduling* policy (TPU-native). The reference controls
    when collectives run imperatively (``overlap_comm`` /
    ``contiguous_gradients`` in ``runtime/zero/stage_1_and_2.py``); here
    GSPMD places them, and this section controls the structure the engine
    hands the compiler (deepspeed_tpu/comm/schedule.py)."""
    # accumulate microbatch grads in a per-device LOCAL (unreduced) buffer
    # inside the scan and issue ONE data-axis reduction at the step boundary
    # (DeepSpeed no_sync semantics): dp-sync collective counts become
    # independent of gradient_accumulation_steps. Costs a full-size (not
    # 1/dp) grad accumulator per device under stage 2.
    deferred_grad_sync: bool = False
    # 0 = lax.scan microbatch loop (one static collective site, compile time
    # independent of gas); K >= gas = fully unrolled microbatches (the
    # latency-hiding scheduler can overlap microbatch i's reduction with
    # microbatch i+1's compute; compile time and census scale with gas)
    microbatch_unroll: int = 0

    def validate(self):
        if self.microbatch_unroll < 0:
            raise ConfigError("comm.microbatch_unroll must be >= 0")


@dataclasses.dataclass
class CommsLoggerConfig(ConfigModel):
    """Reference: ``deepspeed/comm/config.py`` + ``utils/comms_logging.py:58``."""
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = config_field([])


@dataclasses.dataclass
class AIOConfig(ConfigModel):
    """Reference: aio section (``runtime/swap_tensor/constants.py``).

    The offload tiers open TWO native handles — one ring for prefetch
    reads, one for write-behind — so the read and write queues never
    serialize behind each other. ``read_queue_depth``/``write_queue_depth``
    size them independently (None = ``queue_depth`` for both)."""
    block_size: int = 1_048_576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True
    read_queue_depth: Optional[int] = None
    write_queue_depth: Optional[int] = None


@dataclasses.dataclass
class CheckpointConfig(ConfigModel):
    tag_validation: str = "Warn"      # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: Dict[str, Any] = config_field({})
    async_save: bool = False
    # --- integrity chain (deepspeed_tpu/robustness/integrity.py) ---
    # write a per-tag manifest + atomic COMMITTED marker; load_checkpoint
    # (tag=None) validates and walks back past torn/corrupt saves
    integrity: bool = True
    # re-hash file contents on validate (catches bitrot, not just
    # truncation); sizes are always checked
    integrity_checksums: bool = True
    # bounded retention: keep the newest K *valid* tags, prune older good
    # ones after each committed save (0 = unlimited; the tag `latest`
    # names is never pruned)
    keep_last_k: int = 0

    def validate(self):
        if self.keep_last_k < 0:
            raise ConfigError("checkpoint.keep_last_k must be >= 0")


@dataclasses.dataclass
class CurriculumParams(ConfigModel):
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = config_field({})


@dataclasses.dataclass
class CurriculumConfig(ConfigModel):
    """Reference: curriculum_learning section (``runtime/data_pipeline/curriculum_scheduler.py``)."""
    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = config_field({})


@dataclasses.dataclass
class PLDConfig(ConfigModel):
    """Reference: ``runtime/progressive_layer_drop.py`` (theta/gamma keys)."""
    enabled: bool = False
    theta: float = 0.5     # keep-probability floor
    gamma: float = 0.001   # decay rate of theta(t) toward the floor


@dataclasses.dataclass
class DataEfficiencyConfig(ConfigModel):
    enabled: bool = False
    seed: int = 1234
    data_sampling: Dict[str, Any] = config_field({})
    data_routing: Dict[str, Any] = config_field({})


@dataclasses.dataclass
class CompressionConfig(ConfigModel):
    """Reference: ``compression/config.py`` surface (weight/activation quant,
    pruning, layer reduction)."""
    weight_quantization: Dict[str, Any] = config_field({})
    activation_quantization: Dict[str, Any] = config_field({})
    sparse_pruning: Dict[str, Any] = config_field({})
    row_pruning: Dict[str, Any] = config_field({})
    head_pruning: Dict[str, Any] = config_field({})
    channel_pruning: Dict[str, Any] = config_field({})
    layer_reduction: Dict[str, Any] = config_field({})


@dataclasses.dataclass
class ElasticityConfig(ConfigModel):
    """Reference: ``elasticity/config.py`` (v0.1/0.2 keys)."""
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = config_field([2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.2
    ignore_non_elastic_batch_info: bool = False
    prefer_larger_batch: bool = True


@dataclasses.dataclass
class FaultsConfig(ConfigModel):
    """Deterministic fault injection (deepspeed_tpu/robustness/faults.py).
    Entries fire at exact step / operation indices; `seed` feeds the
    rate-based entries so a schedule replays identically. Reference
    analogue: none — the reference's elasticity is only exercised by real
    cluster failures."""
    enabled: bool = False
    seed: int = 0
    # list of fault dicts: {"kind": "device_fault"|"io_error"|"torn_save"|
    # "corrupt_payload"|"preempt"|"step_fault"|"clock_skew"|
    # "decode_dispatch"|"pool_exhaust"|"backend_fault", ...} — see
    # robustness.FaultSchedule for the per-kind keys (the last three are
    # the serving-tier seams; `preempt` also takes a serving `round`)
    entries: List[Dict[str, Any]] = config_field([])

    def validate(self):
        if self.enabled:
            from deepspeed_tpu.robustness.faults import FaultSchedule
            try:
                FaultSchedule(self.entries, self.seed)
            except ValueError as e:  # config surface raises ConfigError
                raise ConfigError(f"robustness.faults: {e}") from e


@dataclasses.dataclass
class RobustnessConfig(ConfigModel):
    """Fault-tolerance knobs (deepspeed_tpu/robustness). Checkpoint
    integrity/retention live under the `checkpoint` section for key parity
    with the reference; this section owns what has no reference analogue."""
    faults: FaultsConfig = config_field(FaultsConfig)


@dataclasses.dataclass
class AutotuningConfig(ConfigModel):
    enabled: bool = False
    results_dir: str = "autotuning_results"
    exps_dir: str = "autotuning_exps"
    overwrite: bool = False
    metric: str = "throughput"
    num_tuning_micro_batch_sizes: int = 3
    tuner_type: str = "gridsearch"     # gridsearch | random | model_based
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50
    max_train_batch_size: Optional[int] = None
    mp_size: int = 1
    fast: bool = True


@dataclasses.dataclass
class AnalysisConfig(ConfigModel):
    """graft-lint (``deepspeed_tpu/analysis``) knobs. TPU-native: the
    reference has no compiled program to lint; its nearest relative is the
    runtime ``comms_logger`` section. All thresholds are bytes."""
    # collectives smaller than this are control-plane sync (loss scalars,
    # overflow flags), exempt from the kind policy
    min_collective_bytes: int = 1024
    # exact census pin {op-kind: count}; any drift is an error. Empty = kind
    # policy only (see analysis/expectations.py). A value may also be
    # {"count": n, "bytes": b} (what the report's census prints): the bytes
    # are then held too, which is what catches a reduction XLA combined
    # into an existing op
    expect_collectives: Dict[str, Any] = config_field({})
    min_donation_bytes: int = 1024
    min_upcast_bytes: int = 1 << 20
    min_replicated_bytes: int = 1 << 20
    max_replicated_bytes: int = 0
    # overlap audit (scheduled-HLO): max synchronous/exposed collectives the
    # compiled step may contain before the "collective-exposed" finding
    # fires. None (default) = report-only — the overlap census still lands
    # in the report/JSON, but CPU lowerings (which never emit async
    # collective pairs) don't fail the gate.
    max_exposed_collectives: Optional[int] = None
    # exposed collectives smaller than this are control-plane sync and
    # exempt from the overlap gate
    min_exposed_bytes: int = 1024
    # memory lint (scheduled-HLO liveness): statically modeled peak HBM a
    # compiled step may reach before "memory-peak" fires. None (default) =
    # report-only — peak_hbm_bytes still lands in the report/JSON with its
    # params/grads/opt/activations breakdown, but absolute budgets are
    # model- and mesh-specific so the gate is opt-in.
    max_hbm_bytes: Optional[int] = None
    # ZeRO memory law: a state class expected to shard 1/dp may exceed
    # logical/dp by this factor (unshardable small leaves, persistence
    # thresholds, padding) before "memory-law" fires, and the absolute
    # excess must also clear min_law_bytes
    memory_law_tolerance: float = 1.5
    min_law_bytes: int = 1 << 20
    # finding keys / rule ids to suppress (accepted exceptions)
    suppress: List[str] = config_field([])
    # path to a baseline JSON (analysis.report.save_baseline): known
    # findings are suppressed, recorded census becomes an exact pin
    baseline: Optional[str] = None


@dataclasses.dataclass
class TelemetryTraceConfig(ConfigModel):
    """Windowed ``jax.profiler`` capture (device-side timeline). The host
    span recorder is always on with telemetry; this section only gates the
    heavyweight profiler window."""
    enabled: bool = False
    start_step: int = 10        # first step of the capture window
    num_steps: int = 2          # window length in steps
    output_dir: str = "telemetry_traces"

    def validate(self):
        if self.num_steps < 1:
            raise ConfigError("telemetry.trace.num_steps must be >= 1")


@dataclasses.dataclass
class AnomalyConfig(ConfigModel):
    """Thresholds for the window anomaly rules (telemetry/anomaly.py)."""
    enabled: bool = True
    ema_alpha: float = 0.3            # baseline EMA weight per window
    warmup_windows: int = 1           # windows that only seed baselines
    loss_spike_factor: float = 2.0    # |loss_mean| > factor x baseline
    gnorm_drift_factor: float = 10.0  # gnorm_mean outside [base/f, base*f]
    overflow_burst_rate: float = 0.25  # overflow-skipped fraction of window
    stall_regression_factor: float = 3.0  # block ms/step > factor x baseline


@dataclasses.dataclass
class TelemetryConfig(ConfigModel):
    """TPU-native observability (``deepspeed_tpu/telemetry``): in-graph
    window accumulators in the donated jitted state, host step tracing,
    anomaly events, and the static x runtime join (modeled comms bytes/sec +
    window MFU). Design constraint: ZERO added steady-state host syncs — the
    accumulator leaf drains through the engine's existing single batched
    device_get at steps_per_print boundaries."""
    enabled: bool = False
    gnorm_hist_buckets: int = 16      # log2 buckets of the grad-norm hist
    update_ratio: bool = True         # per-step ||update||/||param|| stats
    static_join: bool = True          # census/flops x observed rate events
    jsonl_path: Optional[str] = None  # machine-readable event sink (JSONL)
    max_trace_events: int = 20000     # host span ring size
    trace: TelemetryTraceConfig = config_field(TelemetryTraceConfig)
    anomaly: AnomalyConfig = config_field(AnomalyConfig)

    def validate(self):
        if self.gnorm_hist_buckets < 2:
            raise ConfigError("telemetry.gnorm_hist_buckets must be >= 2")


@dataclasses.dataclass
class TransformerTuningConfig(ConfigModel):
    """Model-level perf levers for transformer ModelSpecs. The engine
    applies them with a ``dataclasses.replace`` + ``make_model`` rebuild
    (the act-quant idiom): the param structure is untouched, only the
    compute path changes. Non-transformer models ignore the section with a
    warning."""
    # fused attention backward block (ops/flash_attention fused_backward):
    # the delta epilogue runs inside the backward grids; removes the XLA
    # delta pass + its [B,N,S,1] HBM round-trip per layer per step
    fused_backward: bool = False


@dataclasses.dataclass
class MeshConfig(ConfigModel):
    """TPU-native: explicit mesh override. By default the planner derives the
    mesh from world size and the parallelism degrees."""
    axes: Dict[str, int] = config_field({})   # e.g. {"data": 4, "tensor": 2}
    allow_split_physical_axes: bool = False


# --------------------------------------------------------------------------
# Root config
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Config(ConfigModel):
    # batch triad (reference: runtime/config.py batch reconciliation)
    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None

    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    dump_state: bool = False
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    sparse_gradients: bool = False
    gradient_clipping: float = 0.0
    communication_data_type: Optional[str] = None
    seed: int = 42
    disable_allgather: bool = False
    memory_breakdown: bool = False

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = config_field(FP16Config)
    bf16: BF16Config = config_field(BF16Config)
    zero_optimization: ZeroConfig = config_field(ZeroConfig)
    activation_checkpointing: ActivationCheckpointingConfig = config_field(ActivationCheckpointingConfig)
    pipeline: PipelineConfig = config_field(PipelineConfig)
    tensor_parallel: TensorParallelConfig = config_field(TensorParallelConfig)
    sequence_parallel: SequenceParallelConfig = config_field(SequenceParallelConfig)
    moe: MoEConfig = config_field(MoEConfig)
    mesh: MeshConfig = config_field(MeshConfig)

    tensorboard: MonitorSinkConfig = config_field(MonitorSinkConfig)
    wandb: MonitorSinkConfig = config_field(MonitorSinkConfig)
    csv_monitor: MonitorSinkConfig = config_field(MonitorSinkConfig)
    json_monitor: MonitorSinkConfig = config_field(MonitorSinkConfig)
    telemetry: TelemetryConfig = config_field(TelemetryConfig)
    flops_profiler: FlopsProfilerConfig = config_field(FlopsProfilerConfig)
    comm: CommConfig = config_field(CommConfig)
    comms_logger: CommsLoggerConfig = config_field(CommsLoggerConfig)
    aio: AIOConfig = config_field(AIOConfig)
    checkpoint: CheckpointConfig = config_field(CheckpointConfig)
    curriculum_learning: CurriculumConfig = config_field(CurriculumConfig)
    progressive_layer_drop: PLDConfig = config_field(PLDConfig)
    data_efficiency: DataEfficiencyConfig = config_field(DataEfficiencyConfig)
    compression_training: CompressionConfig = config_field(CompressionConfig)
    # MoQ (reference: runtime/quantize.py Quantizer + "quantize_training"
    # JSON section): start_bits -> target_bits over quantize_period steps,
    # optionally eigenvalue-scheduled per layer
    quantize_training: Dict[str, Any] = config_field({})
    elasticity: ElasticityConfig = config_field(ElasticityConfig)
    autotuning: AutotuningConfig = config_field(AutotuningConfig)
    analysis: AnalysisConfig = config_field(AnalysisConfig)
    robustness: RobustnessConfig = config_field(RobustnessConfig)
    transformer: TransformerTuningConfig = config_field(
        TransformerTuningConfig)

    # ---------------------------------------------------------------------
    @classmethod
    def load(cls, source) -> "Config":
        """Accept a dict, a JSON path, or an existing Config."""
        if isinstance(source, Config):
            return source
        if isinstance(source, str):
            if not os.path.exists(source):
                raise ConfigError(f"config file not found: {source}")
            with open(source) as f:
                source = json.load(f)
        return cls.from_dict(source or {})

    def validate(self):
        if self.fp16.enabled and self.bf16.enabled:
            # reference errors on fp16+bf16 both on; we prefer the explicit one
            logger.warning("config: fp16 and bf16 both enabled — using fp16 "
                           "(disable one explicitly to silence)")
            self.bf16 = BF16Config(enabled=False)
        zero = self.zero_optimization
        if zero.offload_param.enabled and zero.stage != 3:
            raise ConfigError("offload_param requires zero stage 3")

    # --- batch triad (train = micro × gas × dp_world) ---------------------
    def resolve_batch_size(self, dp_world_size: int) -> None:
        train, micro, gas = (self.train_batch_size,
                             self.train_micro_batch_size_per_gpu,
                             self.gradient_accumulation_steps)
        if train is not None and micro is not None and gas is not None:
            if train != micro * gas * dp_world_size:
                raise ConfigError(
                    f"batch mismatch: train_batch_size={train} != "
                    f"micro({micro}) * gas({gas}) * dp({dp_world_size})")
        elif train is not None and micro is not None:
            gas = train // (micro * dp_world_size)
        elif train is not None and gas is not None:
            micro = train // (gas * dp_world_size)
        elif micro is not None and gas is not None:
            train = micro * gas * dp_world_size
        elif train is not None:
            gas = 1
            micro = train // dp_world_size
        elif micro is not None:
            gas = 1
            train = micro * dp_world_size
        else:
            micro, gas = 1, 1
            train = dp_world_size
        if micro is None or micro <= 0 or gas is None or gas <= 0:
            raise ConfigError(
                f"cannot solve batch triad: train={train} micro={micro} gas={gas} dp={dp_world_size}")
        if train != micro * gas * dp_world_size:
            raise ConfigError(
                f"batch triad unsolvable: train_batch_size={train} not divisible into "
                f"micro({micro}) * gas({gas}) * dp({dp_world_size})")
        self.train_batch_size = train
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas

    # --- dtype helpers ----------------------------------------------------
    @property
    def compute_dtype(self):
        import jax.numpy as jnp
        if self.fp16.enabled:
            return jnp.float16
        if self.bf16.enabled:
            return jnp.bfloat16
        return jnp.float32

    @property
    def loss_scale_enabled(self) -> bool:
        return self.fp16.enabled
