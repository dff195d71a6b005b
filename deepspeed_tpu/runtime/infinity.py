"""ZeRO-Infinity layer-streamed training: params + optimizer state on NVMe.

Reference: ``runtime/swap_tensor/partitioned_param_swapper.py:35`` (fp16
params on NVMe, fetched per submodule), ``partitioned_optimizer_swapper.py:27``
and ``runtime/zero/stage3.py:1735`` (per-sub-group swap-in → step → swap-out).
The headline this enables is BASELINE.md metric #2: max trainable params per
chip scales with NVMe capacity instead of HBM (40B on one V100-32GB in the
reference's blog).

TPU-native re-design: instead of hooking a module tree with fetch/release
callbacks (the reference's PartitionedParameterCoordinator), the transformer's
homogeneous stacked-layer structure makes layer streaming a *driver loop*:

    forward:  embed (HBM) → for each layer: fetch params(i) → jitted layer
              forward (one compiled program serves every layer) → save x_i
    backward: CE head vjp (HBM) → for each layer reversed: fetch params(i) →
              jitted recompute-VJP (per-layer remat) → stage grads(i) to host
    update:   global grad norm (clip) → for each layer: fetch opt chunk(i) →
              jitted fused flat-AdamW → write back opt chunk + bf16 params

HBM residency is O(1 layer) of params/grads/opt-state plus the (small)
embedding/head and per-layer activation checkpoints; host DRAM stages the
flat grads (needed for the global-norm clip before any update); NVMe holds
the bf16 param chunks and fp32 (master, m, v) opt chunks. IO is overlapped
with compute by a prefetch thread (reads run one layer ahead; writes are
bounded write-behind). The optimizer state is lazily initialized: a missing
chunk means master = bf16 param upcast, m = v = 0, so the first step pays no
separate O(state) init write.

Storage layout per layer: one flat vector (the layer's leaves concatenated in
a fixed order, padded to the chunk size) — bf16 bits as uint16 for the param
file, (3, C) fp32 for the opt chunk. Layer grads come out of the VJP already
flat because the jitted layer functions take the flat vector and unflatten
inside.
"""

import dataclasses
import math
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.utils.logging import logger

_PLANES = 3  # master, exp_avg, exp_avg_sq


class LayerStore:
    """Per-layer chunk store: bf16 params as uint16 bits, fp32 (3, C)
    optimizer chunks.

    Backends:
      nvme   — AIO chunk files (the true ZeRO-Infinity tier; local-disk
               fast on a real TPU-VM where NVMe sits next to the chip)
      host   — numpy buffers in this process (tests; CPU)
      pinned — jax arrays in TPU-host pinned DRAM (bytes move host<->HBM
               by local DMA, no file system in between)
    """

    def __init__(self, path: Optional[str], n_layers: int, chunk_elems: int,
                 backend: str = "nvme", host_sharding=None, aio_config=None):
        self.n_layers = n_layers
        self.chunk = chunk_elems
        self.backend = backend
        self._host: Dict[str, Any] = {}
        # pinned backend: per-kind pinned_host shardings ({"param": ...,
        # "opt": ...}) — on a multi-device mesh each device pins only its
        # fsdp shard of the chunk
        self._host_sh = host_sharding
        self._aio_r = self._aio_w = None
        self._dir = None
        if backend == "nvme":
            if not path:
                raise ValueError("LayerStore(nvme) requires a path")
            self._dir = os.path.join(path, f"dstpu-infinity-{os.getpid()}")
            os.makedirs(self._dir, exist_ok=True)
            try:
                from deepspeed_tpu.ops.aio import (AIOHandle, aio_available,
                                                   report_fallback)
                if aio_available():
                    # separate handles: reads (prefetch) and writes
                    # (write-behind) each get their own ring, with
                    # independently-sized queue depths from the config
                    # `aio` section (read_queue_depth / write_queue_depth)
                    self._aio_r = AIOHandle.from_config(aio_config, "read")
                    self._aio_w = AIOHandle.from_config(aio_config, "write")
                else:  # pragma: no cover - no toolchain
                    # structured event (not just a log line): a capacity
                    # tier silently on synchronous numpy IO must be
                    # visible in the telemetry stream
                    report_fallback("infinity-layer-store")
            except Exception as e:  # pragma: no cover
                from deepspeed_tpu.ops.aio import report_fallback
                report_fallback("infinity-layer-store", reason=f"{e}")

    def _path(self, kind: str, i: int) -> str:
        return os.path.join(self._dir, f"{kind}_{i}.bin")

    def _key(self, kind: str, i: int) -> str:
        return f"{kind}_{i}"

    def _write(self, kind: str, i: int, arr):
        if self.backend == "pinned":
            # eager DMA into TPU-host pinned DRAM (async dispatch); the
            # handle is the storage
            sh = self._host_sh[kind] if isinstance(self._host_sh, dict) \
                else self._host_sh
            self._host[self._key(kind, i)] = jax.device_put(arr, sh)
        elif self.backend == "host":
            self._host[self._key(kind, i)] = np.ascontiguousarray(arr).copy()
        elif self._aio_w is not None:
            # AIOHandle.pwrite carries its own bounded retry + named error
            self._aio_w.pwrite(self._path(kind, i), arr)
        else:
            from deepspeed_tpu.robustness import faults as rb_faults
            from deepspeed_tpu.robustness.retry import retry_io
            path = self._path(kind, i)
            data = np.ascontiguousarray(arr)

            def do_write():
                rb_faults.io_seam("nvme_write", path)
                data.tofile(path)
            retry_io(do_write, what="layer-chunk write", path=path)

    def _read(self, kind: str, i: int, shape, dtype,
              out: Optional[np.ndarray] = None):
        if self.backend in ("host", "pinned"):
            got = self._host.get(self._key(kind, i))
            return None if got is None else got
        p = self._path(kind, i)
        if not os.path.exists(p):
            return None
        if self._aio_r is not None:
            return self._aio_r.pread(p, shape, dtype, out=out)
        from deepspeed_tpu.robustness import faults as rb_faults
        from deepspeed_tpu.robustness.retry import retry_io

        def do_read():
            rb_faults.io_seam("nvme_read", p)
            if out is not None:
                # staging-buffer path: read straight into the caller's
                # pinned buffer (no per-read allocation in the hot loop).
                # A short read (torn/truncated chunk) must raise like the
                # np.fromfile path does, never hand back a buffer whose
                # tail is the PREVIOUS chunk's bytes
                with open(p, "rb") as f:
                    got = f.readinto(memoryview(out).cast("B"))
                if got != out.nbytes:
                    raise OSError(
                        f"short read: {got} of {out.nbytes} bytes from {p}")
                return out
            return np.fromfile(p, dtype).reshape(shape)
        return retry_io(do_read, what="layer-chunk read", path=p)

    # params: uint16 (bf16 bits), shape (C,)
    def write_param(self, i: int, bits: np.ndarray):
        self._write("param", i, bits)

    def read_param(self, i: int, out=None) -> Optional[np.ndarray]:
        return self._read("param", i, (self.chunk,), np.uint16, out=out)

    # opt: fp32 (3, C)
    def write_opt(self, i: int, buf: np.ndarray):
        self._write("opt", i, buf)

    def read_opt(self, i: int, out=None) -> Optional[np.ndarray]:
        return self._read("opt", i, (_PLANES, self.chunk), np.float32, out=out)

    def save_to(self, dst: str):
        """Checkpoint: copy every chunk into dst. Same PR-6 ``retry_io``
        contract as the step-path IO: a transient EIO mid-copy retries with
        backoff instead of torching the save."""
        from deepspeed_tpu.robustness.retry import retry_io
        os.makedirs(dst, exist_ok=True)
        if self.backend in ("host", "pinned"):
            for k, v in self._host.items():
                p = os.path.join(dst, f"{k}.bin")
                arr = np.asarray(jax.device_get(v))
                retry_io(lambda arr=arr, p=p: arr.tofile(p),
                         what="layer-chunk checkpoint write", path=p)
            return
        for f in os.listdir(self._dir):
            src, out = os.path.join(self._dir, f), os.path.join(dst, f)
            retry_io(lambda src=src, out=out: shutil.copyfile(src, out),
                     what="layer-chunk checkpoint copy", path=out)

    def load_from(self, src: str, saved_chunk: Optional[int] = None):
        """Restore chunks. `saved_chunk` (from the shapes manifest) may
        differ from self.chunk when the fsdp degree changed between save and
        load — chunks are zero-padded past the real layer numel, so
        re-chunking is a truncate-or-pad of the pad region."""
        saved = saved_chunk or self.chunk

        def rechunk(plane):
            if saved == self.chunk:
                return plane
            if saved > self.chunk:
                return np.ascontiguousarray(plane[:self.chunk])
            return np.pad(plane, (0, self.chunk - saved))

        from deepspeed_tpu.robustness.retry import retry_io
        for f in os.listdir(src):
            if not f.endswith(".bin"):
                continue
            kind, i = f[:-4].rsplit("_", 1)
            dtype = np.uint16 if kind == "param" else np.float32
            p = os.path.join(src, f)
            arr = retry_io(lambda p=p, dtype=dtype: np.fromfile(p, dtype),
                           what="layer-chunk checkpoint read", path=p)
            if kind == "opt":
                arr = np.stack([rechunk(p)
                                for p in arr.reshape(_PLANES, saved)])
            else:
                arr = rechunk(arr)
            self._write(kind, int(i), arr)

    def close(self):
        if self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)
            # idempotent (pid-keyed dir): a re-run close() must not rmtree
            # a successor store's live directory
            self._dir = None


class StagingRing:
    """Rotating host staging buffers with write-behind fencing.

    The native host-Adam sweep keeps three operations in flight — read
    chunk i+1, update chunk i, drain chunk i-1 — over ``nbufs`` fixed
    buffers. A buffer may still be draining (its write-behind future is
    live) when the sweep comes back around to it; ``acquire`` is the
    fence that waits that future out before handing the buffer back.
    ``slot`` is the raw, unfenced view — identity checks only. Handing a
    ``slot`` result to a writer is exactly the aliasing race the
    ``staging-buffer-alias`` corpus entry demonstrates.
    """

    def __init__(self, nbufs: int, shape, dtype=np.float32):
        self.nbufs = nbufs
        self._bufs = [np.empty(shape, dtype) for _ in range(nbufs)]
        self._busy: list = [None] * nbufs

    def slot(self, i: int) -> np.ndarray:
        """Raw buffer for slot ``i % nbufs`` — no fence, no wait."""
        return self._bufs[i % self.nbufs]

    def acquire(self, i: int) -> np.ndarray:
        """Buffer for slot ``i % nbufs`` after its drain (if any) lands."""
        k = i % self.nbufs
        busy = self._busy[k]
        if busy is not None:
            busy.result()
            self._busy[k] = None
        return self._bufs[k]

    def mark_busy(self, i: int, fut) -> None:
        """Record the write-behind future draining slot ``i % nbufs``."""
        self._busy[i % self.nbufs] = fut

    def drain(self) -> None:
        """Wait out every live write-behind."""
        for k, busy in enumerate(self._busy):
            if busy is not None:
                busy.result()
                self._busy[k] = None


class InfinityExecutor:
    """Layer-streamed train/eval over NVMe-resident transformer layers.

    Owns: the LayerStore, the per-layer jitted programs, the non-layer
    (embed/head/norm) params + their optimizer, the prefetch/write pools.
    The engine delegates train_batch/eval_batch/checkpoint to this object
    when ``offload_param.device == "nvme"``.
    """

    def __init__(self, model_cfg, *, rng, nvme_path: str,
                 lr=1e-3, betas=(0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0, adam_w_mode: bool = True,
                 bias_correction: bool = True, grad_clip: float = 0.0,
                 backend: str = "nvme", param_cache_bytes: int = 0,
                 gas: int = 1, mesh=None, fp16: Optional[Dict[str, Any]] = None,
                 compression=None, use_cpu_adam: bool = False,
                 max_live_params: int = 0, moq: bool = False,
                 pipeline: bool = True, aio_config=None):
        if model_cfg.num_experts > 1:
            raise ValueError("offload_param.device=nvme supports dense "
                             "transformers (MoE experts not yet streamed)")
        if model_cfg.attn_windows:
            raise ValueError("layer-streamed offload does not thread "
                             "per-layer attn_windows yet (one jit serves "
                             "every layer)")
        self.cfg = dataclasses.replace(model_cfg, scan_layers=False,
                                       offload_params=False)
        self.b1, self.b2 = betas
        self.eps = eps
        self.wd = weight_decay
        self.awm = adam_w_mode
        self.bc = bias_correction
        self.lr = lr
        self.clip = grad_clip
        self.gas = gas
        self.applied_steps = 0
        # fp16 dynamic loss scaling, host-side (reference: the loss-scaler
        # state the fp16 optimizers carry, runtime/fp16/loss_scaler.py:84).
        # Storage bits stay bf16; compute runs in cfg.dtype (fp16), the
        # fp32 master in the opt chunk carries the precision.
        self.fp16 = dict(fp16) if fp16 else None
        if self.fp16:
            static = float(self.fp16.get("loss_scale", 0.0) or 0.0)
            self._dynamic_scale = static == 0.0     # reference: 0 = dynamic
            self._scale = (static if not self._dynamic_scale else
                           float(2.0 ** self.fp16.get("initial_scale_power",
                                                      16)))
            self._scale_window = int(self.fp16.get("loss_scale_window", 1000))
            self._min_scale = float(self.fp16.get("min_loss_scale", 1.0))
            self._hysteresis = int(self.fp16.get("hysteresis", 2))
            self._good_steps = 0
            self._hyst_left = self._hysteresis
        # compression transform applied to each streamed layer's params
        # (path-compatible with the monolithic engine path: the per-layer
        # tree is wrapped under "layers/", masks computed per layer)
        self.compression = compression
        # MoQ composes with layer streaming: each per-layer jit takes the
        # layer's scheduled bit-width as a traced scalar (the engine's
        # [L] ``_moq_bits`` side-channel, indexed per layer), so schedule
        # updates never recompile and the quantize-dequantize runs inside
        # the same program that unflattens the streamed chunk
        self.moq = bool(moq)

        L = self.cfg.num_layers
        # per-layer leaf template from a single-layer config (shapes only)
        cfg1 = dataclasses.replace(self.cfg, num_layers=1)
        from deepspeed_tpu.models.transformer import init_params
        shapes1 = jax.eval_shape(lambda k: init_params(k, cfg1),
                                 jax.random.PRNGKey(0))["layers"]
        self._leaves, self._treedef = jax.tree.flatten(shapes1)
        self._shapes = [l.shape[1:] for l in self._leaves]   # drop L=1 dim
        self._sizes = [int(np.prod(s)) for s in self._shapes]
        numel = sum(self._sizes)
        self._pinned = backend == "pinned"

        # --- host-resident optimizer (ZeRO-Offload's compute design: the
        # fp32 master/m/v never cross the host<->HBM bus; reference:
        # csrc/adam/cpu_adam.cpp:21). Two TPU-native flavors:
        #   "xla_host" (pinned backend): the Adam sweep runs ON the TPU
        #     host's CPUs inside the XLA program via
        #     jax.experimental.compute_on("device_host") — opt chunks stay
        #     in pinned_host memory end to end, and per step only bf16
        #     grads cross down (params were already streaming for fwd/bwd).
        #   "native" (host/nvme backends, i.e. this process IS the TPU
        #     host): the fused C++ AdamW (csrc/adam/dstpu_cpu_adam.cpp)
        #     updates the store's chunks in place.
        self._host_adam = None
        if use_cpu_adam:
            if self._pinned:
                self._host_adam = "xla_host"
            else:
                from deepspeed_tpu.ops.cpu_adam import cpu_adam_available
                if cpu_adam_available():
                    self._host_adam = "native"
                else:  # pragma: no cover - toolchain missing
                    logger.warning("use_cpu_adam requested but the native "
                                   "library failed to build; optimizer "
                                   "chunks will round-trip through HBM")

        # --- mesh: offload composes with data/fsdp parallelism (reference:
        # ZeRO-3 + NVMe at 512 GPUs, stage3.py:65 + partitioned_param_
        # swapper.py:35). Layer chunks shard over `fsdp` (each device stages
        # only its shard; one all-gather on use = the ZeRO-3 fetch); batch
        # shards over (data, fsdp); grads reduce-scatter back to `fsdp`; the
        # fused Adam sweep is fully shard-local.
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        if mesh is not None and mesh.size > 1:
            self.mesh = mesh
        else:
            dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
            self.mesh = Mesh(np.asarray([dev]).reshape(1, 1),
                             ("data", "fsdp"))
        mesh_shape = dict(self.mesh.shape)
        for ax in ("pipe", "seq", "expert"):
            if mesh_shape.get(ax, 1) > 1:
                raise ValueError(f"layer-streamed offload shards over "
                                 f"data/fsdp/tensor; mesh axis '{ax}' > 1")
        self._F = mesh_shape.get("fsdp", 1)
        self._TP = mesh_shape.get("tensor", 1)
        self.dp = self._F * mesh_shape.get("data", 1)
        self._batch_axes = tuple(a for a in ("data", "fsdp")
                                 if a in mesh_shape)
        single = self.mesh.size == 1
        # flat chunks shard over fsdp AND tensor (pure storage
        # distribution); the TP leaf constraints in the layer jits are
        # what turn the tensor axis into Megatron-style compute sharding
        # (reference: ZeRO-3+NVMe under a Megatron mpu,
        # runtime/engine.py:1088-1100 + zero/stage3.py:65)
        chunk_axes = (("fsdp", "tensor") if self._TP > 1 and self._F > 1
                      else ("tensor",) if self._TP > 1 else ("fsdp",))
        # on a 1-device mesh trivially-sharded specs are semantically P(),
        # but the sharded annotation routes pinned<->HBM device_put through
        # a slower path (measured 2.5x on the capacity rung) — use plain P()
        self._x_spec = P() if single else P(self._batch_axes)
        self._bits_spec = P() if single else P(chunk_axes)
        self._opt_spec = P() if single else P(None, chunk_axes)
        # per-leaf tensor-parallel specs for the unflattened layer tree
        # (col/row rules from parallel/partitioning; the leading "layers"
        # logical dim is dropped — the per-layer tree has no L axis)
        self._tp_leaf_specs = None
        if self._TP > 1:
            from deepspeed_tpu.models.transformer import (
                logical_axes as _logical_axes)
            from deepspeed_tpu.parallel.partitioning import (
                make_rules as _make_rules, spec_tree as _spec_tree)
            lay_axes = _logical_axes(self.cfg)["layers"]
            per_layer = jax.tree.map(
                lambda a: a[1:] if isinstance(a, tuple) else a, lay_axes,
                is_leaf=lambda x: x is None or isinstance(x, tuple))
            tp_tree = _spec_tree(per_layer, _make_rules(0, tp=True))
            self._tp_leaf_specs = jax.tree.flatten(
                tp_tree, is_leaf=lambda x: isinstance(x, P))[0]
        # memory_kind="device" is load-bearing: a device_put from a
        # pinned_host source with no explicit kind can keep the array on the
        # host tier, and every downstream jit then reads over PCIe. Some
        # CPU jaxlibs expose no device/pinned_host kinds at all (only
        # unpinned_host) — there the host tier is numpy buffers and the
        # un-kinded sharding means the same thing, so degrade to it rather
        # than failing construction.
        _degraded_kinds = set()

        def _kinded(spec, kind):
            try:
                return NamedSharding(self.mesh, spec, memory_kind=kind)
            except (ValueError, TypeError) as e:
                if kind not in _degraded_kinds:
                    _degraded_kinds.add(kind)
                    logger.warning(
                        f"memory_kind='{kind}' unsupported on this backend "
                        f"({e}); using un-kinded shardings — on real TPU "
                        "hardware this would defeat the host/HBM tiering, "
                        "on CPU jaxlibs there is no tiering to defeat")
                return NamedSharding(self.mesh, spec)

        self._x_sh = _kinded(self._x_spec, "device")
        self._bits_dev_sh = _kinded(self._bits_spec, "device")
        self._opt_dev_sh = _kinded(self._opt_spec, "device")
        self._repl_dev_sh = _kinded(P(), "device")
        self._bits_host_sh = _kinded(self._bits_spec, "pinned_host")
        self._opt_host_sh = _kinded(self._opt_spec, "pinned_host")
        self._repl_host_sh = _kinded(P(), "pinned_host")

        # chunk rounded so every fsdp x tensor shard is lane-aligned
        align = 128 * self._F * self._TP
        self.chunk = ((numel + align - 1) // align) * align
        self.layer_params = numel
        self.num_params = L * numel
        self.store = LayerStore(nvme_path, L, self.chunk, backend=backend,
                                host_sharding={"param": self._bits_host_sh,
                                               "opt": self._opt_host_sh},
                                aio_config=aio_config)
        # --- overlapped offload pipeline (reference: the three-stage
        # pipelined optimizer swapper, pipelined_optimizer_swapper.py:50).
        # pipeline=True (default): fwd/bwd walks keep TWO param fetches in
        # flight ahead of compute, and every update sweep runs the
        # three-way schedule  read(i+1) || update(i) || write(i-1)  with
        # SEPARATE read/write pools (a queued write-behind must never delay
        # the next prefetch behind it) and write-behind bounded to 2.
        # pipeline=False is the fully-drained executor: synchronous
        # resolve-at-use reads and a drain after every layer's write — the
        # `offload-serial-pipeline` corpus twin and the bit-for-bit
        # pipeline-bisection baseline.
        self.pipeline = bool(pipeline)
        self._rpool = ThreadPoolExecutor(max_workers=2)
        self._wpool = ThreadPoolExecutor(max_workers=2)
        self._pending_writes: list = []
        # host staging buffers, lazily allocated on first use: two per
        # plane (param bits / opt planes) for the double-buffered reads of
        # the device-Adam sweep, three opt buffers for the native host-Adam
        # sweep (read fills one while Adam updates another in place and
        # write-behind drains the third)
        self._opt_stage = None
        # host bf16-bits cache of param chunks (fast refetch for bwd/next
        # step; NVMe stays the system of record). Pointless for the pinned
        # backend — the store itself IS host memory.
        if self._pinned:
            self._cache_layers = 0
        else:
            self._cache_layers = param_cache_bytes // (2 * self.chunk) \
                if param_cache_bytes else L
        self._param_cache: Dict[int, np.ndarray] = {}
        # HBM-resident bits cache (reference: stage3_max_live_parameters —
        # params kept live in device memory, stage3.py's max_live knob).
        # Layers whose bf16 bits fit under the budget skip the fwd/bwd
        # re-fetch DMA entirely; the update refreshes cached entries.
        self._hbm_cache: Dict[int, Any] = {}
        self._hbm_cache_layers = (int(max_live_params) // max(1, numel)
                                  if max_live_params else 0)
        if self._hbm_cache_layers:
            logger.info(
                f"param live-cache: up to {min(self._hbm_cache_layers, L)} "
                f"of {L} layers resident in device memory "
                f"({max_live_params/1e9:.2f}B param budget)")

        self._build_jits()
        self._init_params(rng)
        tier = {"xla_host": ", Adam on the TPU host (compute_on; opt state "
                            "never crosses the host<->HBM bus)",
                "native": ", Adam in the native host kernel (opt state "
                          "never touches the device)"}.get(self._host_adam, "")
        logger.info(
            f"ZeRO-Infinity layer streaming: {L} layers x "
            f"{numel/1e6:.1f}M params on {backend} "
            f"({self.num_params/1e9:.2f}B layer params total, chunk "
            f"{self.chunk*2/1e6:.0f}MB bf16 + {self.chunk*12/1e6:.0f}MB opt)"
            f"{tier}")

    # ------------------------------------------------------------------
    def _adam_math(self, master, m, v, g, lr_t, step):
        """The one AdamW core every variant (device chunk, host chunk,
        embed/head device, embed/head host) traces: returns (master', m',
        v'). ``g`` arrives already scaled by the clip/scale coefficient."""
        from deepspeed_tpu.ops.adam import fused_adam_update
        return fused_adam_update(master, m, v, g, lr_t, step,
                                 b1=self.b1, b2=self.b2, eps=self.eps,
                                 wd=self.wd, awm=self.awm, bc=self.bc)

    # ------------------------------------------------------------------
    def _build_jits(self):
        cfg = self.cfg
        sizes, shapes = self._sizes, self._shapes
        treedef = self._treedef
        chunk = self.chunk
        b1, b2, eps = self.b1, self.b2, self.eps
        wd, awm, bc = self.wd, self.awm, self.bc
        multi = self.mesh.size > 1
        x_spec, bits_spec, opt_spec = (self._x_spec, self._bits_spec,
                                       self._opt_spec)
        from jax.sharding import PartitionSpec as P
        from deepspeed_tpu.models.transformer import (
            _norm, transformer_layer, chunked_cross_entropy)

        def wsc(t, spec):
            # constraints are what make the multi-device program ZeRO-3:
            # bits replicate (one all-gather) at use, grads land fsdp-sharded
            # (reduce-scatter), activations stay batch-sharded
            return jax.lax.with_sharding_constraint(t, spec) if multi else t

        compression = self.compression
        moq_on = self.moq

        tp_specs = self._tp_leaf_specs

        def leaves_from_flat(flat, step=None, qbits=None):
            """Gathered flat vector -> layer param pytree (compute dtype).
            The ONE place that slices/reshapes/TP-constrains leaves — used
            by both the forward unflatten and the backward fp32 view."""
            out, off = [], 0
            for j, (size, shape) in enumerate(zip(sizes, shapes)):
                leaf = jax.lax.dynamic_slice_in_dim(flat, off, size) \
                    .reshape(shape).astype(cfg.dtype)
                if tp_specs is not None:
                    # Megatron col/row sharding of the reshaped weight —
                    # this is what makes the tensor axis COMPUTE, not just
                    # storage: GSPMD partitions each matmul and inserts
                    # the psum on the row-parallel outputs
                    leaf = wsc(leaf, tp_specs[j])
                out.append(leaf)
                off += size
            tree = jax.tree.unflatten(treedef, out)
            if compression is not None:
                # same leaf paths as the monolithic engine path sees
                # ("layers/<name>"); masks are per-layer here
                tree = compression.apply(
                    {"layers": tree},
                    step if step is not None else 0)["layers"]
            if moq_on and qbits is not None:
                # MoQ fake-quant at this layer's scheduled bit-width;
                # weight leaves only (matches MoQ.apply's stacked ndim>=3
                # filter — per-layer norm scales/biases are 1-d)
                from deepspeed_tpu.runtime.quantize import (
                    _ste_quant_traced_bits)
                tree = {k: (_ste_quant_traced_bits(v, qbits)
                            if getattr(v, "ndim", 0) >= 2 else v)
                        for k, v in tree.items()}
            return tree

        def unflatten(flat_bits, step=None, qbits=None):
            """uint16 bf16-bits (C,) -> layer param pytree (compute dtype)."""
            flat = jax.lax.bitcast_convert_type(flat_bits, jnp.bfloat16)
            # one explicit all-gather of the bf16 chunk (the ZeRO-3 fetch);
            # without it every dynamic_slice below would gather separately
            flat = wsc(flat, P())
            return leaves_from_flat(flat, step, qbits)

        def layer_fwd(flat_bits, x, mask, positions, step, qbits):
            p = unflatten(flat_bits, step, qbits)
            y, _aux = transformer_layer(x, p, cfg, mask=mask,
                                        positions=positions,
                                        deterministic=True)
            return wsc(y, x_spec)

        self._layer_fwd = jax.jit(layer_fwd)

        def layer_bwd(flat_bits, x, dy, mask, positions, step, qbits):
            """Recompute-VJP for one layer: returns (flat fp32 grads, dx,
            grad sq-norm). The fwd recompute inside vjp IS the remat."""
            def f(bits_f32, x):
                # differentiate w.r.t. a fp32 VIEW of the params so the
                # cotangent comes back fp32 (bitcast isn't differentiable)
                p = leaves_from_flat(bits_f32, step, qbits)
                y, _aux = transformer_layer(x, p, cfg, mask=mask,
                                            positions=positions,
                                            deterministic=True)
                return y
            flat32 = wsc(jax.lax.bitcast_convert_type(
                flat_bits, jnp.bfloat16), P()).astype(jnp.float32)
            _, vjp = jax.vjp(f, flat32, x)
            dp, dx = vjp(dy)
            # batch-sum cotangent reduce-scatters onto the fsdp shards
            dp = wsc(dp, bits_spec)
            dx = wsc(dx, x_spec)
            return dp, dx, jnp.sum(dp.astype(jnp.float32) ** 2)

        self._layer_bwd = jax.jit(layer_bwd)

        def embed_fwd(nl, ids):
            x = nl["tok_embed"][ids].astype(cfg.dtype)
            if cfg.position_type == "learned":
                S = ids.shape[1]
                x = x + nl["pos_embed"][jnp.arange(S)[None]].astype(cfg.dtype)
            if cfg.embed_norm:
                x = _norm(x, nl["embed_norm_scale"],
                          nl.get("embed_norm_bias"), cfg)
            return wsc(x, x_spec)

        def top_loss(nl, x, labels):
            h = _norm(x, nl["final_norm_scale"], nl.get("final_norm_bias"),
                      cfg)
            head = nl.get("lm_head")
            tied = head is None
            if tied:
                head = nl["tok_embed"]
            c = cfg.loss_chunk if cfg.loss_chunk else min(1024, x.shape[1])
            return chunked_cross_entropy(h, head, labels, c, tied_embed=tied)

        def top_fwd_bwd(nl, x, labels, scale):
            def scaled(nl, x):
                return top_loss(nl, x, labels) * scale
            (loss, (dnl, dx)) = jax.value_and_grad(
                scaled, argnums=(0, 1))(nl, x)
            return loss, dnl, wsc(dx, x_spec)

        self._top_fwd_bwd = jax.jit(top_fwd_bwd)
        self._top_loss = jax.jit(top_loss)
        self._embed_fwd = jax.jit(embed_fwd)

        def embed_bwd(nl, ids, dx0):
            _, vjp = jax.vjp(lambda nl: embed_fwd(nl, ids), nl)
            (dnl,) = vjp(dx0)
            return dnl

        self._embed_bwd = jax.jit(embed_bwd)

        def tree_add(a, b):
            return jax.tree.map(jnp.add, a, b)

        self._tree_add = jax.jit(tree_add)
        self._scalar_add = jax.jit(lambda a, b: a + b)
        self._sq = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32) ** 2))
        self._nl_sq = jax.jit(
            lambda t, inv: sum(jnp.sum((l.astype(jnp.float32) * inv) ** 2)
                               for l in jax.tree.leaves(t)))

        adam_math = self._adam_math

        def adam_chunk(opt_buf, grad, param_bits, have_opt, lr_t, step,
                      coef):
            """Fused flat AdamW on one layer chunk. have_opt=False -> lazy
            init (master from the bf16 params, m = v = 0). grad: fp32, or
            bf16 bits as uint16 (the host-Adam wire dtype)."""
            p32 = jax.lax.bitcast_convert_type(
                param_bits, jnp.bfloat16).astype(jnp.float32)
            master = jnp.where(have_opt, opt_buf[0], p32)
            m = jnp.where(have_opt, opt_buf[1], 0.0)
            v = jnp.where(have_opt, opt_buf[2], 0.0)
            if grad.dtype == jnp.uint16:
                grad = jax.lax.bitcast_convert_type(
                    grad, jnp.bfloat16).astype(jnp.float32)
            master, m, v = adam_math(master, m, v, grad * coef, lr_t, step)
            new_bits = jax.lax.bitcast_convert_type(
                master.astype(jnp.bfloat16), jnp.uint16)
            return jnp.stack([master, m, v]), new_bits

        self._adam_chunk = jax.jit(adam_chunk, donate_argnums=(0,))
        # lazily-initialized opt chunk, born with the right fsdp sharding
        self._zeros_opt = jax.jit(
            lambda: jnp.zeros((_PLANES, chunk), jnp.float32),
            out_shardings=self._opt_dev_sh)

        if self._host_adam == "xla_host":
            # the same math compiled INTO the host memory space: opt chunks
            # live (and stay) in pinned_host; the sweep runs on the TPU
            # host's cores; only the fence scalar lands in device memory.
            # `have` is STATIC (two compiled variants): a traced
            # jnp.where(have, ...) would select between host-space planes
            # and default-space constants, which XLA rejects inside a
            # compute_on region.
            from jax.experimental.compute_on import compute_on

            def adam_chunk_host(opt_buf, grad_bits, param_bits, lr_t, step,
                                coef, have):
                @compute_on("device_host")
                @jax.jit
                def upd(opt_buf, grad_bits, param_bits, lr_t, step, coef):
                    flat = jax.lax.bitcast_convert_type(grad_bits,
                                                        jnp.bfloat16)
                    g = flat.astype(jnp.float32) * coef
                    if have:
                        master, m, v = opt_buf[0], opt_buf[1], opt_buf[2]
                    else:
                        master = jax.lax.bitcast_convert_type(
                            param_bits, jnp.bfloat16).astype(jnp.float32)
                        # derive zeros from the host array: a fresh
                        # jnp.zeros constant would be default-space
                        m = master * 0.0
                        v = master * 0.0
                    master, m, v = adam_math(master, m, v, g, lr_t, step)
                    new_bits = jax.lax.bitcast_convert_type(
                        master.astype(jnp.bfloat16), jnp.uint16)
                    return jnp.stack([master, m, v]), new_bits, master[0]
                return upd(opt_buf, grad_bits, param_bits, lr_t, step, coef)

            # scalars must enter host space too — a device-space scalar
            # poisons every elementwise op it touches with the default space
            scalar = (self._repl_host_sh,) * 3
            self._adam_chunk_host = jax.jit(
                adam_chunk_host,
                in_shardings=(self._opt_host_sh, self._bits_host_sh,
                              self._bits_host_sh) + scalar,
                out_shardings=(self._opt_host_sh, self._bits_host_sh,
                               self._repl_dev_sh),
                donate_argnums=(0,), static_argnums=(6,))
            self._zeros_opt_host = jax.jit(
                lambda: jnp.zeros((_PLANES, chunk), jnp.float32),
                out_shardings=self._opt_host_sh)
            # device-side grad -> bf16-bits cast (halves the staging DMA)
            self._grad_bits = jax.jit(
                lambda g: jax.lax.bitcast_convert_type(
                    g.astype(jnp.bfloat16), jnp.uint16))

    # ------------------------------------------------------------------
    def _init_params(self, rng):
        """Streamed init: one layer at a time (the full tree never exists)."""
        cfg = self.cfg
        L = cfg.num_layers
        from deepspeed_tpu.models.transformer import init_params
        cfg1 = dataclasses.replace(cfg, num_layers=1)
        # init_params scales residual-out weights by 1/sqrt(2*num_layers);
        # with a num_layers=1 config the draw comes out sqrt(L) too large
        rescale = 1.0 / math.sqrt(L)
        out_keys = ("wo", "w_out", "moe_w_out")
        sizes, shapes = self._sizes, self._shapes

        def one_layer(key):
            tree = init_params(key, cfg1)["layers"]
            tree = {k: (v * rescale if k in out_keys else v)
                    for k, v in tree.items()}
            flat = jnp.concatenate([
                jnp.reshape(v, (-1,)) for v in jax.tree.leaves(tree)
            ]).astype(jnp.bfloat16)
            flat = jnp.pad(flat, (0, self.chunk - flat.shape[0]))
            return jax.lax.bitcast_convert_type(flat, jnp.uint16)

        one_layer = jax.jit(one_layer, out_shardings=self._bits_dev_sh)
        keys = jax.random.split(jax.random.fold_in(rng, 17), L + 1)
        for i in range(L):
            bits = one_layer(keys[i])
            if self._pinned:
                self.store.write_param(i, bits)  # device->pinned_host DMA
            else:
                self.store.write_param(i, np.asarray(jax.device_get(bits)))

        # non-layer params (embed/pos/final norm/head) live in HBM; init with
        # an L=1 config and drop the layers subtree
        def nl_init(key):
            full = init_params(key, cfg1)
            return {k: jax.tree.map(lambda a: a.astype(cfg.dtype), v)
                    for k, v in full.items() if k != "layers"}

        self.nl_params = jax.jit(nl_init,
                                 out_shardings=self._repl_dev_sh)(keys[L])
        self.nl_opt = jax.tree.map(
            lambda p: {"master": p.astype(jnp.float32),
                       "m": jnp.zeros(p.shape, jnp.float32),
                       "v": jnp.zeros(p.shape, jnp.float32)},
            self.nl_params)
        if self._pinned:
            # embed/head fp32 state (12 bytes/param — GBs at 7B vocab+width)
            # lives on the host tier too
            self.nl_opt = jax.device_put(self.nl_opt, self._repl_host_sh)
        elif self.mesh.size > 1:
            self.nl_opt = jax.device_put(self.nl_opt, self._repl_dev_sh)

        from deepspeed_tpu.ops.adam import adam_tree_update

        def nl_update_tree(opt, grads, lr_t, step, coef):
            """Shared embed/head update over the {master,m,v}-leaf tree."""
            return adam_tree_update(
                opt, grads, lr_t, step, coef, b1=self.b1, b2=self.b2,
                eps=self.eps, wd=self.wd, awm=self.awm, bc=self.bc,
                out_dtype=self.cfg.dtype)

        def nl_adam(opt, grads, params, lr_t, step, coef):
            return nl_update_tree(opt, grads, lr_t, step, coef)

        self._nl_adam = jax.jit(nl_adam, donate_argnums=(0,))

        if self._host_adam == "xla_host":
            # embed/head update on the TPU host too: its fp32 state
            # (12 bytes/param — GBs at 7B vocab+width) stops round-tripping
            # host<->HBM; per step only compute-dtype grads go down and
            # compute-dtype params come back up.
            from jax.experimental.compute_on import compute_on

            def nl_adam_host(opt, grads, lr_t, step, coef):
                @compute_on("device_host")
                @jax.jit
                def upd_all(opt, grads, lr_t, step, coef):
                    return nl_update_tree(opt, grads, lr_t, step, coef)
                return upd_all(opt, grads, lr_t, step, coef)

            host_of = lambda t: jax.tree.map(  # noqa: E731
                lambda _: self._repl_host_sh, t)
            grads_shape = jax.tree.map(
                lambda o: o["master"], self.nl_opt,
                is_leaf=lambda x: isinstance(x, dict) and "master" in x)
            self._nl_adam_host = jax.jit(
                nl_adam_host,
                in_shardings=(host_of(self.nl_opt), host_of(grads_shape),
                              self._repl_host_sh, self._repl_host_sh,
                              self._repl_host_sh),
                out_shardings=(host_of(self.nl_opt), host_of(grads_shape)),
                donate_argnums=(0,))
            self._nl_grads_host_sh = host_of(grads_shape)

    # ------------------------------------------------------------------
    # IO helpers (prefetched)
    # ------------------------------------------------------------------
    def _get_param(self, i: int):
        got = self._param_cache.get(i)
        if got is None:
            got = self.store.read_param(i)
            if got is None:
                raise RuntimeError(f"missing param chunk {i}")
            if len(self._param_cache) < self._cache_layers:
                self._param_cache[i] = got
        return got

    def _param_dev(self, i: int):
        """Device handle for layer i's param bits. Live-cached layers skip
        IO entirely. Pinned backend: eager pinned_host->HBM DMA (async
        dispatch — issuing it a layer ahead IS the prefetch). File
        backends: host numpy (the jit call uploads; multi-device meshes
        shard the upload so each chip receives only its fsdp slice)."""
        got = self._hbm_cache.get(i)
        if got is not None:
            return got
        h = self._get_param(i)
        if self._pinned or self.mesh.size > 1:
            h = jax.device_put(h, self._bits_dev_sh)
        if self._hbm_cache_layers and \
                len(self._hbm_cache) < self._hbm_cache_layers:
            if not (self._pinned or self.mesh.size > 1):
                h = jnp.asarray(h)   # materialize on device for the cache
            self._hbm_cache[i] = h
        return h

    def _refresh_live_cache(self, i: int, bits, *, from_host: bool = False):
        """After an update, keep layer i's NEW bits live in device memory
        (within budget) so the next fwd/bwd skips the fetch."""
        if not self._hbm_cache_layers:
            return
        if i in self._hbm_cache or \
                len(self._hbm_cache) < self._hbm_cache_layers:
            self._hbm_cache[i] = (jax.device_put(bits, self._bits_dev_sh)
                                  if from_host else bits)

    def _fetch_param_async(self, i: int):
        got = self._hbm_cache.get(i)
        if got is not None:
            return got
        if self._pinned:
            return self._param_dev(i)  # async dispatch, returns a handle
        if not self.pipeline:
            return None   # drained executor: resolve-at-use, synchronously
        if i in self._param_cache:
            return None
        return self._rpool.submit(self._get_param, i)

    def _stream_params(self, order):
        """Yield ``(i, resolved_bits)`` over layer indices ``order``,
        keeping TWO fetches in flight ahead of the consumer (double-
        buffered streaming): while layer i computes, layer order[+1]'s
        read is resolving and order[+2]'s is queued on the read pool.
        pipeline=False degrades to synchronous resolve-at-use.

        Pinned backend stays at depth 1: there a "fetch" IS the
        pinned->HBM device_put dispatch, so each prefetched layer is
        DEVICE-resident bits — depth 2 would hold a third layer's chunk
        in HBM on rungs sized for two (the 7B capacity rung budgets one
        working layer + one prefetch), for no IO win over the already-
        async dispatch."""
        order = list(order)
        depth = (1 if self._pinned else 2) if self.pipeline else 0
        futs = {}
        for k in order[:depth]:
            futs[k] = self._fetch_param_async(k)
        for pos, i in enumerate(order):
            fut = futs.pop(i, None)
            if depth and pos + depth < len(order):
                nxt = order[pos + depth]
                futs[nxt] = self._fetch_param_async(nxt)
            yield i, self._resolve_param(fut, i)

    def _resolve_param(self, fut, i: int):
        if fut is not None and not hasattr(fut, "result"):
            return fut   # already a device handle (live cache / pinned)
        if self._pinned:
            return fut if fut is not None else self._param_dev(i)
        h = fut.result() if fut is not None else self._get_param(i)
        if self.mesh.size > 1:
            # sharded upload: each chip receives only its fsdp slice (the
            # in-graph all-gather redistributes over ICI, not host links)
            return jax.device_put(h, self._bits_dev_sh)
        return h

    def _to_host(self, x_dev, host_sh=None):
        """Stage a device array on the TPU host (pinned) or here (numpy)."""
        if self._pinned:
            return jax.device_put(x_dev, host_sh or self._bits_host_sh)
        return np.asarray(jax.device_get(x_dev))

    def _to_dev(self, h, dev_sh=None):
        if self._pinned or self.mesh.size > 1:
            return jax.device_put(h, dev_sh or self._bits_dev_sh)
        return jnp.asarray(h)

    def _drain_write(self):
        """Drain ALL in-flight write-behind. Called only at step
        boundaries (and on overflow/checkpoint/close) — never inside the
        sweeps, where it would serialize the pipeline."""
        pend, self._pending_writes = self._pending_writes, []
        for f in pend:
            f.result()

    def _bound_writes(self, limit: int = 2):
        """Write-behind depth: two writes in flight (double buffer);
        the oldest completes before a third is queued."""
        while len(self._pending_writes) >= limit:
            self._pending_writes.pop(0).result()

    def _write_layer_async(self, i: int, opt_buf_dev, bits_dev):
        if self._pinned:
            # device->pinned_host DMAs dispatch asynchronously; the store
            # keeps the handles
            self.store.write_opt(i, opt_buf_dev)
            self.store.write_param(i, bits_dev)
            return

        def work(opt_dev, bits_dev):
            # the device_get runs ON the writer thread: the main thread
            # keeps dispatching chunk i+1's update while chunk i's result
            # drains off the device and onto storage
            opt_host = np.asarray(jax.device_get(opt_dev))
            bits_host = np.asarray(jax.device_get(bits_dev))
            self.store.write_opt(i, opt_host)
            self.store.write_param(i, bits_host)
            if i in self._param_cache or len(self._param_cache) < self._cache_layers:
                self._param_cache[i] = bits_host

        if not self.pipeline:
            # drained twin: write synchronously, nothing in flight past
            # this layer
            work(opt_buf_dev, bits_dev)
            return
        self._bound_writes()
        self._pending_writes.append(
            self._wpool.submit(work, opt_buf_dev, bits_dev))

    # ------------------------------------------------------------------
    def _batch_arrays(self, batch):
        ids = jnp.asarray(batch["input_ids"])
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate(
                [ids[:, 1:], jnp.full((ids.shape[0], 1), -100, ids.dtype)],
                axis=1)
        else:
            labels = jnp.asarray(labels)
        mask = batch.get("attention_mask")
        if mask is not None:
            mask = jnp.asarray(mask)
        if self.mesh.size > 1:
            mb = ids.shape[0] // self.gas if self.gas > 1 else ids.shape[0]
            if mb % self.dp:
                raise ValueError(
                    f"microbatch {mb} not divisible by data*fsdp={self.dp}")
            ids = jax.device_put(ids, self._x_sh)
            labels = jax.device_put(labels, self._x_sh)
            if mask is not None:
                mask = jax.device_put(mask, self._x_sh)
        return ids, labels, mask

    def train_batch(self, batch) -> Dict[str, Any]:
        """One optimizer step: forward/backward sweeps over the layer files,
        host-staged grads, global-norm clip, fused-Adam update sweep. The
        mesh context makes the jits' sharding constraints resolvable
        (no-op on the 1-device mesh)."""
        with self.mesh:
            return self._train_batch(batch)

    def measure_decomposition(self, batch, reps: int = 2) -> Dict[str, float]:
        """Measured transfer-vs-compute decomposition of the streamed step
        (VERDICT Weak #2: the offload ratio was prose, not attributable).

        Direct measurements, no modeling:
          - ``offload_chunk_dma_ms``: wall time to stage ONE layer's param
            chunk host->device (the store's own staging path) with a fence;
          - ``offload_layer_ms``: wall time of one layer's fwd+bwd with the
            bits already device-resident (pure compute) with a fence;
          - ``offload_update_ms`` / ``offload_top_ms`` /
            ``offload_opt_io_ms``: the update sweep's three legs — one
            chunk's Adam compute, the embed/CE-head top (once per step),
            and one opt chunk's storage round-trip.
        Scaled to the step: param DMA crosses twice per layer (fwd + bwd
        fetch — ``offload_dma_ms``), layer fwd+bwd and the chunk Adam run
        once per layer (``offload_compute_ms`` /
        ``offload_update_sweep_ms``), and ``offload_io_ms`` totals the
        step's storage traffic (param fetches + opt round-trips).
        Callers price overlap through
        ``profiling.doctor.diagnose_offload``: exposure =
        max(0, step_ms - ALL measured compute) clamped to the io budget,
        ``offload_overlap_fraction = 1 - exposed/io`` — the storage time
        the step did NOT hide under compute.
        """
        import time
        with self.mesh:
            ids, labels, mask = self._batch_arrays(batch)
            mb = ids.shape[0] // self.gas if self.gas > 1 else ids.shape[0]
            ids, labels = ids[:mb], labels[:mb]
            mask = mask[:mb] if mask is not None else None
            L = self.cfg.num_layers
            step_t = jnp.int32(self.applied_steps)
            qb = jnp.float32(32.0)

            def fence(a):
                return np.asarray(jax.device_get(jnp.ravel(a)[0]))

            x = self._embed_fwd(self.nl_params, ids)
            fence(x)
            bits = self._to_dev(self._get_param(0))
            dy = jnp.ones_like(x)
            # warm the compiles outside the timed region
            y = self._layer_fwd(bits, x, mask, None, step_t, qb)
            _, _, sq = self._layer_bwd(bits, x, dy, mask, None, step_t, qb)
            fence(y)
            fence(sq)
            t0 = time.perf_counter()
            for _ in range(reps):
                y = self._layer_fwd(bits, x, mask, None, step_t, qb)
                _, _, sq = self._layer_bwd(bits, x, dy, mask, None,
                                           step_t, qb)
                fence(sq)
            layer_ms = (time.perf_counter() - t0) / reps * 1000
            # DMA probe: the same staging path the sweeps use
            h = self._get_param(0)
            d = self._to_dev(h)
            fence(d)
            t0 = time.perf_counter()
            for _ in range(reps):
                d = self._to_dev(h)
                fence(d)
            chunk_ms = (time.perf_counter() - t0) / reps * 1000

            # --- update-sweep probes (the pipelined sweep's three legs:
            # what the Adam compute costs, what the embed/head top costs,
            # and what one opt chunk's storage round-trip costs — callers
            # price exposure against compute INCLUDING these, so the
            # overlap fraction attributes the sweep too, not just the
            # fwd/bwd fetches)
            update_ms = top_ms = opt_io_ms = 0.0
            try:
                top_ms = self._measure_top_ms(ids, labels, scale=1.0,
                                              reps=reps)
            except Exception:   # noqa: BLE001 — secondary probe
                pass
            try:
                update_ms = self._measure_update_ms(reps=reps)
            except Exception:   # noqa: BLE001 — secondary probe
                pass
            try:
                if self.store.backend == "nvme":
                    opt0 = self.store.read_opt(0)
                    if opt0 is not None:
                        t0 = time.perf_counter()
                        for _ in range(reps):
                            opt0 = self.store.read_opt(0)
                            # same bytes back: a pure IO probe, no state
                            # change
                            self.store.write_opt(0, opt0)
                        opt_io_ms = ((time.perf_counter() - t0) / reps
                                     * 1000)
            except Exception:   # noqa: BLE001 — secondary probe
                pass
        io_ms = chunk_ms * 2 * L + opt_io_ms * L
        return {
            "offload_chunk_dma_ms": round(chunk_ms, 3),
            "offload_layer_ms": round(layer_ms, 3),
            # per step: every layer's chunk is fetched twice (fwd sweep +
            # bwd sweep); its fwd and bwd each run once — layer_ms times
            # them together
            "offload_dma_ms": round(chunk_ms * 2 * L, 2),
            "offload_compute_ms": round(layer_ms * L, 2),
            # the sweep legs: per-layer Adam compute, embed/head top
            # compute (once per step), per-layer opt-chunk storage IO,
            # and the step's TOTAL io (param fetches + opt round-trips)
            "offload_update_ms": round(update_ms, 3),
            "offload_update_sweep_ms": round(update_ms * L, 2),
            "offload_top_ms": round(top_ms, 2),
            "offload_opt_io_ms": round(opt_io_ms, 3),
            "offload_io_ms": round(io_ms, 2),
            "offload_pipeline": bool(self.pipeline),
        }

    def _measure_top_ms(self, ids, labels, scale: float, reps: int) -> float:
        """Embed fwd + CE-head fwd/bwd + embed bwd wall time (the step's
        non-layer compute)."""
        import time
        scale_t = jnp.float32(scale)
        x = self._embed_fwd(self.nl_params, ids)
        loss, dnl, dx = self._top_fwd_bwd(self.nl_params, x, labels, scale_t)
        dnl_e = self._embed_bwd(self.nl_params, ids, dx)
        np.asarray(jax.device_get(loss))
        jax.tree.leaves(jax.device_get(dnl_e))
        t0 = time.perf_counter()
        for _ in range(reps):
            x = self._embed_fwd(self.nl_params, ids)
            loss, dnl, dx = self._top_fwd_bwd(self.nl_params, x, labels,
                                              scale_t)
            dnl_e = self._embed_bwd(self.nl_params, ids, dx)
            np.asarray(jax.device_get(jnp.ravel(
                jax.tree.leaves(dnl_e)[0])[0]))
        return (time.perf_counter() - t0) / reps * 1000

    def _measure_update_ms(self, reps: int) -> float:
        """One layer chunk's Adam update cost on scratch state — the
        compute leg of the update sweep (no store writes)."""
        import time
        if self._host_adam == "native":
            from deepspeed_tpu.ops.cpu_adam import adam_step_flat
            scratch = np.zeros((_PLANES, self.chunk), np.float32)
            g = np.zeros(self.chunk, np.float32)
            t0 = time.perf_counter()
            for _ in range(reps):
                adam_step_flat(scratch[0], scratch[1], scratch[2], g,
                               step_num=1, lr=self.lr
                               if not callable(self.lr) else self.lr(1),
                               betas=(self.b1, self.b2), eps=self.eps,
                               weight_decay=self.wd, adamw_mode=self.awm,
                               bias_correction=self.bc, grad_scale=1.0)
            return (time.perf_counter() - t0) / reps * 1000
        lr_t, stepc, coef_t = (jnp.float32(1e-3), jnp.float32(1.0),
                               jnp.float32(1.0))
        if self._host_adam == "xla_host":
            lr_h, step_h, coef_h = jax.device_put((lr_t, stepc, coef_t),
                                                  self._repl_host_sh)
            pbits = self.store.read_param(0)
            gbits = self._to_host(self._grad_bits(
                jnp.zeros((self.chunk,), jnp.float32)))
            # warm
            _o, _b, fence = self._adam_chunk_host(
                self._zeros_opt_host(), gbits, pbits, lr_h, step_h,
                coef_h, False)
            np.asarray(jax.device_get(fence))
            t0 = time.perf_counter()
            for _ in range(reps):
                _o, _b, fence = self._adam_chunk_host(
                    self._zeros_opt_host(), gbits, pbits, lr_h, step_h,
                    coef_h, False)
                np.asarray(jax.device_get(fence))
            return (time.perf_counter() - t0) / reps * 1000
        g_dev = jnp.zeros((self.chunk,), jnp.float32)
        pbits = self._param_dev(0)
        _buf, _bits = self._adam_chunk(self._zeros_opt(), g_dev, pbits,
                                       jnp.asarray(False), lr_t, stepc,
                                       coef_t)
        np.asarray(jax.device_get(_bits[0]))
        t0 = time.perf_counter()
        for _ in range(reps):
            _buf, _bits = self._adam_chunk(self._zeros_opt(), g_dev, pbits,
                                           jnp.asarray(False), lr_t, stepc,
                                           coef_t)
            np.asarray(jax.device_get(_bits[0]))
        return (time.perf_counter() - t0) / reps * 1000

    def _qbits(self, batch, i: int):
        """Layer i's traced MoQ bit-width (engine side-channel), or a dummy
        scalar when MoQ is off (the jit operand is dead code then)."""
        if self.moq and isinstance(batch, dict) and "_moq_bits" in batch:
            return jnp.float32(np.asarray(batch["_moq_bits"])[i])
        return jnp.float32(32.0)

    def _train_batch(self, batch) -> Dict[str, Any]:
        L = self.cfg.num_layers
        ids_all, labels_all, mask_all = self._batch_arrays(batch)
        B = ids_all.shape[0]
        gas = self.gas
        mb = B // gas if gas > 1 else B

        # host fp32 grad staging, accumulated across microbatches
        grad_stage = [None] * L
        nl_grads = None
        loss_sum = 0.0
        sq_layer = [0.0] * L

        scale = self._scale if self.fp16 else 1.0
        scale_t = jnp.float32(scale)
        step_t = jnp.int32(self.applied_steps)

        # ---- update/backward overlap (xla_host Adam only) ----
        # With no clip, no fp16 overflow gate, and gas=1, the Adam update
        # for layer i depends only on layer i's grads (coef = 1 is known
        # up front) — so it can dispatch the moment layer i's grads are
        # staged, and the TPU-host cores run the Adam sweep CONCURRENTLY
        # with the device's backward of the remaining layers. (The generic
        # path must wait for the global grad norm.)
        overlap = (self._host_adam == "xla_host" and gas == 1
                   and not self.fp16
                   and not (self.clip and self.clip > 0))
        overlap_fence = None
        pending_refresh = []
        if overlap:
            step_next = self.applied_steps + 1
            lr_val = (self.lr if not callable(self.lr)
                      else self.lr(step_next))
            ov_lr, ov_step, ov_coef = jax.device_put(
                (jnp.float32(lr_val), jnp.float32(step_next),
                 jnp.float32(1.0)), self._repl_host_sh)

        for g in range(gas):
            sl = slice(g * mb, (g + 1) * mb) if gas > 1 else slice(None)
            ids, labels = ids_all[sl], labels_all[sl]
            mask = mask_all[sl] if mask_all is not None else None
            positions = None

            # ---- forward sweep (double-buffered: two fetches in flight
            # ahead of compute; _stream_params resolves at use) ----
            x = self._embed_fwd(self.nl_params, ids)
            acts = [x]
            for i, bits in self._stream_params(range(L)):
                x = self._layer_fwd(bits, x, mask, positions, step_t,
                                    self._qbits(batch, i))
                acts.append(x)
                if not self.pipeline:
                    # fully-drained executor: fence the layer before the
                    # next synchronous fetch — fetch -> compute -> drain,
                    # strictly in sequence (the offload-serial-pipeline
                    # corpus shape; async dispatch would otherwise still
                    # hide the next fetch under this layer's compute)
                    np.asarray(jax.device_get(jnp.ravel(x)[0]))

            loss, dnl_top, dx = self._top_fwd_bwd(self.nl_params, acts[L],
                                                  labels, scale_t)
            loss_sum += float(np.asarray(jax.device_get(loss))) / scale

            # ---- backward sweep (reverse, double-buffered: two fetches
            # in flight behind the walk) ----
            last_mb = g == gas - 1
            for i, bits in self._stream_params(range(L - 1, -1, -1)):
                dp, dx, sq = self._layer_bwd(bits, acts[i], dx, mask,
                                             positions, step_t,
                                             self._qbits(batch, i))
                acts[i + 1] = None  # free the activation as we pass it
                if self._pinned:
                    if grad_stage[i] is not None:  # accumulate on device
                        dp = self._scalar_add(self._to_dev(grad_stage[i]), dp)
                        if last_mb:
                            sq = self._sq(dp)
                    if overlap:
                        # stage bf16 grad bits and dispatch the host Adam
                        # for this layer right now — it runs on the TPU
                        # host while the device keeps doing backward
                        gbits = self._to_host(self._grad_bits(dp))
                        opt_h = self.store.read_opt(i)
                        have = opt_h is not None
                        if not have:
                            opt_h = self._zeros_opt_host()
                        new_opt, new_bits, overlap_fence = \
                            self._adam_chunk_host(
                                opt_h, gbits, self.store.read_param(i),
                                ov_lr, ov_step, ov_coef, have)
                        self.store.write_opt(i, new_opt)
                        self.store.write_param(i, new_bits)
                        # cache refresh is DEFERRED to after the backward:
                        # an eager pinned->HBM device_put here would make
                        # the device stream wait on this layer's host Adam
                        # before running the next backward layer
                        pending_refresh.append((i, new_bits))
                    elif last_mb and self._host_adam == "xla_host":
                        # final stage in bf16 bits — the host-Adam wire
                        # dtype (halves the grad DMA; reference ships f16
                        # grads to its CPU-Adam the same way)
                        grad_stage[i] = self._to_host(self._grad_bits(dp))
                    else:
                        grad_stage[i] = self._to_host(dp)
                    sq_layer[i] = sq
                else:
                    dp_host = np.asarray(jax.device_get(dp))
                    if grad_stage[i] is None:
                        # device_get buffers are read-only; copy only when
                        # we must accumulate into them
                        grad_stage[i] = dp_host if gas == 1 else dp_host.copy()
                    else:
                        grad_stage[i] += dp_host
                    sq_layer[i] = sq  # device scalar; summed after the loop

            dnl_emb = self._embed_bwd(self.nl_params, ids, dx)
            dnl = self._tree_add(dnl_top, dnl_emb)
            nl_grads = dnl if nl_grads is None else self._tree_add(nl_grads,
                                                                   dnl)

        # ---- global grad norm + overflow + clip coefficient ----
        inv = 1.0 / gas
        sq_total = 0.0
        for i in range(L):
            # staged grads are microbatch SUMS; norm uses the mean
            if gas == 1 or self._pinned:
                s = float(np.asarray(jax.device_get(sq_layer[i]))) * inv * inv
            else:
                s = float(np.sum((grad_stage[i] * inv) ** 2))
            sq_total += s
        nl_sq = float(np.asarray(jax.device_get(
            self._nl_sq(nl_grads, jnp.float32(inv)))))
        if self.fp16 and not np.isfinite(sq_total + nl_sq):
            # overflow: nothing is written (chunks untouched), the loss
            # scale shrinks — reference: loss_scaler.py:84 + step:1635
            self._on_overflow()
            self._drain_write()
            return {"loss": jnp.float32(loss_sum / gas),
                    "grad_norm": jnp.float32(float("nan")),
                    "overflow": jnp.asarray(True),
                    "loss_scale": jnp.float32(self._scale)}
        gnorm = math.sqrt(sq_total + nl_sq) / scale
        coef = inv / scale
        if self.clip and self.clip > 0 and gnorm > self.clip:
            coef *= self.clip / (gnorm + 1e-6)
        if self.fp16:
            self._on_good_step()

        # ---- update sweep ----
        self.applied_steps += 1
        lr_t = jnp.float32(self.lr if not callable(self.lr)
                           else self.lr(self.applied_steps))
        stepc = jnp.float32(self.applied_steps)
        coef_t = jnp.float32(coef)

        # non-layer (embed/head) update first: frees its fp32 grads before
        # the layer sweep's chunk buffers arrive
        if self._host_adam == "xla_host":
            # embed/head Adam on the TPU host: stage 2-byte grads down,
            # bring compute-dtype params up — the fp32 state stays
            # pinned-resident. Wire is bf16 even under fp16: scaled fp32
            # embed grads can exceed f16's 65504 max, which would silently
            # become inf AFTER the overflow check already passed
            wire = (jnp.bfloat16 if self.cfg.dtype == jnp.float16
                    else self.cfg.dtype)
            nl_g_host = jax.device_put(
                jax.tree.map(lambda g: g.astype(wire), nl_grads),
                self._nl_grads_host_sh)
            lr_h, step_h, coef_h = jax.device_put(
                (lr_t, stepc, coef_t), self._repl_host_sh)
            self.nl_opt, nl_params_host = self._nl_adam_host(
                self.nl_opt, nl_g_host, lr_h, step_h, coef_h)
            self.nl_params = jax.device_put(nl_params_host,
                                            self._repl_dev_sh)
        else:
            nl_opt_dev = (jax.device_put(self.nl_opt, self._repl_dev_sh)
                          if self._pinned else self.nl_opt)
            new_nl_opt, self.nl_params = self._nl_adam(
                nl_opt_dev, nl_grads, self.nl_params, lr_t, stepc, coef_t)
            self.nl_opt = (jax.device_put(new_nl_opt, self._repl_host_sh)
                           if self._pinned else new_nl_opt)
        del nl_grads

        if overlap:
            # layer updates were dispatched during backward; one tail fence
            # orders them before the step returns. Cache refreshes go out
            # now — each pinned->HBM transfer depends only on its own
            # layer's host Adam, so they pipeline with the sweep's tail.
            for i_r, bits_r in pending_refresh:
                self._refresh_live_cache(i_r, bits_r, from_host=True)
            pending_refresh.clear()
            if overlap_fence is not None:
                np.asarray(jax.device_get(overlap_fence))
        elif self._host_adam == "xla_host":
            # opt chunks never leave pinned_host: the Adam sweep runs on the
            # TPU host's cores (compute_on). No per-layer fence needed — the
            # chunks stay host-side, so nothing piles up in HBM; one tail
            # fence orders the sweep before the step returns.
            fence = None
            lr_h, step_h, coef_h = jax.device_put(
                (lr_t, stepc, coef_t), self._repl_host_sh)
            for i in range(L):
                opt_h = self.store.read_opt(i)
                have = opt_h is not None
                if not have:
                    opt_h = self._zeros_opt_host()
                new_opt, new_bits, fence = self._adam_chunk_host(
                    opt_h, grad_stage[i], self.store.read_param(i),
                    lr_h, step_h, coef_h, have)
                grad_stage[i] = None
                self.store.write_opt(i, new_opt)
                self.store.write_param(i, new_bits)
                self._refresh_live_cache(i, new_bits, from_host=True)
            if fence is not None:
                np.asarray(jax.device_get(fence))
        elif self._host_adam == "native":
            self._native_update_sweep(grad_stage, float(lr_t), coef)
        else:
            # three-way pipelined sweep (reference schedule,
            # swap_tensor.py:16):  read(i+1)  ||  adam(i) on device  ||
            # write(i-1).  Opt reads prefetch on the read pool, the write-
            # behind (device_get runs ON the writer thread) drains on the
            # write pool two layers deep, and _drain_write happens only at
            # the step boundary below. The drained twin (pipeline=False)
            # resolves reads at use and syncs every write. Reads come back
            # as fresh host arrays (no staging reuse here: the jit upload
            # may be zero-copy on CPU jaxlibs, so a recycled buffer could
            # alias a live device array — the native host-Adam sweep is
            # where the rotating staging buffers live).
            pipe = self.pipeline and not self._pinned
            opt_fut = self._rpool.submit(self.store.read_opt, 0) \
                if pipe else None
            for i in range(L):
                opt_host = (opt_fut.result() if pipe
                            else self.store.read_opt(i))
                if pipe:
                    opt_fut = (self._rpool.submit(self.store.read_opt, i + 1)
                               if i + 1 < L else None)
                have = opt_host is not None
                opt_dev = (self._to_dev(opt_host, self._opt_dev_sh) if have
                           else self._zeros_opt())
                new_buf, new_bits = self._adam_chunk(
                    opt_dev, self._to_dev(grad_stage[i]), self._param_dev(i),
                    jnp.asarray(have), lr_t, stepc, coef_t)
                grad_stage[i] = None
                self._write_layer_async(i, new_buf, new_bits)
                self._refresh_live_cache(i, new_bits)
                if self._pinned:
                    # bound in-flight chunk buffers to one layer: at 7B a
                    # layer's (3, C) fp32 opt buffer is 2.4 GB, and letting
                    # the async dispatch run ahead piles up donated+new
                    # buffers past HBM
                    jax.block_until_ready(new_buf)
                del opt_dev, new_buf, new_bits
        self._drain_write()

        out = {"loss": jnp.float32(loss_sum / gas),
               "grad_norm": jnp.float32(gnorm),
               "overflow": jnp.zeros((), jnp.bool_)}
        if self.fp16:
            out["loss_scale"] = jnp.float32(scale)
        return out

    def _opt_read_staged(self, i: int):
        """Read opt chunk i into one of the three rotating host staging
        buffers (lazy-init from the bf16 params when the chunk is missing).
        Waits for any write-behind still draining the target buffer, so
        read(i+1), update(i) and write(i-1) can all be in flight at once
        without aliasing. Only meaningful for the native host-Adam sweep,
        whose consumption is pure numpy (in-place update + same-buffer
        write)."""
        import ml_dtypes
        buf = self._opt_stage.acquire(i)
        got = self.store.read_opt(i, out=buf)
        if got is None:   # lazy init: master from the bf16 params
            np.copyto(buf[0], self._get_param(i).view(ml_dtypes.bfloat16))
            buf[1:] = 0.0
            return buf
        # host backend returns the stored array itself (out is ignored
        # there) — same in-place-update-then-copy-back contract as before
        return np.ascontiguousarray(got)

    def _native_update_sweep(self, grad_stage, lr: float, coef: float):
        """Fused C++ AdamW (csrc/adam/dstpu_cpu_adam.cpp) over the store's
        chunks — this process IS the TPU host, so the fp32 state never
        touches the device; updated bf16 param bits are derived host-side.
        Pipelined as the reference's three-stage optimizer swapper
        (pipelined_optimizer_swapper.py:50): chunk i+1's AIO read fills one
        staging buffer while the host cores run Adam on chunk i in a second
        and the write ring drains chunk i-1 from the third.
        Reference: stage_1_and_2.py's cpu_offload step over DeepSpeedCPUAdam."""
        import ml_dtypes
        from deepspeed_tpu.ops.cpu_adam import adam_step_flat
        L = self.cfg.num_layers
        step = self.applied_steps
        pipe = self.pipeline
        if self._opt_stage is None:
            self._opt_stage = StagingRing(3, (_PLANES, self.chunk),
                                          np.float32)
        opt_fut = self._rpool.submit(self._opt_read_staged, 0) \
            if pipe else None
        for i in range(L):
            opt = opt_fut.result() if pipe else self._opt_read_staged(i)
            if pipe:
                opt_fut = (self._rpool.submit(self._opt_read_staged, i + 1)
                           if i + 1 < L else None)
            adam_step_flat(opt[0], opt[1], opt[2], grad_stage[i],
                           step_num=step, lr=lr, betas=(self.b1, self.b2),
                           eps=self.eps, weight_decay=self.wd,
                           adamw_mode=self.awm, bias_correction=self.bc,
                           grad_scale=coef)
            grad_stage[i] = None
            bits = np.ascontiguousarray(
                opt[0].astype(ml_dtypes.bfloat16).view(np.uint16))

            def work(i=i, opt=opt, bits=bits):
                self.store.write_opt(i, opt)
                self.store.write_param(i, bits)
                if i in self._param_cache or \
                        len(self._param_cache) < self._cache_layers:
                    self._param_cache[i] = bits

            if pipe:
                self._bound_writes()
                fut = self._wpool.submit(work)
                if opt is self._opt_stage.slot(i):
                    self._opt_stage.mark_busy(i, fut)
                self._pending_writes.append(fut)
            else:
                work()   # drained twin: write + implicit drain per layer
            self._refresh_live_cache(i, bits, from_host=True)

    def _on_overflow(self):
        if not self._dynamic_scale:
            return  # static scale: overflow skips the step, scale holds
        self._hyst_left -= 1
        if self._hyst_left <= 0:
            self._scale = max(self._min_scale, self._scale / 2.0)
            self._hyst_left = self._hysteresis
        self._good_steps = 0

    def _on_good_step(self):
        if not self._dynamic_scale:
            return
        self._good_steps += 1
        if self._good_steps >= self._scale_window:
            self._scale *= 2.0
            self._good_steps = 0
            self._hyst_left = self._hysteresis

    def eval_batch(self, batch):
        L = self.cfg.num_layers
        with self.mesh:
            ids, labels, mask = self._batch_arrays(batch)
            x = self._embed_fwd(self.nl_params, ids)
            for i, bits in self._stream_params(range(L)):
                x = self._layer_fwd(bits, x, mask, None,
                                    jnp.int32(self.applied_steps),
                                    self._qbits(batch, i))
            return self._top_loss(self.nl_params, x, labels)

    # ------------------------------------------------------------------
    def save_checkpoint(self, path: str) -> Dict[str, Any]:
        """Copy chunk files + return the small HBM-resident state for the
        engine's regular checkpoint machinery. A shapes manifest makes the
        chunks self-describing (utils/zero_to_fp32.py reconstructs the fp32
        tree offline with no engine)."""
        import json as _json
        self.store.save_to(os.path.join(path, "infinity_chunks"))
        leaf_names = ["/".join(str(getattr(k, "key", k)) for k in p)
                      for p, _ in jax.tree_util.tree_flatten_with_path(
                          jax.tree.unflatten(self._treedef,
                                             list(range(len(self._sizes)))))[0]]
        with open(os.path.join(path, "infinity_shapes.json"), "w") as f:
            _json.dump({"chunk": self.chunk,
                        "num_layers": self.cfg.num_layers,
                        "leaf_names": leaf_names,
                        "leaf_shapes": [list(s) for s in self._shapes]}, f)
        out = {"nl_params": jax.device_get(self.nl_params),
               "nl_opt": jax.device_get(self.nl_opt),
               "applied_steps": self.applied_steps}
        if self.fp16:
            out["loss_scale"] = [self._scale, self._good_steps,
                                 self._hyst_left]
        return out

    def load_checkpoint(self, path: str, small_state: Dict[str, Any]):
        import json as _json
        saved_chunk = None
        manifest = os.path.join(path, "infinity_shapes.json")
        if os.path.exists(manifest):
            with open(manifest) as f:
                meta = _json.load(f)
            saved_chunk = meta.get("chunk")
            if meta.get("num_layers") != self.cfg.num_layers:
                raise ValueError(
                    f"checkpoint has {meta.get('num_layers')} layers, model "
                    f"has {self.cfg.num_layers}")
            # re-chunking only ever touches the zero-pad region: both the
            # saved and the current chunk are >= the real layer numel
        self.store.load_from(os.path.join(path, "infinity_chunks"),
                             saved_chunk=saved_chunk)
        self._param_cache.clear()
        self._hbm_cache.clear()
        self.nl_params = jax.tree.map(jnp.asarray, small_state["nl_params"])
        self.nl_opt = jax.tree.map(jnp.asarray, small_state["nl_opt"])
        if self._pinned:
            self.nl_opt = jax.device_put(self.nl_opt, self._repl_host_sh)
        elif self.mesh.size > 1:
            self.nl_params = jax.device_put(self.nl_params, self._repl_dev_sh)
            self.nl_opt = jax.device_put(self.nl_opt, self._repl_dev_sh)
        self.applied_steps = int(small_state["applied_steps"])
        if self.fp16 and "loss_scale" in small_state:
            s, g, h = [float(x) for x in np.asarray(
                small_state["loss_scale"]).reshape(-1)]
            self._scale, self._good_steps, self._hyst_left = s, int(g), int(h)

    def close(self):
        self._drain_write()
        self._rpool.shutdown(wait=True)
        self._wpool.shutdown(wait=True)
        self.store.close()
