"""Accelerator abstraction.

Reference: ``accelerator/abstract_accelerator.py:7`` (~60-method ABC over device
management, streams, events, memory, RNG, tensor factories) and
``accelerator/real_accelerator.py:34,52`` (global get/set singleton).

TPU-native re-design: XLA owns scheduling, so the stream/event surface of the
reference is intentionally absent — async dispatch plus buffer donation is the
idiomatic equivalent, and the few callers that genuinely need ordering use
``synchronize()``. What remains is the part that is real on TPU: device
enumeration, platform naming, memory stats, RNG seeding, default dtypes, and
the communication-backend name (ICI/DCN via XLA collectives instead of NCCL).
"""

import os
from typing import List, Optional

import numpy as np


_GiB = 1 << 30


def _chip(bf16_flops, ici_bytes_per_sec, hbm_bytes_per_sec, hbm_bytes):
    return {"bf16_flops": bf16_flops, "ici_bytes_per_sec": ici_bytes_per_sec,
            "hbm_bytes_per_sec": hbm_bytes_per_sec, "hbm_bytes": hbm_bytes}


# Published per-chip figures, keyed by a lower-cased substring of
# ``jax.devices()[0].device_kind`` (a v5e reports "TPU v5 lite"). First
# match wins, so the more specific keys come first.
_V5E = _chip(197e12, 2.0e11, 8.2e11, 16 * _GiB)
_CHIPS = {
    "v5 lite": _V5E, "v5e": _V5E, "v5litepod": _V5E,
    "v5p": _chip(459e12, 6.0e11, 2.77e12, 95 * _GiB),
    "v6": _chip(918e12, 4.5e11, 1.6e12, 32 * _GiB),
    "v4": _chip(275e12, 3.0e11, 1.2e12, 32 * _GiB),
    "v3": _chip(123e12, 2.0e11, 9.0e11, 16 * _GiB),
}


class Accelerator:
    """Base accelerator over JAX device APIs; concrete for any JAX platform."""

    def __init__(self, platform: Optional[str] = None):
        import jax
        self._jax = jax
        self._platform = platform or jax.default_backend()

    # --- naming -----------------------------------------------------------
    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._platform
        return f"{self._platform}:{device_index}"

    @property
    def platform(self) -> str:
        return self._platform

    def is_available(self) -> bool:
        try:
            return len(self.devices()) > 0
        except RuntimeError:
            return False

    def communication_backend_name(self) -> str:
        """'xla' — collectives compile onto ICI/DCN; reference returns 'nccl'
        (``accelerator/cuda_accelerator.py``)."""
        return "xla"

    # --- devices ----------------------------------------------------------
    def devices(self) -> List:
        return self._jax.devices(self._platform)

    def local_devices(self) -> List:
        return self._jax.local_devices(backend=self._platform)

    def device_count(self) -> int:
        return len(self.devices())

    def local_device_count(self) -> int:
        return len(self.local_devices())

    def process_index(self) -> int:
        return self._jax.process_index()

    def process_count(self) -> int:
        return self._jax.process_count()

    def current_device(self):
        return self.local_devices()[0]

    def synchronize(self, device=None) -> None:
        """Block until all dispatched work is complete (reference:
        ``torch.cuda.synchronize``)."""
        self._jax.effects_barrier()

    # --- memory -----------------------------------------------------------
    def memory_stats(self, device=None) -> dict:
        from deepspeed_tpu.utils.memory import device_memory_stats
        return device_memory_stats(device or self.current_device())

    def memory_allocated(self, device=None) -> int:
        device = device or self.current_device()
        try:
            return (device.memory_stats() or {}).get("bytes_in_use", 0)
        except Exception:
            return 0

    def max_memory_allocated(self, device=None) -> int:
        device = device or self.current_device()
        try:
            return (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
        except Exception:
            return 0

    def total_memory(self, device=None) -> int:
        device = device or self.current_device()
        try:
            return (device.memory_stats() or {}).get("bytes_limit", 0)
        except Exception:
            return 0

    def hbm_bytes(self, device=None) -> int:
        """Per-device HBM capacity: live ``memory_stats`` where the backend
        reports a limit, else the chip table. Used by bench auto-sizing and
        the autotuner."""
        limit = self.total_memory(device)
        if limit:
            return limit
        return int(self._chip_constant("hbm_bytes", cpu_value=8 * _GiB))

    def available_memory(self, device=None) -> int:
        return max(0, self.total_memory(device) - self.memory_allocated(device))

    def empty_cache(self) -> None:
        """No-op: XLA's BFC allocator manages HBM; live buffers are freed by GC."""

    # --- RNG --------------------------------------------------------------
    def manual_seed(self, seed: int):
        """Return a root PRNG key. JAX threads explicit keys instead of global
        RNG state (reference mutates ``torch.cuda`` RNG)."""
        return self._jax.random.PRNGKey(seed)

    def default_generator(self, seed: int = 0):
        return self._jax.random.PRNGKey(seed)

    # --- dtypes -----------------------------------------------------------
    def preferred_dtype(self):
        import jax.numpy as jnp
        return jnp.bfloat16 if self._platform == "tpu" else jnp.float32

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def supported_dtypes(self):
        import jax.numpy as jnp
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    # --- HLO/interconnect hints ------------------------------------------
    def device_kind(self) -> str:
        devs = self.local_devices()
        return devs[0].device_kind if devs else "unknown"

    def _chip_constant(self, field: str, cpu_value: float) -> float:
        """One published per-chip figure from ``_CHIPS``. A non-CPU device
        kind the table does not know RAISES: a guessed peak would make
        every MFU, roofline and wire-time number derived from it wrong
        while looking plausible."""
        if self._platform == "cpu":
            return cpu_value
        kind = self.device_kind()
        for key, row in _CHIPS.items():
            if key in kind.lower():
                return row[field]
        raise ValueError(
            f"unknown device_kind {kind!r} (platform {self._platform!r}): "
            f"no {field} in deepspeed_tpu.accelerator._CHIPS — add the "
            "chip's published figures there")

    def peak_flops_per_device(self, dtype: str = "bf16") -> float:
        """Published peak bf16 matmul FLOP/s for MFU math; see BASELINE.md."""
        return self._chip_constant("bf16_flops", cpu_value=1e11)

    def interconnect_bytes_per_sec(self) -> float:
        """Aggregate per-chip ICI bandwidth (bytes/sec), used to PRICE
        exposed collective bytes into modeled wire time (telemetry
        ``exposed_comm_ms``). Rough published per-chip aggregates — a
        modeling constant for trend tracking, not a measured number."""
        return self._chip_constant("ici_bytes_per_sec", cpu_value=1e10)

    def hbm_bytes_per_sec(self) -> float:
        """Per-chip HBM bandwidth (bytes/sec). Used with
        ``peak_flops_per_device`` as the roofline balance point when the
        perf doctor classifies a traced bucket compute- vs memory-bound.
        Published chip numbers — a modeling constant, not a measurement."""
        return self._chip_constant("hbm_bytes_per_sec", cpu_value=5e10)

    def pin_memory(self, array):
        """Host staging; JAX host buffers are already DMA-capable — identity."""
        return array

    def on_device(self, array, device=None):
        return self._jax.device_put(array, device or self.current_device())


class TPU_Accelerator(Accelerator):
    def __init__(self):
        super().__init__(platform=None)


class CPU_Accelerator(Accelerator):
    def __init__(self):
        super().__init__(platform="cpu")


_ACCELERATOR: Optional[Accelerator] = None


def get_accelerator() -> Accelerator:
    """Global accelerator singleton (reference:
    ``accelerator/real_accelerator.py:34``). Honors DSTPU_ACCELERATOR=cpu|tpu."""
    global _ACCELERATOR
    if _ACCELERATOR is None:
        forced = os.environ.get("DSTPU_ACCELERATOR", "").lower()
        if forced == "cpu":
            _ACCELERATOR = CPU_Accelerator()
        else:
            _ACCELERATOR = Accelerator()
    return _ACCELERATOR


def set_accelerator(accel: Accelerator) -> None:
    global _ACCELERATOR
    _ACCELERATOR = accel
