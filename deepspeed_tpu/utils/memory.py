"""Device/host memory reporting, and arrays read as shapes.

Reference: ``deepspeed/runtime/utils.py:768`` (``see_memory_usage``) — reads the
CUDA caching-allocator stats. The TPU equivalent reads per-device memory stats
from the JAX runtime (``device.memory_stats()``) plus host RSS from /proc.
"""

from typing import Dict, Optional

from deepspeed_tpu.utils.logging import logger


def _host_mem_gb() -> Dict[str, float]:
    try:
        with open("/proc/self/status") as f:
            status = f.read()
        out = {}
        for key, label in (("VmRSS", "rss"), ("VmHWM", "rss_peak")):
            for line in status.splitlines():
                if line.startswith(key + ":"):
                    out[label] = float(line.split()[1]) / 1e6  # kB -> GB
        return out
    except Exception:
        return {}


def device_memory_stats(device=None) -> Dict[str, float]:
    """Bytes in use / limit for one device, in GB. Empty dict on platforms
    without memory_stats (CPU)."""
    import jax
    device = device or jax.devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:
        stats = {}
    out = {}
    if "bytes_in_use" in stats:
        out["device_gb_in_use"] = stats["bytes_in_use"] / 1e9
    if "peak_bytes_in_use" in stats:
        out["device_gb_peak"] = stats["peak_bytes_in_use"] / 1e9
    if "bytes_limit" in stats:
        out["device_gb_limit"] = stats["bytes_limit"] / 1e9
    return out


def abstractify(tree):
    """Concrete arrays -> ShapeDtypeStructs carrying the same shardings, so
    `.lower()` never touches device data."""
    import jax

    def one(x):
        if isinstance(x, jax.ShapeDtypeStruct) or x is None:
            return x
        sharding = getattr(x, "sharding", None)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return jax.tree.map(one, tree)


def see_memory_usage(message: str, force: bool = False, device=None) -> Optional[str]:
    if not force:
        return None
    parts = [f"{k}={v:.2f}" for k, v in device_memory_stats(device).items()]
    parts += [f"host_{k}_gb={v:.2f}" for k, v in _host_mem_gb().items()]
    msg = f"MEM {message} | " + ", ".join(parts)
    logger.info(msg)
    return msg
