"""Published peaks of the chips the benchmark may run on, keyed by
``jax.devices()[0].device_kind``. A device that is not in the table is an
error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip,
    # 1,600 Gbit/s of chip-to-chip interconnect.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device_kind {device_kind!r} has no entry in benchmark/harness/"
            f"peaks.py (known: {sorted(PEAKS)}); add its published peaks "
            "with their source before measuring on it")
    return PEAKS[device_kind]
