"""Share of the train step's device time spent in the banded attention
kernels of the sliding-window layers: ``flash_fwd_band`` (and its remat
replay), ``flash_bwd_band_dq``, ``flash_bwd_band_dkv`` (``moe_share_of_step``'s
reading, other kernels). The full layers' causal kernels are not in it."""
from benchmark.layer_metrics import moe_share_of_step as _base

HEADER = dict(_base.HEADER,
              layer="window attention (models/hybrid.py, ops/flash_attention.py)")
KERNELS = ("flash_fwd_band", "flash_bwd_band_dq", "flash_bwd_band_dkv")


def read(run):
    return _base.read(run, only=KERNELS)
