"""sat_moe_ffn_roofline of the SORTED form alone: the grouped-matmul
kernel's own share of its roofline (``%moe_gmm.N``, in prefills past 256
tokens), which ``sat_moe_ffn_roofline`` mixes with the one-hot einsums of
every decode step."""
from benchmark.layer_metrics import sat_moe_ffn_roofline as _base

HEADER = _base.HEADER


def read(run):
    return _base.read(run, grouped_only=True)
