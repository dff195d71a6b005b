"""sat_moe_share_of_device of the SORTED form alone: the grouped matmuls
(``%moe_gmm.N``) that a prompt past 256 tokens runs over its tokens x top-k
sorted rows. Every decode step and every shorter prompt takes the one-hot
form, which this leaves out: read beside ``sat_moe_share_of_device`` it
says how much of the expert layer's time the sorted dispatch carries."""
from benchmark.layer_metrics import sat_moe_share_of_device as _base

HEADER = _base.HEADER


def read(run):
    return _base.read(run, grouped_only=True)
