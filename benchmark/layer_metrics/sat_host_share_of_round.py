"""host_share_of_round with the slots kept full."""
from benchmark.layer_metrics import host_share_of_round as _base

HEADER = dict(_base.HEADER, moves="serve_tokens_per_s")
read = _base.read
