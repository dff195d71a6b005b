"""host_bound_idle_share with the slots kept full."""
from benchmark.layer_metrics import host_bound_idle_share as _base

HEADER = dict(_base.HEADER, moves="serve_tokens_per_s")
read = _base.read
