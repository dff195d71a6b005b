"""ahead_covered_share with the slots kept full."""
from benchmark.layer_metrics import ahead_covered_share as _base

HEADER = dict(_base.HEADER, moves="serve_tokens_per_s")
read = _base.read
