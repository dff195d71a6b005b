"""decode_step_roofline with the slots kept full."""
from benchmark.layer_metrics import decode_step_roofline as _base

HEADER = dict(_base.HEADER, moves="serve_tokens_per_s")
read = _base.read
