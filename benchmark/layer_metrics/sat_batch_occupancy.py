"""batch_occupancy where the slots are kept full: anything under 100 % is
decode capacity the scheduler left empty."""
from benchmark.layer_metrics import batch_occupancy as _base

HEADER = dict(_base.HEADER, moves="serve_tokens_per_s", better="higher")
read = _base.read
