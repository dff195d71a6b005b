"""The Mixtral family: the Mistral block with a sparse-expert feed-forward.

``mistral.py`` already holds both: its ``Reference`` takes the expert path
when the parameter tree carries a router (``wg``), and its operation and byte
counts read ``num_local_experts`` / ``num_experts_per_tok``. The toy widths
are the same: a configuration's expert count is not a width and stays."""
from benchmark.families.mistral import (  # noqa: F401
    TOY, Reference, decode_step_bytes, flash_flops, train_flops_per_token)
