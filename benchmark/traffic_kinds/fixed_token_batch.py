"""fixed-token-batch: a training job's batches, counted in tokens.

Parameters: ``seq_len``, ``tokens_per_step`` (held while the layout
changes: sequences per step = tokens_per_step / seq_len), ``pool_batches``
(distinct batches of uniform random token ids, cycled in order so the loss
can fall as the pool is learnt and a PR that leaves the arithmetic alone
reproduces the parent's curve)."""
import numpy as np

JOB = "train"


def generate(seed: int, params: dict, ctx: dict) -> dict:
    seq, tokens = int(params["seq_len"]), int(params["tokens_per_step"])
    if tokens % seq:
        raise ValueError(f"tokens_per_step {tokens} not a multiple of seq_len {seq}")
    rng = np.random.default_rng([seed, 0x7261696E])
    pool = [rng.integers(0, ctx["vocab_size"], size=(tokens // seq, seq),
                         dtype=np.int32)
            for _ in range(int(params["pool_batches"]))]
    return {"seq_len": seq, "sequences_per_step": tokens // seq,
            "tokens_per_step": tokens, "pool": pool}
