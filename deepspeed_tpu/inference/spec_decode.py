"""Speculative decoding: cheap proposals, one paged verify, JAX accept.

Greedy decode at small batch is latency-bound: every output token pays a
full read of the weights for ONE matmul row per layer. Speculation buys
tokens-per-weight-read: a cheap **proposer** guesses the next K tokens,
the target model scores all K+1 positions (the committed pending token
plus the K guesses) in ONE ``decode_span_paged`` pass, and the accept
rule keeps the longest prefix of guesses the model itself would have
produced — plus the model's own token at the first divergence, so every
verify step nets at least one real token and at most K+1.

**Greedy acceptance is output-preserving by induction**: position 0's
logits depend only on committed state, so its argmax is the token greedy
decoding would emit; a guess is accepted only when it EQUALS that argmax,
which makes position 1's inputs exactly the sequential ones, and so on.
Emitted tokens are always the target model's argmaxes — proposals only
decide how many positions are trustworthy — so the decoded stream is the
K=0 stream token for token (pinned by the latency-frontier parity tests;
the engine enforces temperature 0.0 while speculation is armed — the
stochastic accept/reject rule is future work behind the same hook).

Rejected guesses cost only their already-spent verify FLOPs: the serving
engine rolls the per-slot cursor back (``seq_lens`` simply doesn't
advance past the accepted prefix) and the stale rows are overwritten by
later writes — no block frees, so refcounted/shared blocks are never
disturbed (the CoW fork already ran before any span dispatch).

The default proposer is **self-drafting n-gram lookup** (a.k.a. prompt
lookup): find the most recent earlier occurrence of the context's last n
tokens and propose what followed it — free, model-less, and strong on
agent/chat traffic full of repeated tool names, code identifiers and
copied spans. A learned draft model drops into the same hook
(``ServingConfig.spec_proposer``): any callable
``(context: np.ndarray, k: int) -> array of <= k token ids``.
"""

import numpy as np

import jax.numpy as jnp


class NgramProposer:
    """Self-drafting proposer: match the trailing ``n``-gram of the
    context against its own history (rightmost earlier occurrence wins —
    recency beats frequency on chat transcripts) and propose the tokens
    that followed it. No match proposes nothing; the engine pads with
    zeros, which the verify step simply rejects (a pad can only be
    "accepted" when it coincidentally IS the model's argmax — which is by
    definition the correct token, so padding never perturbs output)."""

    def __init__(self, n: int = 3):
        if n < 1:
            raise ValueError(f"ngram n={n}: need >= 1")
        self.n = int(n)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        ctx = np.asarray(context, np.int64).reshape(-1)
        out = np.zeros((k,), np.int32)
        n = min(self.n, ctx.size - 1)
        if n < 1 or ctx.size <= n:
            return out
        gram = ctx[ctx.size - n:]
        win = np.lib.stride_tricks.sliding_window_view(ctx, n)
        hits = np.flatnonzero((win[:-1] == gram).all(axis=1))
        if hits.size:
            s = int(hits[-1])
            cont = ctx[s + n:s + n + k].astype(np.int32)
            out[:cont.size] = cont
        return out


def greedy_accept_len(next_tokens, proposals):
    """Length of the accepted proposal prefix, pure JAX (runs inside the
    verify program — no host round-trip in the accept/reject decision).

    next_tokens: [..., K+1] the target model's argmax at each verified
    position; proposals: [..., K] the guesses. Accepted = leading run
    where ``next_tokens[i] == proposals[i]`` (guess i was exactly what
    the model emits at position i, so position i+1 was verified against
    sequential-equivalent inputs). Returns [...] ints in [0, K]."""
    k = proposals.shape[-1]
    match = (next_tokens[..., :k] == proposals).astype(jnp.int32)
    return jnp.cumprod(match, axis=-1).sum(axis=-1)
