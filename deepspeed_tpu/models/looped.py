"""A looped stack: the layers run ``ut_steps`` times over the SAME weights.

What ``models/transformer.py`` needs beyond one pass, in one place:

- ``walk``: every layer scan of the model file goes through it. One pass
  (every family but the looped one) is the plain ``lax.scan`` it always was;
  ``ut_steps`` passes are a scan over passes around it, the end of each pass
  handed to ``pass_end`` (the final norm, whose output the next pass starts
  from), and the per-layer outputs come back pass-major: what pass ``t``'s
  layer ``i`` returned sits at ``t * L + i``, the index of its K/V plane.
- the exit gate. ``lambda_t = sigmoid(s_t . w + b)`` on each pass's output
  ``s_t``; ``exit_distribution`` turns the lambdas into the probability of
  leaving at each pass (``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the
  last pass takes the remainder). Every pass always runs: a token that left
  early would write no K/V for the passes it skipped, and what later tokens
  read there is a cache policy the published config does not give. So the
  distribution leaves a program as a COUNTER, through ``exit_tap`` — the
  ``moe.sharded_moe.expert_load_tap`` idea: no signature between a serving
  step and the walk changes, and with no tap open nothing is computed.
"""

import contextlib
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax


class LoopedModelUnsupported(NotImplementedError):
    """What a looped model (``ut_steps`` > 1) cannot be run with, refused
    where it is asked for: nothing may run it over a cache sized for one
    pass, or walk its stack once."""

    def __init__(self, what: str):
        super().__init__(
            f"{what} is not supported on a looped model (ut_steps > 1): its "
            "stack runs several times over shared weights and keeps a K/V "
            "plane per (pass, layer)")
        self.what = what


def check(cfg) -> None:
    """Refuse, when a model is made, the combinations nothing computes."""
    if cfg.ut_steps < 1:
        raise ValueError(f"ut_steps={cfg.ut_steps}: at least one pass")
    if cfg.sandwich_norm and (cfg.norm_style == "post" or cfg.parallel_block):
        raise ValueError("sandwich_norm is a norm after each sublayer of a "
                         "PRE-norm block with two residuals")
    if cfg.ut_steps == 1 and not cfg.exit_gate:
        return
    if not cfg.final_norm:
        raise ValueError("a looped stack / an exit gate reads the final "
                         "norm at the end of every pass: final_norm=False")
    for armed, what in ((cfg.block_pattern, "a hybrid block_pattern"),
                        (not cfg.scan_layers, "scan_layers=False"),
                        (cfg.random_ltd, "random-LTD"),
                        (cfg.progressive_layer_drop,
                         "progressive layer drop")):
        if armed and cfg.ut_steps > 1:
            raise LoopedModelUnsupported(what)


# --------------------------------------------------------------------------
# the walk
# --------------------------------------------------------------------------

def plane(i, t, num_layers: int):
    """The K/V plane of pass ``t``'s layer ``i`` (``t`` None: the one pass
    of an unlooped model, whose plane is its layer)."""
    return i if t is None else t * num_layers + i


def walk(body, carry, xs, ut_steps: int, pass_end=None):
    """Scan ``body(carry, x, t) -> (carry, ys)`` over ``xs`` (an int: over
    the indices of that many layers) once per pass.

    ``t`` is the pass (a traced scalar), or None when ``ut_steps`` is 1: the
    scan is then exactly ``lax.scan(body, carry, xs)`` under the scope
    ``layers``, op for op what every unlooped family lowered before there
    was a looped one. Otherwise ``pass_end(carry) -> (carry, lambda)`` runs
    after each pass (``lambda``: the exit gate's value on the pass's output,
    or None when no tap listens; handed to the tap here), the layer scans
    sit under ``passes/layers``, and ``ys`` comes back with its pass and
    layer dims merged, pass-major."""
    def layers():           # xs, or the indices of that many layers
        return jnp.arange(xs) if isinstance(xs, int) else xs

    if ut_steps == 1:
        with jax.named_scope("layers"):
            return lax.scan(lambda c, x: body(c, x, None), carry, layers())

    def one_pass(c, t):
        with jax.named_scope("layers"):
            c, ys = lax.scan(lambda c, x: body(c, x, t), c, layers())
        c, lam = pass_end(c)
        return c, (ys, lam)

    with jax.named_scope("passes"):
        carry, (ys, lambdas) = lax.scan(one_pass, carry,
                                        jnp.arange(ut_steps))
    if lambdas is not None:
        record(lambdas)
    return carry, jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), ys)


# --------------------------------------------------------------------------
# the exit gate, and the tap that carries it out of a traced program
# --------------------------------------------------------------------------

def exit_distribution(lambdas):
    """[T, ...] gate values in (0, 1), pass-major -> [T, ...] probability of
    leaving at each pass: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and
    the last pass takes what is left (its own lambda is not read)."""
    stay = jnp.cumprod(1.0 - lambdas[:-1], axis=0)     # after passes 0..T-2
    before = jnp.concatenate([jnp.ones_like(lambdas[:1]), stay], axis=0)
    return jnp.concatenate([lambdas[:-1] * before[:-1], before[-1:]], axis=0)


class _ExitTap:
    """What one ``exit_tap`` block collected, at TRACE time: the gate values
    [T, ...] of the walk traced inside it and the mask of the tokens that
    count (None: all of them)."""

    def __init__(self):
        self.lambdas: Optional[jnp.ndarray] = None
        self.counted: Optional[jnp.ndarray] = None

    def summed(self) -> Optional[jnp.ndarray]:
        """float32 [T + 1]: per pass the sum over the counted tokens of the
        probability of leaving there, then the number of counted tokens.
        None if no gated pass was walked."""
        if self.lambdas is None:
            return None
        p = exit_distribution(self.lambdas)
        p = p.reshape(p.shape[0], -1)
        w = (jnp.ones(p.shape[1:], jnp.float32) if self.counted is None
             else self.counted.reshape(-1).astype(jnp.float32))
        return jnp.concatenate([p @ w, jnp.sum(w)[None]])


class _TraceState(threading.local):
    """Trace-time state, one per THREAD (``sharded_moe._TraceState`` says
    why)."""

    def __init__(self):
        self.taps: List[_ExitTap] = []            # innermost last
        self.counted: List[jnp.ndarray] = []      # innermost last


_STATE = _TraceState()


@contextlib.contextmanager
def exit_tap():
    """Collect the exit gate of every looped walk traced inside the block.
    No tap open: no gate is computed."""
    tap = _ExitTap()
    _STATE.taps.append(tap)
    try:
        yield tap
    finally:
        _STATE.taps.remove(tap)


@contextlib.contextmanager
def counted_tokens(mask):
    """The tokens whose exit distribution a tap sums: ``mask`` is bool with
    one entry per token of the stream the walk carries. A decode step's
    inactive slots compute in lockstep and do not count; of a prefill only
    the last real position, whose logits are sampled, does."""
    _STATE.counted.append(mask)
    try:
        yield
    finally:
        _STATE.counted.pop()


def gate(s, params):
    """``lambda = sigmoid(s . w + b)`` of a pass's output ``s`` [..., H], in
    float32 [...]; None when no tap listens or the tree has no gate."""
    if not _STATE.taps or "exit_gate_w" not in params:
        return None
    with jax.named_scope("exit_gate"):
        z = (s.astype(jnp.float32)
             @ params["exit_gate_w"].astype(jnp.float32))[..., 0]
        return jax.nn.sigmoid(z + params["exit_gate_b"].astype(jnp.float32))


def record(lambdas) -> None:
    """Hand a walk's gate values [T, ...] (the pass scan's stacked output)
    to the innermost open tap, with the mask that is current."""
    tap = _STATE.taps[-1]
    tap.lambdas = lambdas
    tap.counted = _STATE.counted[-1] if _STATE.counted else None
