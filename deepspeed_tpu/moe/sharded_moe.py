"""Sharded mixture-of-experts: routing, then one of two dispatches.

Reference: ``deepspeed/moe/sharded_moe.py`` — ``top1gating:176`` /
``top2gating:274`` (capacity, load-balance aux loss, random token priority),
einsum dispatch/combine, ``_AllToAll:87`` applied at ``:506,520``;
``deepspeed/moe/layer.py:15`` (MoE wrapper), ``experts.py``.

TPU-native: the reference wraps torch.distributed all_to_all in an autograd
Function around per-rank expert stacks. Here experts are a stacked leading
`experts` dim sharded over the `expert` mesh axis.

Routing is one scoring in float32 and one ``lax.top_k`` whatever k is
(``route``). The scoring is the model's (``cfg.moe_scoring``): a softmax over
all experts (Mixtral, OLMoE), or a sigmoid per expert whose CHOICE adds a
stored correction bias and whose WEIGHTS are the plain scores, times
``cfg.routed_scaling_factor`` (``nemotron_h``). The kept weights are divided
by their sum where the model says so (``cfg.norm_topk_prob``: Mixtral,
``nemotron_h`` and ``qwen3_next`` do, OLMoE does not). The experts are gated
(SwiGLU) when the stack has a ``w_gate``, plain otherwise, with the
activation the model names (``cfg.activation``: ``relu2`` is ``relu(x)^2``),
and a ``shared_w_in`` / ``shared_w_out`` pair in the parameters is an expert
of the same form (gated when there is a ``shared_w_gate``) that EVERY token
passes, added unweighted or, with a ``shared_gate`` vector, scaled per token
by ``sigmoid(w_s . x)`` (named scope ``moe/shared``).

**The chip's share.** The router's width (``wg``'s columns) and the experts
held (the stacks' leading dim) are two numbers. Where they differ the layer
holds experts ``cfg.moe_held_first .. + held - 1`` of a deployment that
splits each layer's experts over chips: it scores ALL the experts, takes the
top-k of all, normalises the weights over all the k chosen, and computes the
part of the result that the chosen experts IT HOLDS give (plus the shared
expert, which every chip computes alike). The other chips' parts and the
exchange that would add them are not here: on one chip the layer returns its
partial result. Both dispatches do this by numbering the held experts 0 ..
held - 1 and giving an assignment elsewhere no expert (a zero one-hot row /
a row past the last group); the load row counts the held experts and, last,
ALL the assignments asked for.

Dispatches, chosen by what the model IS (``cfg.drop_tokens``) and by the
call's shapes, not by an option of their own:

- **capacity** (``drop_tokens=True``, training's default): dispatch/combine
  are einsums with one-hot ``[T, E, C]`` masks (same math as the reference's
  fairscale lineage), and GSPMD inserts the all-to-alls when the
  token-sharded input meets the expert-sharded stack — over ICI, with static
  capacity shapes (drop/pad exactly like the reference's capacity
  semantics).
- **dropless** (``drop_tokens=False``: Mixtral, OLMoE as published), a call
  on one device that ``_sorts``, at inference and in training alike (the
  grouped matmul has a backward: ``ops/grouped_matmul.py``, d rows through
  the same kernel, d experts through ``moe_gmm_dw``; beside that kernel the
  two permutations are kernels too, which move the rows of the experts
  HELD alone and are each other's gradient, ``_dispatch_rows`` /
  ``_combine_rows`` over ``ops/moe_rows.py``): the T·k (token, expert) pairs
  are sorted by expert, each projection is ONE grouped matmul over the T·k
  rows (``_grouped_matmul``: static shapes — T·k rows and E group sizes),
  and the rows are weighted and summed back per token. Work and memory are
  proportional to the T·k assignments, and only the experts that got a row
  are read; nothing has both a token and an expert-times-capacity extent
  (capacity = T computes E·T rows for T·k assignments: 8 x too many at 64
  experts top-8, ``[E, T, H]`` rows written and read on either side of the
  experts, and a ``[T, E, T]`` mask contracted twice besides).
  Which calls: the two forms are PRICED from the call's shapes
  (``_one_hot_is_cheaper``: tokens, experts held, assignments expected on
  them, a token's row and an expert's matrices in bytes) by what each was
  measured to cost alone on the chip, and the cheaper one is taken. Many
  narrow experts sort at every length (Nemotron's, Qwen3-Next's and
  Trinity's steps and prompts, OLMoE's prompts), and so does a step whose
  rows are expected to reach few of the experts held (a chip's share of a
  wide router).
- **dropless**, a call whose rows reach every expert of a FEW experts of
  large matrices (Mixtral's 8: T rows per expert hide under the expert's
  weight bytes to 256 tokens, and the ``[E, T, H]`` rows are nothing beside
  them), a call inside the rule's tie (OLMoE's 32-slot step), and EVERY
  dropless call under a mesh: the capacity dispatch with capacity = T, which
  drops nothing, needs no sort and no kernel, reads all E experts, and
  carries the sharding constraints of the capacity path.

``expert_load_tap`` is how a serving step reads what routing did.
"""

import contextlib
import functools
import math
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _constrain(x, spec: P):
    """Sharding constraint that degrades to a no-op when no mesh is in
    context (e.g. model called directly outside the engine)."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (RuntimeError, ValueError):
        return x


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


# --------------------------------------------------------------------------
# the routing tap: expert load out of a traced program
# --------------------------------------------------------------------------

class _LoadTap:
    """What one ``expert_load_tap`` block collected, at TRACE time: one
    int32 ``[E + 1]`` row per MoE layer called inside it — assignments KEPT
    per expert (E the experts HELD), then the assignments ASKED for (counted
    tokens x k), so ``1 - kept / asked`` is the dropped share where every
    expert is held, and ``kept / asked`` the share of the assignments that
    land on this chip where it holds a part."""

    def __init__(self):
        self.rows: List[jnp.ndarray] = []
        # the dispatch form of the ``moe_ffn`` calls traced inside the
        # block: ``capacity`` | ``one-hot`` | ``sorted/moe_gmm`` |
        # ``sorted/ragged_dot`` (None if no MoE layer ran)
        self.form: Optional[str] = None

    def stacked(self) -> Optional[jnp.ndarray]:
        """[layers, E + 1] in call order; None if no MoE layer ran."""
        return jnp.stack(self.rows) if self.rows else None


class _TraceState(threading.local):
    """Trace-time state, one per THREAD: the serving engine's watchdog
    traces a step on a thread of its own and may abandon it, and two engines
    of one process (router replicas) may trace at once."""

    def __init__(self):
        self.taps: List[_LoadTap] = []        # innermost last
        self.counted: List[jnp.ndarray] = []  # innermost last: which tokens count


_STATE = _TraceState()


@contextlib.contextmanager
def expert_load_tap():
    """Collect the expert load of every ``moe_ffn`` traced inside the block
    (a serving step reads what routing did without a change to any
    signature between it and ``moe_ffn``). No tap open: nothing is computed.

    The rows are tracers of the trace that is current where ``moe_ffn``
    runs, so the body of a layer scan opens ``layer_load_tap`` around its
    layer, returns ``tap.stacked()`` among the body's outputs, and the
    caller hands the scan's stacked rows on with ``record_expert_load``."""
    tap = _LoadTap()
    _STATE.taps.append(tap)
    try:
        yield tap
    finally:
        _STATE.taps.remove(tap)


@contextlib.contextmanager
def layer_load_tap():
    """``expert_load_tap`` for the body of a layer scan: yields None, and
    costs nothing, when nobody outside is listening."""
    if not _STATE.taps:
        yield None
        return
    with expert_load_tap() as tap:
        yield tap


def expert_load_wanted() -> bool:
    return bool(_STATE.taps)


def record_expert_load(rows) -> None:
    """Hand rows ([..., E + 1]: a layer scan's stacked output, or None) to
    the innermost open tap."""
    if _STATE.taps and rows is not None:
        rows = rows.reshape(-1, rows.shape[-1])
        _STATE.taps[-1].rows.extend(rows[i] for i in range(rows.shape[0]))


@contextlib.contextmanager
def counted_tokens(mask):
    """The tokens whose routing a tap counts: ``mask`` is bool with one
    entry per token of the ``x`` that ``moe_ffn`` will see (any shape of
    that size). A serving step's inactive slots and a prompt bucket's pad
    tokens compute in lockstep and must not count."""
    _STATE.counted.append(mask)
    try:
        yield
    finally:
        _STATE.counted.pop()


def _tap_load(kept, k: int) -> None:
    """One row for the innermost tap from ``kept`` [T, E]: the assignments
    of each token that got a place at each expert."""
    T = kept.shape[0]
    kept = kept.astype(jnp.int32)
    asked = jnp.int32(T * k)
    if _STATE.counted:
        mask = _STATE.counted[-1].reshape(T).astype(jnp.int32)
        kept, asked = kept * mask[:, None], jnp.sum(mask) * k
    _STATE.taps[-1].rows.append(
        jnp.concatenate([jnp.sum(kept, axis=0), asked[None]]))


# --------------------------------------------------------------------------
# routing
# --------------------------------------------------------------------------

def route(logits, k: int, *, renormalize: bool = True, rng=None,
          noise_policy: Optional[str] = None, train: bool = True,
          scoring: str = "softmax", bias=None, scale: float = 1.0):
    """Scores over ALL experts in float32, one ``lax.top_k``.

    logits: [T, E] -> (weights [T, k] f32, experts [T, k] int32, gates
    [T, E] f32). ``renormalize`` divides the k kept weights by their sum
    (Mixtral); without it they are the softmax's own values and sum to less
    than 1 (OLMoE, ``norm_topk_prob: false``). Ties go to the lower expert
    index, as the argmax chain this replaces broke them.

    ``scoring="sigmoid"`` (``nemotron_h``): the scores are a sigmoid per
    expert, the k experts are the top-k of ``scores + bias`` (``bias`` [E],
    the stored ``e_score_correction_bias``), the weights are the SCORES at
    those experts (the bias chooses, it does not weigh). ``scale`` multiplies
    the weights last."""
    if noise_policy == "Jitter" and train and rng is not None:
        logits = logits * jax.random.uniform(rng, logits.shape, logits.dtype,
                                             1.0 - 1e-2, 1.0 + 1e-2)
    elif noise_policy == "RSample" and train and rng is not None:
        logits = logits + jax.random.gumbel(rng, logits.shape, logits.dtype)
    if scoring == "sigmoid":
        gates = jax.nn.sigmoid(logits.astype(jnp.float32))           # [T, E]
        chosen = gates if bias is None else gates + bias.astype(jnp.float32)
        experts = lax.top_k(chosen, k)[1]
        weights = jnp.take_along_axis(gates, experts, axis=-1)
    else:
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]
        weights, experts = lax.top_k(gates, k)
    if renormalize and k > 1:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32), gates


def _switch_aux(gates, first_choice):
    """Switch load-balance loss from the FIRST choice only (reference: top2
    uses mask1): E * sum_e mean(gates_e) * mean(assignment_e)."""
    E = gates.shape[-1]
    ce = jnp.mean(jax.nn.one_hot(first_choice, E, dtype=jnp.float32), axis=0)
    return jnp.sum(jnp.mean(gates, axis=0) * ce) * E, ce


def top_k_gating(logits, k: int, capacity: int, *, rng=None,
                 noise_policy: Optional[str] = None, train: bool = True,
                 renormalize: bool = True, scoring: str = "softmax",
                 bias=None, scale: float = 1.0, held=None):
    """Compute dispatch/combine tensors with capacity limits, for any k.

    logits: [T, E]. Returns (combine [T,E,C] f32, dispatch [T,E,C] bool,
    aux_loss scalar, metrics dict). Same semantics as the reference's
    top1gating/top2gating: per-expert position by cumsum order (token
    priority = sequence order, earlier choices before later ones), tokens
    over capacity dropped; aux loss = E * mean(gates_e) * mean(assignment_e)
    summed over experts (switch loss). With ``renormalize`` the weights that
    SURVIVE the capacity are divided by their sum (reference: top2 denom).

    ``held`` = (first, count): the masks cover the ``count`` experts from
    ``first`` only (``[T, count, C]``); an assignment to an expert
    elsewhere takes no place here and is never dropped here, so its weight
    stays in the sum the kept weights are divided by.
    """
    T, E = logits.shape
    # the division happens after the drop, over the survivors
    weights, experts, gates = route(logits, k, renormalize=False, rng=rng,
                                    noise_policy=noise_policy, train=train,
                                    scoring=scoring, bias=bias)
    aux, ce = _switch_aux(gates, experts[:, 0])
    metrics = {"expert_load": ce}

    # position of each (choice, token) within its expert: ONE cumsum over
    # the choice-major list, so a later choice is offset by the FULL
    # pre-drop count of the earlier ones (reference top2gating offsets
    # locations2 by sum(mask1)): choice-2 tokens must not reuse slots freed
    # by dropped choice-1 tokens, or drop statistics diverge.
    if held is None:
        onehot = jax.nn.one_hot(experts.T, E, dtype=jnp.float32)  # [k, T, E]
    else:            # an index outside 0 .. count - 1 is a row of zeros
        first, E = held
        onehot = jax.nn.one_hot(experts.T - first, E, dtype=jnp.float32)
    flat = onehot.reshape(k * T, E)
    pos = jnp.sum((jnp.cumsum(flat, axis=0) - 1.0) * flat, axis=-1)
    pos = pos.astype(jnp.int32).reshape(k, T)
    keep = pos < capacity
    gate_val = jnp.where(keep, weights.T, 0.0)                   # [k, T]
    pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity,
                            dtype=jnp.float32)                   # [k, T, C]
    # one elementwise pass per choice builds the [T, E, C] mask (a token's
    # choices are distinct experts, so each entry gets at most one term)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    for c in range(k):
        combine = combine + (gate_val[c][:, None] * onehot[c]
                             * keep[c][:, None])[..., None] * pos_oh[c][:, None, :]
    if renormalize and k > 1:
        gate_sum = jnp.sum(gate_val, axis=0)
        safe = jnp.where(gate_sum > 0, gate_sum, 1.0)
        combine = combine / safe[:, None, None]
    if scale != 1.0:
        combine = combine * scale

    dispatch = combine > 0
    metrics["dropped_fraction"] = 1.0 - jnp.sum(dispatch) / (T * k)
    # [T, E] assignments that got a slot, whatever their weight (a gate that
    # underflowed to 0 is kept, not dropped)
    metrics["kept"] = jnp.sum(onehot * keep[..., None], axis=0)
    return combine, dispatch, aux, metrics


# --------------------------------------------------------------------------
# expert compute
# --------------------------------------------------------------------------

def _glu_or_gelu(up, gate, act: str = "gelu"):
    """Gated (a ``w_gate`` stack: SwiGLU) or plain, by the model's
    activation: ``relu2`` is ``relu(x)^2``."""
    if gate is not None:
        return jax.nn.silu(gate) * up
    if act == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.relu(up) if act == "relu" else jax.nn.gelu(up)


def _routing(moe_params, cfg) -> dict:
    """The model's scoring as ``route`` / ``top_k_gating`` take it."""
    return {"scoring": getattr(cfg, "moe_scoring", "softmax"),
            "bias": moe_params.get("e_bias"),
            "scale": float(getattr(cfg, "routed_scaling_factor", 1.0))}


class LayerOf:
    """Layer ``index`` (traced) of a stacked ``[L, E, K, N]`` expert array
    that has NOT been sliced. A Pallas call's operand is a whole buffer, so
    a layer scan that hands it ``stack[i]`` first COPIES the layer's experts
    (0.8 GB a stack and layer at OLMoE's widths, three stacks; XLA's own
    ``ragged_dot`` is such a call too, and with it and the copies OLMoE's
    cell serves 12.7 % fewer tokens a second: PERF.md section 6, PR 26).
    The grouped-matmul kernel reads the layer's experts out of the whole
    stack instead; every other consumer calls ``whole()`` and gets the slice
    XLA fuses into it — ``sliced`` where the caller has made it already (a
    decode step, whose program is then the one it was while its rows keep
    the one-hot form)."""
    __slots__ = ("stack", "index", "sliced")

    def __init__(self, stack, index, sliced=None):
        self.stack, self.index, self.sliced = stack, index, sliced

    def whole(self):
        if self.sliced is not None:
            return self.sliced
        return lax.dynamic_index_in_dim(self.stack, self.index, 0,
                                        keepdims=False)


def _whole(w, dtype):
    return (w.whole() if isinstance(w, LayerOf) else w).astype(dtype)


def _grouped_matmul(rows, w, group_sizes, kernel: bool,
                    transposed: bool = False):
    """rows [M, K] sorted by expert, w [E, K, N] (or a ``LayerOf`` such;
    ``transposed``: [E, N, K]), group_sizes [E] (sum <= M) -> [M, N]: row i
    times the matrix of the expert whose group it is in.

    ``kernel`` (a TPU in bf16; ``_sorts`` has already kept a mesh away): the
    Pallas kernel of ``ops/grouped_matmul.py``
    (``%moe_gmm.N`` in a trace), which reads the layer's experts out of the
    whole stack. Elsewhere — the CPU, float32, widths off the kernel's
    tiles — XLA's own ``ragged_dot`` (``%ragged-dot-none.N`` on a TPU, a
    Mosaic call too: fed a layer's slice it costs the copy above).
    Measured, OLMoE's cell on the chip, same call and seeds: 473.9 / 476.1
    tokens/s with the kernel, 415.7 / 414.6 with ``ragged_dot`` alone
    (PERF.md section 6, PR 26)."""
    if not kernel:
        w = _whole(w, rows.dtype)
        return lax.ragged_dot(rows, jnp.swapaxes(w, 1, 2) if transposed else w,
                              group_sizes)
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
    if isinstance(w, LayerOf):
        return grouped_matmul(rows, w.stack, w.index, group_sizes, transposed)
    return grouped_matmul(rows, w[None], 0, group_sizes, transposed)


def _w_in(moe_params):
    """(the up projection's stack, whether its matrices are stored [F, H]):
    ``w_in`` [E, H, F], or ``w_in_t`` [E, F, H], what a width F off the 128
    grid is served from (``ops/grouped_matmul.grouped_matmul``)."""
    if "w_in_t" in moe_params:
        return moe_params["w_in_t"], True
    return moe_params["w_in"], False


def _use_gmm_kernel(moe_params, tokens, k: int, train: bool) -> bool:
    """Whether a sorted call of ``tokens`` [T, H] runs on the Pallas kernels
    (``sorted/moe_gmm``) or on ``ragged_dot`` beside ``jnp.take``. TRAINING
    takes the kernels together or not at all: the grouped matmul's leaves the
    rows past the groups undefined, in its gradient too, and only the row
    kernels (``ops/moe_rows.py``) never read them — so a train step off THEIR
    tiles is ``ragged_dot``'s, each mover with its native gradient."""
    from deepspeed_tpu.ops import moe_rows
    from deepspeed_tpu.ops.grouped_matmul import supported
    w = _w_in(moe_params)[0]
    w = w.stack if isinstance(w, LayerOf) else w
    return (tokens.dtype == jnp.bfloat16 and w.dtype == tokens.dtype
            and supported(*w.shape[-2:]) and supported(*w.shape[-2:][::-1])
            and (not train or moe_rows.supported(*tokens.shape, k))
            and jax.default_backend() == "tpu")


def _one_hot_ffn(moe_params, tokens, logits, cfg, C: int, rng, train,
                  expert_axis, held=None):
    """Dispatch by one-hot [T, E, C] masks; tokens over capacity dropped
    (none at C = T). ``held``: ``top_k_gating``'s."""
    dt = tokens.dtype
    with jax.named_scope("route"):
        combine, dispatch, aux, metrics = top_k_gating(
            logits, cfg.top_k, C, rng=rng, noise_policy=cfg.noisy_gate_policy,
            train=train, renormalize=cfg.norm_topk_prob,
            held=held, **_routing(moe_params, cfg))
        if _STATE.taps:
            _tap_load(metrics["kept"], cfg.top_k)
    # dispatch: [T,E,C] x [T,H] -> [E,C,H]; GSPMD all-to-alls tokens to the
    # expert-sharded dim (reference: _AllToAll.apply at sharded_moe.py:506)
    with jax.named_scope("dispatch"):
        expert_in = jnp.einsum("tec,th->ech", dispatch.astype(dt), tokens)
        expert_in = _constrain(expert_in, P(expert_axis, None, None))
    with jax.named_scope("experts"):
        w_in, transposed = _w_in(moe_params)
        up = jnp.einsum("ech,efh->ecf" if transposed else "ech,ehf->ecf",
                        expert_in, _whole(w_in, dt))
        gate = (jnp.einsum("ech,ehf->ecf", expert_in,
                           _whole(moe_params["w_gate"], dt))
                if "w_gate" in moe_params else None)
        out = jnp.einsum("ecf,efh->ech",
                         _glu_or_gelu(up, gate, cfg.activation),
                         _whole(moe_params["w_out"], dt))
        out = _constrain(out, P(expert_axis, None, None))
    with jax.named_scope("combine"):
        y = jnp.einsum("tec,ech->th", combine.astype(dt), out)
    return y, aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _dispatch_rows(tokens, order, pos, n_live, k: int, readers: int):
    """tokens [T, H] -> the sorted rows [T k, H]: row i is the token of pair
    ``order[i]``, for the ``n_live`` pairs that sort first (the pairs of the
    experts held); the rows behind them are never written
    (``ops/moe_rows.moe_rows_gather``). The rows come back once per READER
    (the same array: the up and the gate projection), so that the gradient
    gets each reader's cotangent apart and adds them over the live rows
    alone — XLA's own sum runs over all T k. The gradient sums, per token,
    the rows of its live pairs: the combine with unit weights; ``pos``
    [T, k] is the inverse of ``order``."""
    from deepspeed_tpu.ops.moe_rows import moe_rows_gather
    return (moe_rows_gather(tokens, order // k, n_live),) * readers


def _dispatch_rows_fwd(tokens, order, pos, n_live, k, readers):
    return (_dispatch_rows(tokens, order, pos, n_live, k, readers),
            (pos, n_live))


def _dispatch_rows_bwd(k, readers, residuals, gs):
    from deepspeed_tpu.ops.moe_rows import moe_rows_combine
    pos, n_live = residuals
    return (moe_rows_combine(gs, pos, jnp.ones(pos.shape, jnp.float32),
                             n_live), None, None, None)


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(rows, weights, order, pos, n_live):
    """The sorted rows [T k, H] weighted and summed back per token, in
    float32, over the token's live pairs: y [T, H]
    (``ops/moe_rows.moe_rows_combine``). Its gradient by the rows is the
    dispatch of d y scaled by each pair's weight, by the weights the dot of
    d y with the pair's row — one call of ``moe_rows_gather``."""
    from deepspeed_tpu.ops.moe_rows import moe_rows_combine
    return moe_rows_combine(rows, pos, weights, n_live)


def _combine_rows_fwd(rows, weights, order, pos, n_live):
    return (_combine_rows(rows, weights, order, pos, n_live),
            (rows, weights, order, pos, n_live))


def _combine_rows_bwd(residuals, g):
    from deepspeed_tpu.ops.moe_rows import moe_rows_gather
    rows, weights, order, pos, n_live = residuals
    k = pos.shape[1]

    def carried(keys, values):
        # `values` to where `keys` (a permutation) sort: XLA sorts 131 072
        # pairs in a third of the time it gathers as many scalars
        return lax.sort((keys, values), num_keys=1)[1]
    d_rows, dots = moe_rows_gather(
        g, order // k, n_live,
        (carried(pos.reshape(-1), weights.reshape(-1)), rows))
    d_weights = jnp.where(pos < n_live, carried(order, dots).reshape(pos.shape),
                          0.0)
    return d_rows, d_weights.astype(weights.dtype), None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _sorted_ffn(moe_params, tokens, logits, cfg, rng, train, held, kernel):
    """Dispatch by sorting the T*k (token, expert) pairs by expert: every
    token reaches all k of its experts, and the work is T*k rows. ``held`` =
    (first, count): pairs whose expert is elsewhere sort behind the last
    group, where the grouped matmul computes nothing, and add nothing.
    ``kernel``: ``_use_gmm_kernel``'s."""
    T, H = tokens.shape
    E, k, dt = logits.shape[-1], cfg.top_k, tokens.dtype
    with jax.named_scope("route"):
        weights, experts, gates = route(
            logits, k, renormalize=cfg.norm_topk_prob, rng=rng,
            noise_policy=cfg.noisy_gate_policy, train=train,
            **_routing(moe_params, cfg))
        aux, _ = _switch_aux(gates, experts[:, 0])
        if held is not None:
            first, E = held
            mine = (experts >= first) & (experts < first + E)
            experts = jnp.where(mine, experts - first, E)
            weights = jnp.where(mine, weights, 0.0)
    with jax.named_scope("dispatch"):
        flat = experts.reshape(T * k)
        onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)         # [T*k, E]
        group_sizes = jnp.sum(onehot, axis=0)                     # [E]
        if _STATE.taps:
            _tap_load(jnp.sum(onehot.reshape(T, k, E), axis=1), k)
        with jax.named_scope("sort"):
            order = jnp.argsort(flat)            # stable: token order kept
        # training on the kernel path moves the live rows alone, by two
        # kernels that are each other's gradient (`ops/moe_rows.py`);
        # inference keeps the program it had
        if train and kernel:
            n_live = jnp.sum(group_sizes)
            pos = jnp.argsort(order).reshape(T, k)
            rows_in = _dispatch_rows(tokens, order, pos, n_live, k,
                                     2 if "w_gate" in moe_params else 1)
        else:
            rows_in = (jnp.take(tokens, order // k, axis=0),)     # [T*k, H]
    with jax.named_scope("experts"):
        w_in, transposed = _w_in(moe_params)
        up = _grouped_matmul(rows_in[0], w_in, group_sizes, kernel, transposed)
        gate = (_grouped_matmul(rows_in[-1], moe_params["w_gate"],
                                group_sizes, kernel)
                if "w_gate" in moe_params else None)
        rows_out = _grouped_matmul(_glu_or_gelu(up, gate, cfg.activation),
                                   moe_params["w_out"], group_sizes, kernel)
    with jax.named_scope("combine"):
        # back to (token, choice) order, then the weighted sum over a
        # token's k rows in float32
        if train and kernel:
            return _combine_rows(rows_out, weights, order, pos, n_live), aux
        per_choice = jnp.take(rows_out, jnp.argsort(order), axis=0)
        if held is not None:         # rows past the groups are undefined
            per_choice = jnp.where(mine.reshape(T * k, 1), per_choice, 0)
        y = jnp.sum(per_choice.reshape(T, k, H).astype(jnp.float32)
                    * weights[..., None], axis=1).astype(dt)
    return y, aux


def _expert_shapes(moe_params):
    """(bytes of one token's row of the model's width H, bytes of ONE
    expert's matrices, two or three of them): the two extents the dispatch
    is priced by."""
    mats = [w.stack if isinstance(w, LayerOf) else w
            for w in map(moe_params.get, ("w_in", "w_in_t", "w_gate", "w_out"))
            if w is not None]
    w_out = mats[-1]                                      # [.., F, H]
    return (w_out.shape[-1] * w_out.dtype.itemsize,
            sum(math.prod(w.shape[-2:]) * w.dtype.itemsize for w in mats))


def _one_hot_is_cheaper(T: int, E: int, k: float, row_bytes: int,
                        expert_bytes: int) -> bool:
    """Which dropless dispatch a call of T tokens takes, from its shapes: E
    the experts held, k the assignments a token is expected to have on them
    (``top_k`` where all are held, ``top_k x held / router width`` on a
    share), ``row_bytes`` one token's row of H, ``expert_bytes`` one expert's
    matrices. Both forms are priced in one unit, a weight-bound visit of one
    expert, by what each was measured to cost alone on the chip (PERF.md
    section 6, PR 45 and PR 46).

    The one-hot masks with capacity = T give EVERY expert all T rows
    (``ops/grouped_matmul.one_hot_cost``): all E experts' matrices are read
    whatever the router touched, or E x T rows multiplied where that takes
    longer; the ``[E, T, H]`` rows into and out of the experts are written
    and read, E / k times the rows anyone asked for; and the ``[T, E, T]``
    masks are contracted over T to make and to consume them. Nothing to
    sort, no kernel. The sorted dispatch multiplies only the T*k assigned
    rows and reads only the experts that got one: a row tile that straddles
    experts is visited once per expert, an expert with no row never — ``row
    tiles + experts touched - 1`` visits in expectation
    (``ops/grouped_matmul.visit_cost``) —, and it pays a layer's sort,
    gathers and kernel launches besides, a time that does not follow the
    shapes: what of it exceeds the one-hot form's own fixed time is
    ``SORTED_FIXED_BYTES``, over an expert's bytes in visits.

    Few experts of large matrices hide T rows each under their bytes, and
    their ``[E, T, H]`` rows are a thousandth of them: Mixtral's cell (8
    experts: 8.0 - 8.9 against 8.0 - 9.6 at every T to 256) keeps the masks,
    and its set-up does not pay for three Mosaic kernels per program. Many
    narrow experts do not: at 128 slots x 128 experts x 2688 the four passes
    and the einsums are 27 visits on top of 128, against 133 sorted
    (Nemotron's step: 4.64 ms against 3.59 measured), and the terms grow
    with T and T squared while the sorted side's barely move (OLMoE, 64
    experts: 112 against 77 at a 256-token prompt). A decode step whose
    slots put about one assignment on an expert (64 slots x top-4 over a
    256-wide router, 32 held: 32 rows) touches 20 of 32, and sorting reads a
    third fewer bytes (PR 45). Inside ``TIE_BYTES`` the two are one and the
    call keeps the masks, the program it had: OLMoE's 32-slot step, 63.9 of
    64 experts touched, 67.0 against 63.9 + 1.0, the tie 2.6."""
    from deepspeed_tpu.ops.grouped_matmul import (SORTED_FIXED_BYTES,
                                                  TIE_BYTES, one_hot_cost,
                                                  row_tile, visit_cost)
    rows = math.ceil(T * k)
    return (one_hot_cost(T, E, row_bytes, expert_bytes)
            <= visit_cost(rows, E, row_tile(rows, E))
            + (SORTED_FIXED_BYTES + TIE_BYTES) / expert_bytes)


def _sorts(T: int, E: int, k: float, row_bytes: int,
           expert_bytes: int) -> bool:
    """Whether a dropless call of T tokens sorts. Only on ONE device, and
    only past ``_one_hot_is_cheaper`` — the same price at inference and in
    training, whose backward is each form's forward over again: the sorted
    dispatch sets no sharding constraint and has never been compiled with
    the experts sharded, and the one-hot einsums are what GSPMD places the
    all-to-alls around (``_constrain(..., P(expert_axis))``), so under a
    mesh a dropless call keeps them, as before PR 26."""
    from deepspeed_tpu.parallel.context import kernel_mesh
    return (kernel_mesh()[0] is None
            and not _one_hot_is_cheaper(T, E, k, row_bytes, expert_bytes))


def moe_ffn(moe_params, x, cfg, *, rng=None, train: bool = True,
            expert_axis: str = "expert"):
    """MoE feed-forward over tokens.

    x: [B, S, H]; moe_params: {"wg": [H, E], "w_in": [E, H, F] (or
    "w_in_t": the same matrices stored [E, F, H], what a width F off the
    128 grid is served from: ``ops/grouped_matmul.grouped_matmul``),
    "w_out": [E, F, H], optional "w_gate": [E, H, F], "e_bias": [E] (the
    sigmoid scoring's correction bias), "shared_w_in" [H, Fs] and
    "shared_w_out" [Fs, H] (an always-on expert; with "shared_w_gate" [H,
    Fs] a gated one, with "shared_gate" [H] scaled per token by
    sigmoid(shared_gate . x))}.

    ``wg`` has one column per expert of the MODEL, the stacks one matrix per
    expert HELD. Where the two differ (``wg`` [H, R], R > E) the stacks hold
    experts ``cfg.moe_held_first .. + E - 1``: the router scores and chooses
    over all R, the weights are normalised over all the k chosen, and y is
    the shared expert plus the sum over the chosen experts that are HELD —
    this chip's part of the layer's result (module docstring).
    Returns (y [B,S,H], aux_loss scalar).
    """
    B, S, H = x.shape
    R = moe_params["wg"].shape[-1]            # experts the router scores
    w = _w_in(moe_params)[0]
    E = (w.stack.shape[1] if isinstance(w, LayerOf) else w.shape[0])
    # None where every expert is held: the program of every such model is
    # the one it was before the share existed
    held = None if E == R else (int(cfg.moe_held_first), E)
    if held and not 0 <= held[0] <= R - E:
        raise ValueError(f"experts {held[0]} .. {held[0] + E - 1} held of "
                         f"the router's {R}")
    T = B * S
    tokens = x.reshape(T, H)
    with jax.named_scope("route"):
        logits = tokens.astype(jnp.float32) @ moe_params["wg"].astype(jnp.float32)
    if cfg.drop_tokens:
        cf = cfg.capacity_factor if train else cfg.eval_capacity_factor
        C = _capacity(T, R, cf, cfg.min_capacity)
        form = "capacity"
        y, aux = _one_hot_ffn(moe_params, tokens, logits, cfg, C, rng, train,
                               expert_axis, held)
    elif _sorts(T, E, cfg.top_k if held is None else cfg.top_k * E / R,
                *_expert_shapes(moe_params)):
        kernel = _use_gmm_kernel(moe_params, tokens, cfg.top_k, train)
        form = "sorted/moe_gmm" if kernel else "sorted/ragged_dot"
        y, aux = _sorted_ffn(moe_params, tokens, logits, cfg, rng, train, held,
                             kernel)
    else:
        # capacity = tokens: the masks drop nothing
        form = "one-hot"
        y, aux = _one_hot_ffn(moe_params, tokens, logits, cfg, T, rng, train,
                               expert_axis, held)
    for tap in _STATE.taps:       # a serving engine reports it (`stats()`)
        tap.form = form
    if "shared_w_in" in moe_params:
        with jax.named_scope("shared"):
            up = tokens @ moe_params["shared_w_in"].astype(tokens.dtype)
            gate = (tokens @ moe_params["shared_w_gate"].astype(tokens.dtype)
                    if "shared_w_gate" in moe_params else None)
            out = _glu_or_gelu(up, gate, cfg.activation) \
                @ moe_params["shared_w_out"].astype(tokens.dtype)
            if "shared_gate" in moe_params:
                on = jax.nn.sigmoid(
                    tokens.astype(jnp.float32)
                    @ moe_params["shared_gate"].astype(jnp.float32))
                out = out * on[:, None].astype(out.dtype)
            y = y + out
    return y.reshape(B, S, H), aux
