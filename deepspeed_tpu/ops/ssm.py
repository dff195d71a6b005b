"""The Mamba-2 recurrence: a chunked scan over a prompt and a one-step update
for decode, each a Pallas TPU kernel with the plain ``jax.numpy`` form beside
it (the CPU runs that one; the tests run both and hold them to a sequential
scan).

Per head ``h`` (``P`` = head dim, ``N`` = state size), with ``a_t = dt_t A_h``
(``A_h < 0``, ``dt_t >= 0``):

    S_t = exp(a_t) S_{t-1} + dt_t x_t (x) B_t          S in R^{P x N}
    y_t = S_t C_t

``B`` and ``C`` are shared by the heads of a group. ``D x`` and everything
around the recurrence (projections, convolution, gate, norm) are the
caller's (``models/mamba.py``). A position with ``dt = 0`` leaves the state
exactly as it is (``exp(0) S + 0``): that is how a prompt bucket's pad
positions and a decode step's inactive slots are kept out of the state.

**The scan** (``ssm_scan``, kernel ``%ssm_scan``): the sequence is cut into
chunks of ``Q`` positions. Inside a chunk the outputs are matrix products —
``(C B^T * L) x`` with ``L[t, s] = exp(sum a_{s+1..t}) dt_s`` for ``s <= t``
— and the state entering the chunk adds ``exp(sum a_{..t}) C_t S_in``; the
state is carried from chunk to chunk in float32. The decay factors are
elementwise work on ``[heads, Q]`` values and are computed by XLA outside the
kernel (an ``exp`` of a difference of cumulative sums taken in float32: the
differences are formed BEFORE any rounding); the kernel does the four matrix
products of a (head, chunk) and the carry.

**The step** (``ssm_step``, kernel ``%ssm_step``): one position for every
slot of a serving batch over the state pool ``[layers, slots, heads, P, N]``
float32, read and written IN PLACE (``input_output_aliases``; the layer is a
coordinate of the block index, so no layer's slice is ever copied). A step is
bound by the state's bytes: read once, written once.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# float32 state a grid step of either kernel holds: the step kernel reads
# that much and writes as much, each double-buffered (four buffers of it in
# VMEM); the scan keeps it three times (entering, leaving, carried) beside a
# chunk's operands. 512 KiB is 16 heads of 64 x 128 (the first shape these
# kernels ran on the chip) and 4 heads of 128 x 256.
STEP_STATE_BYTES = 512 * 1024
SCAN_STATE_BYTES = 1024 * 1024


def block_heads(H: int, per_group: int, P: int, N: int, budget: int) -> int:
    """Heads a grid step holds: the most whose float32 state [heads, P, N]
    fits ``budget``, as whole groups (a multiple of ``per_group`` that
    divides ``H``) or, where one group's heads are over it, as a divisor of
    a group — so a block never straddles a group's edge. At least 1."""
    fit = max(1, budget // (4 * P * N))
    unit, whole = (per_group, H) if fit >= per_group else (1, per_group)
    return max(h for h in range(unit, whole + 1, unit)
               if whole % h == 0 and h <= max(fit, unit))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# decay factors of a chunked sequence (XLA, float32)
# --------------------------------------------------------------------------

def chunk_decays(dt, A, Q: int):
    """dt [T, H] float32 (>= 0; 0 at positions that must not move the
    state), A [H] (< 0), T a multiple of Q ->

    - ``L``     [H, T/Q, Q, Q]: ``exp(cum_t - cum_s) dt_s`` for s <= t, else 0
    - ``d_in``  [H, T]: ``exp(cum_t)``, what the entering state has decayed
      by at position t of its chunk
    - ``d_out`` [H, T]: ``exp(cum_Q - cum_s) dt_s``, the weight of position
      s in the state that leaves its chunk
    - ``d_all`` [H, T/Q]: ``exp(cum_Q)``, the decay of a whole chunk
    with ``cum`` the inclusive cumulative sum of ``dt A`` within a chunk."""
    T, H = dt.shape
    nc = T // Q
    a = (dt * A[None, :]).T.reshape(H, nc, Q)                  # <= 0
    dtc = dt.T.reshape(H, nc, Q)
    cum = jnp.cumsum(a, axis=-1)
    seg = cum[..., :, None] - cum[..., None, :]                # [H,nc,t,s]
    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    L = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0) \
        * dtc[..., None, :]
    d_in = jnp.exp(cum).reshape(H, T)
    d_out = (jnp.exp(cum[..., -1:] - cum) * dtc).reshape(H, T)
    return L, d_in, d_out, jnp.exp(cum[..., -1])


# --------------------------------------------------------------------------
# the chunked scan
# --------------------------------------------------------------------------

def _scan_kernel(x_ref, xw_ref, b_ref, c_ref, l_ref, dall_ref, s0_ref,
                 yi_ref, yo_ref, sf_ref, st_ref, *, heads: int):
    """One (group of heads, chunk): x [heads, Q, P], xw [heads, P, Q] (x
    weighted by d_out, transposed), B, C [Q, N], L [heads, Q, Q], d_all
    [heads, 1, 1], S0 [heads, P, N] -> y of the chunk's own positions
    [heads, Q, P], y of the entering state BEFORE its decay [heads, Q, P],
    and the state after the last chunk. ``st_ref``: the carried state."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _load():
        st_ref[...] = s0_ref[...].astype(jnp.float32)

    dt = x_ref.dtype
    B, C = b_ref[...], c_ref[...]
    nt = (((1,), (1,)), ((), ()))                      # contract last dims
    cb = lax.dot_general(C, B, nt, preferred_element_type=jnp.float32)
    for i in range(heads):
        m = (cb * l_ref[i]).astype(dt)                             # [Q, Q]
        yi_ref[i] = jnp.dot(m, x_ref[i],
                            preferred_element_type=jnp.float32
                            ).astype(yi_ref.dtype)
        s_in = st_ref[i]                                           # [P, N]
        yo_ref[i] = lax.dot_general(
            C, s_in.astype(dt), nt, preferred_element_type=jnp.float32
        ).astype(yo_ref.dtype)
        st_ref[i] = s_in * dall_ref[i] + jnp.dot(
            xw_ref[i], B, preferred_element_type=jnp.float32)

    @pl.when(c == pl.num_programs(1) - 1)
    def _store():
        sf_ref[...] = st_ref[...]


def _scan_pallas(xh, xw, Bg, Cg, L, d_all, S0, Q: int, interpret: bool):
    H, T, P = xh.shape
    G, _, N = Bg.shape
    per_group = H // G
    # a grid row is a group's heads, or a part of them where a group's state
    # is over the budget (16 heads of 128 x 256: two rows of 8)
    hb = min(per_group, block_heads(H, per_group, P, N, SCAN_STATE_BYTES))
    rows = per_group // hb                            # grid rows a group
    group = (lambda g: g) if rows == 1 else (lambda g: g // rows)
    nc = T // Q
    return pl.pallas_call(
        functools.partial(_scan_kernel, heads=hb),
        grid=(H // hb, nc),
        in_specs=[
            pl.BlockSpec((hb, Q, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((hb, P, Q), lambda g, c: (g, 0, c)),
            pl.BlockSpec((None, Q, N), lambda g, c: (group(g), c, 0)),
            pl.BlockSpec((None, Q, N), lambda g, c: (group(g), c, 0)),
            pl.BlockSpec((hb, None, Q, Q), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((hb, None, 1, 1), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((hb, P, N), lambda g, c: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((hb, Q, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((hb, Q, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((hb, P, N), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((H, T, P), jnp.float32),
                   jax.ShapeDtypeStruct((H, T, P), jnp.float32),
                   jax.ShapeDtypeStruct((H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(xh, xw, Bg, Cg, L, d_all[..., None, None], S0)


def _scan_jnp(xh, xw, Bg, Cg, L, d_all, S0, Q: int):
    """The kernel's arithmetic in ``jax.numpy``: the same four products per
    (head, chunk), a ``lax.scan`` over chunks for the carry."""
    H, T, P = xh.shape
    G, _, N = Bg.shape
    hb, nc, dt = H // G, T // Q, xh.dtype
    f32 = jnp.float32
    x_c = xh.reshape(G, hb, nc, Q, P)
    xw_c = xw.reshape(G, hb, P, nc, Q)
    B_c, C_c = Bg.reshape(G, nc, Q, N), Cg.reshape(G, nc, Q, N)
    L_c = L.reshape(G, hb, nc, Q, Q)
    cb = jnp.einsum("gctn,gcsn->gcts", C_c, B_c, preferred_element_type=f32)
    m = (cb[:, None] * L_c).astype(dt)
    y_in = jnp.einsum("ghcts,ghcsp->ghctp", m, x_c, preferred_element_type=f32)
    s_loc = jnp.einsum("ghpcs,gcsn->cghpn", xw_c, B_c,
                       preferred_element_type=f32)
    d_c = d_all.reshape(G, hb, nc).transpose(2, 0, 1)[..., None, None]

    def carry(s, xs):
        loc, d = xs
        return s * d + loc, s                          # the ENTERING state

    s_fin, s_ent = lax.scan(carry, S0.reshape(G, hb, P, N).astype(f32),
                            (s_loc, d_c))
    y_out = jnp.einsum("gctn,cghpn->ghctp", C_c, s_ent.astype(dt),
                       preferred_element_type=f32)
    return (y_in.reshape(H, T, P), y_out.reshape(H, T, P),
            s_fin.reshape(H, P, N))


def ssm_scan(x, dt, A, B, C, S0, chunk: int = 128, kernel=None):
    """The recurrence over one sequence.

    x [T, H, P] (compute dtype), dt [T, H] float32 (softplus already
    applied; 0 where a position must not move the state), A [H] float32
    (negative), B, C [T, G, N] (compute dtype), S0 [H, P, N] float32 ->
    (y [T, H, P] float32 WITHOUT the ``D x`` term, final state [H, P, N]
    float32). T is padded to a multiple of ``chunk`` here (dt = 0 there).

    ``kernel``: None picks the Pallas kernel on a TPU and the ``jax.numpy``
    form elsewhere; True forces the kernel (interpret mode off the TPU)."""
    T, H, P = x.shape
    Q = chunk
    pad = -T % Q
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
        B = jnp.pad(B, ((0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, pad), (0, 0), (0, 0)))
    with jax.named_scope("decays"):
        L, d_in, d_out, d_all = chunk_decays(dt.astype(jnp.float32),
                                             A.astype(jnp.float32), Q)
        xh = jnp.swapaxes(x, 0, 1)                                  # [H,T,P]
        xw = jnp.swapaxes(xh.astype(jnp.float32) * d_out[..., None],
                          1, 2).astype(x.dtype)                     # [H,P,T]
        Bg, Cg = jnp.swapaxes(B, 0, 1), jnp.swapaxes(C, 0, 1)       # [G,T,N]
    use_kernel = not _interpret() if kernel is None else kernel
    if use_kernel:
        y_in, y_out, s_fin = _scan_pallas(xh, xw, Bg, Cg, L, d_all, S0, Q,
                                          _interpret())
    else:
        y_in, y_out, s_fin = _scan_jnp(xh, xw, Bg, Cg, L, d_all, S0, Q)
    y = jnp.swapaxes(y_in + y_out * d_in[..., None], 0, 1)          # [T,H,P]
    return (y[:T] if pad else y), s_fin


# --------------------------------------------------------------------------
# the one-step update over the state pool
# --------------------------------------------------------------------------

def _step_kernel(dtx_ref, da_ref, b_ref, c_ref, s_ref, y_ref, so_ref, *,
                 heads: int, per_group: int):
    """One (slot, block of heads): dtx, da [P, heads] (``dt x`` and
    ``exp(dt A)``, the head in the LANES so that a head's column broadcasts
    over the state's lanes), B, C [groups, 1, N], state [heads, P, N] ->
    y [P, heads], state."""
    for h in range(heads):
        brow, crow = b_ref[h // per_group], c_ref[h // per_group]   # [1, N]
        s = s_ref[h] * da_ref[:, h:h + 1] + dtx_ref[:, h:h + 1] * brow
        so_ref[h] = s
        y_ref[:, h:h + 1] = jnp.sum(s * crow, axis=-1, keepdims=True)


def _step_pallas(pool, layer: int, dtx, da, Bs, Cs, interpret: bool):
    Lm, S, H, P, N = pool.shape
    G = Bs.shape[1]
    per_group = H // G
    hb = block_heads(H, per_group, P, N, STEP_STATE_BYTES)
    # groups a block reads: its heads' whole groups, or the ONE it lies in
    nb, gb = H // hb, max(1, hb // per_group)
    of_group = max(1, per_group // hb)                # blocks a group
    group = (lambda b: b) if of_group == 1 else (lambda b: b // of_group)

    def lanes(a):                     # [S, H, P] -> [S, nb, P, hb]
        return a.reshape(S, nb, hb, P).swapaxes(2, 3)

    state = pl.BlockSpec((None, None, hb, P, N),
                         lambda s, b: (layer, s, b, 0, 0))
    vec = pl.BlockSpec((None, None, P, hb), lambda s, b: (s, b, 0, 0))
    grp = pl.BlockSpec((None, gb, 1, N), lambda s, b: (s, group(b), 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, per_group=per_group),
        grid=(S, nb),
        in_specs=[vec, vec, grp, grp, state],
        out_specs=[vec, state],
        out_shape=[jax.ShapeDtypeStruct((S, nb, P, hb), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={4: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_step",
    )(lanes(dtx), lanes(da), Bs[:, :, None, :], Cs[:, :, None, :], pool)
    return y.swapaxes(2, 3).reshape(S, H, P), pool


def _step_jnp(pool, layer: int, dtx, da, Bs, Cs):
    H, G = pool.shape[2], Bs.shape[1]
    Bh = jnp.repeat(Bs, H // G, axis=1)                          # [S, H, N]
    Ch = jnp.repeat(Cs, H // G, axis=1)
    s = pool[layer] * da[..., None] + dtx[..., None] * Bh[:, :, None, :]
    y = jnp.sum(s * Ch[:, :, None, :], axis=-1)
    return y, pool.at[layer].set(s.astype(pool.dtype))


def ssm_step(pool, layer: int, x, dt, A, B, C, kernel=None):
    """One position for every slot, the state pool updated in place.

    pool [layers, S, H, P, N] (float32 as served), ``layer`` a Python int,
    x [S, H, P], dt [S, H] float32 (0 for a slot that must keep its state),
    A [H], B, C [S, G, N] -> (y [S, H, P] float32 without ``D x``, pool)."""
    f32 = jnp.float32
    with jax.named_scope("decays"):
        dt = dt.astype(f32)
        da = jnp.broadcast_to(jnp.exp(dt * A.astype(f32)[None])[..., None],
                              x.shape)
        dtx = x.astype(f32) * dt[..., None]
    Bs, Cs = B.astype(f32), C.astype(f32)
    use_kernel = not _interpret() if kernel is None else kernel
    if use_kernel:
        return _step_pallas(pool, layer, dtx, da, Bs, Cs, _interpret())
    return _step_jnp(pool, layer, dtx, da, Bs, Cs)
