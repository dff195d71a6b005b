"""The gated delta rule (``ops/gated_delta.py``): the chunk form and the
one-step update, each as its ``jax.numpy`` form and as its Pallas kernel in
interpret mode, held to the sequential scan of the recurrence as written.

The tolerance is 2e-6 on values of size ~0.2 in float32: the chunk form
reorders a sum of at most ``chunk`` products a position, and the triangular
solve is exact forward substitution (by blocks) — a solve that cancelled
large terms, or a state carried in bf16 (error 1e-3 here), would not pass."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gated_delta as gd

TOL = 2e-6
HK, HV, DK, DV = 2, 4, 32, 16

# jitted: op by op, each small program is compiled on its own
_sequential = jax.jit(gd.gdn_sequential)
_chunk = jax.jit(gd.gdn_chunk, static_argnames=("chunk", "kernel"))
_step = jax.jit(gd.gdn_step, static_argnames=("kernel",))      # layer traced


def _inputs(T, seed=0, alike=0.0):
    """q, k l2-normed (q scaled) as the mixer hands them; ``alike`` shifts
    every key the same way, so that neighbouring keys are nearly parallel
    (what a SiLU'd convolution gives) and the solve's matrix is far from the
    identity."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (T, HK, DK))
    k = jax.random.normal(ks[1], (T, HK, DK)) + alike
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (q, k, jax.random.normal(ks[2], (T, HV, DV)),
            -jax.random.uniform(ks[3], (T, HV), minval=0.001, maxval=0.5),
            jax.nn.sigmoid(jax.random.normal(ks[4], (T, HV))),
            jax.random.normal(ks[5], (HV, DK, DV)) * 0.1)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("alike", [0.0, 2.0], ids=["spread", "alike"])
@pytest.mark.parametrize("T", [1, 5, 16, 17, 40, 64])
def test_chunk_form_equals_the_sequential_scan(T, alike, kernel):
    """A prompt shorter than one chunk, exactly one, one and a row, several
    and a part: chunk boundaries and the pad to a whole chunk."""
    q, k, v, g, beta, S0 = _inputs(T, T, alike)
    o0, s0 = _sequential(q, k, v, g, beta, S0)
    o, s = _chunk(q, k, v, g, beta, S0, chunk=16, kernel=kernel)
    assert float(jnp.abs(o - o0).max()) < TOL
    assert float(jnp.abs(s - s0).max()) < TOL


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("T,keep", [(16, 3), (40, 33), (64, 48), (80, 1)])
def test_pad_rows_leave_the_state_alone(T, keep, kernel):
    """Rows with g = 0 and beta = 0 (a bucket's pad) move nothing: the state
    after T rows is the state after the ``keep`` true ones, whatever the pad
    rows hold."""
    q, k, v, g, beta, S0 = _inputs(T, 7)
    g, beta = g.at[keep:].set(0.0), beta.at[keep:].set(0.0)
    _, s = _chunk(q, k, v, g, beta, S0, chunk=16, kernel=kernel)
    _, s_true = _sequential(q[:keep], k[:keep], v[:keep], g[:keep],
                                  beta[:keep], S0)
    assert float(jnp.abs(s - s_true).max()) < TOL


def test_the_chunk_of_64_at_a_2_to_1_head_ratio():
    """The served chunk size, through both forms."""
    q, k, v, g, beta, S0 = _inputs(150, 3, alike=1.0)
    o0, s0 = _sequential(q, k, v, g, beta, S0)
    for kernel in (False, True):
        o, s = _chunk(q, k, v, g, beta, S0, chunk=64, kernel=kernel)
        assert float(jnp.abs(o - o0).max()) < TOL
        assert float(jnp.abs(s - s0).max()) < TOL


def test_a_bf16_state_would_not_pass():
    q, k, v, g, beta, S0 = _inputs(40, 5)
    o0, _ = _sequential(q, k, v, g, beta, S0)
    o, s = o0[:0], S0
    for t in range(40):                    # the state rounded after each row
        o_t, s = _sequential(q[t:t + 1], k[t:t + 1], v[t:t + 1],
                                   g[t:t + 1], beta[t:t + 1], s)
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        o = jnp.concatenate([o, o_t])
    assert float(jnp.abs(o - o0).max()) > 50 * TOL


@pytest.mark.parametrize("Q", [2, 16, 64])
def test_unit_lower_inverse(Q):
    A = np.tril(np.random.default_rng(Q).normal(size=(3, 2, Q, Q)), -1) * 0.4
    T = jax.jit(gd.unit_lower_inverse)(jnp.asarray(A, jnp.float32))
    want = np.linalg.inv(np.eye(Q) - A)
    assert np.abs(np.asarray(T) - want).max() < 1e-5 * np.abs(want).max()
    with pytest.raises(ValueError, match="power of two"):
        gd.unit_lower_inverse(jnp.zeros((48, 48)))


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_step_updates_the_pool_in_place(kernel):
    S = 5
    q, k, v, g, beta, _ = _inputs(S, seed=1)
    g, beta = g.at[2].set(0.0), beta.at[2].set(0.0)       # an inactive slot
    pool = jax.random.normal(jax.random.PRNGKey(9), (3, S, HV, DK, DV))
    o, new = _step(pool, 1, q, k, v, g, beta, kernel=kernel)
    for s in range(S):
        o1, s1 = _sequential(q[s:s + 1], k[s:s + 1], v[s:s + 1],
                                   g[s:s + 1], beta[s:s + 1], pool[1, s])
        assert float(jnp.abs(o[s] - o1[0]).max()) < TOL
        assert float(jnp.abs(new[1, s] - s1).max()) < TOL
    assert bool((new[0] == pool[0]).all()) and bool((new[2] == pool[2]).all())
    assert bool((new[1, 2] == pool[1, 2]).all())


def test_chunk_then_steps_continue_one_sequence():
    """A prompt through the chunk form, then its next rows one step at a
    time over the pool: the same outputs as the whole sequence at once."""
    q, k, v, g, beta, _ = _inputs(30, 11)
    S0 = jnp.zeros((HV, DK, DV))
    o0, _ = _sequential(q, k, v, g, beta, S0)
    _, s = _chunk(q[:23], k[:23], v[:23], g[:23], beta[:23], S0,
                        chunk=16)
    pool = jnp.zeros((2, 1, HV, DK, DV)).at[1, 0].set(s)
    for t in range(23, 30):
        o, pool = _step(pool, 1, q[t:t + 1], k[t:t + 1], v[t:t + 1],
                              g[t:t + 1], beta[t:t + 1])
        assert float(jnp.abs(o[0] - o0[t]).max()) < TOL


def test_the_jnp_form_differentiates():
    """Training runs the ``jax.numpy`` form on the CPU: gradients are finite
    and those of the sequential scan."""
    q, k, v, g, beta, S0 = _inputs(20, 2)

    def loss(f, v, g):
        return jnp.sum(jnp.square(f(q, k, v, g, beta, S0)[0]))

    got = jax.grad(lambda v, g: loss(
        lambda *a: gd.gdn_chunk(*a, chunk=16, kernel=False), v, g),
        argnums=(0, 1))(v, g)
    want = jax.grad(lambda v, g: loss(gd.gdn_sequential, v, g),
                    argnums=(0, 1))(v, g)
    for a, b in zip(got, want):
        assert bool(jnp.isfinite(a).all())
        assert float(jnp.abs(a - b).max()) < 1e-4
