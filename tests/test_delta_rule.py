"""ops/delta_rule.py: the chunked gated delta rule against the token-by-
token recurrence it must equal, in value and in gradient, on the CPU in
float32; the inverse of a unit lower triangular matrix and its written-out
backward; the padding of a sequence that is no whole number of chunks or
spans; and the bf16 path's distance from the float32 one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import delta_rule as dr

B, G, R, DK, DV = 2, 2, 2, 8, 6
H = G * R


def recurrence(q, k, v, g, beta, state0):
    """``delta_rule_step`` over the positions: the definition."""
    def step(state, x):
        o, state = dr.delta_rule_step(state, *x)
        return state, o
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (
        jnp.repeat(q, R, 2), jnp.repeat(k, R, 2), v, g, beta))
    state, o = lax.scan(step, state0, xs)
    return jnp.moveaxis(o, 0, 1), state


def inputs(t, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = dr.l2norm(normal(B, t, G, DK)) / np.sqrt(DK)
    k = dr.l2norm(normal(B, t, G, DK))
    v = normal(B, t, H, DV)
    g = -jnp.asarray(rng.uniform(0.01, 1.0, (B, t, H)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (B, t, H)), jnp.float32)
    state0 = normal(B, H, DK, DV)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            state0)


# T, chunk: a whole number of chunks; not one; one chunk with room; more
# chunks than a span holds (19 > SPAN), so that a state crosses a span's edge
CASES = [(32, 8), (37, 8), (5, 8), (37, 2), (64, 64)]


@pytest.mark.parametrize("t,chunk", CASES)
def test_the_chunked_form_equals_the_recurrence(t, chunk):
    q, k, v, g, beta, state0 = inputs(t)
    want_o, want_s = recurrence(q, k, v, g, beta, state0)
    got_o, got_s = dr.gated_delta_rule(q, k, v, g, beta, chunk=chunk,
                                       state0=state0)
    assert got_o.shape == (B, t, H, DV) and got_s.shape == (B, H, DK, DV)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    assert np.abs(want_o).max() > 0.1


def test_no_state0_is_a_zero_state():
    q, k, v, g, beta, state0 = inputs(20)
    got = dr.gated_delta_rule(q, k, v, g, beta, chunk=8)
    want = dr.gated_delta_rule(q, k, v, g, beta, chunk=8,
                               state0=jnp.zeros_like(state0))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_a_call_goes_on_from_the_state_of_the_one_before():
    q, k, v, g, beta, state0 = inputs(40)
    whole_o, whole_s = dr.gated_delta_rule(q, k, v, g, beta, chunk=8,
                                           state0=state0)
    cut = 13                                    # inside a chunk
    first_o, mid = dr.gated_delta_rule(
        *(a[:, :cut] for a in (q, k, v, g, beta)), chunk=8, state0=state0)
    then_o, last = dr.gated_delta_rule(
        *(a[:, cut:] for a in (q, k, v, g, beta)), chunk=8, state0=mid)
    np.testing.assert_allclose(jnp.concatenate([first_o, then_o], 1),
                               whole_o, atol=1e-5)
    np.testing.assert_allclose(last, whole_s, atol=1e-5)


@pytest.mark.parametrize("t,chunk", [(37, 8), (37, 2)])
def test_the_gradients_equal_the_recurrences(t, chunk):
    args = inputs(t, seed=1)

    def objective(fn):
        def loss(*a):
            o, s = fn(*a)
            return (o ** 2).sum() + (s * jnp.cos(s)).sum()
        return jax.grad(loss, argnums=tuple(range(6)))
    want = objective(recurrence)(*args)
    got = objective(lambda *a: dr.gated_delta_rule(
        *a[:5], chunk=chunk, state0=a[5]))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state0"), got, want):
        scale = np.abs(b).max()
        assert scale > 0.1, name
        np.testing.assert_allclose(a, b, atol=1e-5 * scale, err_msg=name)


@pytest.mark.parametrize("c", [1, 2, 5, 8, 64])
def test_the_inverse_of_a_unit_lower_triangular_matrix(c):
    rng = np.random.default_rng(c)
    a = np.tril(rng.normal(size=(3, c, c)), -1).astype(np.float32)
    got = dr.inverse_unit_lower(jnp.asarray(a))
    np.testing.assert_allclose(got, np.linalg.inv(np.eye(c) + a), atol=2e-4,
                               rtol=2e-4)


def test_the_inverses_backward_is_the_derivative_of_the_inverse():
    c = 8
    rng = np.random.default_rng(0)
    a = jnp.asarray(np.tril(rng.normal(size=(2, c, c)), -1), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(2, c, c)), jnp.float32)

    def through(inverse):
        return jax.grad(lambda a: (inverse(a) * probe).sum())(a)
    want = through(lambda a: jnp.linalg.inv(jnp.eye(c) + a))
    np.testing.assert_allclose(through(dr.inverse_unit_lower), want,
                               atol=1e-3, rtol=1e-3)


def test_a_very_fast_decay_overflows_nothing():
    """exp(c_i - c_j) above the diagonal would be exp(+large): it is never
    made, forward or backward."""
    q, k, v, g, beta, state0 = inputs(16)
    g = g * 200.0
    o, s = dr.gated_delta_rule(q, k, v, g, beta, chunk=8, state0=state0)
    grads = jax.grad(lambda g: dr.gated_delta_rule(
        q, k, v, g, beta, chunk=8, state0=state0)[0].sum())(g)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    assert np.isfinite(grads).all()


def test_bf16_activations_stay_near_the_float32_form():
    """Operands of the products outside the solve are bf16 in a bf16
    model (the state never where it is carried): the output moves by
    rounding, not by more."""
    q, k, v, g, beta, state0 = inputs(37)
    want, want_s = dr.gated_delta_rule(q, k, v, g, beta, chunk=8,
                                       state0=state0)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    got, got_s = dr.gated_delta_rule(*low, g, beta, chunk=8, state0=state0)
    assert got.dtype == jnp.bfloat16 and got_s.dtype == jnp.float32
    scale = np.abs(want).max()
    err = np.abs(np.asarray(got, np.float32) - want).max()
    assert 0 < err < 0.03 * scale
    assert np.abs(got_s - want_s).max() < 0.03 * np.abs(want_s).max()
