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


@pytest.mark.parametrize("heads,width", [(4, 8), (2, 128)])
def test_l2norm_over_runs_of_lanes_is_l2norm_over_a_heads_axis(heads, width):
    """``l2norm_heads`` leaves (..., heads x d) as it lies and equals
    ``l2norm`` over a heads axis, in value and in gradient."""
    rng = np.random.default_rng(heads)
    x = jnp.asarray(rng.normal(size=(2, 5, heads * width)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def split(x):
        return dr.l2norm(x.reshape(2, 5, heads, width)).reshape(x.shape)
    np.testing.assert_allclose(dr.l2norm_heads(x, heads), split(x),
                               atol=1e-6)
    got, want = (jax.grad(lambda x: (f(x) * probe).sum())(x)
                 for f in (lambda x: dr.l2norm_heads(x, heads), split))
    np.testing.assert_allclose(got, want, atol=1e-5)
    low = dr.l2norm_heads(x.astype(jnp.bfloat16), heads)
    assert low.dtype == jnp.float32


# ----------------------------------------------------- the Pallas kernels
# ``delta_rule_chunks`` in interpret mode at the smallest shape the kernels
# take: 128-wide heads, chunks of 64, two value heads on a key head.
KDK = KDV = 128


def kernel_inputs(t, seed=0, dtype=jnp.float32, b=1, groups=1):
    rng = np.random.default_rng(seed)
    h = groups * R

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = dr.l2norm(normal(b, t, groups, KDK)) * 4 / np.sqrt(KDK)
    k = dr.l2norm(normal(b, t, groups, KDK))
    v = normal(b, t, h, KDV)
    g = -jnp.asarray(rng.uniform(0.01, 0.3, (b, t, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (b, t, h)), jnp.float32)
    state0 = 0.3 * normal(b, h, KDK, KDV)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            state0)


def kernels(*args):
    return dr.delta_rule_chunks(*args, interpret=True)


def in_float32(args):
    return tuple(a.astype(jnp.float32) for a in args)


@pytest.fixture
def a_chunk_a_step(monkeypatch):
    """A grid step of one chunk: the interpreter traces a step's chunks
    one after another, so a case that needs no more takes a chunk a step."""
    monkeypatch.setattr(dr, "STEP_CHUNKS", 1)


# T, chunks a grid step, batch, key heads: whole chunks in one step; a
# length that is no whole number of chunks, padded to whole steps; a state
# that crosses a grid step's edge; rows of the grid by batch and key head
KERNEL_CASES = [(128, 2, 1, 1), (150, 2, 1, 1), (150, 1, 1, 1),
                (128, 1, 2, 2)]


@pytest.mark.parametrize("t,step_chunks,b,groups", KERNEL_CASES)
def test_the_kernels_equal_the_recurrence(monkeypatch, t, step_chunks, b,
                                          groups):
    monkeypatch.setattr(dr, "STEP_CHUNKS", step_chunks)
    args = kernel_inputs(t, b=b, groups=groups)
    want_o, want_s = recurrence(*args)
    got_o, got_s = kernels(*args)
    assert got_o.shape == want_o.shape and got_s.shape == want_s.shape
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    assert np.abs(want_o).max() > 0.1


def _objective(fn):
    def loss(*a):
        o, s = fn(*a)
        o, s = o.astype(jnp.float32), s.astype(jnp.float32)
        return (o ** 2).sum() + (s * jnp.cos(s)).sum()
    return jax.grad(loss, argnums=tuple(range(6)))


@pytest.mark.parametrize("t,step_chunks", [(150, 2), (150, 1)])
def test_the_kernels_gradients_equal_the_recurrences(monkeypatch, t,
                                                     step_chunks):
    """The written-out backward: q, k (summed over the two value heads of
    a key head), v, g (through the cumulative sum, every decay and what a
    chunk keeps of its state), beta and state0."""
    monkeypatch.setattr(dr, "STEP_CHUNKS", step_chunks)
    args = kernel_inputs(t, seed=1)
    want = _objective(recurrence)(*args)
    got = _objective(kernels)(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state0"), got, want):
        scale = np.abs(b).max()
        assert scale > 0.01, name
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, err_msg=name)


def test_the_kernels_go_on_from_the_state_of_a_call_before(a_chunk_a_step):
    q, k, v, g, beta, state0 = kernel_inputs(192)
    whole_o, whole_s = kernels(q, k, v, g, beta, state0)
    cut = 77                                    # inside a chunk
    first_o, mid = kernels(*(a[:, :cut] for a in (q, k, v, g, beta)), state0)
    then_o, last = kernels(*(a[:, cut:] for a in (q, k, v, g, beta)), mid)
    np.testing.assert_allclose(jnp.concatenate([first_o, then_o], 1),
                               whole_o, atol=1e-5)
    np.testing.assert_allclose(last, whole_s, atol=1e-5)


def test_in_the_kernels_a_very_fast_decay_overflows_nothing(a_chunk_a_step):
    q, k, v, g, beta, state0 = kernel_inputs(128)
    g = g * 600.0
    o, s = kernels(q, k, v, g, beta, state0)
    grads = jax.grad(lambda g, beta: kernels(
        q, k, v, g, beta, state0)[0].sum(), argnums=(0, 1))(g, beta)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    assert all(np.isfinite(x).all() for x in grads)


def test_the_kernels_bf16_activations_stay_near_the_float32_form(
        a_chunk_a_step):
    args = kernel_inputs(150)
    want, want_s = kernels(*args)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3])
    got, got_s = kernels(*low, *args[3:])
    assert got.dtype == jnp.bfloat16 and got_s.dtype == jnp.float32
    err = np.abs(np.asarray(got, np.float32) - want).max()
    assert 0 < err < 0.03 * np.abs(want).max()
    assert np.abs(got_s - want_s).max() < 0.03 * np.abs(want_s).max()


def test_the_xla_form_and_the_kernels_agree_to_the_bf16_products_rounding(
        a_chunk_a_step):
    """What every shape but the cell's runs (``_spans_form``) beside what
    the cell runs, on the same bf16 inputs, in value and in gradient: the
    same chunked algorithm at the same precisions, so they differ by the
    order of float32 sums and where a bf16 operand rounds, far below what
    either differs from the float32 recurrence by."""
    args = kernel_inputs(150, seed=2, dtype=jnp.bfloat16)
    xla = lambda *a: dr._spans_form(*a[:5], dr.CHUNK, a[5])
    exact = recurrence(*in_float32(args))
    got, want = kernels(*args), xla(*args)
    for a, b, c in zip(got, want, exact):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        apart, off = np.abs(a - b).max(), np.abs(b - c).max()
        assert apart <= 0.008 * np.abs(c).max() and apart <= 2 * off
    got, want = _objective(kernels)(*args), _objective(xla)(*args)
    exact = _objective(recurrence)(*in_float32(args))
    for name, a, b, c in zip(("q", "k", "v", "g", "beta", "state0"), got,
                             want, exact):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        apart, off = np.abs(a - b).max(), np.abs(b - c).max()
        assert apart <= 0.02 * np.abs(c).max(), name
        assert apart <= 2 * off + 1e-6, name


@pytest.mark.parametrize("backend,dtype,chunk,width,runs", [
    ("tpu", jnp.bfloat16, 64, 128, True),
    ("cpu", jnp.bfloat16, 64, 128, False),
    ("tpu", jnp.float32, 64, 128, False),
    ("tpu", jnp.bfloat16, 32, 128, False),
    ("tpu", jnp.bfloat16, 64, 64, False),
])
def test_which_form_runs_is_read_from_the_call(monkeypatch, backend, dtype,
                                               chunk, width, runs):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct((1, 128, 2, width), dtype)
    v = jax.ShapeDtypeStruct((1, 128, 4, width), dtype)
    assert dr._kernels_run(q, v, chunk) is runs
