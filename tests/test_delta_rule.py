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


# ------------------------------------- the conv's output, whole and unnormed
def apart(qkv, heads, dk):
    """q | k | v (B, T, 2 heads dk + H dv) -> (q, k, v) as
    ``models/qwen3_next._gdn_mixer`` handed them over before the rule took
    the conv's output whole: an l2 norm a head (over a heads axis here:
    ``l2norm_heads`` is held to that above), the queries' scale, one
    rounding to the activations' type, v's slice."""
    (b, t, w), kw = qkv.shape, heads * dk
    q = dr.l2norm(qkv[..., :kw].reshape(b, t, heads, dk)) * dk ** -0.5
    k = dr.l2norm(qkv[..., kw:2 * kw].reshape(b, t, heads, dk))
    return (q.astype(qkv.dtype), k.astype(qkv.dtype),
            qkv[..., 2 * kw:].reshape(b, t, heads * R, -1))


def whole(q, k, v):
    """Unnormed q, k and v side by side, as the conv leaves them."""
    return jnp.concatenate([a.reshape(*a.shape[:2], -1) for a in (q, k, v)],
                           -1)


@pytest.mark.parametrize("t,chunk", [(37, 8), (64, 64)])
def test_the_conv_s_whole_output_in_xla_equals_the_recurrence(t, chunk):
    """``gated_delta_rule_qkv`` off the kernels' path (float32, 8-wide
    heads, the CPU): the slices, ``l2norm_heads`` and the XLA form, in
    value and in gradient."""
    q, k, v, g, beta, state0 = inputs(t, seed=3)
    qkv = whole(3.0 * q, 0.5 * k, v)        # any length: the rule norms

    def rule(qkv, g, beta, state0):
        return dr.gated_delta_rule_qkv(qkv, g, beta, G, DK, chunk=chunk,
                                       state0=state0)

    def want(qkv, g, beta, state0):
        return recurrence(*apart(qkv, G, DK), g, beta, state0)
    for a, b in zip(rule(qkv, g, beta, state0), want(qkv, g, beta, state0)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_array_equal(
        dr.gated_delta_rule_qkv(qkv, g, beta, G, DK, chunk=chunk)[0],
        rule(qkv, g, beta, jnp.zeros_like(state0))[0])
    got, want = (_objective(f)(qkv, g, beta, state0) for f in (rule, want))
    for name, a, b in zip(("qkv", "g", "beta", "state0"), got, want):
        scale = np.abs(b).max()
        assert scale > 0.01, name
        np.testing.assert_allclose(a, b, atol=1e-5 * scale, err_msg=name)


# ----------------------------------------------------- the Pallas kernels
# ``delta_rule_chunks`` and ``delta_rule_chunks_qkv`` in interpret mode at
# the smallest shape the kernels take: 128-wide heads, chunks of 64, two
# value heads on a key head.  A case's inputs are (x, g, beta, state0)
# with ``x`` what differs between the two entries: (q, k, v) normed by the
# caller, or (qkv,) as the conv leaves it, which the kernels norm.
KDK = KDV = 128
ENTRIES = ("q_k_v", "qkv")


def kernel_inputs(t, seed=0, dtype=jnp.float32, b=1, groups=1,
                  entry="q_k_v"):
    rng = np.random.default_rng(seed)
    h = groups * R

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k = normal(b, t, groups, KDK), normal(b, t, groups, KDK)
    v = normal(b, t, h, KDV)
    g = -jnp.asarray(rng.uniform(0.01, 0.3, (b, t, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (b, t, h)), jnp.float32)
    state0 = 0.3 * normal(b, h, KDK, KDV)
    if entry == "qkv":
        # the rule's own scale of q is a quarter of the one below: v and
        # the state make it up, so that o is the same size
        x = (whole(0.7 * q, 1.5 * k, 4 * v),)
        state0 = 4 * state0
    else:
        x = (dr.l2norm(q) * 4 / np.sqrt(KDK), dr.l2norm(k), v)
    return tuple(a.astype(dtype) for a in x), g, beta, state0


def kernels(x, g, beta, state0):
    if len(x) == 3:
        return dr.delta_rule_chunks(*x, g, beta, state0, interpret=True)
    groups = g.shape[-1] // R
    return dr.delta_rule_chunks_qkv(*x, g, beta, state0, groups, KDK,
                                    interpret=True)


def on_q_k_v(fn):
    """``fn(q, k, v, g, beta, state0)`` as a function of a case's inputs:
    the conv's whole output is sliced and normed by XLA first."""
    def call(x, g, beta, state0):
        if len(x) == 1:
            x = apart(*x, g.shape[-1] // R, KDK)
        return fn(*x, g, beta, state0)
    return call


def in_float32(args):
    return (tuple(a.astype(jnp.float32) for a in args[0]), *args[1:])


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


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("t,step_chunks,b,groups", KERNEL_CASES)
def test_the_kernels_equal_the_recurrence(monkeypatch, t, step_chunks, b,
                                          groups, entry):
    monkeypatch.setattr(dr, "STEP_CHUNKS", step_chunks)
    args = kernel_inputs(t, b=b, groups=groups, entry=entry)
    want_o, want_s = on_q_k_v(recurrence)(*args)
    got_o, got_s = kernels(*args)
    assert got_o.shape == want_o.shape and got_s.shape == want_s.shape
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    assert np.abs(want_o).max() > 0.1


def _objective(fn):
    """The gradients of a loss over (o, the last state), with respect to
    every input: a flat tuple of them."""
    def loss(*a):
        o, s = fn(*a)
        o, s = o.astype(jnp.float32), s.astype(jnp.float32)
        return (o ** 2).sum() + (s * jnp.cos(s)).sum()

    def grads(*a):
        return tuple(jax.tree_util.tree_leaves(
            jax.grad(loss, argnums=tuple(range(len(a))))(*a)))
    return grads


def _names(entry):
    return (("q", "k", "v") if entry == "q_k_v" else ("qkv",)) \
        + ("g", "beta", "state0")


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("t,step_chunks", [(150, 2), (150, 1)])
def test_the_kernels_gradients_equal_the_recurrences(monkeypatch, t,
                                                     step_chunks, entry):
    """The written-out backward: q, k (summed over the two value heads of
    a key head), v, g (through the cumulative sum, every decay and what a
    chunk keeps of its state), beta and state0; of the conv's whole
    output, through the norms too."""
    monkeypatch.setattr(dr, "STEP_CHUNKS", step_chunks)
    args = kernel_inputs(t, seed=1, entry=entry)
    want = _objective(on_q_k_v(recurrence))(*args)
    got = _objective(kernels)(*args)
    for name, a, b in zip(_names(entry), got, want, strict=True):
        scale = np.abs(b).max()
        assert scale > 0.01, name
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, err_msg=name)


@pytest.mark.parametrize("entry", ENTRIES)
def test_the_kernels_go_on_from_the_state_of_a_call_before(a_chunk_a_step,
                                                           entry):
    x, g, beta, state0 = kernel_inputs(192, entry=entry)
    whole_o, whole_s = kernels(x, g, beta, state0)
    cut = 77                                    # inside a chunk
    first_o, mid = kernels(tuple(a[:, :cut] for a in x), g[:, :cut],
                           beta[:, :cut], state0)
    then_o, last = kernels(tuple(a[:, cut:] for a in x), g[:, cut:],
                           beta[:, cut:], mid)
    np.testing.assert_allclose(jnp.concatenate([first_o, then_o], 1),
                               whole_o, atol=1e-5)
    np.testing.assert_allclose(last, whole_s, atol=1e-5)


@pytest.mark.parametrize("entry", ENTRIES)
def test_in_the_kernels_a_very_fast_decay_overflows_nothing(a_chunk_a_step,
                                                            entry):
    x, g, beta, state0 = kernel_inputs(128, entry=entry)
    g = g * 600.0
    o, s = kernels(x, g, beta, state0)
    grads = jax.grad(lambda g, beta: kernels(
        x, g, beta, state0)[0].sum(), argnums=(0, 1))(g, beta)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    assert all(np.isfinite(x).all() for x in grads)


@pytest.mark.parametrize("entry", ENTRIES)
def test_the_kernels_bf16_activations_stay_near_the_float32_form(
        a_chunk_a_step, entry):
    x, *rest = kernel_inputs(150, entry=entry)
    want, want_s = kernels(x, *rest)
    got, got_s = kernels(tuple(a.astype(jnp.bfloat16) for a in x), *rest)
    assert got.dtype == jnp.bfloat16 and got_s.dtype == jnp.float32
    err = np.abs(np.asarray(got, np.float32) - want).max()
    assert 0 < err < 0.03 * np.abs(want).max()
    assert np.abs(got_s - want_s).max() < 0.03 * np.abs(want_s).max()


@pytest.mark.parametrize("entry", ENTRIES)
def test_the_xla_form_and_the_kernels_agree_to_the_bf16_products_rounding(
        a_chunk_a_step, entry):
    """What every shape but the cell's runs (``_spans_form``, behind
    XLA's slices and norms where the conv's output comes whole) beside
    what the cell runs, on the same bf16 inputs, in value and in gradient:
    the same chunked algorithm at the same precisions, so they differ by
    the order of float32 sums and where a bf16 operand rounds, far below
    what either differs from the float32 recurrence by."""
    args = kernel_inputs(150, seed=2, dtype=jnp.bfloat16, entry=entry)
    xla = on_q_k_v(lambda *a: dr._spans_form(*a[:5], dr.CHUNK, a[5]))
    exact = on_q_k_v(recurrence)(*in_float32(args))
    got, want = kernels(*args), xla(*args)
    for a, b, c in zip(got, want, exact):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        apart_, off = np.abs(a - b).max(), np.abs(b - c).max()
        assert apart_ <= 0.008 * np.abs(c).max() and apart_ <= 2 * off
    got, want = _objective(kernels)(*args), _objective(xla)(*args)
    exact = _objective(on_q_k_v(recurrence))(*in_float32(args))
    for name, a, b, c in zip(_names(entry), got, want, exact, strict=True):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        apart_, off = np.abs(a - b).max(), np.abs(b - c).max()
        assert apart_ <= 0.02 * np.abs(c).max(), name
        assert apart_ <= 2 * off + 1e-6, name


def test_the_kernels_norms_are_xlas_to_one_bf16_rounding(a_chunk_a_step):
    """The conv's whole bf16 output through the kernels that norm what
    they load, beside ``l2norm_heads``, the slices and the SAME kernels on
    three normed arrays.  The normed values round to bf16 once either
    way, so o, the last state and the gradients of g and beta are the
    same to a twentieth of their distance from the float32 recurrence;
    the float32 cotangents of q and k go through the norm's derivative
    before their one rounding and not between two, so qkv's gradient
    moves by one bf16 step of its largest entry, and no further from the
    recurrence's."""
    args = kernel_inputs(150, seed=4, dtype=jnp.bfloat16, b=2, groups=2,
                         entry="qkv")
    on_three = on_q_k_v(lambda q, k, v, *rest: kernels((q, k, v), *rest))
    on_exact = on_q_k_v(recurrence)
    float32 = lambda xs: [np.asarray(x, np.float32) for x in xs]  # noqa: E731
    for a, b, c in zip(float32(kernels(*args)), float32(on_three(*args)),
                       on_exact(*in_float32(args))):
        assert np.abs(a - b).max() <= 0.05 * np.abs(b - c).max()
    (dqkv, *got), (dqkv_three, *want), (dqkv_exact, *exact) = (
        float32(_objective(fn)(*a)[:3]) for fn, a in (
            (kernels, args), (on_three, args),
            (on_exact, in_float32(args))))
    for name, a, b, c in zip(("g", "beta"), got, want, exact):
        assert np.abs(a - b).max() <= 0.05 * np.abs(b - c).max(), name
    assert np.abs(dqkv - dqkv_three).max() <= 2 ** -7 * np.abs(dqkv_exact).max()
    assert np.abs(dqkv - dqkv_exact).mean() \
        <= 1.05 * np.abs(dqkv_three - dqkv_exact).mean()
    assert np.abs(dqkv - dqkv_exact).max() < 0.02 * np.abs(dqkv_exact).max()


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("backend,dtype,chunk,width,runs", [
    ("tpu", jnp.bfloat16, 64, 128, True),
    ("cpu", jnp.bfloat16, 64, 128, False),
    ("tpu", jnp.float32, 64, 128, False),
    ("tpu", jnp.bfloat16, 32, 128, False),
    ("tpu", jnp.bfloat16, 64, 64, False),
])
def test_which_form_runs_is_read_from_the_call(monkeypatch, backend, dtype,
                                               chunk, width, runs, entry):
    """Both entries, from the call alone: which of the two forms each
    hands its arrays to."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    ran = []
    o = jnp.zeros((1, 128, 4, width), dtype), None
    monkeypatch.setattr(dr, "_in_kernels",
                        lambda *a: ran.append("kernels") or o)
    monkeypatch.setattr(dr, "_spans_form", lambda *a: ran.append("xla") or o)
    q = jnp.ones((1, 128, 2, width), dtype)
    g = jnp.zeros((1, 128, 4), jnp.float32)
    if entry == "qkv":
        dr.gated_delta_rule_qkv(whole(q, q, o[0]), g, g, 2, width,
                                chunk=chunk)
    else:
        dr.gated_delta_rule(q, q, o[0], g, g, chunk=chunk)
    assert ran == ["kernels" if runs else "xla"]


def test_a_group_s_values_in_no_whole_blocks_behind_q_and_k_take_xla(
        monkeypatch):
    """Three value heads on a key head: 2 G dk lanes of q and k are no
    whole number of a group's 3 dv, so no column block of ``qkv`` starts
    where v does, and the conv's whole output takes the XLA form where
    three arrays take the kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dr._kernels_run(jnp.bfloat16, 2, 128, 6, 128, 64)
    assert not dr._kernels_run(jnp.bfloat16, 2, 128, 6, 128, 64, whole=True)
    assert dr._kernels_run(jnp.bfloat16, 3, 128, 6, 128, 64, whole=True)
