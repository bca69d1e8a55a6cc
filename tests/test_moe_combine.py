"""The dropless layer's way to the experts and back (``ops/moe.py``): the
router's weight multiplies the hidden rows, and the combine is the dispatch
transposed, so its gradient reads no output row and a checkpointed layer's
backward runs no down projection a second time.  One sort makes the
permutation, its inverse, the sorted weights and (beside it) the counts:
the indices are permutations whatever the routing, which is what the
layer's gathers promise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

N, D, F, E = 48, 16, 24, 8


def _draw(k, held, seed=0):
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(keys[0], (N, D), jnp.float32)
    # k distinct experts a token, and weights that do not sum to one
    expert_idx = jnp.argsort(jax.random.uniform(keys[1], (N, E)), axis=-1
                             )[:, :k].astype(jnp.int32)
    weights = jax.random.uniform(keys[2], (N, k), jnp.float32, 0.1, 1.0)
    w_gate = jax.random.normal(keys[3], (held, D, F), jnp.float32) * 0.3
    w_up = jax.random.normal(keys[4], (held, D, F), jnp.float32) * 0.3
    w_down = jax.random.normal(keys[5], (held, F, D), jnp.float32) * 0.3
    return x, expert_idx, weights, w_gate, w_up, w_down


def _dense(x, expert_idx, weights, w_gate, w_up, w_down, *, first_held):
    """Every token through every held expert, in float32, weighted by the
    weight of the slot that chose it (zero where none did)."""
    held = w_gate.shape[0]
    local = (expert_idx - first_held) % E                        # (N, k)
    per_expert = (jax.nn.one_hot(local, E, dtype=jnp.float32)
                  * weights[..., None]).sum(1)[:, :held]         # (N, H)
    hidden = jax.nn.silu(jnp.einsum("nd,hdf->nhf", x, w_gate)) \
        * jnp.einsum("nd,hdf->nhf", x, w_up)
    return jnp.einsum("nhf,hfd,nh->nd", hidden, w_down, per_expert)


def _weight_on_the_output(x, expert_idx, weights, w_gate, w_up, w_down, *,
                          first_held):
    """The combine this one replaced: the experts' rows permuted back and
    weighted by ``nkd,nk->nd``."""
    n, k = expert_idx.shape
    flat = (expert_idx.reshape(n * k) - first_held) % E
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    rows = jnp.take(x, order // k, axis=0)
    gate = moe.grouped_matmul(rows, w_gate, sizes)
    up = moe.grouped_matmul(rows, w_up, sizes)
    out = moe.grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes)
    out = jnp.take(out, jnp.argsort(order), axis=0).reshape(n, k, -1)
    return jnp.einsum("nkd,nk->nd", out, weights)


CASES = [
    pytest.param(2, E, 0, id="k2-all-held"),
    pytest.param(6, E, 0, id="k6-all-held"),
    pytest.param(2, 3, 0, id="k2-held-3-of-8"),
    pytest.param(6, 3, 0, id="k6-held-3-of-8"),
    pytest.param(2, 3, 5, id="k2-held-3-of-8-from-5"),
    pytest.param(6, 2, 7, id="k6-held-2-of-8-from-7-wrapping"),
    pytest.param(8, E, 0, id="k8-all-held"),
    pytest.param(8, 3, 5, id="k8-held-3-of-8-from-5"),
]


@pytest.mark.parametrize("k,held,first_held", CASES)
def test_the_combine_equals_the_weighted_sum_it_replaced(k, held, first_held):
    args = _draw(k, held)
    y, sizes = moe.dropless_experts(*args, num_experts=E,
                                    first_held=first_held)
    assert int(sizes.sum()) == N * k
    if held < E:        # some choices are of absent experts and add nothing
        assert 0 < int(sizes[:held].sum()) < N * k
    np.testing.assert_allclose(
        y, _weight_on_the_output(*args, first_held=first_held),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, _dense(*args, first_held=first_held),
                               rtol=1e-5, atol=1e-5)


def _value_and_grads(fn, x, expert_idx, *rest, seed=7):
    """``fn``'s output and the gradients, for x, the weights and the three
    expert matrices, of its product with one drawn cotangent."""
    cotangent = jax.random.normal(jax.random.key(seed), (N, D), jnp.float32)

    def loss(x, weights, w_gate, w_up, w_down):
        y = fn(x, expert_idx, weights, w_gate, w_up, w_down)
        return (y * cotangent).sum(), y

    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(x, *rest)
    return y, grads


def _both_ways(first_held, *args):
    """(y, gradients) of the layer and of the dense reference."""
    return (_value_and_grads(lambda *a: moe.dropless_experts(
                *a, num_experts=E, first_held=first_held)[0], *args),
            _value_and_grads(lambda *a: _dense(*a, first_held=first_held),
                             *args))


@pytest.mark.parametrize("k,held,first_held", CASES)
def test_the_gradients_equal_a_dense_float32_reference(k, held, first_held):
    x, expert_idx, *rest = _draw(k, held, seed=1)
    (_, got), (_, want) = _both_ways(first_held, x, expert_idx, *rest)
    for name, g, w in zip(("x", "weights", "w_gate", "w_up", "w_down"),
                          got, want):
        assert np.abs(np.asarray(w)).max() > 0, name
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5, err_msg=name)
    if held < E:
        # a slot that chose an absent expert has no say in the output
        absent = (expert_idx - first_held) % E >= held
        assert absent.any()
        assert np.abs(np.asarray(got[1])[np.asarray(absent)]).max() == 0


def _every_token_to_one_expert(expert_idx):
    return jnp.full_like(expert_idx, 6)


def _an_expert_nobody_chose(expert_idx):
    return jnp.where(expert_idx == 5, 7, expert_idx)


def _padded_rows(expert_idx):
    """A decode step's bucket: rows 3 .. 28 are some sequence's, the others
    take the first live row's choice (``models/lfm2.py``)."""
    row = jnp.arange(N)
    return moe.choice_of_live_rows(expert_idx, (row >= 3) & (row < 29))


SKEWED = [
    pytest.param(route, k, held, first_held,
                 id=f"{route.__name__.strip('_')}-k{k}-held-{held}-from-"
                    f"{first_held}")
    for route in (_every_token_to_one_expert, _an_expert_nobody_chose,
                  _padded_rows)
    for k, held, first_held in ((6, E, 0), (8, E, 0), (6, 3, 5))
]


@pytest.mark.parametrize("route,k,held,first_held", SKEWED)
def test_the_sort_gives_permutations_whatever_the_routing(route, k, held,
                                                          first_held):
    """What the gathers promise: ``order`` and ``inverse`` are permutations
    of the N k assignments and each other's inverse, a row's token
    ``order % N`` is a token, the rows lie by expert (as numbered inside
    one), the weights came along, and the counts are ``bincount``'s."""
    _, expert_idx, weights, *_ = _draw(k, held)
    expert_idx = route(expert_idx)
    order, inverse, w_sorted, sizes = map(np.asarray, moe._sorted_assignments(
        expert_idx, weights, E, first_held))
    every = np.arange(N * k)
    np.testing.assert_array_equal(np.sort(order), every)
    np.testing.assert_array_equal(np.sort(inverse), every)
    np.testing.assert_array_equal(order[inverse], every)
    assert (order % N).min() >= 0 and (order % N).max() < N
    local = np.asarray((expert_idx - first_held) % E).T.reshape(-1)
    by_expert = local[order]
    assert (np.diff(by_expert) >= 0).all()
    assert (np.diff(order)[np.diff(by_expert) == 0] > 0).all()   # stable
    np.testing.assert_array_equal(
        w_sorted, np.asarray(weights).T.reshape(-1)[order])
    assert sizes.dtype == np.int32
    np.testing.assert_array_equal(sizes, jnp.bincount(local, length=E))


@pytest.mark.parametrize("route,k,held,first_held", SKEWED)
def test_skewed_routing_equals_the_dense_reference_forward_and_back(
        route, k, held, first_held):
    x, expert_idx, *rest = _draw(k, held, seed=2)
    (y, got), (y_dense, want) = _both_ways(first_held, x, route(expert_idx),
                                           *rest)
    np.testing.assert_allclose(y, y_dense, rtol=1e-4, atol=1e-4)
    for name, g, w in zip(("x", "weights", "w_gate", "w_up", "w_down"),
                          got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4, err_msg=name)
    assert np.abs(np.asarray(want[0])).max() > 0


def _grouped_products(jaxpr):
    """ragged_dot equations of a jaxpr and everything nested in it (the
    grouped matmuls off the TPU, forward and both gradients)."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name.startswith("ragged_dot")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _grouped_products(sub)
    return count


@pytest.mark.parametrize("k,held", [
    pytest.param(2, E, id="k2-all-held"),
    pytest.param(6, 3, id="k6-held-3-of-8"),
])
def test_a_checkpointed_layer_recomputes_no_down_projection(k, held):
    """Forward 3, backward 2 each: 9; the recomputation needs ``gate`` and
    ``up`` again (the hidden rows' gradient reads them) and not ``out``,
    which nothing in the backward reads: 11 where the weight on the output
    rows made 12."""
    x, expert_idx, weights, *ws = _draw(k, held)

    @jax.checkpoint
    def layer(x, weights, w_gate, w_up, w_down):
        return moe.dropless_experts(x, expert_idx, weights, w_gate, w_up,
                                    w_down, num_experts=E)[0]

    def loss(*a):
        return layer(*a).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        x, weights, *ws)
    assert _grouped_products(jaxpr.jaxpr) == 11


# ----------------------------------------- the passes that stop at held_rows
# A layer that holds a share of the experts, at shapes the kernels' tiles
# divide: 1,536 sorted rows are three row tiles of 512, 256 tokens one
# token tile.
HN, HK, HD = 256, 6, 128


def _onto_the_held(first_held, held):
    """Every slot of every token chooses a held expert: the kernels walk
    all N k rows (the dropless promise)."""
    def route(expert_idx):
        slot = jnp.arange(expert_idx.shape[1], dtype=expert_idx.dtype)
        return jnp.broadcast_to((first_held + slot % held) % E,
                                expert_idx.shape)
    return route


def _onto_the_absent(first_held, held):
    """No slot chooses a held expert: ``held_rows`` is 0."""
    def route(expert_idx):
        return jnp.full_like(expert_idx, (first_held + held) % E)
    return route


def _as_drawn(first_held, held):
    return lambda expert_idx: expert_idx


HELD_CASES = [
    pytest.param(dtype, route, held, first_held,
                 id=f"{jnp.dtype(dtype).name}-{route.__name__.strip('_')}-"
                    f"held-{held}-from-{first_held}")
    for dtype in (jnp.bfloat16, jnp.float32)
    for route, held, first_held in (
        (_as_drawn, 3, 0), (_as_drawn, 3, 5), (_as_drawn, 2, 7),
        (_onto_the_absent, 3, 0), (_onto_the_absent, 3, 5),
        (_onto_the_held, 3, 0), (_onto_the_held, 3, 5))
]


def _held_layer(dtype, route, held, first_held, seed=3):
    keys = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(keys[0], (HN, HD), jnp.float32).astype(dtype)
    expert_idx = route(first_held, held)(jnp.argsort(
        jax.random.uniform(keys[1], (HN, E)), axis=-1)[:, :HK].astype(
            jnp.int32))
    weights = jax.random.uniform(keys[2], (HN, HK), jnp.float32, 0.1, 1.0)
    w_gate, w_up = (jax.random.normal(key, (held, HD, F), jnp.float32) * 0.3
                    for key in keys[3:5])
    w_down = jax.random.normal(keys[5], (held, F, HD), jnp.float32) * 0.3
    cotangent = jax.random.normal(keys[6], (HN, HD), jnp.float32)
    return (x, expert_idx, weights, w_gate.astype(dtype), w_up.astype(dtype),
            w_down.astype(dtype)), cotangent.astype(dtype)


@pytest.mark.parametrize("dtype,route,held,first_held", HELD_CASES)
def test_the_passes_that_stop_at_held_rows_equal_the_gathers(
        monkeypatch, dtype, route, held, first_held):
    """``spread_held_rows`` and ``sum_held_slots`` (interpret mode) against
    ``_take_rows``' path: the same bits in every visited row and in every
    token, forward, and through the layer the same output and the same
    gradients of x, the weights and the three expert matrices, with
    ``held_rows`` 0, between two row tiles and N k."""
    (x, expert_idx, weights, *ws), cotangent = _held_layer(
        dtype, route, held, first_held)
    order, inverse, _, sizes = moe._sorted_assignments(
        expert_idx, weights, E, first_held)
    held_rows = sizes[:held].sum()
    expected = {"onto_the_absent": 0, "onto_the_held": HN * HK}.get(
        route.__name__.strip("_"))
    if expected is None:
        assert 0 < int(held_rows) < HN * HK
        # inside a row tile, whichever of them the rule gives
        assert all(int(held_rows) % t for t in moe._GMM_ROW_TILES)
    else:
        assert int(held_rows) == expected
    visited = np.arange(HN * HK) < int(held_rows)

    def bits(a):
        return np.asarray(a.astype(jnp.float32))
    np.testing.assert_array_equal(
        bits(moe._spread_rows(x, order, held_rows))[visited],
        bits(moe._spread_rows(x, order))[visited])
    rows = jax.random.normal(jax.random.key(9), (HN * HK, HD),
                             jnp.float32).astype(dtype)
    np.testing.assert_array_equal(
        bits(moe._sum_slots(rows, inverse, HK, held_rows)),
        bits(moe._sum_slots(jnp.where(visited[:, None], rows, 0), inverse,
                            HK)))

    def layer(x, weights, *ws):
        y = moe.dropless_experts(x, expert_idx, weights, *ws, num_experts=E,
                                 first_held=first_held)[0]
        return (y.astype(jnp.float32) * cotangent).sum(), y
    both = []
    for walks in (False, True):
        monkeypatch.setattr(
            moe, "_walks_held_rows",
            lambda n, k, d, f, held, num_experts, dtype, walks=walks:
            walks and held < num_experts)
        step = jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)
        # forward: a spread and a sum; backward: each the other's
        assert str(jax.make_jaxpr(step)(x, weights, *ws)).count(
            "pallas_call") == (4 if walks else 0)
        both.append(step(x, weights, *ws))
    ((_, want_y), want), ((_, got_y), got) = both
    np.testing.assert_array_equal(bits(got_y), bits(want_y))
    for name, g, w in zip(("x", "weights", "w_gate", "w_up", "w_down"),
                          got, want):
        np.testing.assert_array_equal(bits(g), bits(w), err_msg=name)
    if int(held_rows):
        assert np.abs(bits(want[0])).max() > 0
