"""The dropless layer's way back from the experts (``ops/moe.py``): the
router's weight multiplies the hidden rows, and the combine is the dispatch
transposed, so its gradient reads no output row and a checkpointed layer's
backward runs no down projection a second time."""

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


@pytest.mark.parametrize("k,held,first_held", CASES)
def test_the_gradients_equal_a_dense_float32_reference(k, held, first_held):
    x, expert_idx, *rest = _draw(k, held, seed=1)
    cotangent = jax.random.normal(jax.random.key(7), (N, D), jnp.float32)

    def loss(fn, x, weights, w_gate, w_up, w_down):
        return (fn(x, expert_idx, weights, w_gate, w_up, w_down)
                * cotangent).sum()

    got = jax.grad(lambda *a: loss(
        lambda *b: moe.dropless_experts(*b, num_experts=E,
                                        first_held=first_held)[0], *a),
        argnums=(0, 1, 2, 3, 4))(x, *rest)
    want = jax.grad(lambda *a: loss(
        lambda *b: _dense(*b, first_held=first_held), *a),
        argnums=(0, 1, 2, 3, 4))(x, *rest)
    for name, g, w in zip(("x", "weights", "w_gate", "w_up", "w_down"),
                          got, want):
        assert np.abs(np.asarray(w)).max() > 0, name
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5, err_msg=name)
    if held < E:
        # a slot that chose an absent expert has no say in the output
        absent = (expert_idx - first_held) % E >= held
        assert absent.any()
        assert np.abs(np.asarray(got[1])[np.asarray(absent)]).max() == 0


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
