"""``ops/moe.choose_experts``: the Pallas kernel that picks a token's k
experts in VMEM (interpret mode on the CPU) against ``lax.top_k`` and
``take_along_axis``, which define it: the same ids in the same order with
the same ties, the same picked numbers; the gradient a select where the
parent's was a scatter-add, equal to the last bit; and which of the two
forms a call takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import moe


def definition(keys, payload, k):
    """The parent's lines: ``route_softmax``'s (the keys are the payload)
    and ``route_sigmoid``'s."""
    vals, idx = lax.top_k(keys, k)
    if payload is None:
        return idx, vals
    return idx, jnp.take_along_axis(payload, idx, axis=-1)


def kernel(keys, payload, k):
    return moe._choice_by_kernel(keys, payload, k, interpret=True)


def drawn(n, e, seed=0, biased=False):
    """Softmax rows (keys that are their own payload), or sigmoid scores
    under a bias a tenth of their spread, which reorders the choice."""
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(n, e)) * 2, jnp.float32)
    if not biased:
        return jax.nn.softmax(logits, -1), None
    scores = jax.nn.sigmoid(logits)
    return scores + jnp.asarray(rng.normal(size=(e,)) * 0.1,
                                jnp.float32), scores


def with_ties(keys):
    """Rows 0-7 one number everywhere, rows 8-15 their largest number at
    lanes 5-8 and at the last lane, rows 16-23 in equal pairs of lanes."""
    e = keys.shape[1]
    keys = keys.at[:8].set(0.25)
    keys = keys.at[8:16, 5:9].set(2.0).at[8:16, e - 1].set(2.0)
    return keys.at[16:24].set(jnp.repeat(keys[16:24, :e // 2], 2, axis=1))


def same(got, want):
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# rows, experts, k, whether a bias reorders the choice: Kanana's routers,
# Qwen3-Next's, SDAR's and Keye's, and the rule's smallest call
SHAPES = [
    pytest.param(512, 128, 6, True, id="kanana"),
    pytest.param(512, 512, 10, False, id="qwen3_next"),
    pytest.param(256, 128, 8, False, id="sdar_one_tile"),
    pytest.param(512, 256, 3, True, id="two_blocks_biased"),
]


@pytest.mark.parametrize("n,e,k,biased", SHAPES)
def test_the_kernel_picks_what_top_k_picks(n, e, k, biased):
    keys, payload = drawn(n, e, biased=biased)
    same(kernel(keys, payload, k), definition(keys, payload, k))


@pytest.mark.parametrize("n,e,k,biased", SHAPES)
def test_equal_keys_go_to_the_lowest_ids_in_top_ks_order(n, e, k, biased):
    keys, payload = drawn(n, e, seed=1, biased=biased)
    keys = with_ties(keys)
    idx, picked = kernel(keys, payload, k)
    same((idx, picked), definition(keys, payload, k))
    assert np.asarray(idx[:8]).tolist() == [list(range(k))] * 8
    first = [5, 6, 7, 8, e - 1][:k]
    assert np.asarray(idx[8:16, :len(first)]).tolist() == [first] * 8


def test_a_row_with_fewer_than_k_finite_keys_names_k_experts_once():
    """``-inf`` where ``_within_best_groups`` shut a group out: the rounds
    go on among the shut-out lanes, lowest first, and name none twice."""
    keys, payload = drawn(256, 128, seed=2, biased=True)
    keys = keys.at[:, 3:].set(-jnp.inf)
    idx, picked = kernel(keys, payload, 6)
    same((idx, picked), definition(keys, payload, 6))
    assert np.asarray(idx[0]).tolist()[3:] == [3, 4, 5]


# what the rule sends to XLA: few rows (a decode step), rows that are no
# whole tiles, experts that are no whole lane blocks (OLMoE, LFM2, Ling)
@pytest.mark.parametrize("n,e,k,biased", [
    pytest.param(64, 128, 6, True, id="decode_rows"),
    pytest.param(512, 64, 8, False, id="olmoe_experts"),
    pytest.param(320, 128, 4, True, id="no_whole_tiles"),
])
def test_other_shapes_take_top_k_as_they_did(n, e, k, biased):
    assert not moe._choice_in_kernel(n, e)
    keys, payload = drawn(n, e, biased=biased)
    same(moe.choose_experts(with_ties(keys), payload, k),
         definition(with_ties(keys), payload, k))


@pytest.mark.parametrize("backend,n,e,runs", [
    ("tpu", 16384, 512, True),
    ("tpu", 16384, 128, True),
    ("tpu", 2048, 128, True),
    ("tpu", 256, 128, True),
    ("cpu", 16384, 512, False),
    ("tpu", 8192, 64, False),       # OLMoE; LFM2 and Ling at a chunk
    ("tpu", 2048, 32, False),       # Trinity
    ("tpu", 128, 128, False),       # SDAR's pass, every decode step
    ("tpu", 4, 128, False),
    ("tpu", 320, 128, False),
])
def test_which_form_runs_is_read_from_the_call(monkeypatch, backend, n, e,
                                               runs):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert moe._choice_in_kernel(n, e) is runs


# ------------------------------------------------------------- gradients
def parent_softmax(x, w_router, k, norm_topk):
    """``route_softmax`` as the parent had it, ``jax.grad`` through
    ``lax.top_k``."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, k)
    if norm_topk:
        gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    return expert_idx, gate_vals


def parent_sigmoid(x, w_router, select_bias, k, weight_scale, eps=1e-20,
                   n_group=1, topk_group=1):
    """``route_sigmoid`` as the parent had it."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    select = scores + lax.stop_gradient(select_bias.astype(jnp.float32))
    if n_group > 1:
        select = moe._within_best_groups(select, n_group, topk_group)
    _, expert_idx = lax.top_k(select, k)
    chosen = jnp.take_along_axis(scores, expert_idx, axis=-1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + eps)
    return expert_idx, weights * weight_scale


def routers(which, k, bias):
    """-> (ours, the parent's): each ``(x, w_router) -> (expert_idx,
    weights)``."""
    if which.startswith("softmax"):
        norm = which == "softmax_norm"
        return (lambda x, w: moe.route_softmax(x, w, k, norm_topk=norm)[:2],
                lambda x, w: parent_softmax(x, w, k, norm))
    groups = dict(n_group=4, topk_group=2) if which == "sigmoid_groups" \
        else {}
    return (lambda x, w: moe.route_sigmoid(x, w, bias, k, 2.5, **groups),
            lambda x, w: parent_sigmoid(x, w, bias, k, 2.5, **groups))


def objective(route, probe):
    def loss(x, w):
        return (route(x, w)[1] * probe).sum()
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))


@pytest.mark.parametrize("in_kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("which", ["softmax", "softmax_norm", "sigmoid",
                                   "sigmoid_groups"])
def test_a_routers_gradient_is_the_parents_to_the_last_bit(monkeypatch,
                                                           which, in_kernel):
    """Both forms' backward is the select over (N, E); the parent's was the
    scatter-add of ``top_k`` / ``take_along_axis``.  A row's k are distinct,
    so the two are the same sums of one term."""
    n, d, e, k = 256, 32, 128, 6
    monkeypatch.setattr(moe, "_choice_in_kernel", lambda n, e: in_kernel)
    rng = np.random.default_rng(3)
    x, w, bias, probe = (
        jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
        for shape, scale in (((n, d), 1), ((d, e), 0.3), ((e,), 0.1),
                             ((n, k), 1)))
    ours, parent = routers(which, k, bias)
    same(ours(x, w), parent(x, w))
    (got, got_grads), (want, want_grads) = \
        objective(ours, probe)(x, w), objective(parent, probe)(x, w)
    assert float(got) == float(want)
    for a, b in zip(got_grads, want_grads):
        assert np.abs(np.asarray(b)).max() > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_keys_carry_no_gradient_and_the_payload_a_select():
    keys, payload = drawn(64, 128, biased=True)
    probe = jnp.asarray(np.random.default_rng(5).normal(size=(64, 6)),
                        jnp.float32)

    def loss(keys, payload):
        return (moe.choose_experts(keys, payload, 6)[1] * probe).sum()
    d_keys, d_payload = jax.grad(loss, argnums=(0, 1))(keys, payload)
    assert not np.asarray(d_keys).any()
    idx = np.asarray(moe.choose_experts(keys, payload, 6)[0])
    want = np.zeros((64, 128), np.float32)
    np.put_along_axis(want, idx, np.asarray(probe), axis=1)
    np.testing.assert_array_equal(np.asarray(d_payload), want)
    text = jax.jit(jax.grad(loss, argnums=1)).lower(keys, payload).as_text()
    assert "scatter" not in text


def test_a_step_traces_the_kernels_body_once_a_shape():
    moe._router_choice.cache_clear()
    keys, payload = drawn(256, 128, biased=True)
    for _ in range(3):
        kernel(keys, payload, 6)
        kernel(keys, None, 6)
    info = moe._router_choice.cache_info()
    assert (info.misses, info.hits) == (2, 4)
