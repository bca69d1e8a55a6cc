"""The first backward through ``ops/ssm.ssd_scan`` and the two-matrix
``relu ** 2`` form of ``ops/moe.dropless_experts`` (what training
Nemotron-H forced), on the CPU at small sizes: ``jax.grad`` through the
chunked scan against ``jax.grad`` through the token-by-token recurrence,
for every operand and the entering state; the training step's pair of
Pallas kernels (``ssd_scan_train``, PR 74) in interpret mode against both;
the experts against a loop over tokens and picks, forward and gradients,
with all experts held and with a share.  The SwiGLU form's own cases are
tests/test_moe_combine.py's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe, ssm

B, T, H, P, G, N, CHUNK = 2, 21, 16, 4, 8, 6, 8      # T: 2 chunks and 5


def recurrence(x, dt, a, b, c, state0):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t, token
    by token; head h reads group h // (H / G)."""
    b, c = (jnp.repeat(v, H // G, axis=2) for v in (b, c))

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return s, jnp.einsum("zhpn,zhn->zhp", s, c_t)
    s, y = jax.lax.scan(step, state0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), s


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.key(0), 8)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, T, H)) - 1.0)
    # the last three positions are padding: dt = 0 freezes the state
    dt = dt * (jnp.arange(T) < T - 3)[None, :, None]
    return {"x": jax.random.normal(keys[0], (B, T, H, P)), "dt": dt,
            "a": -jnp.exp(jax.random.normal(keys[2], (H,))),
            "b": jax.random.normal(keys[3], (B, T, G, N)),
            "c": jax.random.normal(keys[4], (B, T, G, N)),
            "state0": jax.random.normal(keys[5], (B, H, P, N)),
            "probe_y": jax.random.normal(keys[6], (B, T, H, P)),
            "probe_s": jax.random.normal(keys[7], (B, H, P, N))}


def _loss(fn, ops, with_state0):
    def loss(x, dt, a, b, c, state0):
        y, s = fn(x, dt, a, b, c, state0 if with_state0 else None)
        return (y * ops["probe_y"]).sum() + (s * ops["probe_s"]).sum()
    return loss


@pytest.mark.parametrize("with_state0", [True, False])
def test_the_scans_gradient_is_the_recurrences(operands, with_state0):
    """8 groups, a length that is no whole number of chunks, ``dt = 0``
    padding; from a carried state and from none."""
    args = [operands[k] for k in ("x", "dt", "a", "b", "c", "state0")]

    def chunked(x, dt, a, b, c, state0):
        return ssm.ssd_scan(x, dt, a, b, c, CHUNK, state0)

    def plain(x, dt, a, b, c, state0):
        zero = jnp.zeros((B, H, P, N)) if state0 is None else state0
        return recurrence(x, dt, a, b, c, zero)
    argnums = tuple(range(6 if with_state0 else 5))
    with jax.default_matmul_precision("highest"):
        got_v, got = jax.jit(jax.value_and_grad(
            _loss(chunked, operands, with_state0), argnums))(*args)
        want_v, want = jax.jit(jax.value_and_grad(
            _loss(plain, operands, with_state0), argnums))(*args)
    assert float(got_v) == pytest.approx(float(want_v), rel=1e-5)
    for name, g, w in zip(("x", "dt", "a", "b", "c", "state0"), got, want):
        scale = np.abs(w).max()
        assert scale > 0, name
        assert np.abs(g - w).max() < 1e-4 * scale, name
    # the padding moves nothing: no gradient reaches its inputs
    assert np.abs(np.asarray(got[0])[:, T - 3:]).max() == 0
    assert np.abs(np.asarray(got[3])[:, T - 3:]).max() == 0


def test_the_padded_tail_leaves_the_state_where_it_was(operands):
    args = [operands[k] for k in ("x", "dt", "a", "b", "c")]
    _, whole = ssm.ssd_scan(*args, CHUNK, operands["state0"])
    _, cut = ssm.ssd_scan(*(v[:, :T - 3] if v.ndim > 1 else v for v in args),
                          CHUNK, operands["state0"])
    np.testing.assert_allclose(whole, cut, atol=1e-5)


def test_a_sequence_in_two_calls_has_the_whole_sequences_gradient(operands):
    """``state0`` lets a sequence run as several calls: the gradient through
    the carried state is the one call's."""
    args = [operands[k] for k in ("x", "dt", "a", "b", "c", "state0")]
    cut = 13                                 # not on a chunk's edge

    def halves(x, dt, a, b, c, state0):
        y1, s = ssm.ssd_scan(x[:, :cut], dt[:, :cut], a, b[:, :cut],
                             c[:, :cut], CHUNK, state0)
        y2, s = ssm.ssd_scan(x[:, cut:], dt[:, cut:], a, b[:, cut:],
                             c[:, cut:], CHUNK, s)
        return jnp.concatenate([y1, y2], 1), s

    def whole(x, dt, a, b, c, state0):
        return ssm.ssd_scan(x, dt, a, b, c, CHUNK, state0)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(_loss(halves, operands, True),
                               tuple(range(6))))(*args)
        want = jax.jit(jax.grad(_loss(whole, operands, True),
                                tuple(range(6))))(*args)
    for g, w in zip(got, want):
        assert np.abs(g - w).max() < 1e-4 * np.abs(w).max()


# ------------------------------------------ the training step's two kernels
# 4 chunks of 128 in 2 grid steps, 2 groups of 2 heads that share B and C
# (the ratio ``recurrence`` reads from this module), 128 state columns
KB, KT, KH, KG, KN = 2, 512, 4, 2, 128


@functools.lru_cache(maxsize=None)
def _kernel_operands(p):
    keys = jax.random.split(jax.random.key(p), 6)
    return {"xbc": jax.random.normal(keys[0], (KB, KT, KH * p + 2 * KG * KN)),
            "dt": jax.nn.softplus(jax.random.normal(keys[1], (KB, KT, KH))
                                  - 1.0),
            "a": -jnp.exp(jax.random.normal(keys[2], (KH,))),
            "d": jax.random.normal(keys[3], (KH,)),
            "probe": jax.random.normal(keys[4], (KB, KT, KH, p))}


def _apart(xbc, p, n=KN):
    """The conv's x | B | C -> x (B, T, H, P), B and C (B, T, G, N)."""
    lead = xbc.shape[:2]
    return (xbc[..., :KH * p].reshape(*lead, KH, p),
            xbc[..., KH * p:KH * p + KG * n].reshape(*lead, KG, n),
            xbc[..., KH * p + KG * n:].reshape(*lead, KG, n))


def _by_the_scan(xbc, dt, a, d, p):
    x, b, c = _apart(xbc, p)
    return ssm.ssd_scan(x, dt, a, b, c, ssm.SCAN_CHUNK)[0] + d[:, None] * x


def _by_the_recurrence(xbc, dt, a, d, p):
    x, b, c = _apart(xbc, p)
    zero = jnp.zeros((KB, KH, p, KN))
    return recurrence(x, dt, a, b, c, zero)[0] + d[:, None] * x


@pytest.mark.parametrize("p", [
    pytest.param(64, id="two_heads_share_a_lane_block"),
    pytest.param(128, id="a_head_a_lane_block")])
@pytest.mark.parametrize("definition", [_by_the_scan, _by_the_recurrence])
def test_the_training_kernels_equal_the_scan_and_the_recurrence(definition,
                                                                p):
    """``ssd_scan_fwd`` and the written-out ``ssd_scan_bwd`` in interpret
    mode: y, and the gradients of x, B and C (as the cotangent of the
    conv's one array), dt, a and D, float32."""
    ops = _kernel_operands(p)
    args = [ops[k] for k in ("xbc", "dt", "a", "d")]
    # two chunks a grid step forward, one backward: two steps and four
    assert ssm.SCAN_CHUNKS == {"ssd_scan_fwd": 2, "ssd_scan_bwd": 1}

    def loss(form):
        return lambda *args: (form(*args) * ops["probe"]).sum()
    kernels = functools.partial(ssm.ssd_scan_kernels, head_dim=p, groups=KG,
                                interpret=True)
    with jax.default_matmul_precision("highest"):
        got_v, got = jax.jit(jax.value_and_grad(loss(kernels),
                                                (0, 1, 2, 3)))(*args)
        want_v, want = jax.jit(jax.value_and_grad(
            loss(functools.partial(definition, p=p)), (0, 1, 2, 3)))(*args)
        y, want_y = kernels(*args), definition(*args, p=p)
    # log-decays down to -125 a chunk: how a chunk's sums are added up (a
    # product with the triangle of ones here, ``cumsum`` there) shows
    assert float(got_v) == pytest.approx(float(want_v), rel=1e-4)
    np.testing.assert_allclose(y, want_y, atol=1e-4 * np.abs(want_y).max())
    gx, gb, gc = _apart(got[0], p)
    wx, wb, wc = _apart(want[0], p)
    for name, g, w in zip(("x", "b", "c", "dt", "a", "d"),
                          (gx, gb, gc, *got[1:]), (wx, wb, wc, *want[1:])):
        scale = np.abs(w).max()
        assert scale > 0, name
        assert np.abs(g - w).max() < 1e-4 * scale, name


def test_the_chunks_a_grid_step_takes_change_no_number(monkeypatch):
    """``SCAN_CHUNKS`` is how the walk is cut into grid steps, forward
    and backward: one chunk a step and two give the same y and the same
    gradients (the backward's step of two walks its chunks from the
    second to the first)."""
    p = 64
    ops = _kernel_operands(p)
    args = [ops[k] for k in ("xbc", "dt", "a", "d")]

    def both():
        kernels = functools.partial(ssm.ssd_scan_kernels, head_dim=p,
                                    groups=KG, interpret=True)
        return jax.value_and_grad(
            lambda *args: (kernels(*args) * ops["probe"]).sum(),
            (0, 1, 2, 3))(*args)
    monkeypatch.setattr(ssm, "SCAN_CHUNKS",
                        {"ssd_scan_fwd": 1, "ssd_scan_bwd": 2})
    got_v, got = both()
    monkeypatch.undo()
    want_v, want = both()
    assert float(got_v) == pytest.approx(float(want_v), rel=1e-6)
    for g, w in zip(got, want):
        assert np.abs(g - w).max() < 1e-5 * np.abs(w).max()


@pytest.mark.parametrize("t,n,runs", [
    pytest.param(512, 128, True, id="whole_chunks_of_128_columns"),
    pytest.param(500, 128, False, id="ragged_t"),
    pytest.param(512, 64, False, id="narrow_state")])
def test_a_shape_the_kernels_rule_refuses_takes_the_scan(monkeypatch, t, n,
                                                         runs):
    """Which form a training call takes is read from the call: on a TPU,
    and at a shape the kernels' rule takes; everything else is
    ``ssd_scan`` under autodiff, with the same numbers."""
    p = 64
    ops = _kernel_operands(p)
    xbc = ops["xbc"][:, :t, :KH * p + 2 * KG * n]
    dt = ops["dt"][:, :t]
    assert not ssm._scan_kernels_run(xbc, KH, p, KG, 128)   # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm._scan_kernels_run(xbc, KH, p, KG, 128) == runs
    assert not ssm._scan_kernels_run(xbc, KH, p, KG, 64)
    assert not ssm._scan_kernels_run(xbc.astype(jnp.bfloat16), KH, p, KG, 128)
    if runs:
        return
    x, b, c = _apart(xbc, p, n)
    want = ssm.ssd_scan(x, dt, ops["a"], b, c, 128)[0] \
        + ops["d"][:, None] * x
    got = ssm.ssd_scan_train(xbc, dt, ops["a"], ops["d"], p, KG, 128)
    np.testing.assert_array_equal(got, want)


# --------------------------------------------- the two-matrix relu^2 experts
E, K, D, F, TOKENS = 8, 3, 16, 12, 16


@pytest.fixture(scope="module")
def layer():
    keys = jax.random.split(jax.random.key(1), 6)
    idx = jnp.stack([jax.random.permutation(k, E)[:K] for k in
                     jax.random.split(keys[0], TOKENS)]).astype(jnp.int32)
    return {"x": jax.random.normal(keys[1], (TOKENS, D)), "idx": idx,
            "weights": jax.random.uniform(keys[2], (TOKENS, K)) + 0.1,
            "w_up": jax.random.normal(keys[3], (E, D, F)) * 0.3,
            "w_down": jax.random.normal(keys[4], (E, F, D)) * 0.3,
            "probe": jax.random.normal(keys[5], (TOKENS, D))}


def loop(x, idx, weights, w_up, w_down, first):
    """Every token through each of its picks that is held, one at a time:
    ``w down(relu(up(x)) ** 2)``; no gate matrix."""
    held = w_up.shape[0]
    y = jnp.zeros_like(x)
    for t in range(x.shape[0]):
        for slot in range(idx.shape[1]):
            e = (int(idx[t, slot]) - first) % E
            if e < held:
                hidden = jnp.square(jax.nn.relu(x[t] @ w_up[e]))
                y = y.at[t].add(weights[t, slot] * (hidden @ w_down[e]))
    return y


@pytest.mark.parametrize("first,held", [(0, E), (0, 3), (5, 2), (6, 4)])
def test_the_two_matrix_experts_equal_a_loop(layer, first, held):
    """All experts held, a share, a share that wraps round the router's
    width: values and the gradients of x, the weights and both matrices."""
    ids = [(first + i) % E for i in range(held)]
    w_up, w_down = layer["w_up"][jnp.asarray(ids)], \
        layer["w_down"][jnp.asarray(ids)]

    def ours(x, weights, w_up, w_down):
        y, sizes = moe.dropless_experts(x, layer["idx"], weights, None, w_up,
                                        w_down, num_experts=E,
                                        first_held=first)
        return (y * layer["probe"]).sum(), (y, sizes)

    def plain(x, weights, w_up, w_down):
        y = loop(x, layer["idx"], weights, w_up, w_down, first)
        return (y * layer["probe"]).sum(), y
    args = (layer["x"], layer["weights"], w_up, w_down)
    with jax.default_matmul_precision("highest"):
        (_, (y, sizes)), got = jax.value_and_grad(
            ours, (0, 1, 2, 3), has_aux=True)(*args)
        (_, want_y), want = jax.value_and_grad(
            plain, (0, 1, 2, 3), has_aux=True)(*args)
    np.testing.assert_allclose(y, want_y, atol=1e-5)
    assert np.abs(want_y).max() > 0.1
    counts = np.bincount(np.asarray(layer["idx"]).ravel(), minlength=E)
    np.testing.assert_array_equal(np.asarray(sizes)[:held], counts[ids])
    for g, w in zip(got, want):
        assert np.abs(w).max() > 0
        np.testing.assert_allclose(g, w, atol=1e-5 * np.abs(w).max() + 1e-7)


def test_the_swiglu_form_is_what_it_was(layer):
    """With a gate matrix the layer is SwiGLU as before; the two forms
    differ, and neither is the other under a unit gate."""
    gate = jnp.ones_like(layer["w_up"])
    args = (layer["x"], layer["idx"], layer["weights"])
    swiglu, _ = moe.dropless_experts(*args, gate, layer["w_up"],
                                     layer["w_down"], num_experts=E)
    rows = layer["x"][:, None, :]
    up = jnp.einsum("tkd,tkdf->tkf", jnp.broadcast_to(
        rows, (TOKENS, K, D)), layer["w_up"][layer["idx"]])
    g = jnp.einsum("tkd,tkdf->tkf", jnp.broadcast_to(
        rows, (TOKENS, K, D)), gate[layer["idx"]])
    want = jnp.einsum("tkf,tkfd->td", jax.nn.silu(g) * up
                      * layer["weights"][..., None],
                      layer["w_down"][layer["idx"]])
    np.testing.assert_allclose(swiglu, want, atol=1e-4)
    relu2, _ = moe.dropless_experts(*args, None, layer["w_up"],
                                    layer["w_down"], num_experts=E)
    assert np.abs(np.asarray(relu2 - swiglu)).max() > 0.1


def test_the_stack_of_two_matrix_experts_has_two_leaves():
    from ray_tpu.models._common import experts_in_place
    two = experts_in_place({"w_up": jnp.zeros((3, 4, 5, 6)),
                            "w_down": jnp.zeros((3, 4, 6, 5))})
    assert [w.shape for w in two] == [(12, 5, 6), (12, 6, 5)]
    three = experts_in_place({"w_gate": jnp.zeros((3, 4, 5, 6)),
                              "w_up": jnp.zeros((3, 4, 5, 6)),
                              "w_down": jnp.zeros((3, 4, 6, 5))})
    assert [w.shape for w in three] == [(12, 5, 6)] * 2 + [(12, 6, 5)]


@pytest.mark.parametrize("m,d,f,want", [
    (98304, 2688, 1856, (512, 384, 1856)),      # Nemotron-H: 1,856 whole
    (98304, 1856, 2688, (512, 1856, 384)),
    (98304, 2048, 768, (256, 2048, 768)),       # Kanana's, as it was
    (163840, 2048, 512, (256, 2048, 512)),      # Qwen3-Next's
    (65536, 2048, 1024, (512, 1024, 1024)),     # OLMoE's
    (98304, 2688, 1864, None),                  # no multiple of 16
    (98300, 2688, 1856, None),                  # rows no multiple of 512
])
def test_the_tile_of_a_width_no_lane_tile_divides(m, d, f, want):
    assert moe.gmm_tiling(m, d, f) == want
    if want is not None:
        assert moe._gmm_vmem_bytes(*want, 2) <= moe._VMEM_DEFAULT - 2 ** 20
