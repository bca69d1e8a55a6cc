"""``ops/ssm.causal_conv_silu``: the two Pallas kernels (interpret mode on
the CPU, small blocks) against the XLA form that defines them, in value
and in every gradient, and which of the two a call takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm


def definition(x, w):
    return jax.nn.silu(ssm.causal_conv(x, w, None)[0]).astype(x.dtype)


def kernels(x, w):
    return ssm._conv_silu_kernels(x, w, True)


def inputs(b, t, c, k_w, seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, t, c)), jnp.float32).astype(dtype)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (k_w, c)), jnp.float32)
    return x, w


def f32(a):
    return np.asarray(a, np.float32)


# rows a time block, rows a step, channels a block at most; T, B, K, C:
# one block of one step; a tile before a block and a g after it cross a
# block's edge, two batch rows; several steps a block (a step's edge inside
# a block); another K and two channel blocks (the taps' sums start anew a
# channel block); the most taps the kernels take; several of everything
CASES = [
    pytest.param(32, 32, 512, 32, 1, 4, 128, id="one_block"),
    pytest.param(32, 16, 512, 96, 2, 4, 128, id="three_blocks_two_rows"),
    pytest.param(64, 16, 512, 64, 1, 4, 128, id="four_steps_a_block"),
    pytest.param(32, 16, 128, 64, 2, 3, 256, id="three_taps_two_lane_blocks"),
    pytest.param(32, 32, 512, 64, 1, 8, 128, id="eight_taps"),
    pytest.param(64, 32, 256, 128, 2, 2, 512, id="two_taps_two_of_each"),
]


@pytest.fixture
def blocks(monkeypatch):
    def set_sizes(rows, step, lanes):
        monkeypatch.setattr(ssm, "CONV_ROWS", rows)
        monkeypatch.setattr(ssm, "CONV_STEP", step)
        monkeypatch.setattr(ssm, "CONV_LANES", lanes)
    return set_sizes


@pytest.mark.parametrize("rows,step,lanes,t,b,k_w,c", CASES)
def test_the_kernels_value_is_the_definitions(blocks, rows, step, lanes, t,
                                              b, k_w, c):
    blocks(rows, step, lanes)
    x, w = inputs(b, t, c, k_w)
    got, want = kernels(x, w), definition(x, w)
    assert got.dtype == want.dtype == jnp.bfloat16 and got.shape == x.shape
    # the same float32 arithmetic and one rounding: a bf16 step apart at most
    np.testing.assert_allclose(f32(got), f32(want), rtol=2 ** -7, atol=1e-6)
    assert np.abs(f32(want)).max() > 0.5


def _split_loss(fn, probe):
    """What the mixer does with the result: slices into q | k | v, each
    read by something else."""
    def loss(x, w):
        y = fn(x, w)
        kw = y.shape[-1] // 4
        q, k, v = y[..., :kw], y[..., kw:2 * kw], y[..., 2 * kw:]
        return ((q.astype(jnp.float32) * probe[..., :kw]).sum()
                + (jnp.tanh(k.astype(jnp.float32)) * probe[..., kw:2 * kw])
                .sum() + (v.astype(jnp.float32) ** 2 * probe[..., 2 * kw:])
                .sum())
    return loss


@pytest.mark.parametrize("rows,step,lanes,t,b,k_w,c", CASES)
@pytest.mark.parametrize("param_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32_taps", "bf16_taps"])
def test_the_kernels_gradients_are_the_definitions(blocks, rows, step, lanes,
                                                   t, b, k_w, c, param_dtype):
    """dx (the g of the K - 1 rows that follow a row, across a step's and
    a block's edge, zeros past the end), dw (summed over every row of
    every batch row, in the taps' type) and both through a downstream
    slice into q | k | v."""
    blocks(rows, step, lanes)
    x, w = inputs(b, t, c, k_w, seed=1)
    w = w.astype(param_dtype)
    probe = jnp.asarray(np.random.default_rng(2).normal(size=x.shape),
                        jnp.float32)
    got = jax.grad(_split_loss(kernels, probe), argnums=(0, 1))(x, w)
    want = jax.grad(_split_loss(definition, probe), argnums=(0, 1))(x, w)
    for name, a, d in zip(("dx", "dw"), got, want):
        assert a.dtype == d.dtype and a.shape == d.shape, name
        scale = np.abs(f32(d)).max()
        assert scale > 0.1, name
        np.testing.assert_allclose(f32(a), f32(d), rtol=2 ** -7,
                                   atol=2 ** -8 * scale, err_msg=name)


@pytest.mark.parametrize("k_w", [4, 3])
def test_no_batch_row_reads_another(blocks, k_w):
    """A sequence's first K - 1 outputs see zeros before them and its last
    rows' dx no g after them: each row of a batch of two is what it is
    alone, in value and in gradient."""
    blocks(32, 16, 512)
    x, w = inputs(2, 64, 128, k_w, seed=3)
    loss = lambda x, w: (kernels(x, w).astype(jnp.float32) ** 2).sum()
    both, dboth = kernels(x, w), jax.grad(loss)(x, w)
    for row in range(2):
        alone = x[row:row + 1]
        np.testing.assert_array_equal(f32(kernels(alone, w)[0]),
                                      f32(both[row]))
        np.testing.assert_array_equal(f32(jax.grad(loss)(alone, w)[0]),
                                      f32(dboth[row]))
    # and the first output of a row is the last tap's alone
    first = jax.nn.silu(f32(x[:, 0]) * f32(w[k_w - 1]))
    np.testing.assert_allclose(f32(both[:, 0]), first, rtol=2 ** -7,
                               atol=1e-6)


@pytest.mark.parametrize("backend,dtype,t,c,k_w,runs", [
    pytest.param("tpu", jnp.bfloat16, 1024, 256, 4, True, id="the_cells_kind"),
    pytest.param("cpu", jnp.bfloat16, 1024, 256, 4, False, id="the_cpu"),
    pytest.param("tpu", jnp.float32, 1024, 256, 4, False, id="float32"),
    pytest.param("tpu", jnp.bfloat16, 1000, 256, 4, False, id="a_ragged_t"),
    pytest.param("tpu", jnp.bfloat16, 1024, 40, 4, False, id="a_narrow_c"),
    pytest.param("tpu", jnp.bfloat16, 1024, 192, 4, False,
                 id="no_whole_lane_blocks"),
    pytest.param("tpu", jnp.bfloat16, 1024, 256, 9, False, id="nine_taps"),
])
def test_which_form_runs_is_read_from_the_call(monkeypatch, backend, dtype,
                                               t, c, k_w, runs):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x = jax.ShapeDtypeStruct((2, t, c), dtype)
    w = jax.ShapeDtypeStruct((k_w, c), jnp.float32)
    assert ssm._conv_kernels_run(x, w) is runs
    # a function of its own: a trace is kept by function and shapes
    text = str(jax.make_jaxpr(lambda x, w: ssm.causal_conv_silu(x, w))(x, w))
    assert ("pallas_call" in text) is runs


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_on_the_cpu_the_entry_is_the_two_lines(dtype):
    x, w = inputs(2, 40, 24, 4, dtype=dtype)
    got = ssm.causal_conv_silu(x, w)
    assert got.dtype == dtype
    np.testing.assert_array_equal(f32(got), f32(definition(x, w)))
