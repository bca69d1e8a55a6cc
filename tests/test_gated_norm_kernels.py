"""``ops/ssm.gated_rms_norm``: the two Pallas kernels (interpret mode on
the CPU, small blocks) against the four lines that define them, in value
and in every gradient, and which of the two a call takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import ssm

EPS = 1e-6


def definition(o, z, scale):
    """The four lines as ``models/qwen3_next._gdn_mixer`` had them, on
    (B, T, H dv)."""
    dv = scale.shape[0]
    x = o.astype(jnp.float32).reshape(*o.shape[:2], -1, dv)
    x = x * lax.rsqrt((x * x).mean(-1, keepdims=True) + EPS) \
        * scale.astype(jnp.float32)
    x = x * jax.nn.silu(z.astype(jnp.float32).reshape(x.shape))
    return x.astype(o.dtype).reshape(o.shape)


def kernels(o, z, scale):
    return ssm._gated_norm_kernels(o, z, scale, EPS, True)


def inputs(b, t, h, dv, seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    # heads of different sizes: a head's rsqrt is its own
    o = rng.normal(size=(b, t, h, dv)) * rng.uniform(0.1, 4.0, (1, 1, h, 1))
    z = rng.normal(size=(b, t, h * dv)) * 2.0
    scale = rng.uniform(0.5, 1.5, (dv,))
    return (jnp.asarray(o.reshape(b, t, h * dv), jnp.float32).astype(dtype),
            jnp.asarray(z, jnp.float32).astype(dtype),
            jnp.asarray(scale, jnp.float32))


def f32(a):
    return np.asarray(a, np.float32)


# rows a time block, rows a step, lanes a block at most; T, B, H, dv: one
# block of one step; several time blocks and two batch rows (dscale's sums
# go on across grid steps); several steps a block; two lane blocks of two
# heads (the sums start anew a lane block); heads two tiles wide; several
# of everything
CASES = [
    pytest.param(32, 32, 512, 32, 1, 1, 128, id="one_block"),
    pytest.param(32, 16, 512, 96, 2, 2, 128, id="three_blocks_two_rows"),
    pytest.param(64, 16, 512, 64, 1, 4, 128, id="four_steps_a_block"),
    pytest.param(32, 16, 256, 64, 2, 4, 128, id="two_lane_blocks"),
    pytest.param(32, 32, 512, 64, 1, 3, 256, id="wide_heads_a_block_each"),
    pytest.param(64, 32, 256, 128, 2, 6, 128, id="three_of_each"),
]


@pytest.fixture
def blocks(monkeypatch):
    def set_sizes(rows, step, lanes):
        monkeypatch.setattr(ssm, "NORM_ROWS", rows)
        monkeypatch.setattr(ssm, "NORM_STEP", step)
        monkeypatch.setattr(ssm, "NORM_LANES", lanes)
    return set_sizes


@pytest.mark.parametrize("rows,step,lanes,t,b,h,dv", CASES)
def test_the_kernels_value_is_the_definitions(blocks, rows, step, lanes, t,
                                              b, h, dv):
    blocks(rows, step, lanes)
    o, z, scale = inputs(b, t, h, dv)
    got, want = kernels(o, z, scale), definition(o, z, scale)
    assert got.dtype == want.dtype == jnp.bfloat16 and got.shape == o.shape
    # the same float32 arithmetic and one rounding: a bf16 step apart at most
    np.testing.assert_allclose(f32(got), f32(want), rtol=2 ** -7, atol=1e-6)
    assert np.abs(f32(want)).max() > 0.5


def _probed(fn, probe):
    def loss(o, z, scale):
        return (fn(o, z, scale).astype(jnp.float32) * probe).sum()
    return loss


@pytest.mark.parametrize("rows,step,lanes,t,b,h,dv", CASES)
@pytest.mark.parametrize("param_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32_scale", "bf16_scale"])
def test_the_kernels_gradients_are_the_definitions(blocks, rows, step, lanes,
                                                   t, b, h, dv, param_dtype):
    """do (through the head's rsqrt), dz (through the silu) and dscale
    (summed over every row of every batch row and over the heads, in the
    scale's type) under a cotangent that differs by head and by lane."""
    blocks(rows, step, lanes)
    o, z, scale = inputs(b, t, h, dv, seed=1)
    scale = scale.astype(param_dtype)
    probe = jnp.asarray(np.random.default_rng(2).normal(size=o.shape),
                        jnp.float32)
    got = jax.grad(_probed(kernels, probe), argnums=(0, 1, 2))(o, z, scale)
    want = jax.grad(_probed(definition, probe), argnums=(0, 1, 2))(
        o, z, scale)
    for name, a, d in zip(("do", "dz", "dscale"), got, want):
        assert a.dtype == d.dtype and a.shape == d.shape, name
        size = np.abs(f32(d)).max()
        assert size > 0.1, name
        np.testing.assert_allclose(f32(a), f32(d), rtol=2 ** -7,
                                   atol=2 ** -8 * size, err_msg=name)


def test_no_head_and_no_batch_row_reads_another(blocks):
    """Each head of each row of a batch of two is what it is alone, in
    value and in gradient: a head's mean of squares is over its own lanes
    and a block's rows are one sequence's."""
    blocks(32, 16, 256)
    o, z, scale = inputs(2, 64, 4, 128, seed=3)
    loss = lambda o, z: (kernels(o, z, scale).astype(jnp.float32) ** 2).sum()
    both, dboth = kernels(o, z, scale), jax.grad(loss, argnums=(0, 1))(o, z)
    for row in range(2):
        for head in (0, 3):
            at = (slice(row, row + 1), slice(None),
                  slice(128 * head, 128 * head + 128))
            alone = kernels(o[at], z[at], scale)
            np.testing.assert_array_equal(f32(alone), f32(both[at]))
            for d, dall in zip(jax.grad(loss, argnums=(0, 1))(o[at], z[at]),
                               dboth):
                np.testing.assert_array_equal(f32(d), f32(dall[at]))
    # and a head far larger than its neighbours leaves them as they were
    loud = o.at[:, :, 128:256].multiply(64.0)
    quiet = kernels(loud, z, scale)
    np.testing.assert_array_equal(f32(quiet[..., :128]), f32(both[..., :128]))
    np.testing.assert_array_equal(f32(quiet[..., 256:]), f32(both[..., 256:]))


@pytest.mark.parametrize("backend,dtype,t,dv,runs", [
    pytest.param("tpu", jnp.bfloat16, 1024, 128, True, id="the_cells_kind"),
    pytest.param("cpu", jnp.bfloat16, 1024, 128, False, id="the_cpu"),
    pytest.param("tpu", jnp.float32, 1024, 128, False, id="float32"),
    pytest.param("tpu", jnp.bfloat16, 1000, 128, False, id="a_ragged_t"),
    pytest.param("tpu", jnp.bfloat16, 1024, 8, False, id="a_narrow_head"),
    pytest.param("tpu", jnp.bfloat16, 1024, 192, False,
                 id="no_whole_lane_tiles"),
])
@pytest.mark.parametrize("by_head", [False, True], ids=["flat", "by_head"])
def test_which_form_runs_is_read_from_the_call(monkeypatch, backend, dtype,
                                               t, dv, runs, by_head):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    shape = (2, t, 2, dv) if by_head else (2, t, 2 * dv)
    o = jax.ShapeDtypeStruct(shape, dtype)
    z = jax.ShapeDtypeStruct((2, t, 2 * dv), dtype)
    scale = jax.ShapeDtypeStruct((dv,), jnp.float32)
    assert ssm._norm_kernels_run(o, z, scale) is runs
    # a function of its own: a trace is kept by function and shapes
    traced = jax.make_jaxpr(
        lambda o, z, s: ssm.gated_rms_norm(o, z, s, EPS))(o, z, scale)
    assert ("pallas_call" in str(traced)) is runs
    assert traced.out_avals[0].shape == shape
    assert traced.out_avals[0].dtype == dtype


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("by_head", [False, True], ids=["flat", "by_head"])
def test_on_the_cpu_the_entry_is_the_four_lines(dtype, by_head):
    o, z, scale = inputs(2, 40, 3, 8, dtype=dtype)
    want = definition(o, z, scale)
    if by_head:
        o, want = o.reshape(2, 40, 3, 8), want.reshape(2, 40, 3, 8)
    got = ssm.gated_rms_norm(o, z, scale, EPS)
    assert got.dtype == dtype and got.shape == o.shape
    np.testing.assert_array_equal(f32(got), f32(want))
