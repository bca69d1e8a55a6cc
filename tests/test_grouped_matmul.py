"""The experts' grouped matmuls (``ops/moe.py``): megablox's kernels under
the tile ``gmm_tiling`` chooses, in interpret mode, against a dense loop
over the groups; and ``tile_fill``, the scalar that says how well that
tile fits the routing a step had, against a count of the kernel's visits."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

# the module: the package's attribute ``gmm`` is the function
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")


@pytest.fixture
def on_megablox(monkeypatch):
    """``grouped_matmul`` takes the chip's path, its kernels interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("gmm", "tgmm"):
        monkeypatch.setattr(megablox, name, functools.partial(
            getattr(megablox, name), interpret=True))


def _sizes_by_tile(m, groups, tile):
    """Groups smaller than, equal to and larger than the row tile, an empty
    one between them, one that starts inside a tile and ends on an edge,
    and the rest of the ``m`` rows spread over the other groups."""
    head = [tile // 4, tile, 0, tile + tile // 2 + 8, tile // 4 - 8]
    rest = m - sum(head)
    tail = [rest // (groups - len(head))] * (groups - len(head))
    tail[-1] += rest - sum(tail)
    return np.asarray(head + tail, np.int32)


def _dense(rows, w, sizes):
    """Each held group's rows times its matrix, float32; zero behind."""
    out, start = [], 0
    for g, size in enumerate(sizes):
        part = rows[start:start + size].astype(jnp.float32)
        out.append(part @ w[g].astype(jnp.float32) if g < w.shape[0]
                   else jnp.zeros((size, w.shape[-1]), jnp.float32))
        start += size
    return jnp.concatenate(out)


@pytest.mark.parametrize("m,groups,held", [
    pytest.param(1024, 8, 8, id="every-group-held"),
    pytest.param(1024, 8, 6, id="held-6-of-8"),
    pytest.param(2048, 8, 5, id="held-5-of-8-ends-in-the-empty-groups-tile"),
    pytest.param(512, 512, 64, id="a-decode-steps-rows-one-a-group"),
])
def test_megablox_under_the_chosen_tile_equals_a_loop_over_the_groups(
        on_megablox, m, groups, held):
    """Forward, the rows' gradient and the weights' gradient, each with
    the tile of its own shape, where groups are smaller than, as large as
    and larger than the row tile, one is empty and ``H < E``: the rows
    behind the held groups come out zero and get no gradient."""
    d, f = 256, 128
    tiling = moe.gmm_tiling(m, d, f, 4)
    assert tiling is not None and m % tiling[0] == 0
    sizes = (np.ones(groups, np.int32) if m == groups
             else _sizes_by_tile(m, groups, tiling[0]))
    assert sizes.sum() == m and (sizes >= 0).all()
    keys = jax.random.split(jax.random.key(m + held), 3)
    rows = jax.random.normal(keys[0], (m, d), jnp.float32)
    w = jax.random.normal(keys[1], (held, d, f), jnp.float32) * 0.1
    cotangent = jax.random.normal(keys[2], (m, f), jnp.float32)

    def through(fn):
        return jax.value_and_grad(
            lambda rows, w: (fn(rows, w) * cotangent).sum(), argnums=(0, 1))
    got_y = moe.grouped_matmul(rows, w, jnp.asarray(sizes))
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda r, w: moe.grouped_matmul(r, w, jnp.asarray(sizes)))(rows, w))
    want_y = _dense(rows, w, sizes)
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    behind = np.arange(m) >= sizes[:held].sum()
    assert not np.asarray(got_y)[behind].any()
    (_, got), (_, want) = (
        through(lambda r, w: moe.grouped_matmul(r, w, jnp.asarray(sizes)))(
            rows, w),
        through(lambda r, w: _dense(r, w, sizes))(rows, w))
    for name, g, v in zip(("rows", "w"), got, want):
        np.testing.assert_allclose(g, v, rtol=2e-5, atol=2e-4, err_msg=name)
    assert not np.asarray(got[0])[behind].any()


def _visits(sizes, held, tile):
    """The (group, row tile) overlaps megablox's grid walks, by rows."""
    owner = np.repeat(np.arange(len(sizes)), sizes)
    pairs = {(g, r // tile) for r, g in enumerate(owner) if g < held}
    return len(pairs)


@pytest.mark.parametrize("tile", [512, 256, 128])
@pytest.mark.parametrize("held", [64, 16, 1])
def test_the_fill_is_the_held_rows_over_the_rows_the_visits_multiply(
        tile, held):
    rng = np.random.default_rng(tile + held)
    sizes = rng.multinomial(8192, np.full(128, 1 / 128)).astype(np.int32)
    sizes[3] += sizes[2]
    sizes[2] = 0                                    # an empty held group
    got = float(moe.tile_fill(jnp.asarray(sizes), held, (tile, 128, 128)))
    want = sizes[:held].sum() / (_visits(sizes, held, tile) * tile)
    assert got == pytest.approx(want, rel=1e-6)
    assert 0 < got <= 1


def test_the_fill_at_its_ends():
    """Groups that end on the tile's edges fill it; no held row is 0; a
    layer that takes ``ragged_dot`` has no tile to fit, and says 1."""
    whole = jnp.asarray([256, 512, 0, 256], jnp.int32)
    assert float(moe.tile_fill(whole, 3, (256, 128, 128))) == 1.0
    assert float(moe.tile_fill(whole, 3, (512, 128, 128))) == 0.5
    assert float(moe.tile_fill(jnp.asarray([0, 0, 1024], jnp.int32), 2,
                               (256, 128, 128))) == 0.0
    assert float(moe.tile_fill(whole, 3, None)) == 1.0


def test_the_held_layers_statistics_carry_the_fill():
    """``dropless_moe_ffn`` over a share of the experts, at a shape a tile
    divides: the fill of ITS row tile over the counts it returned."""
    n, k, d, f, experts, held = 256, 4, 128, 128, 8, 3
    keys = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(keys[0], (n, d), jnp.float32)
    w_router = jax.random.normal(keys[1], (d, experts), jnp.float32)
    w_gate, w_up = (jax.random.normal(key, (held, d, f), jnp.float32) * 0.1
                    for key in keys[2:4])
    w_down = jax.random.normal(keys[4], (held, f, d), jnp.float32) * 0.1
    _, stats, idx = moe.dropless_moe_ffn(x, w_router, w_gate, w_up, w_down,
                                         k=k, choices=True)
    tiling = moe.gmm_tiling(n * k, d, f, 4)
    assert tiling is not None
    sizes = np.bincount(np.asarray(idx).ravel(), minlength=experts)
    want = sizes[:held].sum() / (_visits(sizes, held, tiling[0]) * tiling[0])
    assert float(stats.tile_fill) == pytest.approx(want, rel=1e-6)
    assert float(stats.held_rows) == sizes[:held].sum()
