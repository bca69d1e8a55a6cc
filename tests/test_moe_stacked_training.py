"""A training scan's layer reads its experts in their stack, in place
(``ops/moe._megablox_at``; ``models/_common.experts_in_place``): loss and
every leaf's gradient against the road that reads the scan's own slice
(``ops/moe._megablox``, what the three models ran until PR 66), megablox's
kernels interpreted, at widths a tile divides."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import deepseek_v3, llama, qwen3_next
from ray_tpu.ops import moe
from test_grouped_matmul import on_megablox as kernels_interpreted  # noqa: F401

WIDTH = 128                   # the model's and an expert's: one lane tile


@pytest.fixture
def on_megablox(kernels_interpreted, monkeypatch):  # noqa: F811
    """``grouped_matmul`` takes the chip's path, its kernels interpreted
    (``tests/test_grouped_matmul.py``'s fixture); the rows go to the
    experts and back by XLA's gathers (the row kernels of a held share
    choose interpret mode by the backend they see)."""
    monkeypatch.setattr(moe, "_walks_held_rows", lambda *a: False)


def _llama(layers, remat):
    """Every expert held (H == E): 8 experts, 2 a token, 256 tokens."""
    cfg = dataclasses.replace(
        llama.tiny_moe(seq=128), n_embd=WIDTH, ffn_dim=WIDTH, n_layer=layers,
        attn_impl="dense", dtype=jnp.float32, remat=remat,
        remat_policy="full")
    return llama, cfg, 2


def _deepseek_v3(layers, remat):
    """4 of 8 experts held from expert 0 (H < E), ``layers`` sparse layers
    behind one dense one."""
    cfg = deepseek_v3.tiny(
        seq=128, n_embd=WIDTH, expert_dim=WIDTH, n_layer=1 + layers,
        attn_impl="dense", dtype=jnp.float32, remat=remat,
        remat_policy="full")
    return deepseek_v3, cfg, 2


def _qwen3_next(periods, remat):
    """4 of 8 experts held, 3 a token (512 tokens: 1,536 rows); a period
    is a stack of three DeltaNet layers' experts and one of the attention
    layer's, so one period has a stack of three and a stack of ONE, and the
    index of a DeltaNet layer counts through the periods."""
    cfg = qwen3_next.tiny(
        seq=128, n_embd=WIDTH, expert_dim=WIDTH, n_layer=4 * periods,
        attn_impl="dense", dtype=jnp.float32, remat=remat,
        remat_policy="full")
    return qwen3_next, cfg, 4


def _held_groups_of_a_layer_empty(params):
    """The second sparse layer's choice never falls on a held expert: the
    scores lie in (0, 1), the bias decides the choice."""
    bias = params["moe_blocks"]["router"]["select_bias"]
    params["moe_blocks"]["router"]["select_bias"] = bias.at[1, :4].set(-9.0)
    return params


CASES = {
    **{f"{name}-{layers}-layer{'s' * (layers > 1)}{'-remat' * remat}":
       (build, layers, remat, None)
       for name, build in (("llama", _llama), ("deepseek_v3", _deepseek_v3))
       for layers in (1, 3) for remat in (False, True)},
    **{f"qwen3_next-{periods}-period{'s' * (periods > 1)}{'-remat' * remat}":
       (_qwen3_next, periods, remat, None)
       for periods in (1, 2) for remat in (False, True)},
    **{f"deepseek_v3-3-layers-one-with-no-held-row{'-remat' * remat}":
       (_deepseek_v3, 3, remat, _held_groups_of_a_layer_empty)
       for remat in (False, True)},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_stack_read_in_place_gives_the_sliced_roads_loss_and_gradients(
        on_megablox, monkeypatch, case):
    """Both roads run the same kernels under the same tiles on the same
    values, so the loss and each leaf's gradient (every layer's three
    expert matrices, the routers, the shared experts, the embeddings) are
    the same bits; the stack itself gets no gradient of its own (its
    leaves' gradients are the layers' ``tgmm`` results, stacked)."""
    build, layers, remat, arrange = CASES[case]
    mod, cfg, rows = build(layers, remat)
    assert cfg.param_dtype == cfg.dtype
    params = mod.init_params(jax.random.key(layers), cfg)
    if arrange is not None:
        params = arrange(params)
    tokens = np.random.default_rng(layers).integers(
        0, cfg.vocab_size, (rows, 129)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens)}

    def run():
        fn = jax.jit(jax.value_and_grad(lambda p: mod.loss_fn(p, batch, cfg)))
        return fn, fn(params)

    in_place, (loss, grads) = run()
    text = str(in_place.lower(params).as_text())
    stacked = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
        params) if "experts" in jax.tree_util.keystr(path)]
    assert stacked and all(leaf.ndim == 4 for leaf in stacked)
    monkeypatch.setattr(
        moe, "_megablox_at", lambda rows, w, stack, sizes, group_sizes, held:
        moe._megablox(rows, w, group_sizes))
    sliced, (want_loss, want) = run()
    # the two programs differ, and by the road: only the first multiplies
    # under counts laid among a whole stack's groups
    assert text != str(sliced.lower(params).as_text())
    assert np.isfinite(float(loss)) and float(loss) == float(want_loss)
    flat, flat_want = (jax.tree_util.tree_leaves_with_path(g)
                       for g in (grads, want))
    assert len(flat) == len(flat_want) > 10
    for (path, got), (_, ref) in zip(flat, flat_want):
        name = jax.tree_util.keystr(path)
        assert got.shape == ref.shape and got.dtype == ref.dtype, name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                      err_msg=name)
    moved = [jax.tree_util.keystr(path) for path, g in flat
             if "experts" in jax.tree_util.keystr(path) and np.asarray(g).any()]
    assert len(moved) == len(stacked), moved
    if arrange is not None:
        # the layer no held expert saw: its experts' gradient is zero, in
        # the layer's own place of the stack, and its neighbours' is not
        for path, g in flat:
            if "moe_blocks" in jax.tree_util.keystr(path) \
                    and "experts" in jax.tree_util.keystr(path):
                g = np.asarray(g)
                assert not g[1].any() and g[0].any() and g[2].any()


def test_the_counts_lie_among_the_stacks_groups_at_the_layers_place():
    """``_sizes_in_stack``: the held counts at groups ``at x H ..``, zero
    elsewhere; a layer that holds a share has one more group behind the
    stack's, which is what makes megablox zero the rows no held group
    covers."""
    counts = jnp.asarray([5, 0, 7, 3, 9, 1], jnp.int32)
    whole = np.asarray(moe._sizes_in_stack(counts, 6, 18, jnp.int32(2)))
    assert whole.shape == (18,)
    assert whole[12:].tolist() == [5, 0, 7, 3, 9, 1] and not whole[:12].any()
    share = np.asarray(moe._sizes_in_stack(counts, 2, 6, jnp.int32(1)))
    assert share.tolist() == [0, 0, 5, 0, 0, 0, 0]


def test_parameters_wider_than_the_compute_type_take_the_slice(on_megablox):
    """Float32 parameters under bf16 compute: the slice carries the cast,
    and a cast of the whole stack would hold every expert a second time,
    so ``grouped_matmul`` reads ``w`` as it always did."""
    rows = jnp.ones((512, WIDTH), jnp.bfloat16)
    w = jnp.ones((4, WIDTH, WIDTH), jnp.float32)
    sizes = jnp.asarray([128, 128, 128, 128], jnp.int32)
    whole = jnp.ones((12, WIDTH, WIDTH), jnp.float32)
    in_stack = moe._sizes_in_stack(sizes, 4, 12, jnp.int32(1))

    def product(stack):
        return str(jax.make_jaxpr(lambda r, w: moe.grouped_matmul(
            r, w.astype(r.dtype), sizes, stack))(rows, w))
    assert product((whole, in_stack)) == product(None)
    assert "12,128,128" not in product((whole, in_stack))
    assert "12,128,128" in product((whole.astype(jnp.bfloat16), in_stack))
