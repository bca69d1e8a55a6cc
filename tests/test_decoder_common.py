"""What the two decoders share (models/_common.py) and ask of ops: the
attention choice passed through ``attn_impl``, the remat rule, the batch's
two forms and the loss head, and GPT-2's layer norm (XLA's own at every
width).  Both models' ``tiny`` presets, on the CPU; the flash kernel runs
in interpret mode."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from ray_tpu.models import deepseek_v3, gpt2, llama
from ray_tpu.models._common import next_token_nll, remat_block, split_batch

MODELS = {"gpt2": (gpt2, gpt2.tiny()), "llama": (llama, llama.tiny()),
          "llama_moe": (llama, llama.tiny_moe(seq=64)),
          "deepseek_v3": (deepseek_v3, deepseek_v3.tiny(seq=64))}
T = 64                        # flash_runs(64, "flash"): one 64-wide block


def _model(name, **over):
    mod, cfg = MODELS[name]
    return mod, dataclasses.replace(cfg, **over)


def _tokens(cfg, rows=2, length=T + 1, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length)).astype(np.int32)


def _block_of_layer_0(mod, cfg, params):
    """(the model's own block as fn(x, layer params) -> x, its arguments)"""
    lp = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jnp.ones((2, T, cfg.n_embd), cfg.dtype)
    if mod is gpt2:
        return partial(mod._block, cfg=cfg), (x, lp)
    return (lambda x, lp: mod._block(x, lp, cfg)[0]), (x, lp)


# ------------------------------------------------------------ attention
@pytest.mark.parametrize("name", sorted(MODELS))
def test_flash_matches_dense_on_the_logits(name):
    """``attn_impl="flash"`` (the kernel, interpreted here) against
    ``"dense"`` (ops.attention.dense_attention), in float32 so that no
    routing decision of the experts hangs on a rounding."""
    mod, dense = _model(name, attn_impl="dense", dtype=jnp.float32)
    params = mod.init_params(jax.random.key(0), dense)
    toks = _tokens(dense)[:, :T]
    want = mod.forward(params, toks, dense)
    got = mod.forward(params, toks,
                      dataclasses.replace(dense, attn_impl="flash"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("value", ["blockwise", "nope"])
@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_unknown_attn_impl_raises(name, value):
    mod, cfg = _model(name, attn_impl=value)
    params = mod.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="unknown attn_impl"):
        mod.loss_fn(params, {"tokens": _tokens(cfg)}, cfg)


# ---------------------------------------------------------------- remat
@pytest.mark.parametrize("value", ["dots", "nope"])
@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_unknown_remat_policy_raises(name, value):
    mod, cfg = _model(name, remat_policy=value)
    params = mod.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="unknown remat_policy"):
        mod.loss_fn(params, {"tokens": _tokens(cfg)}, cfg)


@pytest.mark.parametrize("impl,length", [("dense", T), ("auto", T),
                                         ("flash", 192)])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_remat_attn_raises_where_flash_will_not_run(name, impl, length):
    """One error, from ``remat_block``: the kept names exist only in the
    flash kernel's vjp (``auto`` is dense off a TPU; 192 has no tile)."""
    mod, cfg = _model(name, attn_impl=impl, remat_policy="attn")
    if mod is gpt2:
        cfg = dataclasses.replace(cfg, n_positions=256)
    params = mod.init_params(jax.random.key(0), cfg)
    batch = {"tokens": _tokens(cfg, length=length + 1)}
    with pytest.raises(ValueError, match="flash attention will not run"):
        mod.loss_fn(params, batch, cfg)


@pytest.mark.parametrize("name,policy,kept", [
    ("gpt2", "full", 0), ("gpt2", "attn", 2), ("gpt2", "attn_qkv", 3),
    ("llama", "full", 0), ("llama", "attn", 2), ("llama", "attn_qkv", 2)])
def test_remat_policy_keeps_the_flash_residuals(capsys, name, policy, kept):
    """What a block saves for its backward beside its arguments: nothing
    (full), the kernel's output and lse (attn), the qkv projection too
    (attn_qkv: GPT-2's block tags it, Llama's does not)."""
    mod, cfg = _model(name, attn_impl="flash", dtype=jnp.float32)
    params = mod.init_params(jax.random.key(0), cfg)
    block, args = _block_of_layer_0(mod, cfg, params)
    block = remat_block(block, policy, True)
    print_saved_residuals(lambda x, lp: block(x, lp).sum(), *args)
    saved = [line for line in capsys.readouterr().out.splitlines()
             if line.strip() and "from the argument" not in line]
    assert len(saved) == kept, saved
    if kept:
        assert any("flash_attn_lse" in line for line in saved)
        heads = (2, T, cfg.n_head, cfg.head_dim)
        assert any(str(list(heads)).replace(" ", "") in line.replace(" ", "")
                   for line in saved), saved


@pytest.mark.parametrize("name", sorted(MODELS))
def test_remat_attn_trains_like_full(name):
    """The whole loss and its gradients under ``attn`` against ``full``:
    what is kept changes no value."""
    mod, full = _model(name, attn_impl="flash", dtype=jnp.float32)
    params = mod.init_params(jax.random.key(0), full)
    batch = {"tokens": _tokens(full)}
    attn = dataclasses.replace(full, remat_policy="attn")
    l0, g0 = jax.value_and_grad(lambda p: mod.loss_fn(p, batch, full))(params)
    l1, g1 = jax.value_and_grad(lambda p: mod.loss_fn(p, batch, attn))(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- the loss
def _reference_nll(logits, targets):
    logp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    return float(-jnp.take_along_axis(logp, jnp.asarray(targets)[..., None],
                                      -1).mean())


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-5)])
def test_next_token_nll_is_minus_log_softmax(dtype, tol):
    """logsumexp of the float32 logits less the target's own logit, on
    the very values the activation-dtype logits hold."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 3, (2, 8, 50)), dtype)
    targets = rng.integers(0, 50, (2, 8)).astype(np.int32)
    got = next_token_nll(logits, targets)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(float(got), _reference_nll(logits, targets),
                               rtol=tol)


@pytest.mark.parametrize("form", ["tokens", "inputs_targets"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-3)])
@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_loss_fn_is_the_nll_of_the_models_logits(name, dtype, tol, form):
    """Each model's ``loss_fn`` (its own head under its ``lm_head`` scope,
    then ``next_token_nll``) against ``-log_softmax`` of its ``forward``,
    through both forms of a batch."""
    mod, cfg = _model(name, dtype=dtype)
    params = mod.init_params(jax.random.key(0), cfg)
    toks = _tokens(cfg, length=17)
    batch = {"tokens": toks} if form == "tokens" else \
        {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    inputs, targets = split_batch(batch)
    np.testing.assert_array_equal(inputs, toks[:, :-1])
    np.testing.assert_array_equal(targets, toks[:, 1:])
    want = _reference_nll(mod.forward(params, inputs, cfg), targets)
    np.testing.assert_allclose(float(mod.loss_fn(params, batch, cfg)), want,
                               rtol=tol)


# --------------------------------------------------- GPT-2's layer norm
def _np_layer_norm(x, scale, bias, eps=1e-5):
    x = np.asarray(x, np.float64)
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * np.asarray(scale, np.float64) \
        + np.asarray(bias, np.float64)


def _ln_inputs(shape, dtype):
    ks = jax.random.split(jax.random.key(shape[-1]), 3)
    x = (jax.random.normal(ks[0], shape, jnp.float32) * 3 + 1).astype(dtype)
    scale = jax.random.normal(ks[1], shape[-1:], jnp.float32)
    bias = jax.random.normal(ks[2], shape[-1:], jnp.float32)
    return x, scale, bias


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 0.05)])
@pytest.mark.parametrize("shape", [(32, 128), (4, 16, 768), (7, 11, 1600)])
def test_layer_norm_matches_numpy(shape, dtype, tol):
    """XLA's norm at the widths of GPT-2 small and XL and at a lane-tile
    width, odd row counts among them; statistics in float32, the result
    in the input's type."""
    x, scale, bias = _ln_inputs(shape, dtype)
    got = gpt2._layer_norm(x, scale, bias)
    assert got.dtype == dtype and got.shape == shape
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               _np_layer_norm(x, scale, bias),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("width", [128, 768, 1600])
def test_layer_norm_grads_match_the_closed_form(width):
    """d/dx, d/dscale, d/dbias of mean(LN(x)^2) against the closed form
    in float64 numpy."""
    x, scale, _ = _ln_inputs((8, 6, width), jnp.float32)
    scale = scale * 0.1 + 1.3
    bias = jnp.zeros((width,), jnp.float32)
    g = jax.grad(lambda x, s, b: (gpt2._layer_norm(x, s, b) ** 2).mean(),
                 argnums=(0, 1, 2))(x, scale, bias)
    x64, s64 = np.asarray(x, np.float64), np.asarray(scale, np.float64)
    mu, var = x64.mean(-1, keepdims=True), x64.var(-1, keepdims=True)
    rstd = 1 / np.sqrt(var + 1e-5)
    xhat = (x64 - mu) * rstd
    dy = 2 * (xhat * s64) / x64.size              # bias is zero
    dxhat = dy * s64
    dx = rstd * (dxhat - dxhat.mean(-1, keepdims=True)
                 - xhat * (dxhat * xhat).mean(-1, keepdims=True))
    want = (dx, (dy * xhat).sum((0, 1)), dy.sum((0, 1)))
    for a, b in zip(g, want):
        np.testing.assert_allclose(np.asarray(a, np.float64), b,
                                   rtol=2e-3, atol=1e-7)
