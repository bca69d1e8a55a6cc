"""The plain float32 GPT-2 against the program's model at a tiny size on
the CPU: loss_fn, and prefill -> decode through the paged cache; the
operation count against the model file's; the table of peaks."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import device, flops, manifest
from perfbench.reference import gpt2_ref
from ray_tpu.models import gpt2

CFG = gpt2.GPT2Config(vocab_size=211, n_positions=48, n_embd=64, n_layer=3,
                      n_head=4, dtype=jnp.float32, remat=False,
                      attn_impl="dense")
# both sides compute in float32 here, so they differ by the order of sums
# only; 2e-4 is ~100 float32 roundings of O(1) logits, and a wrong mask,
# position or cache slot moves logits by ~0.1
ATOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.key(3), CFG)


def test_logits_equal_the_programs_forward(params):
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 40))
    want = gpt2_ref.logits(params, toks, CFG.n_head)
    got = gpt2.forward(params, jnp.asarray(toks, jnp.int32), CFG)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < ATOL


def test_loss_equals_loss_fn(params):
    toks = np.random.default_rng(1).integers(0, CFG.vocab_size, (3, 33))
    batch = {"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
             "targets": jnp.asarray(toks[:, 1:], jnp.int32)}
    per_seq = gpt2_ref.loss(params, toks[:, :-1], toks[:, 1:], CFG.n_head)
    assert per_seq.shape == (3,)
    got = float(gpt2.loss_fn(params, batch, CFG))
    assert got == pytest.approx(float(per_seq.mean()), abs=ATOL)
    # a real loss: near ln(vocab) at random weights
    assert abs(got - np.log(CFG.vocab_size)) < 0.5


def test_loss_catches_lower_precision(params):
    """The tolerance would fail a forward in bf16."""
    toks = np.random.default_rng(2).integers(0, CFG.vocab_size, (2, 33))
    low = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    got = np.asarray(gpt2.forward(params, jnp.asarray(toks), low))
    want = np.asarray(gpt2_ref.logits(params, toks, CFG.n_head))
    assert np.abs(got - want).max() > ATOL


def test_prefill_then_decode_through_the_paged_cache(params):
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    n, k, bs = 21, 4, 8
    prompt = np.random.default_rng(4).integers(0, CFG.vocab_size, n).tolist()
    cache = PagedKVCache(16, CFG.n_layer, bs, CFG.n_head, CFG.head_dim,
                         dtype=np.float32)
    try:
        cache.alloc_seq("s", n)
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = prompt
        logits, ks, vs = gpt2.forward_prefill(
            params, jnp.asarray(padded), CFG, last_pos=jnp.int32(n - 1))
        cache.scatter_prefill("s", np.asarray(ks, np.float32)[:, 0],
                              np.asarray(vs, np.float32)[:, 0], n)
        got, seq = [np.asarray(logits)[0]], list(prompt)
        for _ in range(k):
            seq.append(int(np.argmax(got[-1])))
            blk, off, _ = cache.append_slot("s")
            table = cache.table("s")
            tables = np.zeros((1, 6), np.int32)
            tables[0, :len(table)] = table
            at = np.asarray([len(seq) - 1], np.int32)
            lg, nk, nv = gpt2.forward_decode(
                params, np.asarray([seq[-1]], np.int32), at, cache.pool,
                tables, at, CFG)
            cache.write_token(blk, off, np.asarray(nk[:, 0], np.float32),
                              np.asarray(nv[:, 0], np.float32))
            got.append(np.asarray(lg)[0])
        ref = np.asarray(gpt2_ref.logits(params, [seq], CFG.n_head))[0]
        for i, g in enumerate(got):
            assert np.abs(g - ref[n - 1 + i]).max() < ATOL, i
    finally:
        cache.close()


@pytest.mark.parametrize("name,preset", [
    ("gpt2-124m", gpt2.gpt2_small), ("gpt2-xl-1558m", gpt2.gpt2_xl),
    ("gpt2-large-774m", gpt2.gpt2_large)])
def test_flops_copy_equals_the_model_files(name, preset):
    sizes = json.loads((manifest.BENCH_DIR / "configs" / f"{name}.json")
                       .read_text())
    cfg = preset()
    fam = manifest.family(sizes["family"])
    fam.check_sizes(sizes, cfg)             # the file is the preset's sizes
    assert flops.param_count(sizes) == gpt2.param_count_analytic(cfg)
    for seq in (128, 1024):
        assert flops.flops_per_token(sizes, seq) == \
            gpt2.flops_per_token(cfg, seq)


def test_the_param_count_is_the_trees(params):
    sizes = {"vocab_size": CFG.vocab_size, "n_positions": CFG.n_positions,
             "n_embd": CFG.n_embd, "n_layer": CFG.n_layer}
    leaves = jax.tree_util.tree_leaves(params)
    assert flops.param_count(sizes) == sum(x.size for x in leaves)


def test_peaks_know_the_v5e_and_nothing_else():
    v5e = device.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and "source" in v5e
    with pytest.raises(KeyError):
        device.peaks_for("TPU v9 imaginary")


def test_a_config_whose_sizes_differ_from_the_preset_is_refused():
    sizes = json.loads((manifest.BENCH_DIR / "configs" / "gpt2-124m.json")
                       .read_text())
    with pytest.raises(ValueError):
        manifest.family("gpt2").check_sizes(sizes, gpt2.gpt2_xl())
