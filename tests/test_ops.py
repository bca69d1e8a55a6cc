"""Long-context attention ops (SURVEY.md §5.7 greenfield components).

Strategy per SURVEY.md §4: CPU JAX with 8 virtual devices stands in for a
TPU slice; every kernel/schedule is checked against the dense reference
for values AND gradients; the Pallas kernel runs in interpret mode.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import (causal_attention, dense_attention, flash_attention,
                         flash_runs, latent_causal_attention,
                         ring_attention_sharded, ulysses_attention_sharded)

B, T, H, D = 2, 64, 4, 16


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(k, (B, T, H, D), jnp.float32) for k in ks)


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:8]).reshape(2, 1, 1, 4, 1, 1)
    return Mesh(devs, ("data", "fsdp", "pipeline", "context", "tensor",
                       "expert"))


def _allclose(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_matches_dense(qkv, causal):
    q, k, v = qkv
    ref = dense_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, 16)
    _allclose(out, ref)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads(qkv, causal):
    q, k, v = qkv
    # Non-uniform cotangent exercises the full dQ/dK/dV backward kernels.
    w = jnp.linspace(0.5, 1.5, T)[None, :, None, None]

    def loss(fn):
        return lambda q_, k_, v_: (fn(q_, k_, v_) * w).sum()

    gq, gk, gv = jax.grad(
        loss(lambda a, b, c: flash_attention(a, b, c, causal, 16)),
        argnums=(0, 1, 2))(q, k, v)
    dq, dk, dv = jax.grad(
        loss(lambda a, b, c: dense_attention(a, b, c, causal=causal)),
        argnums=(0, 1, 2))(q, k, v)
    _allclose(gq, dq, tol=1e-4)
    _allclose(gk, dk, tol=1e-4)
    _allclose(gv, dv, tol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(qkv, mesh, causal):
    q, k, v = qkv
    ref = dense_attention(q, k, v, causal=causal)
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh, causal=causal))(q, k, v)
    _allclose(out, ref)


def test_ring_attention_grads(qkv, mesh):
    q, k, v = qkv

    @jax.jit
    def loss_r(q, k, v):
        return ring_attention_sharded(q, k, v, mesh=mesh).astype(
            jnp.float32).sum()

    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: dense_attention(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        _allclose(a, b, tol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_dense(qkv, mesh, causal):
    q, k, v = qkv
    ref = dense_attention(q, k, v, causal=causal)
    out = jax.jit(lambda q, k, v: ulysses_attention_sharded(
        q, k, v, mesh=mesh, causal=causal))(q, k, v)
    _allclose(out, ref)


def test_ulysses_rejects_indivisible_heads(qkv, mesh):
    q, k, v = qkv
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(q[:, :, :3], k[:, :, :3], v[:, :, :3],
                                  mesh=mesh)


def test_sharded_inputs_stay_sharded(qkv, mesh):
    """Ring consumes/produces context-sharded arrays without gathering."""
    q, k, v = qkv
    sh = NamedSharding(mesh, P(("data",), "context", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh))(qs, ks, vs)
    assert out.sharding.spec == P(("data",), "context", None, None)
    _allclose(out, dense_attention(q, k, v, causal=True))


def test_gpt2_context_parallel_end_to_end(mesh):
    """Tiny GPT-2 trains with ring attention on a context-sharded mesh and
    matches the dense-attention loss exactly at init."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_lib

    cfg_d = gpt2.tiny()
    cfg_r = gpt2.GPT2Config(**{**cfg_d.__dict__, "attn_impl": "ring",
                               "context_axis": "context", "remat": False})
    rng = jax.random.key(1)
    params = gpt2.init_params(rng, cfg_d)
    tokens = jax.random.randint(jax.random.key(2), (4, 65), 0,
                                cfg_d.vocab_size)
    batch = {"tokens": tokens}
    loss_dense = gpt2.loss_fn(params, batch, cfg_d)
    with mesh_lib.ambient_mesh(mesh):
        loss_ring = jax.jit(
            lambda p, b: gpt2.loss_fn(p, b, cfg_r))(params, batch)
    _allclose(loss_ring, loss_dense, tol=1e-5)


# --------------------------------------------- the choice (ops/attention.py)
TILES = {64: True, 128: True, 192: False, 1024: True, 4096: True}


@pytest.mark.parametrize("impl", ["auto", "dense", "flash"])
@pytest.mark.parametrize("seq_len", sorted(TILES))
@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_flash_runs_says_what_causal_attention_traces(monkeypatch, backend,
                                                      seq_len, impl):
    """``flash_runs`` is the one statement of when the Pallas kernel runs:
    asked for, or ``auto`` on a TPU, and the kernel's block tiles the
    sequence (192 has no clean tile).  The traced call holds a
    ``pallas_call`` named ``flash_fwd`` exactly when it says so."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    want = (impl == "flash" or (impl == "auto" and backend == "tpu")) \
        and TILES[seq_len]
    assert flash_runs(seq_len, impl) is want
    q = jax.ShapeDtypeStruct((1, seq_len, 1, 64), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(
        lambda q, k, v: causal_attention(q, k, v, impl=impl))(q, q, q))
    assert ("name=flash_fwd" in jaxpr) is want
    assert ("pallas_call" in jaxpr) is want


# ------------------------------------------------- the one-pass backward
# (the queries' and keys' parts, the values' width, the last key part one
# (B, T, D_i) array for all heads): what the training cells run, GPT-2 XL,
# OLMoE, Kanana's latent call
BACKWARD_WIDTHS = {"d64": ((64,), 64, False), "d128": ((128,), 128, False),
                   "latent": ((128, 64), 128, True)}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("tiles", [1, 3])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("widths", sorted(BACKWARD_WIDTHS))
def test_flash_backward_equals_dense_gradients(widths, causal, tiles, dtype,
                                               tol):
    """dq, dk and dv of ``flash_bwd`` on the kernels' own flat operands
    against ``dense_attention``'s gradients on the joined ones.  One tile
    is the diagonal alone; three run the masked and the unmasked path and
    accumulate dq over the k-steps.  A shared key part's gradient comes
    back a head."""
    from ray_tpu.ops.flash_attention import (_flash_backward_flat,
                                             _flash_forward_lse_flat)
    parts, dv, shared = BACKWARD_WIDTHS[widths]
    batch, heads, bs = 2, 2, 32
    t = tiles * bs
    keys = iter(jax.random.split(jax.random.key(11), 2 * len(parts) + 2))

    def draw(lead, d):
        return jax.random.normal(next(keys), (lead, t, d),
                                 jnp.float32).astype(dtype)
    qs = tuple(draw(batch * heads, d) for d in parts)
    ks = tuple(draw(batch if shared and i == len(parts) - 1
                    else batch * heads, d) for i, d in enumerate(parts))
    v, do = draw(batch * heads, dv), draw(batch * heads, dv)
    out, lse = _flash_forward_lse_flat(qs, ks, v, causal=causal, bs=bs,
                                       interpret=True)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]
    dqs, dks, dvs = _flash_backward_flat(qs, ks, v, lse, delta, do,
                                         causal=causal, block_size=bs,
                                         interpret=True)

    def heads_out(x):       # (B.H, T, D) or (B, T, D) -> float32 (B,T,H,D)
        x = x.astype(jnp.float32)
        if x.shape[0] == batch:
            return jnp.broadcast_to(x[:, :, None], (batch, t, heads,
                                                    x.shape[-1]))
        return x.reshape(batch, heads, t, -1).transpose(0, 2, 1, 3)

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=causal)
    joined = (jnp.concatenate([heads_out(q) for q in qs], -1),
              jnp.concatenate([heads_out(k) for k in ks], -1), heads_out(v))
    want_q, want_k, want_v = jax.vjp(dense, *joined)[1](heads_out(do))
    got_q = jnp.concatenate([heads_out(dq) for dq in dqs], -1)
    got_k = jnp.concatenate([heads_out(dk) for dk in dks], -1)
    assert [dk.shape[0] for dk in dks] == [batch * heads] * len(parts)
    for got, want in ((got_q, want_q), (got_k, want_k),
                      (heads_out(dvs), want_v)):
        assert got.shape == want.shape
        scale = 1.0 if dtype == jnp.float32 else float(jnp.abs(want).max())
        _allclose(got, want, tol * max(1.0, scale))


# ------------------------------- heads of 64 unsplit, two a 128-lane block
def _split_heads(qkv, heads):
    """(B, 3, T, E) -> q, k, v (B, T, H, 64), float32."""
    b, _, t, _ = qkv.shape
    return [qkv[:, i].astype(jnp.float32).reshape(b, t, heads, 64)
            for i in range(3)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("tiles", [1, 3])
@pytest.mark.parametrize("heads", [12, 5])
def test_unsplit_flash_equals_dense_attention_and_its_gradients(
        capsys, heads, tiles, dtype, tol):
    """``flash_attention_pairs`` on a fused projection (B, 3, T, H x 64)
    against ``dense_attention`` on the split heads, output and vjp.  12
    heads are six whole pairs; 5 end in half a pair, a block whose upper
    lanes lie past the array (the interpreter fills them with NaN, the
    chip with whatever stands there: nothing of them may reach head 4).
    One tile is the diagonal alone; three run the masked and the unmasked
    path, accumulate dq over the k-steps and make delta for every q-block
    at the first.  Under ``remat_block(..., "attn", True)`` the block
    keeps the two names the policy lists and nothing else."""
    from jax.ad_checkpoint import print_saved_residuals
    from ray_tpu.models._common import remat_block
    from ray_tpu.ops.flash_attention import flash_attention_pairs
    batch, bs = 2, 32
    t, e = tiles * bs, heads * 64
    k_qkv, k_do = jax.random.split(jax.random.key(13))
    qkv = jax.random.normal(k_qkv, (batch, 3, t, e), jnp.float32) \
        .astype(dtype)
    do = jax.random.normal(k_do, (batch, t, e), jnp.float32).astype(dtype)

    def pairs(x):
        return flash_attention_pairs(x, heads, bs, True)

    def dense(x):
        return dense_attention(*_split_heads(x, heads)).reshape(batch, t, e)
    got, vjp = jax.vjp(pairs, qkv)
    want, want_vjp = jax.vjp(dense, qkv.astype(jnp.float32))
    assert got.shape == (batch, t, e) and got.dtype == dtype
    _allclose(got.astype(jnp.float32), want, tol)
    (dqkv,), (want_dqkv,) = vjp(do), want_vjp(do.astype(jnp.float32))
    assert dqkv.shape == qkv.shape and dqkv.dtype == dtype
    scale = 1.0 if dtype == jnp.float32 else float(jnp.abs(want_dqkv).max())
    _allclose(dqkv.astype(jnp.float32), want_dqkv, tol * max(1.0, scale))

    kept = remat_block(lambda x: pairs(x * 2), "attn", True)
    print_saved_residuals(lambda x: kept(x).astype(jnp.float32).sum(), qkv)
    saved = [line for line in capsys.readouterr().out.splitlines()
             if line.strip() and "from the argument" not in line]
    assert len(saved) == 2, saved
    assert any("flash_attn_out" in line and f"[{batch},{t},{e}]" in line
               for line in saved), saved
    pair_form = f"f32[{batch},{-(-heads // 2)},2,{t}]"      # lse, compact
    assert any("flash_attn_lse" in line and pair_form in line
               for line in saved), saved


def _kernels_of(jaxpr_text):
    return sorted(set(re.findall(r"name=(flash_(?:fwd|bwd)\w*)", jaxpr_text)))


@pytest.mark.parametrize("embd,heads,seq_len,impl,backend,unsplit", [
    (1600, 25, 1024, "auto", "tpu", True),      # GPT-2 XL's step
    (768, 12, 128, "flash", "cpu", True),       # asked for: interpret mode
    (1600, 25, 192, "auto", "tpu", False),      # no tile: dense
    (1600, 25, 1024, "auto", "cpu", False),     # auto off a TPU: dense
    (1600, 25, 1024, "dense", "tpu", False),
    (2048, 16, 1024, "auto", "tpu", False),     # heads of 128
    (64, 4, 128, "flash", "cpu", False),        # heads of 16
])
def test_unsplit_heads_run_says_which_kernels_a_gpt2_block_traces(
        monkeypatch, embd, heads, seq_len, impl, backend, unsplit):
    """``unsplit_heads_run`` is the one statement of when GPT-2's block
    hands the projection over whole: heads of 64 and ``flash_runs``.  The
    traced block then holds ``flash_fwd_pairs`` and no (B, T, H, 64)
    array; every other width, length and backend traces what it traced
    (``flash_fwd`` on split heads, or no kernel), and a caller with three
    arrays reaches the old kernel whatever its width."""
    from ray_tpu.models import gpt2
    from ray_tpu.ops.attention import unsplit_heads_run
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert unsplit_heads_run(embd, heads, seq_len, impl) is unsplit
    cfg = gpt2.GPT2Config(n_embd=embd, n_head=heads, n_layer=1,
                          attn_impl=impl, n_positions=seq_len)
    params = jax.eval_shape(lambda k: gpt2.init_params(k, cfg),
                            jax.random.key(0))
    layer = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                         params["blocks"])
    x = jax.ShapeDtypeStruct((1, seq_len, embd), cfg.dtype)
    text = str(jax.make_jaxpr(
        lambda x, lp: gpt2._block(x, lp, cfg, collect_kv=True))(x, layer))
    flash = flash_runs(seq_len, impl)
    assert _kernels_of(text) == (["flash_fwd_pairs"] if unsplit else
                                 ["flash_fwd"] if flash else [])
    split = f"[1,{seq_len},{heads},{embd // heads}]"
    # the prefill's K and V are the only split arrays of an unsplit block
    assert text.count(split) == (2 if unsplit else text.count(split))
    q = jax.ShapeDtypeStruct((1, seq_len, heads, embd // heads), jnp.bfloat16)
    three = str(jax.make_jaxpr(
        lambda q, k, v: causal_attention(q, k, v, impl=impl))(q, q, q))
    assert _kernels_of(three) == (["flash_fwd"] if flash else [])


def test_unsplit_heads_stay_split_where_a_mesh_splits_them(monkeypatch):
    """An ambient mesh that splits the heads (tensor) or the sequence
    through attention (context) keeps today's block, constraints
    included; one that splits neither hands the projection over whole."""
    from ray_tpu.ops.attention import unsplit_heads_run
    from ray_tpu.parallel import mesh as mesh_lib
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    devices = np.array(jax.devices()[:1])
    for axes, want in ((("data", "fsdp"), True), (("tensor",), True)):
        with mesh_lib.ambient_mesh(Mesh(devices.reshape((1,) * len(axes)),
                                        axes)):
            assert unsplit_heads_run(1600, 25, 1024) is want
    if len(jax.devices()) >= 2:
        two = np.array(jax.devices()[:2])
        for axis, want in (("tensor", False), ("context", False),
                           ("data", True)):
            with mesh_lib.ambient_mesh(Mesh(two, (axis,))):
                assert unsplit_heads_run(1600, 25, 1024) is want


# ------------------------------------------- latent attention's five operands
# (nope, rope, Dv, T, tile): a small one, and the Kanana cell's widths
LATENT = {"small": (16, 8, 16, 64, 16), "kanana": (128, 64, 128, 256, 128)}
PARTS = ("q_nope", "q_rope", "k_nope", "k_rope", "v")


def _latent_operands(nope, rope, dv, t, heads=2, batch=2):
    ks = jax.random.split(jax.random.key(7), 6)
    shapes = [(batch, t, heads, nope), (batch, t, heads, rope),
              (batch, t, heads, nope), (batch, t, rope),
              (batch, t, heads, dv)]
    operands = [jax.random.normal(k, s, jnp.float32)
                for k, s in zip(ks, shapes)]
    probe = jax.random.normal(ks[5], (batch, t, heads, dv), jnp.float32)
    return operands, probe


def _joined(q_nope, q_rope, k_nope, k_rope, v):
    """What the model built before the kernels took the parts: the rotary
    key repeated a head behind each head's k_nope."""
    k_rope = jnp.broadcast_to(k_rope[:, :, None],
                              k_nope.shape[:3] + k_rope.shape[-1:])
    return (jnp.concatenate([q_nope, q_rope], -1),
            jnp.concatenate([k_nope, k_rope], -1), v)


@pytest.fixture(scope="module", params=sorted(LATENT))
def latent_case(request):
    """-> {forward, gradient a part}: (the five-operand kernels in
    interpret mode, dense attention on the joined operands)."""
    from ray_tpu.ops.flash_attention import latent_flash_attention
    nope, rope, dv, t, tile = LATENT[request.param]
    operands, probe = _latent_operands(nope, rope, dv, t)

    def kernel(*parts):
        return latent_flash_attention(*parts, tile, True)

    def dense(*parts):
        return dense_attention(*_joined(*parts))

    found = {"forward": (kernel(*operands), dense(*operands))}
    grads = [jax.grad(lambda *parts, fn=fn: (fn(*parts) * probe).sum(),
                      argnums=tuple(range(5)))(*operands)
             for fn in (kernel, dense)]
    for name, got, want in zip(PARTS, *grads):
        found[name] = (got, want)
    return found


@pytest.mark.parametrize("what", ("forward",) + PARTS)
def test_latent_flash_equals_dense_on_the_joined_operands(latent_case, what):
    """Output and all five gradients; ``k_rope``'s is (B, T, rope): the
    kernel's result a head, summed over the heads, against the gradient of
    the broadcast."""
    got, want = latent_case[what]
    assert got.shape == want.shape
    _allclose(got, want, 1e-4)


def test_latent_kernels_read_the_rotary_key_once_and_build_nothing_joined():
    """Traced at bf16: the kernels' operands are the five parts (the rotary
    key with no head axis), and no array nope + rope wide exists, forward
    or backward; the first results are Dv and nope wide."""
    nope, rope, dv, t, _ = LATENT["kanana"]
    operands, _ = _latent_operands(nope, rope, dv, t)
    operands = [x.astype(jnp.bfloat16) for x in operands]
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *parts: latent_causal_attention(*parts, impl="flash")
        .astype(jnp.float32).sum(), argnums=tuple(range(5))))(*operands))
    assert jaxpr.count("name=flash_fwd") == 1
    assert jaxpr.count("name=flash_bwd") == 1
    assert f",{nope + rope}]" not in jaxpr and "concatenate" not in jaxpr
    assert not re.search(rf"\[2,{t},2,{rope}\] = broadcast_in_dim", jaxpr)
    # the backward's results: dq_nope first (what mla.attention_ms knows
    # the kernel by), a rotary-key gradient a (batch, head) fourth
    assert re.search(rf"bf16\[4,{t},{nope}\] \w+:bf16\[4,{t},{rope}\] "
                     rf"\w+:bf16\[4,{t},{nope}\] \w+:bf16\[4,{t},{rope}\] "
                     rf"\w+:bf16\[4,{t},{dv}\] = pallas_call", jaxpr)


@pytest.mark.parametrize("backend,seq_len,impl,kernel", [
    ("tpu", 128, "auto", True), ("cpu", 128, "auto", False),
    ("cpu", 128, "flash", True), ("tpu", 192, "auto", False),
    ("tpu", 128, "dense", False)])
def test_latent_attention_asks_flash_runs_as_causal_attention_does(
        monkeypatch, backend, seq_len, impl, kernel):
    """Off the kernel the parts are joined for ``causal_attention``:
    the same values either way."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert flash_runs(seq_len, impl) is kernel
    shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
        (1, seq_len, 2, 16), (1, seq_len, 2, 8), (1, seq_len, 2, 16),
        (1, seq_len, 8), (1, seq_len, 2, 16))]
    jaxpr = str(jax.make_jaxpr(
        lambda *parts: latent_causal_attention(*parts, impl=impl))(*shapes))
    assert ("name=flash_fwd" in jaxpr) is kernel
    assert ("concatenate" in jaxpr) is not kernel


def test_latent_attention_off_the_kernel_is_dense_on_the_joined_operands():
    operands, _ = _latent_operands(16, 8, 16, 48)
    _allclose(latent_causal_attention(*operands, impl="dense"),
              dense_attention(*_joined(*operands)), 1e-6)


# The jaxpr text of a three-operand call as the parent of the PR that gave
# latent attention its entry traced it (commit 9c24c8f, this jax): forward
# and gradient, at GPT-2's head width and at keys wider than values.  The
# kernels' body is shared with the five-operand call; what every other
# caller lowers, and its compile-cache key, must not move with it.  PR 50
# replaced the two gradients' digests: the backward kernel's body, which a
# jaxpr prints, computes its tile keys-down since then, for every caller;
# the forward's did not move.
PARENT_JAXPRS = {
    ("forward", 64, 64): "c1b26728f00cf0d7",
    ("gradient", 64, 64): "2bc4c709c2cd664a",
    ("forward", 192, 128): "43732ca3c853f0ee",
    ("gradient", 192, 128): "bf54646e7181a4a9",
}


@pytest.mark.parametrize("what,d,dv", sorted(PARENT_JAXPRS))
def test_a_three_operand_flash_call_traces_to_the_jaxpr_it_had(what, d, dv):
    import hashlib
    shape = {64: (2, 256, 4), 192: (1, 512, 2)}[d]
    qk = jax.ShapeDtypeStruct(shape + (d,), jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape + (dv,), jnp.bfloat16)

    def forward(q, k, v):
        return flash_attention(q, k, v, True, None, True)

    fn = forward if what == "forward" else jax.grad(
        lambda *a: forward(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = str(jax.make_jaxpr(fn)(qk, qk, v))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_JAXPRS[(what, d, dv)]


@pytest.mark.parametrize("impl", ["blockwise", "Flash", ""])
def test_unknown_attn_impl_raises_in_one_place(qkv, impl):
    with pytest.raises(ValueError, match="unknown attn_impl"):
        flash_runs(64, impl)
    with pytest.raises(ValueError, match="unknown attn_impl"):
        causal_attention(*qkv, impl=impl)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_context_parallel_impls_through_the_choice(qkv, mesh, impl):
    """``ring`` / ``ulysses`` run over the ambient mesh's context axis and
    are XLA's dense attention where no mesh splits it."""
    from ray_tpu.parallel import mesh as mesh_lib
    ref = dense_attention(*qkv, causal=True)
    _allclose(causal_attention(*qkv, impl=impl), ref)       # no mesh
    with mesh_lib.ambient_mesh(mesh):
        out = jax.jit(lambda q, k, v: causal_attention(
            q, k, v, impl=impl, context_axis="context"))(*qkv)
    _allclose(out, ref)
