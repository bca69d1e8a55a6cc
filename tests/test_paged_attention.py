"""The paged-decode kernel against the gather-then-mask path.

The kernel (``ops/paged_attention._paged_decode_kernel``) runs here in
Pallas' TPU interpreter at tiny shapes: memory nothing wrote reads as NaN
there and a read out of bounds raises.  The ``jnp`` path in the same file
is the reference, and is itself held to plain numpy over K/V in head
form.  Both read the engine's pool whole, in its device format
``(L, 2, N, bs, F)`` (``kv_cache.device_shape``), and one layer of it.
Each edge the block-table walk has is one case of one test.  The kernel
at the chip's real shapes is compiled, not run, in
``tests/test_chip_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve.llm.kv_cache import device_shape

BS = 4          # block size
MAXB = 24       # table columns: three of the kernel's chunks, as cut here
N = 80          # blocks in the pool
LAYERS, LAYER = 3, 1    # the pool's layers, and the one that is read


def _device_pool(k_pool, v_pool, rng):
    """The engine's pool with ``k_pool`` / ``v_pool`` (N, bs, KV, D) as
    layer ``LAYER`` and noise in every other layer.  The lanes that pad
    ``KV * D`` up to ``F`` hold noise too: the writer leaves zeros there,
    and the reader must not care."""
    n, bs, kvh, d = k_pool.shape
    pool = rng.standard_normal(device_shape(n, LAYERS, bs, kvh, d)
                               ).astype(np.float32)
    pool[LAYER, 0, :, :, :kvh * d] = k_pool.reshape(n, bs, kvh * d)
    pool[LAYER, 1, :, :, :kvh * d] = v_pool.reshape(n, bs, kvh * d)
    return jnp.asarray(pool)


def _case(ctx_lens, *, h=3, kvh=3, d=8, q_dtype=jnp.float32, seed=0,
          shared=False, poison=False):
    """Inputs for one call: the query, the pool the kernel gets, the
    pool the reference gets, and the rest.  Block 0 is never named by a
    live column.  ``poison``: every table column past a row's context names a block
    (one of the pool's, so a valid index) that holds NaN in the pool the
    kernel gets; the reference gets the same pool with those blocks
    zeroed, since the gather path multiplies what it masks by zero."""
    rng = np.random.default_rng(seed)
    b = len(ctx_lens)
    k_pool = rng.standard_normal((N, BS, kvh, d)).astype(np.float32)
    v_pool = rng.standard_normal((N, BS, kvh, d)).astype(np.float32)
    tables = np.zeros((b, MAXB), np.int32)
    free = list(rng.permutation(np.arange(1, N - 4)))
    for i, ctx in enumerate(ctx_lens):
        n = -(-ctx // BS)
        if shared and i > 0:
            # a prefix of row 0's blocks, then blocks of its own
            n_share = min(n, -(-ctx_lens[0] // BS)) // 2
            tables[i, :n_share] = tables[0, :n_share]
            own = n - n_share
            tables[i, n_share:n] = [free.pop() for _ in range(own)]
        else:
            tables[i, :n] = [free.pop() for _ in range(n)]
        tables[i, n:] = rng.integers(N - 4, N, MAXB - n) if poison \
            else rng.integers(0, N, MAXB - n)
    clean = (k_pool.copy(), v_pool.copy())
    if poison:
        k_pool[N - 4:] = np.nan
        v_pool[N - 4:] = np.nan
        clean[0][N - 4:] = 0.0
        clean[1][N - 4:] = 0.0
    q = jnp.asarray(rng.standard_normal((b, h, d)), q_dtype)
    k_new = jnp.asarray(rng.standard_normal((b, kvh, d)), q_dtype)
    v_new = jnp.asarray(rng.standard_normal((b, kvh, d)), q_dtype)
    rest = (jnp.asarray(tables), jnp.asarray(ctx_lens, jnp.int32),
            k_new, v_new)
    return q, _device_pool(k_pool, v_pool, np.random.default_rng(seed)), \
        _device_pool(*clean, np.random.default_rng(seed)), rest


@pytest.fixture(autouse=True)
def chunks_of_eight_blocks(monkeypatch):
    monkeypatch.setattr(pa, "_CHUNK_TOKENS", 8 * BS)


CASES = {
    # 5 < one block; 33 and 70 span chunks (8 blocks = 32 positions)
    "ragged": dict(ctx_lens=[5, 33, 70, 17]),
    "padded_rows": dict(ctx_lens=[9, 0, 0, 0]),
    "block_multiple": dict(ctx_lens=[BS, 8 * BS, 16 * BS, MAXB * BS]),
    "one_past_multiple": dict(ctx_lens=[BS + 1, 8 * BS + 1, 16 * BS + 1, 1]),
    "unread_blocks_hold_nan": dict(ctx_lens=[3, 32, 41, 0], poison=True),
    "shared_blocks": dict(ctx_lens=[40, 37, 12, 40], shared=True),
    "grouped_query": dict(ctx_lens=[5, 33, 70, 0], h=6, kvh=2),
    "bf16_query": dict(ctx_lens=[5, 33, 70, 0], q_dtype=jnp.bfloat16),
    # the cells' lanes: 25 x 64 = 1,600, padded to 1,664; 4 x 128 = 512,
    # whole tiles as they are, five queries a KV head
    "xl_lanes_padded": dict(ctx_lens=[5, 33, 70, 0], h=25, kvh=25, d=64),
    "falcon_h1_lanes_whole": dict(ctx_lens=[5, 33, 70, 0], h=20, kvh=4,
                                  d=128),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_kernel_matches_the_gather_path(case):
    q, pool, clean, rest = _case(**case)
    # the layer traced, as the models' scans hand it over
    got = jax.jit(lambda layer: pa._paged_decode_kernel(
        q, pool, layer, *rest, interpret=pltpu.InterpretParams()))(
            jnp.int32(LAYER))
    want = pa._paged_decode_gather(q, clean, LAYER, *rest)
    assert got.dtype == want.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # bf16 results differ by a rounding of the result itself
    tol = 1e-5 if q.dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    padded = np.asarray(rest[1]) == 0
    if padded.any():                  # nothing but the new token's term
        v_new = np.asarray(rest[3], np.float32)
        rep = q.shape[1] // v_new.shape[1]
        np.testing.assert_allclose(got[padded],
                                   np.repeat(v_new, rep, axis=1)[padded],
                                   rtol=tol, atol=tol)


def test_cpu_calls_take_the_gather_path(monkeypatch):
    """What the call chooses from: the backend, then the shapes."""
    q, pool, _, rest = _case([5, 9])
    called = []
    monkeypatch.setattr(pa, "_paged_decode_kernel",
                        lambda *a, **k: called.append(a) or a[0])
    want = pa._paged_decode_gather(q, pool, LAYER, *rest)
    np.testing.assert_array_equal(
        pa.paged_attention_decode(q, pool, LAYER, *rest), want)
    assert not called                               # this rig is a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pa.paged_attention_decode(q, pool, LAYER, *rest)
    assert len(called) == 1
    pa.paged_attention_decode(q, pool.astype(jnp.bfloat16), LAYER,
                              *rest)                # declined: not f32
    assert len(called) == 1


@pytest.mark.parametrize("traced", [False, True])
def test_gather_path_reads_the_pools_layer(traced):
    """The gather path over the device format against plain numpy over
    K/V in head form: the layer it is told, static and traced (as the
    models' decode scans pass it), heads apart and the lane padding cut,
    grouped-query heads repeated in ``jnp.repeat``'s order."""
    ctx_lens, h, kvh, d = [5, 33, 70, 0], 6, 2, 8
    rng = np.random.default_rng(3)
    k_pool, v_pool = rng.standard_normal((2, N, BS, kvh, d)
                                         ).astype(np.float32)
    pool = _device_pool(k_pool, v_pool, rng)
    assert pool.shape == (LAYERS, 2, N, BS, 128)
    tables = rng.integers(1, N, (len(ctx_lens), MAXB)).astype(np.int32)
    q, k_new, v_new = (rng.standard_normal((len(ctx_lens), n, d)
                                           ).astype(np.float32)
                       for n in (h, kvh, kvh))
    args = (jnp.asarray(tables), jnp.asarray(ctx_lens, jnp.int32),
            jnp.asarray(k_new), jnp.asarray(v_new))
    if traced:
        got = jax.jit(lambda layer: pa._paged_decode_gather(
            jnp.asarray(q), pool, layer, *args))(jnp.int32(LAYER))
    else:
        got = pa._paged_decode_gather(jnp.asarray(q), pool, LAYER, *args)
    for i, ctx in enumerate(ctx_lens):
        # (ctx + 1, KV, D): the context through the table, then the token
        keys = np.concatenate([k_pool[tables[i]].reshape(-1, kvh, d)[:ctx],
                               k_new[i][None]])
        vals = np.concatenate([v_pool[tables[i]].reshape(-1, kvh, d)[:ctx],
                               v_new[i][None]])
        for head in range(h):
            kv = head // (h // kvh)
            s = keys[:, kv] @ q[i, head] / np.sqrt(d)
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                np.asarray(got)[i, head], (p / p.sum()) @ vals[:, kv],
                rtol=1e-5, atol=1e-5)
