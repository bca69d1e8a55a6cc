"""The paged-decode kernel against the gather-then-mask path.

The kernel (``ops/paged_attention._paged_decode_kernel``) runs here in
Pallas' TPU interpreter at tiny shapes: memory nothing wrote reads as NaN
there and a read out of bounds raises.  The ``jnp`` path in the same file
is the reference.  Each edge the block-table walk has is one case of
one test.  The kernel at the chip's real shapes is compiled, not run, in
``tests/test_chip_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_attention as pa

BS = 4          # block size
MAXB = 24       # table columns: three of the kernel's chunks, as cut here
N = 80          # blocks in the pool


def _case(ctx_lens, *, h=3, kvh=3, d=8, q_dtype=jnp.float32, seed=0,
          shared=False, poison=False):
    """Inputs for one call.  Block 0 is never named by a live column.
    ``poison``: every table column past a row's context names a block
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
    return q, (jnp.asarray(k_pool), jnp.asarray(v_pool)), \
        (jnp.asarray(clean[0]), jnp.asarray(clean[1])), rest


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
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_kernel_matches_the_gather_path(case):
    q, pools, clean, rest = _case(**case)
    got = pa._paged_decode_kernel(q, *pools, *rest,
                                  interpret=pltpu.InterpretParams())
    want = pa._paged_decode_gather(q, *clean, *rest)
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
    q, pools, _, rest = _case([5, 9])
    called = []
    monkeypatch.setattr(pa, "_paged_decode_kernel",
                        lambda *a, **k: called.append(a) or a[0])
    want = pa._paged_decode_gather(q, *pools, *rest)
    np.testing.assert_array_equal(
        pa.paged_attention_decode(q, *pools, *rest), want)
    assert not called                               # this rig is a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pa.paged_attention_decode(q, *pools, *rest)
    assert len(called) == 1
    bf16_pools = tuple(p.astype(jnp.bfloat16) for p in pools)
    pa.paged_attention_decode(q, *bf16_pools, *rest)  # declined: not f32
    assert len(called) == 1


@pytest.mark.parametrize("traced", [False, True])
def test_layer_pools_is_the_pools_layer(traced):
    """``layer_pools`` against numpy indexing of the engine's
    ``(N, L, 2, bs, KV, D)`` pool, with the layer static and traced (as
    the models' decode scans pass it).  Both models' ``forward_decode``
    through it are held to the gather reference by
    ``tests/test_serve_llm.py``."""
    pool = np.random.default_rng(0).normal(
        size=(5, 3, 2, 4, 2, 8)).astype(np.float32)
    for layer in range(3):
        if traced:
            k, v = jax.jit(pa.layer_pools)(pool, jnp.int32(layer))
        else:
            k, v = pa.layer_pools(jnp.asarray(pool), layer)
        np.testing.assert_array_equal(np.asarray(k), pool[:, layer, 0])
        np.testing.assert_array_equal(np.asarray(v), pool[:, layer, 1])
