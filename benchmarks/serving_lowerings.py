"""Digest of every serving program's lowered text, for the seven served
families' ``tiny`` presets, on the CPU: the guard of a change that may not
alter a device program (PR 61 used it; ``tests/test_falcon_h1.py`` and
``tests/test_lfm2.py`` pin a part of it, and no test pins Trinity's or
Ling's programs).

    cd <tree> && env JAX_PLATFORMS=cpu PYTHONPATH=<tree> \
        python benchmarks/serving_lowerings.py <out.json>

Run it on the parent (``git archive`` into a scratch directory) and on the
change, and compare the two files: a program's digest is of its whole
``lowered.as_text()``, so an operation moved, added or renamed shows.  Each
engine runs two short prompts, so every program the loop builds is
registered (``tracing.register_program``); the cache's ``write_rows`` is
lowered through ``write_token``'s own arguments.  Also written: the tokens,
the holder's leaves and ``stats()``'s keys.  Nothing here is a device
number."""

import hashlib
import json
import sys

import jax
import numpy as np

from ray_tpu.serve import llm
from ray_tpu.util import tracing

_SMALL = dict(block_size=8, num_blocks=64, max_num_seqs=4, max_model_len=64,
              max_prefill_tokens=32, prefill_len_buckets=(16, 32, 64),
              decode_batch_buckets=(1, 2, 4))
_CHUNKED = dict(block_size=16, num_blocks=96, max_num_seqs=4,
                max_prefill_tokens=256, max_model_len=256,
                decode_batch_buckets=(4,), prefill_len_buckets=(64, 128, 256))
ENGINES = {
    "gpt2": _SMALL, "llama": _SMALL, "falcon_h1": _SMALL, "lfm2": _SMALL,
    "minicpm_sala": dict(block_size=8, num_blocks=64, max_num_seqs=4,
                         max_prefill_tokens=128, max_model_len=128,
                         decode_batch_buckets=(4,),
                         prefill_len_buckets=(32, 64, 128)),
    "afmoe": _CHUNKED, "ling": _CHUNKED,
}


def _digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def _family(family: str) -> dict:
    tracing._PROGRAMS.clear()
    eng = llm.LLMEngine(llm.EngineConfig(
        model=f"{family}:tiny", share_weights=False, **ENGINES[family]),
        start=False)
    rng = np.random.default_rng(0)
    streams = [eng.submit(list(rng.integers(1, eng.runner.vocab, n)),
                          llm.SamplingParams(max_tokens=6)) for n in (5, 19)]
    while eng.step() or eng._work_pending():
        pass
    out = {"tokens": [s.tokens() for s in streams]}
    for name, (jitted, args) in sorted(tracing._PROGRAMS.items()):
        out[name] = _digest(jitted.lower(*args))
    # the cache's own writer of rows, through write_token's arguments
    cache, seen = eng.cache, []
    cache.alloc_seq("w", 3)
    rows = np.zeros((cache.kv_layers + cache.window_layers
                     + cache.latent_layers,)
                    + ((1, cache.latent_dim) if cache.latent_layers
                       else cache.block_shape[3:]), np.float32)
    donate = cache.pool.donate
    cache.pool.donate = lambda program, *args: (
        seen.append((program, tracing.abstract(args))), donate(program,
                                                                *args))[1]
    cache.write_token(cache.table("w")[0], 1, rows, rows)
    cache.pool.donate = donate
    program, args = seen[0]
    out["kv.write_rows"] = _digest(program.lower(cache.pool.abstract(),
                                                 *args))
    cache.free_seq("w")
    out["held"] = {
        "/".join(str(p.key) for p in path): [list(leaf.shape),
                                             str(leaf.dtype)]
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            cache.pool.abstract())[0]}
    out["stats_keys"] = sorted(eng.stats())
    eng.shutdown()
    return out


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump({family: _family(family) for family in ENGINES}, f,
                  indent=1, sort_keys=True)
    print("written", sys.argv[1])
