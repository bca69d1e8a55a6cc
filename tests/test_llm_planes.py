"""What a sequence keeps is declared once (``kv_cache.PLANES``), and a
decode step's counts travel by name (``model_runner.riders_of``): the one
table and the one road, held to what the parent of PR 61 built by hand.

The literals below were read from that parent (commit 80e7c17): the holder
its ``PagedKVCache`` built for each served family's ``tiny`` preset, the
bytes of each plane, and ``LLMEngine.stats()``'s keys."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve import llm
from ray_tpu.serve.llm import kv_cache as kvmod
from ray_tpu.serve.llm.config import SERVED_FAMILIES, resolve_model
from ray_tpu.serve.llm.model_runner import (ModelRunner, _ridden,
                                            _riders_read, riders_of)
from ray_tpu.util import metrics_catalog as mcat

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

# family -> (the holder's leaves: shape and type; the planes' bytes)
PARENT_HOLDERS = {
    "afmoe": ({"kv": [[1, 2, 96, 16, 128], "float32"],
               "kvw": [[4, 2, 16, 16, 128], "float32"]},
              {"lane_pad": 1376256, "latent": 0, "select": 0, "state": 0,
               "window": 1048576}),
    "falcon_h1": ({"kv": [[2, 2, 64, 8, 128], "float32"],
                   "state/conv": [[2, 5, 3, 128], "float32"],
                   "state/ssm": [[2, 5, 4, 16, 16], "float32"]},
                  {"lane_pad": 524288, "latent": 0, "select": 0,
                   "state": 56320, "window": 0}),
    "gpt2": ({"kv": [[2, 2, 64, 8, 128], "float32"]},
             {"lane_pad": 524288, "latent": 0, "select": 0, "state": 0,
              "window": 0}),
    "lfm2": ({"kv": [[2, 2, 64, 8, 128], "float32"],
              "state/conv": [[7, 5, 2, 64], "float32"]},
             {"lane_pad": 917504, "latent": 0, "select": 0, "state": 17920,
              "window": 0}),
    "ling": ({"kv": [[0, 2, 96, 16, 128], "float32"],
              "latent": [[1, 1, 96, 16, 128], "float32"],
              "state/conv": [[3, 5, 3, 96], "float32"],
              "state/s": [[3, 5, 4, 8, 8], "float32"]},
             {"lane_pad": 0, "latent": 786432, "select": 0, "state": 32640,
              "window": 0}),
    "llama": ({"kv": [[2, 2, 64, 8, 128], "float32"]},
              {"lane_pad": 786432, "latent": 0, "select": 0, "state": 0,
               "window": 0}),
    "minicpm_sala": ({"kv": [[2, 2, 64, 8, 128], "float32"],
                      "sel": [[2, 64, 4, 128], "float32"],
                      "state/s": [[4, 5, 4, 16, 16], "float32"]},
                     {"lane_pad": 917504, "latent": 0, "select": 262144,
                      "state": 81920, "window": 0}),
}

PARENT_STATS_KEYS = {
    "admitted", "attn_blocks_read", "attn_blocks_table", "blocks_free",
    "compiles", "decode_drains", "decode_rows_discarded", "decode_steps",
    "decode_steps_ahead", "experts_touched", "kv_host_bytes",
    "kv_lane_pad_bytes", "kv_layers", "latent_bytes", "latent_layers",
    "latent_pages_read", "logits_host_bytes", "param_bytes", "preemptions",
    "prefill_chunks", "prefill_steps", "queue_wait_s", "requeue_wait_s",
    "routed_layer_steps", "running", "sampled_on_device", "sampled_on_host",
    "select_bytes", "span_s", "sparse_pages_held", "sparse_pages_read",
    "staging_bytes", "state_bytes", "state_commits", "state_layers",
    "state_rows", "state_rows_stepped", "state_rows_used", "tokens_out",
    "waiting", "window_blocks", "window_blocks_read",
    "window_blocks_unwindowed", "window_bytes", "window_layers"} | {
    # PR 67: the index plane's four, 0 for a family without one
    "index_layers", "index_bytes", "positions_scored", "positions_read"}


def _cfg(family):
    return llm.EngineConfig(model=f"{family}:tiny", share_weights=False,
                            **ENGINES[family])


# ------------------------------------------------------------- the one road
@pytest.mark.parametrize("routes", [False, True])
@pytest.mark.parametrize("selects", [False, True])
def test_what_the_step_packs_behind_its_ids_is_what_the_pull_names(
        routes, selects):
    """The four kinds of family (only three exist as modules): the pack and
    the read share one layout, and a pull of another length is refused."""
    riders = riders_of({"layers": 2, "k": 2} if routes else None,
                       {"stride": 2, "block": 8} if selects else None)
    layout = tuple(name for rider in riders for name in rider.names)
    assert layout == ("experts_touched",) * routes + (
        "sparse_pages_read", "sparse_pages_held") * selects
    # the forward's results behind K/V: a routing module's choices (layer
    # 0 chose {0, 1, 2}, layer 1 {0, 3}), then a selecting module's counts
    choices = jnp.asarray([[[0, 1], [1, 2]], [[3, 3], [0, 3]]], jnp.int32)
    results = [choices] * routes + [jnp.asarray([7, 11], jnp.int32)] * selects
    taken = [rider.take(results) for rider in riders]
    # the counts came off the results; the choices stay a program's result
    assert len(results) == routes
    width = 4
    packed = np.asarray(_ridden(jnp.arange(width, dtype=jnp.int32), [
        rider.count(x) for rider, x in zip(riders, taken)]))
    want = dict(experts_touched=5) if routes else {}
    if selects:
        want.update(sparse_pages_read=7, sparse_pages_held=11)
    assert _riders_read(packed, width, layout) == want
    assert list(_riders_read(packed, width, layout)) == list(layout)
    with pytest.raises(ValueError, match="another layout"):
        _riders_read(np.append(packed, 0), width, layout)
    if layout:
        with pytest.raises(ValueError, match="another layout"):
            _riders_read(packed[:-1], width, layout)


def test_a_share_of_the_experts_counts_the_held_ones():
    rider, = riders_of({"layers": 1, "k": 2, "held": (2, 2)}, None)
    ids = jnp.asarray([[[0, 2], [3, 5], [2, 2]]], jnp.int32)
    assert int(rider.count(rider.take([ids]))[0]) == 2      # {2, 3}


# ------------------------------------------------------------ the one table
@pytest.mark.parametrize("family", sorted(PARENT_HOLDERS))
def test_the_planes_built_from_the_declaration_are_the_parents_holder(family):
    import jax
    cfg = _cfg(family)
    cache = kvmod.PagedKVCache.for_engine(
        cfg, kvmod.kept_by(*resolve_model(cfg)))
    held = cache.pool.abstract()
    got = {"/".join(str(p.key) for p in path): [list(leaf.shape),
                                                str(leaf.dtype)]
           for path, leaf in jax.tree_util.tree_flatten_with_path(held)[0]}
    want, nbytes = PARENT_HOLDERS[family]
    assert got == want
    assert dict(state=cache.state_bytes, select=cache.select_bytes,
                window=cache.window_bytes, latent=cache.latent_bytes,
                lane_pad=cache.lane_pad_bytes) == nbytes
    # the cache's planes are the table's rows for its entries, in its order
    names = [p.name for p in kvmod.PLANES]
    assert [p.name for p in cache.planes] == [n for n in names if n in held]
    assert names == ["kv", "state", "sel", "kvw", "latent", "index"]
    assert cache.index_bytes == 0 and "index" not in held
    cache.close()


@pytest.mark.parametrize("family", ["gpt2", "falcon_h1", "minicpm_sala",
                                    "afmoe", "ling"])
def test_stats_keeps_every_key_the_parent_had(family):
    """One family of each kind of plane: K/V alone, a store, a selector's
    cache, a window pool, a latent pool."""
    eng = llm.LLMEngine(_cfg(family), start=False)
    try:
        stats = eng.stats()
        assert set(stats) == PARENT_STATS_KEYS
        assert stats["routed_layer_steps"] == stats["experts_touched"] == 0
        assert eng.runner.family.layout == tuple(
            ["experts_touched"] * bool(eng.runner.route_spec)
            + ["sparse_pages_read", "sparse_pages_held"]
            * bool(eng.runner.select_spec))
    finally:
        eng.shutdown()


def test_a_steps_reads_are_summed_by_name_and_told_to_their_series(
        monkeypatch):
    """``_commit``'s one road: the counter, and the one table of series."""
    told = []

    class _Series:
        def __init__(self, name):
            self.name = name

        def __getattr__(self, how):
            return lambda value, tags: told.append(
                (self.name, how, value, tags))

    monkeypatch.setattr(mcat, "get", _Series)
    from ray_tpu.serve.llm.engine import STEP_SERIES
    for series, how in STEP_SERIES.values():
        assert series in mcat.CATALOG and how in ("inc", "observe", "set")
    tags = {"model": "m"}
    mcat.tell_step(dict(experts_touched=12, sparse_pages_read=3,
                        window_positions=99, state_rows_held=2),
                   STEP_SERIES, tags, {"experts_touched": 4})
    assert told == [
        ("rtpu_llm_moe_experts_touched", "observe", 3.0, tags),
        ("rtpu_llm_sparse_pages_read", "inc", 3, tags),
        ("rtpu_llm_state_rows_held", "set", 2, tags)]


def test_the_window_plane_tells_what_it_gave_back_since_last_asked():
    cache = kvmod.PagedKVCache(16, 1, 4, 2, 8, max_seqs=2, window_layers=1,
                               window=4)
    cache.alloc_seq("a", 3)
    assert cache.held_counts() == dict(
        window_blocks_held=1, window_blocks_held_unwindowed=1,
        window_blocks_released=0)
    for _ in range(9):
        cache.append_slot("a")
    first = cache.held_counts()
    assert first["window_blocks_released"] == cache.window_released > 0
    assert cache.held_counts()["window_blocks_released"] == 0
    with pytest.raises(NotImplementedError, match="window layers cannot"):
        cache.fork_seq("a", "b")
    assert kvmod.PagedKVCache(4, 1, 4, 2, 8).held_counts() == {}


# ------------------------------------------------------- the served families
@pytest.mark.parametrize("family", SERVED_FAMILIES)
def test_resolve_model_resolves_every_served_family(family):
    mod, mcfg = resolve_model(llm.EngineConfig(model=f"{family}:tiny"))
    assert mod.__name__ == f"ray_tpu.models.{family}"
    assert mcfg == mod.PRESETS["tiny"]()
    assert callable(mod.forward_prefill) and callable(mod.forward_decode)


def test_resolve_model_refuses_another_family_with_the_served_ones():
    with pytest.raises(ValueError, match="|".join(SERVED_FAMILIES).replace(
            "|", r"\|")):
        resolve_model(llm.EngineConfig(model="bert:tiny"))
    with pytest.raises(ValueError, match="preset 'huge'"):
        resolve_model(llm.EngineConfig(model="gpt2:huge"))
    assert sorted(SERVED_FAMILIES) == sorted(PARENT_HOLDERS)


def test_the_runner_keeps_its_plain_reads_of_the_family():
    runner = ModelRunner(_cfg("afmoe"))
    kept = runner.family.kept
    assert (runner.kv_layers, runner.window_layers, runner.window,
            runner.state_spec, runner.select_spec, runner.chunk) == (
        kept.kv_layers, kept.window_layers, kept.window, None, None,
        runner.mcfg.prefill_chunk)
    assert kept.packed and not kept.staged
    assert runner._window_reads(np.asarray([5, 40])) == kvmod.window_reads(
        [5, 40], 16, kept.window, kept.window_layers)
