"""Bucketed JAX prefill/decode execution for the LLM engine.

Shape discipline: XLA compiles one program per distinct input shape, so
an engine seeing arbitrary prompt lengths and batch sizes would
recompile forever.  Every call here is padded up to a configured bucket
(``EngineConfig.prefill_len_buckets`` / ``decode_batch_buckets``) and
the block-table width is fixed at ``max_blocks_per_seq`` — the total
program count is bounded by ``len(prefill_buckets) +
len(decode_buckets)`` for the engine's life (SURVEY.md §7.3: replica
cold starts are XLA compiles; bounding them is the TPU-serving
equivalent of connection pooling).

The runner is model-family-agnostic: ``models/gpt2.py``,
``models/llama.py``, ``models/falcon_h1.py``, ``models/lfm2.py`` and
``models/minicpm_sala.py`` each
export ``forward_prefill`` / ``forward_decode`` (the decode step reads the paged
pool through ``ops/paged_attention.py`` and returns the new token's K/V);
the runner's decode program then writes that K/V into the pool, which it
was given donated, so the pool stays on the device
(``kv_cache.DevicePool``) and every family gets the write-back from one
place.  Prefill leaves a prompt's K/V on the device for
``PagedKVCache.scatter_prefill``.

What comes to the host.  Each step program returns, beside its float32
logits, every row's greedy token (``argmax`` over the vocabulary, the
first index of the maximum as ``np.argmax`` takes it): still one program
a bucket, the logits a result of it that stays on the device.  A caller
that names the rows whose logits it needs (``logit_rows=``: the engine's
loop names those whose ``SamplingParams`` is not greedy, usually none)
gets ``Chosen``: the ``(B,)`` ids and those rows.  Seeded temperature /
top-k sampling stays host-side on a pulled ``(V,)`` row (``sample``).  A
caller that names nothing gets all the logits on the host, as always.

One step behind another.  A greedy row's next token is the id the step
before chose, and that id is on the device: the decode programs take the
last step's ids (``last_ids``, at the widest bucket's width, which every
bucket's program also returns its own at) and a row map ``src``: row i's
token is ``last_ids[src[i]]``, or ``tokens[i]`` from the host where
``src[i]`` is -1.  ``decode(..., after=step, rows=src, wait=False)``
enqueues such a step and returns at the enqueue with an ``Enqueued``;
``pull_step`` waits for one and brings its ids.  So the engine's loop keeps a
step in flight and reads step n after it has enqueued step n+1.  A call
without ``after`` sends zeros and a map of -1s of the same type and
placement (``_no_ids``): one executable a bucket serves both, and the
harness's warm call has built it.

Recurrent state.  A family whose sequences hold more than K/V says so by
exporting ``recurrent_state(cfg)``: one sequence's state in one layer,
name -> shape and type.  That description is all the runner and the cache
know of it (``runner.state_spec``; the engine builds its cache with it):
what the cache's holder holds is then ``{"kv": pool, "state": store}``
(``kv_cache.py``; ``{"kv": pool}`` for every other family).  There is one
decode body and one prefill body, and each reads what the holder's dict
has.  The prefill program of such a family takes the holder donated and
writes the state at the prompt's last real position into the store's
staging row, for ``scatter_prefill`` to commit to the sequence's row; a
family without a store hands the same body no holder (``None``, no
operand: a pool of 1.3 GB does not go through a donating program to
change nothing).  The decode program hands the model the store and, for
each batch row, the store row the cache names for its block table (rows
padded up to the bucket name none, and write nowhere), an operand that
exists only where there is a store.  ``prefill`` and ``decode`` keep
their signatures and results: the state travels behind them.

Layers that differ in kind.  A family in which some layers hold K/V and
others recurrent state, and none both (``models/lfm2.py``), says how many
of each with ``cache_layers(cfg)`` -> ``{"kv": n, "state": m}``, beside
its ``recurrent_state``: the runner reads it into ``kv_layers`` /
``state_layers`` and the engine builds the pool for the one count and the
store for the other.  Without that export both are ``n_layer`` (the store's
only where there is state), which is what every other family has.

A selector's cache.  A module whose attention chooses the pages it reads
(``models/minicpm_sala.py``; ``ops/sparse_attention.py``) exports
``page_selector(cfg)`` -> ``{"stride", "block"}``: the positions one slot
of the selector's cache pools, and the page the selection wants (the
engine refuses another ``block_size``).  The runner offers it as
``select_spec``; the holder then has a third entry, ``"sel"``
(``kv_cache.py``), the decode program hands it to the forward read-only
(``selector=``) and writes the new K's slot with the K, in
``write_rows``; the forward returns the pages its sparse layers read and
the pages its rows' contexts held, two numbers that ride behind the ids
(``Chosen.pages``), as the count of touched experts does.

A prompt in chunks.  A module that exports ``forward_prefill_chunk`` (and
``prefill_staging``) has ONE prefill program, of ``cfg.prefill_chunk``
positions, whatever the prompt's length: ``prefill_chunk(token_ids,
index)`` runs chunk ``index`` over a staging K/V of the largest bucket's
positions, which the runner keeps and the program takes donated and
returns, and over the store's staging row, which carries the recurrent
state from chunk to chunk; positions past the prompt's end move neither.
``prefill(prompt)`` is those chunks one behind another and
``prefill_result`` after the last: same signature, same results (logits,
K/V of the bucket's length out of the staging, the state in the staging
row), so ``scatter_prefill`` commits a chunked prompt as any other.  The
prefill buckets of such a family are lengths in whole chunks and cost a
scatter program each, not a model program.  The engine's loop calls
``prefill_chunk`` itself, one an iteration with a decode step of the live
rows behind it (``engine.py``), and ``after=`` keeps one chunk in flight.
One prompt is in prefill at a time: a chunk 0 starts the next.  A module
without the export has the one-program prefill it had.  A chunked family
that carries no recurrent state (``models/afmoe.py``) hands the same chunk
body no holder, as its one-program prefill would: what its chunks carry is
the staging alone.

Pages of two kinds.  A module whose ``cache_layers(cfg)`` counts a third
kind, ``"window"`` (``models/afmoe.py``: layers that hold only the last
``cfg.sliding_window`` positions), gets a holder with a second pool,
``"kvw"`` (``kv_cache.py``).  The decode program hands the forward both
pools and, beside the block tables, the window tables of the same rows
(``window_tables=``: an operand that exists only then; the cache names
them by who owns a table's first block, as it names rows of state), and
writes the new K/V of the full layers into the one pool and of the window
layers into the other, each through its own table at the same column and
offset.  The forwards return K/V with the full layers first.  After a
prompt's last chunk ``prefill_result`` packs what the cache scatters as
ONE array a K and a V, ``(1, rows, KV, D)``: the full layers' K/V of the
bucket's length and behind them each window layer's run of the prompt's
last positions out of the ring (``PagedKVCache.window_run``), so that
``prefill`` keeps its three results and a caller that copies them to the
host (the serving check) copies the window's run and not the prompt.
``llm.decode.pull`` is told the positions and blocks the step's window
layers read and what full layers would have read in their place.

A latent page.  A module whose ``cache_layers(cfg)`` counts a kind
``"latent"`` (``models/ling.py``: layers of multi-head latent attention,
which cache one row ``[c | k_rope]`` of ``cfg.latent_row`` features a
position) gets a holder with a pool of one plane, ``"latent"``, under the
same block table (``kv_cache.py``, a latent page), beside ``"kv"`` (which
then has no layer) and the store.  The decode program hands the forward
that pool (``latent_pool=``) and writes the rows the forward returns as
its ``k`` (``(latent layers, B, 1, R)``) at the slot a K/V would go to;
the chunk program stages a prompt's rows as another family's stages K/V
(``staging["latent"]``), and ``prefill_result`` hands them on as ``ks``
(and again as ``vs``, which nobody reads).  ``llm.decode.pull`` is told
``latent_pages_read``: the pages the kernel walked, summed over the live
rows and the latent layers.

The hand-over of the choice of experts.  A module that routes exports
``routed_layers(cfg)`` -> ``{"layers": n, "k": k}`` (None for a preset
that does not); the runner offers it as ``route_spec``, asks the module's
forwards for the ids (``choices=True``), and its step programs return them
as one more result, int32 ``(routed layers, rows of the bucket, k)``: the
last step's stay on the device as ``runner.choices`` until somebody reads
them (the serving check of ``perfbench/jobs/serve.py``).  A decode program
of such a module tells the forward which rows of the bucket are live
(``live=``): a padded row takes a live row's choice and reads no expert of
its own (``ops/moe.choice_of_live_rows``).  It also counts the distinct
experts the step chose, summed over the routed layers, and sends that one
number behind the ids it returns (``Chosen.touched``: 4 more bytes of the
pull there is).
``prefill`` and ``decode`` keep their signatures and results.

Where the serving type of the weights is decided: here, once.  Whatever
tree the runner ends up with (the caller's, ``init_params``' own in the
model's ``param_dtype``, the shm plane's) goes through
``models/_common.serving_params`` in ``__init__``: every leaf the
family's forward casts to the model's ``dtype`` is stored in that type,
the leaves the module names in ``WIDE_PARAMS`` stay as stored, and a
table a module with a tied head names in ``ROW_TABLES`` is held a second
time for the embedding's gather where its rows are no whole lanes.  The
step programs take that tree (``runner.params``), so a weight is
converted, and a table laid out as its gather reads it, once in an
engine's life and not in every program run.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

from ray_tpu._private import rtlog
from ray_tpu._private.xla_watchdog import compile_budget
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams, \
    resolve_model
from ray_tpu.serve.llm.kv_cache import DevicePool, PagedKVCache, \
    write_rows, write_rows_by_kind
from ray_tpu.util.tracing import abstract, hot_span, register_program

logger = rtlog.get("serve.llm.runner")


_SEEN = contextlib.nullcontext()     # _note_shape: nothing to build


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds largest bucket {buckets[-1]}")


class _NoHolder:
    """``DevicePool``'s two calls for a program that is handed no holder:
    ``None`` in its place (no operand), and the results alone back."""

    abstract = staticmethod(lambda: None)
    donate = staticmethod(lambda program, *args: program(None, *args))


class Chosen(NamedTuple):
    """What a step hands a caller that named ``logit_rows``."""

    ids: np.ndarray                  # (B,) int32: each row's greedy token
    logits: Dict[int, np.ndarray]    # row -> (V,) float32, the rows named
    # a decode step of a module that routes: the distinct experts its
    # live rows chose, summed over the routed layers
    touched: Optional[int] = None
    # a decode step of a module that chooses its pages: (pages its sparse
    # layers read, pages its rows' contexts hold), each summed over live
    # rows, sparse layers and KV heads
    pages: Optional[Tuple[int, int]] = None

    def token(self, row: int, sp: SamplingParams, step: int) -> int:
        """The row's next token: the device's choice for a greedy
        request, sampled here from the row's logits for any other."""
        if sp.greedy:
            return int(self.ids[row])
        return ModelRunner.sample(self.logits[row], sp, step)

    @property
    def logits_nbytes(self) -> int:
        return sum(row.nbytes for row in self.logits.values())


class Enqueued(NamedTuple):
    """A decode step the device has been handed: what ``ModelRunner.pull_step``
    brings to the host, and what the step enqueued behind it reads."""

    step: int                        # the runner's count of decode steps
    picked: tuple                    # (logits, ids), on the device
    carry: "jax.Array"               # the ids at the widest bucket's width
    n: int                           # the rows that are real
    logit_rows: Optional[Sequence[int]]
    # what the step's window layers read (``ModelRunner._window_reads``)
    # or its latent layers (``latent_pages_read``), told to the pull's
    # span; empty without either
    reads: dict = {}


class ModelRunner:
    """Owns params + the jitted, bucketed prefill/decode programs."""

    def __init__(self, cfg: EngineConfig, params=None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models._common import serving_params, tree_bytes

        self.cfg = cfg
        self.mod, self.mcfg = resolve_model(cfg)
        self.weights_key: str = ""      # set when the shm plane is used
        # hot-span totals, name -> [count, seconds]; the engine shares
        # this dict with its own spans (LLMEngine.stats()["span_s"])
        self.span_s: dict = {}
        if params is None:
            params = self._load_params()
        # once in a runner's life, like llm.compile absent from a window;
        # bytes_out - bytes_in of a tree that was in its serving type
        # already: the table held a second time for the gather, or 0
        with hot_span("llm.weights.prepare", self.span_s,
                      bytes_in=tree_bytes(params)) as span:
            self.params = jax.block_until_ready(serving_params(
                params, self.mcfg.dtype, self.mod.WIDE_PARAMS,
                getattr(self.mod, "ROW_TABLES", None)))
            # LLMEngine.stats()["param_bytes"]: what a step program reads
            self.param_bytes = tree_bytes(self.params)
            span.set(bytes_out=self.param_bytes)
        self.n_layer = self.mcfg.n_layer
        self.n_kv = getattr(self.mcfg, "n_kv_head", self.mcfg.n_head)
        self.head_dim = self.mcfg.head_dim
        self.vocab = self.mcfg.vocab_size
        # one sequence's recurrent state in one layer, for a family that
        # has one (None: K/V is all a sequence holds)
        describe = getattr(self.mod, "recurrent_state", None)
        self.state_spec = describe(self.mcfg) if describe else None
        # the layers that hold K/V and those that hold state: a module
        # whose layers differ in kind counts them (cache_layers)
        describe = getattr(self.mod, "cache_layers", None)
        layers = describe(self.mcfg) if describe else {
            "kv": self.n_layer,
            "state": self.n_layer if self.state_spec else 0}
        self.kv_layers, self.state_layers = layers["kv"], layers["state"]
        # layers that hold the last ``window`` positions only, in a pool
        # of their own (0: every K/V layer holds the whole context)
        self.window_layers = layers.get("window", 0)
        self.window = self.mcfg.sliding_window if self.window_layers else 0
        # layers that cache one latent row a position, in a pool of one
        # plane under the same table (0: none), and the row's features
        self.latent_layers = layers.get("latent", 0)
        self.latent_dim = self.mcfg.latent_row if self.latent_layers else 0
        # the choice of experts a module that routes hands over ({"layers",
        # "k"}; None: it does not route), and the last step's, on the device
        describe = getattr(self.mod, "routed_layers", None)
        self.route_spec = describe(self.mcfg) if describe else None
        self.choices = None
        # what the cache keeps a page for a module whose attention chooses
        # its pages ({"stride", "block"}; None: every page is read), and
        # the positions of one prefill program for a module that runs a
        # prompt in chunks (0: a prompt is one program of its bucket)
        describe = getattr(self.mod, "page_selector", None)
        self.select_spec = describe(self.mcfg) if describe else None
        self.chunk = self.mcfg.prefill_chunk \
            if hasattr(self.mod, "forward_prefill_chunk") else 0
        if self.chunk and any(b % self.chunk
                              for b in cfg.prefill_len_buckets):
            raise ValueError(
                f"{cfg.model} prefills in chunks of {self.chunk} positions: "
                f"its prefill buckets {cfg.prefill_len_buckets} are lengths "
                "in whole chunks")
        asked = {"choices": True} if self.route_spec else {}
        forward_prefill = partial(self.mod.forward_prefill, cfg=self.mcfg,
                                  **asked)
        forward_decode = partial(self.mod.forward_decode, cfg=self.mcfg,
                                 **asked)

        def greedy(logits):
            # each row's token at temperature 0, chosen where the logits
            # are: the first index of the maximum, as np.argmax takes it
            with jax.named_scope("lm_head"):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def touched(ids):
            # the distinct experts a decode step chose, summed over the
            # routed layers: ids (layers, rows, k), a padded row holding a
            # live row's choice (live_rows).  Where the module holds a
            # share of the experts (``route_spec["held"]``: first, count)
            # the distinct ones AMONG THE HELD: what the step reads
            held = self.route_spec.get("held")
            with jax.named_scope("moe_router"):
                flat = ids.reshape(ids.shape[0], -1)
                if held:
                    first, count = held
                    flat = jnp.where((flat >= first) & (flat < first + count),
                                     flat, -1)
                flat = jnp.sort(flat, axis=1)
                distinct = 1 + (flat[:, 1:] != flat[:, :-1]).sum(1)
                if held:
                    # the absent ones were all made one more, the lowest
                    distinct = distinct - (flat[:, 0] < 0)
                return distinct.sum().astype(jnp.int32)

        def live_rows(tokens, n_real):
            # a routing module is told which rows of the bucket are some
            # sequence's: the padded ones then choose no expert of their
            # own, and what ``touched`` counts is what the step reads
            if not self.route_spec:
                return {}
            return {"live": jnp.arange(tokens.shape[0]) < n_real}

        def prefill_step(held, params, toks, last_pos):
            # a family with a store is handed the holder, donated: the
            # state at the prompt's last real position goes to the store's
            # last row, where scatter_prefill finds it.  Any other hands
            # None and gets the results alone
            logits, ks, vs, *ids = forward_prefill(params, toks,
                                                   last_pos=last_pos)
            if held is not None:
                state, *ids = ids
                held = {**held, "state": jax.tree.map(
                    lambda s, new: s.at[:, -1].set(new[:, 0]),
                    held["state"], state)}
            out = (logits, greedy(logits)), ks[:, 0], vs[:, 0], *ids
            return out if held is None else (held, out)

        def new_kv_written(held, k, v, block_tables, ctx_lens, n_real,
                           window_tables=None):
            # a row's new K/V goes to the slot append_slot reserved,
            # (table[ctx // bs], ctx % bs).  Rows padded up to the bucket
            # are sent out of range: they write nowhere.  With a
            # selector's cache the half-kernels are written too
            bs = cfg.block_size
            with jax.named_scope("kv_write"):
                rows = jnp.arange(ctx_lens.shape[0])
                blocks = jnp.where(rows < n_real,
                                   block_tables[rows, ctx_lens // bs],
                                   cfg.num_blocks)
                if window_tables is not None:
                    # pages of two kinds: the full layers' rows lead k / v;
                    # the window layers' go to their pool through the
                    # window table, same column, same offset
                    wblocks = jnp.where(
                        rows < n_real, window_tables[rows, ctx_lens // bs],
                        held["kvw"].shape[2])
                    return write_rows_by_kind(held, blocks, wblocks,
                                              ctx_lens % bs, k, v)
                if "latent" in held:
                    # a latent page: the new rows came back as ``k``
                    return {**held, "latent": write_rows(
                        held["latent"], blocks, ctx_lens % bs, k)}
                sel = held.get("sel")
                out = write_rows(held["kv"], blocks, ctx_lens % bs, k, v,
                                 sel)
            if sel is None:
                return {**held, "kv": out}
            return {**held, "kv": out[0], "sel": out[1]}

        widest = cfg.decode_batch_buckets[-1]

        def tokens_in(tokens, last_ids, src):
            # a row that the step before also held takes the token that
            # step chose from where it lies; -1: the host's
            with jax.named_scope("embed"):
                return jnp.where(src >= 0, last_ids[jnp.maximum(src, 0)],
                                 tokens)

        def chosen_from(logits, chose=(), pages=None):
            # (logits, ids), and the ids at the width every bucket's
            # program takes them back at.  ``chose``: a routing module's
            # expert ids; the count of those its live rows touched rides
            # behind the ids, in the pull there is.  ``pages``: a
            # selecting module's two counts of pages, behind that
            ids = greedy(logits)
            pad = widest - ids.shape[0]
            carry = jnp.pad(ids, (0, pad)) if pad else ids
            if chose:
                ids = jnp.concatenate([ids, touched(chose[0])[None]])
            if pages is not None:
                ids = jnp.concatenate([ids, pages])
            return (logits, ids), carry

        def decode_step(held, params, tokens, positions, block_tables,
                        ctx_lens, n_real, last_ids, src, *by_row):
            # the model reads the pool and attends the new token
            # explicitly; its K/V is written after the reads.  Where the
            # holder has a store the model steps the rows of it that
            # state_rows names (the operand exists only then) and returns
            # the store after its K/V.  Where it has a selector's cache the
            # model reads it and says, last of its results, how many pages
            # it read; the new K's half-kernels are written with the K
            # ``by_row``: what the cache names for each row's table, an
            # operand each: the store's rows where there is a store, the
            # window tables where there is a window pool
            reads, by_row = {}, list(by_row)
            window_tables = None
            if "state" in held:
                reads.update(state=held["state"], rows=by_row.pop(0))
            if "sel" in held:
                reads["selector"] = held["sel"]
            if "kvw" in held:
                window_tables = by_row.pop(0)
                reads.update(window_pool=held["kvw"],
                             window_tables=window_tables)
            if "latent" in held:
                reads["latent_pool"] = held["latent"]
            logits, k, v, *ids = forward_decode(
                params, tokens_in(tokens, last_ids, src), positions,
                held["kv"], block_tables, ctx_lens,
                **live_rows(tokens, n_real), **reads)
            if "state" in held:
                store, *ids = ids
                held = {**held, "state": store}
            pages = ids.pop() if "sel" in held else None
            held = new_kv_written(held, k, v, block_tables, ctx_lens, n_real,
                                  window_tables)
            return held, (*chosen_from(logits, ids, pages), k, v, *ids)

        def prefill_chunk_step(held, params, staging, toks, start, n_total):
            # one chunk of one prompt: K/V and half-kernels into the
            # staging, the state from the store's staging row and back
            # into it; the logits are those of the prompt's last position
            # once a chunk holds it.  A family without a store is handed no
            # holder, and carries the staging alone
            logits, staging, state, *ids = self.mod.forward_prefill_chunk(
                params, toks, self.mcfg, start, n_total, staging,
                None if held is None else jax.tree.map(
                    lambda s: s[:, -1], held["state"]), **asked)
            if held is None:
                return (staging, (logits, greedy(logits)), *ids)
            store = jax.tree.map(lambda s, new: s.at[:, -1].set(new),
                                 held["state"], state)
            return {**held, "state": store}, (
                staging, (logits, greedy(logits)), *ids)

        # bound to a name of its own: jaxlint pins a donating jit by the
        # name it is assigned to (lock_watchdog.DONATED)
        llm_prefill_step = jax.jit(prefill_step, donate_argnums=(0,))
        llm_decode_step = jax.jit(decode_step, donate_argnums=(0,))
        self._prefill = llm_prefill_step
        self._decode = llm_decode_step
        # the prefill body's two call forms: through the cache's holder,
        # donated, for a family that stages its state there; no holder for
        # any other
        self._prefill_holder = (lambda: self._state_cache().pool) \
            if self.state_spec else _NoHolder
        self.staging_bytes = 0
        if self.chunk:
            # the staging is donated with the holder and comes back in the
            # program's result: the runner keeps it from chunk to chunk
            llm_prefill_chunk_step = jax.jit(prefill_chunk_step,
                                             donate_argnums=(0, 2))
            self._prefill_chunk = llm_prefill_chunk_step
            positions = -(-cfg.prefill_len_buckets[-1] // self.chunk) \
                * self.chunk
            # made with the first chunk: a runner over shapes alone (a
            # compile for a described chip) holds none
            self.staging_spec = self.mod.prefill_staging(self.mcfg, positions)
            self._staging = None
            self._chunk_choices: list = []
            self.staging_bytes = tree_bytes(self.staging_spec)
        if self.chunk:
            # a routing module's choices, chunk behind chunk
            self._joined = jax.jit(
                lambda parts: jnp.concatenate(parts, axis=1))
        if self.chunk and self.window_layers:
            def packed(staging, first, *, bucket, positions):
                # what prefill_result hands the cache of a prompt's K/V
                # where pages are of two kinds: (1, rows, KV, D), the full
                # layers' first ``bucket`` positions one layer behind
                # another, then each window layer's ``positions`` from
                # ``first`` on, out of the staging's ring (the module
                # knows its ring)
                heads = (self.n_kv, self.head_dim)
                bands = self.mod.staged_window(self.mcfg, staging, first,
                                               positions)
                return tuple(jnp.concatenate(
                    [staging[name][:, :bucket].reshape(1, -1, *heads),
                     band.reshape(1, -1, *heads)], axis=1)
                    for name, band in zip(("k", "v"), bands))
            self._packed = jax.jit(packed,
                                   static_argnames=("bucket", "positions"))
        # one named row of a step's logits, for a request that samples:
        # built with the first such row a bucket meets, not before
        self._logits_row = jax.jit(lambda logits, row: logits[row])
        # what a decode step with no step before it takes as last_ids:
        # zeros placed as a step's own ids will be.  A program's results
        # are committed to a device when an argument is (the weights: the
        # pool and these are placed by default), and jit keeps an entry
        # for each placement of its arguments: zeros placed otherwise
        # than a real step's ids would be a second entry, and its first
        # call a miss inside somebody's measured window
        weights = jax.tree.leaves(self.params)
        self._no_ids = jax.device_put(np.zeros(widest, np.int32), next(
            (w.sharding for w in weights if getattr(w, "committed", False)),
            None))
        self.steps_enqueued = 0    # decode steps, this runner's life
        # the engine's cache: a bucket's scatter program is built with
        # the bucket's first prefill (None: a runner on its own)
        self.cache: Optional[PagedKVCache] = None
        self.compiles = 0          # observability: distinct programs built
        self._shapes_seen: set = set()
        # XLA watchdog step regions (DESIGN.md §4q): one compile per
        # bucket for the runner's life, zero host transfers inside the
        # dispatch.  The post-dispatch np.asarray pulls of the ids and
        # the logits (_pull) are designed syncs and sit OUTSIDE the regions.
        self._prefill_budget = compile_budget(
            "llm.prefill", len(cfg.prefill_len_buckets))
        self._decode_budget = compile_budget(
            "llm.decode", len(cfg.decode_batch_buckets))

    def _load_params(self):
        import jax
        init = partial(self.mod.init_params,
                       jax.random.key(self.cfg.seed), self.mcfg)
        if self.cfg.share_weights:
            from ray_tpu.serve.llm import weights
            self.weights_key = f"{self.cfg.model_key()}_s{self.cfg.seed}"
            return weights.publish_or_attach(self.weights_key, init)
        return init()

    # ---------------------------------------------------------------- prefill
    def prefill(self, token_ids, *,
                logit_rows: Optional[Sequence[int]] = None
                ) -> Tuple[Union[np.ndarray, Chosen], "jax.Array",
                           "jax.Array"]:
        """One prompt → (last-position logits (V,) on the host, k, v
        (L, T, KV, D) on the device).  With ``logit_rows`` (``()``, or
        ``(0,)`` for a request that samples) the first result is
        ``Chosen``: the first token's greedy id and the logits if named.

        The prompt is padded to its length bucket; KV for pad positions
        is garbage and never written (``scatter_prefill`` stops at the
        true length)."""
        import jax.numpy as jnp
        n = len(token_ids)
        tb = _bucket(n, self.cfg.prefill_len_buckets)
        if self.chunk:
            picked = None
            for index in range(self.prefill_chunks(n)):
                picked = self.prefill_chunk(token_ids, index)
            return self.prefill_result(n, picked, logit_rows)
        compiling = self._note_shape("prefill", tb)
        toks = np.zeros((1, tb), np.int32)
        toks[0, :n] = token_ids
        # last_pos is TRACED (one compile per bucket, not per length);
        # only the last real position's (1, V) logits leave the model
        last_pos = jnp.int32(n - 1)
        # dispatch ends at the ENQUEUE (the jitted call returns before
        # the device finishes); pull ends when the id or the logits are
        # on the host
        holder = self._prefill_holder()
        with compiling, hot_span("llm.prefill.dispatch", self.span_s), \
                self._prefill_budget:
            picked, ks, vs, *ids = holder.donate(self._prefill, self.params,
                                                 toks, last_pos)
            if ids:
                self.choices, = ids
            if compiling is not _SEEN:
                register_program(
                    f"llm.prefill.{tb}", self._prefill,
                    (holder.abstract(),
                     *abstract((self.params, toks, last_pos))))
                if self.cache is not None:
                    self.cache.warm_scatter(ks, vs)
        out = self._pull("llm.prefill.pull", picked, 1, logit_rows)
        return (out[0] if logit_rows is None else out), ks, vs

    # ------------------------------------------------------ prefill in chunks
    def prefill_chunks(self, n_tokens: int) -> int:
        """The chunks a prompt of ``n_tokens`` runs as."""
        return -(-n_tokens // self.chunk)

    def prefill_chunk(self, token_ids, index: int, after=None):
        """Enqueue chunk ``index`` of one prompt (a module with
        ``forward_prefill_chunk``): one program whatever the prompt's
        length or the chunk's place in it, over the runner's staging K/V
        and the store's staging row, which carry what the chunks before
        left.  One prompt at a time: a chunk 0 starts the next.  Returns
        what :meth:`prefill_result` takes, on the device.

        ``after``: the chunk before, waited for inside this call's
        ``llm.prefill.chunk`` span before this one is enqueued: a caller
        that enqueues other work between two chunks (the engine's loop: a
        decode step) then keeps one chunk in flight, and the span is about
        as long as a chunk takes."""
        import jax
        c, n = self.chunk, len(token_ids)
        part = token_ids[index * c:(index + 1) * c]
        toks = np.zeros((1, c), np.int32)
        toks[0, :len(part)] = part
        compiling = self._note_shape("prefill_chunk", c)
        if self._staging is None:
            self._staging = jax.tree.map(
                lambda s: jax.numpy.zeros(s.shape, s.dtype),
                self.staging_spec)
        args = (self.params, self._staging, toks, np.int32(index * c),
                np.int32(n))
        pool = self._prefill_holder()
        if compiling is not _SEEN:
            register_program(f"llm.prefill.chunk.{c}", self._prefill_chunk,
                             (pool.abstract(), *abstract(args)))
        with compiling, hot_span("llm.prefill.chunk", self.span_s,
                                 chunk=index, tokens=n), \
                self._prefill_budget:
            if after is not None:
                np.asarray(after[1])    # its greedy id: 4 bytes, the wait
            self._staging, picked, *ids = pool.donate(self._prefill_chunk,
                                                      *args)
        if ids:
            # a routing module: the chunks' choices, joined when the
            # prompt is done (prefill_result)
            if index == 0:
                self._chunk_choices = []
            self._chunk_choices.append(ids[0])
        return picked

    def prefill_result(self, n_tokens: int, picked, logit_rows=None):
        """What :meth:`prefill` returns, after a prompt's last chunk: its
        logits (or ``Chosen``) and the prompt's K/V out of the staging,
        ``(L, bucket, KV, D)`` on the device."""
        tb = _bucket(n_tokens, self.cfg.prefill_len_buckets)
        if self.window_layers:
            # pages of two kinds: one array a K and a V, the full layers'
            # rows one behind another and then each window layer's run of
            # the prompt's last positions, as the cache scatters them
            first, run = self._state_cache().window_run(n_tokens)
            ks, vs = self._packed(self._staging, first, bucket=tb,
                                  positions=run)
        elif self.latent_layers:
            # a latent page: the prompt's rows, as the cache scatters them
            ks = vs = self._staging["latent"][:, :tb, None]
        else:
            heads = (self.n_kv, self.head_dim)
            ks, vs = (self._staging[name][:, :tb].reshape(
                self.kv_layers, tb, *heads) for name in ("k", "v"))
        if self._chunk_choices:
            self.choices = self._joined(self._chunk_choices)
            self._chunk_choices = []
        if ("scatter", tb) not in self._shapes_seen and \
                self.cache is not None:
            self._shapes_seen.add(("scatter", tb))
            self.cache.warm_scatter(ks, vs)
        out = self._pull("llm.prefill.pull", picked, 1, logit_rows)
        return (out[0] if logit_rows is None else out), ks, vs

    # ----------------------------------------------------------------- decode
    def decode(self, tokens: np.ndarray, positions: np.ndarray,
               kv_pool: DevicePool, block_tables: np.ndarray,
               ctx_lens: np.ndarray, *,
               logit_rows: Optional[Sequence[int]] = None,
               after: Optional[Enqueued] = None,
               rows: Optional[np.ndarray] = None, wait: bool = True
               ) -> Tuple[Union[np.ndarray, Chosen, Enqueued], "jax.Array",
                          "jax.Array"]:
        """One iteration over a batch of sequences.

        tokens/positions/ctx_lens (B,); block_tables (B, MAXB);
        kv_pool — the cache's ``pool``: the holder of the device array,
        which the step takes donated and hands back with each row's new
        K/V written at ``(block_tables[i, ctx_lens[i] // block_size],
        ctx_lens[i] % block_size)``.  Returns (logits (B, V) on the host,
        new_k, new_v (L, bucket, KV, D) on the device; only the first B
        rows are real after bucket padding).  With ``logit_rows`` (the
        rows whose request samples; ``()`` for a greedy batch) the first
        result is ``Chosen``: the (B,) ids and those rows' logits, and
        the rest of the logits stay on the device.

        ``after`` and ``rows`` (B,): the decode step enqueued before this
        one and, for each row here, its row there; such a row's token is
        the id that step chose, read on the device, and ``tokens[i]`` is
        not looked at (-1: the row was not in that step, ``tokens[i]`` it
        is).  With ``wait=False`` the call returns at the enqueue and the
        first result is the ``Enqueued`` that ``pull_step`` takes.
        """
        b = len(tokens)
        bb = _bucket(b, self.cfg.decode_batch_buckets)
        compiling = self._note_shape("decode", bb)
        pad = bb - b
        last_ids, src = self._no_ids, np.full(bb, -1, np.int32)
        if after is not None:
            last_ids, src[:b] = after.carry, rows
        if pad:
            with hot_span("llm.decode.tables", self.span_s):
                tokens = np.concatenate([tokens, np.zeros(pad, np.int32)])
                positions = np.concatenate([positions,
                                            np.zeros(pad, np.int32)])
                ctx_lens = np.concatenate([ctx_lens,
                                           np.zeros(pad, np.int32)])
                block_tables = np.concatenate(
                    [block_tables, np.zeros((pad, block_tables.shape[1]),
                                            np.int32)])
        state_rows, reads = (), {}
        if kv_pool.state:
            # whose table each is, the cache knows; padded rows name none
            cache = self._state_cache()
            state_rows = (np.concatenate(
                [cache.rows_of(block_tables[:b]),
                 np.full(pad, cache.no_row, np.int32)]),)
        if kv_pool.window is not None:
            # the window tables of the same rows, and what the step's
            # window layers read of them (for the pull's span)
            state_rows += (self._state_cache().window_tables(block_tables),)
            reads = self._window_reads(ctx_lens[:b])
        if kv_pool.latent is not None:
            # the pages the absorbed kernel walks, for the pull's span
            reads = dict(latent_pages_read=int(
                (-(-ctx_lens[:b].astype(np.int64) // self.cfg.block_size)
                 ).sum()) * self.latent_layers)
        # dispatch holds the jitted call and ends at the ENQUEUE; pull
        # ends when the ids or the logits are on the host, so it holds the
        # wait for the step and nothing else: the pool stays where it is
        args = (self.params, tokens, positions, block_tables, ctx_lens,
                np.int32(b), last_ids, src, *state_rows)
        if compiling is not _SEEN:
            register_program(f"llm.decode.{bb}", self._decode,
                             (kv_pool.abstract(), *abstract(args)))
        with compiling, hot_span("llm.decode.dispatch", self.span_s), \
                self._decode_budget:
            picked, carry, ks, vs, *ids = kv_pool.donate(self._decode, *args)
        if ids:
            self.choices, = ids
        self.steps_enqueued += 1
        step = Enqueued(self.steps_enqueued, picked, carry, b, logit_rows,
                        reads)
        return (self.pull_step(step) if wait else step), ks, vs

    def _window_reads(self, ctx_lens: np.ndarray) -> dict:
        """What a decode step's window layers read, from its rows'
        context lengths: positions (the window's, or the context where it
        is shorter), blocks (the walk's columns, the first one whole), and
        the blocks full layers would have read in their place; each summed
        over the rows and the window layers."""
        bs, layers = self.cfg.block_size, self.window_layers
        lens = np.asarray(ctx_lens, np.int64)
        lo = np.maximum(lens - (self.window - 1), 0)
        held = -(-lens // bs)
        return dict(
            window_positions=int((lens - lo).sum()) * layers,
            window_blocks=int((held - lo // bs).sum()) * layers,
            window_blocks_unwindowed=int(held.sum()) * layers)

    def pull_step(self, step: Enqueued) -> Union[np.ndarray, Chosen]:
        """Wait for an enqueued decode step and bring the host what its
        caller named (``decode``'s first result), inside an
        ``llm.decode.pull`` span that says which step it is."""
        return self._pull("llm.decode.pull", step.picked, step.n,
                          step.logit_rows, touched=self.route_spec is not None,
                          paged=self.select_spec is not None, step=step.step,
                          **step.reads)

    def _pull(self, span: str, picked, n: int,
              logit_rows: Optional[Sequence[int]], touched: bool = False,
              paged: bool = False, **attrs):
        """A step's results for the host, inside ``span`` (whose ``bytes``
        is what crossed): all its logits, (n, V), for a caller that named
        no rows; else the n ids and the rows named (``touched``: the count
        that a routing module's decode step sends behind its ids)."""
        logits, ids = picked
        with hot_span(span, self.span_s, **attrs) as pull:
            if logit_rows is None:
                pull.set(bytes=logits.nbytes)
                return np.asarray(logits)[:n]
            ids = np.asarray(ids)
            # behind the ids: a routing module's count, then a selecting
            # module's two
            pages = (int(ids[-2]), int(ids[-1])) if paged else None
            count = int(ids[-3 if paged else -1]) if touched else None
            chosen = Chosen(ids[:n], {
                int(row): np.asarray(self._logits_row(logits, np.int32(row)))
                for row in logit_rows}, count, pages)
            if touched:
                pull.set(experts_touched=chosen.touched)
            if paged:
                pull.set(sparse_pages_read=pages[0],
                         sparse_pages_held=pages[1])
            pull.set(bytes=ids.nbytes + chosen.logits_nbytes)
            return chosen

    def _state_cache(self) -> PagedKVCache:
        """The cache whose holder has the store of recurrent state."""
        if self.cache is None:
            raise RuntimeError(
                f"{self.cfg.model} keeps recurrent state, which lives in "
                "the engine's cache: a runner on its own cannot serve it")
        return self.cache

    def _note_shape(self, program: str, bucket: int):
        """A context for the call that follows: an ``llm.compile`` span
        around the first call of a (program, bucket), nothing after."""
        key = (program, bucket)
        if key in self._shapes_seen:
            return _SEEN
        self._shapes_seen.add(key)
        self.compiles += 1
        logger.info("compiling %s program (total %d)", key, self.compiles)
        return hot_span("llm.compile", self.span_s, program=program,
                        bucket=bucket)

    # --------------------------------------------------------------- sampling
    @staticmethod
    def sample(logits: np.ndarray, sp: SamplingParams,
               step: int) -> int:
        """Host-side sampling of one token from (V,) logits."""
        if sp.greedy:
            return int(np.argmax(logits))
        x = logits.astype(np.float64) / sp.temperature
        if sp.top_k:
            kth = np.partition(x, -sp.top_k)[-sp.top_k]
            x = np.where(x < kth, -np.inf, x)
        x -= x.max()
        p = np.exp(x)
        p /= p.sum()
        rng = np.random.default_rng((sp.seed, step))
        return int(rng.choice(len(p), p=p))
