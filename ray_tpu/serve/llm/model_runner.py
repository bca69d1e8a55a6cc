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

The contract with a model's module, said once.  A served family is
``ray_tpu.models.<family>`` for a name in ``config.SERVED_FAMILIES``.  It
exports ``forward_prefill`` / ``forward_decode`` (the decode step reads the
paged pool through ``ops/paged_attention.py`` and returns the new token's
rows), ``init_params``, ``PRESETS``, ``WIDE_PARAMS`` (the leaves that stay
as stored; every other is held in the model's ``dtype``, converted once,
``models/_common.serving_params``) and, each only where it applies, what
``family_of`` asks once and keeps as ``runner.family``:

* ``recurrent_state(cfg)``, ``cache_layers(cfg)``, ``page_selector(cfg)``:
  what a sequence keeps beside or instead of K/V.  ``kv_cache.kept_by``
  reads them; the cache is built from the answer and holds a *plane* for
  each kind (``kv_cache.PLANES``: ``kv``, ``state``, ``sel``, ``kvw``,
  ``latent``, ``index``; DESIGN.md section 4g has the table of kinds and
  families).
  The runner knows no kind by name: a plane's row says which keywords the
  decode forward is handed for it, which per-row operand the cache makes
  for a step, and how new rows are written.
* ``routed_layers(cfg)`` -> ``{"layers", "k"}`` (``route_spec``): the
  forwards are asked for the chosen expert ids (``choices=True``) and the
  step programs return them as one more result, int32 ``(routed layers,
  rows, k)``; the last step's stay on the device as ``runner.choices``.  A
  decode program tells the forward which rows are live (``live=``).
* ``forward_prefill_chunk`` and ``prefill_staging`` (``chunk``,
  ``staging_spec``): ONE prefill program of ``cfg.prefill_chunk``
  positions whatever the prompt's length, over a staging the runner keeps
  and the program takes donated; ``prefill(prompt)`` is those chunks one
  behind another and ``prefill_result`` after the last.  The prefill
  buckets are then lengths in whole chunks and cost a scatter program
  each.  The engine's loop calls ``prefill_chunk`` itself, one an
  iteration with a decode step of the live rows behind it.
* ``ROW_TABLES``: a tied head's table held a second time for the
  embedding's gather where its rows are no whole lanes.
* ``block_stepping(cfg)`` -> ``{"block", "mask_id", "per_pass"}``
  (``block``): the model generates by diffusion over blocks.
  A decode step is then a *pass* over a block of ``block`` positions a row
  ("A block a row", below), a prompt is prefilled in whole blocks under the
  block-causal mask and yields no token.

The step programs.  One decode body and one prefill body (and one chunk
body), each reading what the holder's dict has.  The decode program takes
the holder donated and hands it back with the new rows written
(``kv_cache.rows_written``: the one writer, the cache's own); a family that
stages its state through the holder runs its prefill through it too, any
other hands the same body no holder (``_NoHolder``).  Prefill leaves a
prompt's rows on the device for ``PagedKVCache.scatter_prefill``.  Behind
the eight operands every decode program takes ride the planes' operands
``by_row``, in the table's order at both ends (``decode`` builds them in
one loop, ``kv_cache.handed_to_forward`` takes them apart): the store's
rows, then the window tables.

What comes to the host.  Each step program returns, beside its float32
logits, every row's greedy token (``argmax``, the first index of the
maximum as ``np.argmax`` takes it).  A caller that names the rows whose
logits it needs (``logit_rows=``: the engine names those whose request
samples, usually none) gets ``Chosen``: the ``(B,)`` ids, those rows, and
``reads``, what the step says of itself by name.  Counts the device made
ride behind the ids in the one pull, in ONE layout (``riders_of``:
``experts_touched``, then ``sparse_pages_read``, ``sparse_pages_held``)
that the program's pack and ``_pull``'s read both take from
``family.layout``; counts the host reckons from the context lengths
(``Plane.reads``: ``window_positions``, ``window_blocks``,
``window_blocks_unwindowed``, ``latent_pages_read``, ``positions_scored``,
``positions_read``) travel as
``Enqueued.reads``.  ``llm.decode.pull`` is told both, with ``step`` and
``bytes``.  Seeded temperature / top-k sampling stays host-side on a
pulled ``(V,)`` row (``sample``).  A caller that names nothing gets all
the logits on the host.

A block a row.  For a family that steps by blocks ``decode`` takes
``tokens (R, B)`` (a row's open block), ``decided (R, B)`` (which of its
positions hold a token: the others are fed the mask id, whatever id they
carry) and ``commit (R,)`` (the row's pass writes the block's K/V into the
slots ``PagedKVCache.append_block`` reserved; any other pass writes
nothing); ``positions`` = ``ctx_lens``, the committed positions before the
block.  The one program a bucket runs the forward over ``(R, B)`` positions,
takes each undecided position's ``argmax`` and its softmax probability (the
confidence, float32) and applies the remasking rule ON THE DEVICE: the
block after the pass, ids and decided-flags, stays there as the step's
``carry`` and feeds the pass behind it by row (``rows=src``), and what
crosses to the host is ``ChosenBlocks``: ``(R, B)`` ids, confidences and
flags in one pull, never ``(R, B, V)`` logits unless a row is named.

Two lengths a row.  A family whose sequences fold (``folded_cache(cfg)``:
``kv_cache.py``, a table that shrinks) has two: the positions a row has SEEN
and the rows it HOLDS.  ``prefill`` / ``prefill_chunk`` / ``decode`` are
handed the first, as for any family (``positions`` and ``ctx_lens`` alike,
the prompt's length); the second is a pure function of it
(``kv_cache.held_rows``), taken inside the decode program for the kernels'
context and the new row's slot, and by the cache for a prompt's scatter.  The
fold of a window that closes in decode is this runner's program too
(``fold_windows``: it needs the model's ``phi`` and ``mu``), handed to the
cache when the cache is (``ModelRunner.cache``), which runs it inside
``append_slot``; built and run once, on pages out of range, at that hand-over.

One step behind another.  A greedy row's next token is the id the step
before chose, on the device: the decode programs take the last step's ids
(``last_ids``, at the widest bucket's width) and a row map ``src``: row
i's token is ``last_ids[src[i]]``, or ``tokens[i]`` where ``src[i]`` is
-1.  ``decode(..., after=step, rows=src, wait=False)`` enqueues such a
step and returns an ``Enqueued``; ``pull_step`` waits for one.  A call
without ``after`` sends zeros and a map of -1s of the same type and
placement (``_no_ids``): one executable a bucket serves both.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple, \
    Union

import numpy as np

from ray_tpu._private import rtlog
from ray_tpu._private.xla_watchdog import compile_budget
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams, \
    resolve_model
from ray_tpu.serve.llm.kv_cache import DevicePool, Kept, PagedKVCache, \
    _declared, block_slots_reserved, fold_reads, handed_to_forward, \
    held_rows, kept_by, rows_written, slots_reserved, staged_rows, \
    stepped_by_forward, window_reads, write_rows
from ray_tpu.util.tracing import abstract, hot_span, \
    listen_to_compiles, register_program, setup_span

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
    # what a decode step says of itself, name -> int: what rode behind its
    # ids (``riders_of``) and what the host reckoned (``Enqueued.reads``)
    reads: dict = {}

    @property
    def touched(self) -> Optional[int]:
        """A step of a module that routes: the distinct experts its live
        rows chose, summed over the routed layers."""
        return self.reads.get("experts_touched")

    @property
    def pages(self) -> Optional[Tuple[int, int]]:
        """A step of a module that chooses its pages: (pages its sparse
        layers read, pages its rows' contexts hold), each summed over live
        rows, sparse layers and KV heads."""
        if "sparse_pages_read" not in self.reads:
            return None
        return (self.reads["sparse_pages_read"],
                self.reads["sparse_pages_held"])

    def token(self, row: int, sp: SamplingParams, step: int) -> int:
        """The row's next token: the device's choice for a greedy
        request, sampled here from the row's logits for any other."""
        if sp.greedy:
            return int(self.ids[row])
        return ModelRunner.sample(self.logits[row], sp, step)

    @property
    def logits_nbytes(self) -> int:
        return sum(row.nbytes for row in self.logits.values())


class ChosenBlocks(NamedTuple):
    """What a pass over blocks hands a caller that named ``logit_rows``:
    each row's block AFTER the pass's remasking rule."""

    ids: np.ndarray                  # (R, B) int32: the block's tokens
    conf: np.ndarray                 # (R, B) float32: softmax[argmax]
    decided: np.ndarray              # (R, B) bool: the positions that hold one
    logits: Dict[int, np.ndarray]    # row -> (B, V) float32, the rows named
    reads: dict = {}

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
    # what the planes say the step reads, reckoned on the host from its
    # context lengths (``kv_cache.Plane.reads``), told to the pull's span
    reads: dict = {}


class Rider(NamedTuple):
    """Counts of a decode step that ride behind its ids, in the one pull."""

    names: tuple                     # what ``llm.decode.pull`` is told
    take: Callable                   # (the forward's results, a list) -> x
    count: Callable                  # x -> (len(names),) int32, in the step


def _experts_touched(ids, held=None):
    """The distinct experts a decode step chose, summed over the routed
    layers: ids (layers, rows, k), a padded row holding a live row's
    choice.  Where the module holds a share of the experts (``held``:
    first, count) the distinct ones AMONG THE HELD: what the step reads."""
    import jax
    import jax.numpy as jnp
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


def riders_of(route_spec, select_spec) -> Tuple[Rider, ...]:
    """The one layout of what rides behind a decode step's ids: a routing
    module's count of experts touched (of its choices, the first of the
    forward's results, which stay a result), then a selecting module's two
    counts of pages (the forward's last result, taken off them)."""
    riders = ()
    if route_spec:
        held = route_spec.get("held")
        riders += (Rider(("experts_touched",), lambda results: results[0],
                         lambda ids: _experts_touched(ids, held)[None]),)
    if select_spec:
        riders += (Rider(("sparse_pages_read", "sparse_pages_held"),
                         list.pop, lambda pages: pages),)
    return riders


def _ridden(ids, counts):
    """``ids`` with each rider's ``counts`` behind them, in order."""
    import jax.numpy as jnp
    for count in counts:
        ids = jnp.concatenate([ids, count])
    return ids


def _riders_read(ids: np.ndarray, width: int, names: tuple) -> dict:
    """What rode behind the ``width`` ids of a pulled step, by name."""
    if ids.shape != (width + len(names),):
        raise ValueError(
            f"a step of {width} rows with {names} behind its ids pulled "
            f"{ids.shape}: the program packed another layout")
    return dict(zip(names, map(int, ids[width:])))


@dataclasses.dataclass(frozen=True)
class Family:
    """What the runner asks a model's module, once (:func:`family_of`)."""

    kept: Kept                       # what a sequence keeps (kv_cache.py)
    # the choice of experts a module that routes hands over ({"layers",
    # "k"}; None: it does not route)
    route_spec: Optional[dict]
    # what the cache keeps a page for a module whose attention chooses its
    # pages ({"stride", "block"}; None: every page is read)
    select_spec: Optional[dict]
    # the positions of one prefill program for a module that runs a prompt
    # in chunks (0: a prompt is one program of its bucket), and the staging
    # its chunks carry, as shapes
    chunk: int
    staging_spec: Optional[dict]
    wide_params: tuple               # the leaves that stay as stored
    row_tables: Optional[tuple]      # tables held again for their gather
    riders: Tuple[Rider, ...]
    layout: tuple                    # their names, in the order they ride
    # how a module that generates by diffusion over blocks is stepped
    # ({"block", "mask_id", "per_pass"}; None: by tokens)
    block_spec: Optional[dict] = None


def family_of(mod, mcfg, cfg: EngineConfig) -> Family:
    """Every question the serving stack asks a module, asked here."""
    route = _declared(mod, mcfg, "routed_layers")
    select = _declared(mod, mcfg, "page_selector")
    chunk = mcfg.prefill_chunk if hasattr(mod, "forward_prefill_chunk") else 0
    if chunk and any(b % chunk for b in cfg.prefill_len_buckets):
        raise ValueError(
            f"{cfg.model} prefills in chunks of {chunk} positions: "
            f"its prefill buckets {cfg.prefill_len_buckets} are lengths "
            "in whole chunks")
    # the staging holds the largest bucket's positions, in whole chunks
    staging = mod.prefill_staging(
        mcfg, -(-cfg.prefill_len_buckets[-1] // chunk) * chunk) \
        if chunk else None
    riders = riders_of(route, select)
    blocks = _declared(mod, mcfg, "block_stepping")
    kept = kept_by(mod, mcfg)
    if blocks:
        _blocks_fit(cfg, kept, chunk, blocks["block"])
    return Family(kept, route, select, chunk, staging,
                  mod.WIDE_PARAMS, getattr(mod, "ROW_TABLES", None), riders,
                  tuple(name for rider in riders for name in rider.names),
                  blocks)


def _blocks_fit(cfg: EngineConfig, kept: Kept, chunk: int, span: int) -> None:
    """A family that steps by blocks of ``span`` positions: what the stack
    has written for it, or a refusal that says what is missing."""
    if chunk or kept.state or kept.window_layers or kept.latent_layers \
            or kept.select_stride:
        raise NotImplementedError(
            f"{cfg.model} steps by blocks of {span} positions: only K/V "
            "pages under one table and a prompt in one program are written "
            "for such a step")
    if cfg.block_size % span or any(b % span
                                    for b in cfg.prefill_len_buckets):
        raise ValueError(
            f"{cfg.model} steps by blocks of {span} positions: pages of "
            f"{cfg.block_size} and the prefill buckets "
            f"{cfg.prefill_len_buckets} are whole blocks")


def remasked(logits, tokens, decided, per_pass: int):
    """A pass's remasking rule, on the device: logits (R, B, V) float32, the
    row's block ``tokens`` (R, B) and which positions are ``decided`` ->
    (the block after the pass, its decided-flags, conf (R, B) float32).
    Each undecided position's candidate is its own logits' argmax and its
    confidence that token's softmax probability; the ``per_pass`` undecided
    positions of highest confidence are fixed, the lower position first on
    a tie.  A block with nothing undecided (a commit pass) comes back as it
    was."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("remask"):
        top = logits.max(-1)
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        conf = jnp.exp(top - jax.nn.logsumexp(logits, axis=-1))
        span = tokens.shape[1]
        left = jnp.where(decided, -1.0, conf)
        newly = jnp.zeros(decided.shape, bool)
        for _ in range(per_pass):
            best = jnp.argmax(left, axis=-1)                    # first max
            hot = (jnp.arange(span) == best[:, None]) & (
                left.max(-1, keepdims=True) >= 0.0)
            newly, left = newly | hot, jnp.where(hot, -1.0, left)
        return jnp.where(newly, x0, tokens), decided | newly, conf


class ModelRunner:
    """Owns params + the jitted, bucketed prefill/decode programs."""

    def __init__(self, cfg: EngineConfig, params=None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models._common import serving_params, tree_bytes

        self.cfg = cfg
        self.mod, self.mcfg = resolve_model(cfg)
        # what the module declares, asked once; the attributes below are
        # plain reads of it
        self.family = family = family_of(self.mod, self.mcfg, cfg)
        kept = family.kept
        self.weights_key: str = ""      # set when the shm plane is used
        # hot-span totals, name -> [count, seconds]; the engine shares
        # this dict with its own spans (LLMEngine.stats()["span_s"])
        self.span_s: dict = {}
        listen_to_compiles()
        if params is None:
            params = self._load_params()
        # once in a runner's life, like llm.compile absent from a window;
        # bytes_out - bytes_in of a tree that was in its serving type
        # already: the table held a second time for the gather, or 0
        with setup_span("llm.weights.prepare", self.span_s, "llm.weights",
                        bytes_in=tree_bytes(params)) as span:
            self.params = jax.block_until_ready(serving_params(
                params, self.mcfg.dtype, family.wide_params,
                family.row_tables))
            # LLMEngine.stats()["param_bytes"]: what a step program reads
            self.param_bytes = tree_bytes(self.params)
            span.set(bytes_out=self.param_bytes)
        self.n_layer = self.mcfg.n_layer
        self.n_kv, self.head_dim = kept.n_kv, kept.head_dim
        self.vocab = self.mcfg.vocab_size
        # one sequence's recurrent state in one layer (None: none), and
        # the layers of each kind of plane (kv_cache.Kept)
        self.state_spec = kept.state
        self.kv_layers, self.state_layers = kept.kv_layers, kept.state_layers
        self.window_layers, self.window = kept.window_layers, kept.window
        self.latent_layers, self.latent_dim = \
            kept.latent_layers, kept.latent_dim
        self.route_spec, self.select_spec = \
            family.route_spec, family.select_spec
        self.chunk = family.chunk
        # a module stepped by blocks: its spec (None: by tokens)
        self.block = family.block_spec
        # a routing module's last step's choices, on the device
        self.choices = None
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

        def live_rows(tokens, n_real):
            # a routing module is told which rows of the bucket are some
            # sequence's: the padded ones then choose no expert of their
            # own, and what ``_experts_touched`` counts is what the step reads
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
            chose = greedy(logits)
            if self.block:
                # the last block's (1, B) candidates, flat as a step's ids
                chose = chose.reshape(-1)
            out = (logits, chose), ks[:, 0], vs[:, 0], *ids
            return out if held is None else (held, out)

        widest = cfg.decode_batch_buckets[-1]

        def tokens_in(tokens, last_ids, src):
            # a row that the step before also held takes the token that
            # step chose from where it lies; -1: the host's
            with jax.named_scope("embed"):
                return jnp.where(src >= 0, last_ids[jnp.maximum(src, 0)],
                                 tokens)

        def chosen_from(logits, riding=()):
            # (logits, ids), and the ids at the width every bucket's
            # program takes them back at.  ``riding``: (rider, what it
            # took of the forward's results); their counts ride behind the
            # ids in the layout's order, in the pull there is
            ids = greedy(logits)
            pad = widest - ids.shape[0]
            carry = jnp.pad(ids, (0, pad)) if pad else ids
            return (logits, _ridden(ids, [rider.count(x)
                                          for rider, x in riding])), carry

        def decode_step(held, params, tokens, positions, block_tables,
                        ctx_lens, n_real, last_ids, src, *by_row):
            # the model reads the pool and attends the new token
            # explicitly; its K/V is written after the reads.  What else
            # the holder holds the forward is handed under the keywords its
            # plane names (kv_cache.PLANES), with the plane's operand of
            # ``by_row`` where the cache names one for each row's table
            # (the store's rows, the window tables: in the table's order,
            # as ``decode`` built them); a plane the model steps comes
            # back behind its K/V, and what a rider takes off the results
            # is counted behind the ids.  The new rows, and what is kept
            # beside the K, are written by the cache's own writer
            handed, tables = handed_to_forward(held, by_row)
            # the rows each sequence HOLDS: what it has seen, but under a
            # table that shrinks (``positions`` stay the positions seen)
            ctx_lens = held_rows(ctx_lens, kept.fold_window, kept.fold_chunk)
            logits, k, v, *ids = forward_decode(
                params, tokens_in(tokens, last_ids, src), positions,
                held["kv"], block_tables, ctx_lens,
                **live_rows(tokens, n_real), **handed)
            held = stepped_by_forward(held, ids)
            riding = [(rider, rider.take(ids)) for rider in family.riders]
            with jax.named_scope("kv_write"):
                held = rows_written(held, *slots_reserved(
                    held, {"kv": block_tables, **tables}, ctx_lens, n_real),
                    k, v)
            return held, (*chosen_from(logits, riding), k, v, *ids)

        def block_step(held, params, tokens, positions, block_tables,
                       ctx_lens, n_real, last, src, decided, commit):
            # a pass over a block a row.  A row the pass before also held
            # takes its block, ids and flags, from where that pass left it
            # (``last``, at the widest bucket's width); the undecided
            # positions are fed the mask id, whatever id they carry.  The
            # rule runs here, on the float32 logits; the block's K/V is
            # written for the rows whose pass commits and for no other
            spec = self.block
            span = spec["block"]
            with jax.named_scope("embed"):
                took = (src >= 0)[:, None]
                at = jnp.maximum(src, 0)
                tokens = jnp.where(took, last[0][at], tokens)
                decided = jnp.where(took, last[1][at], decided)
                fed = jnp.where(decided, tokens, jnp.int32(spec["mask_id"]))
            live = live_rows(tokens, n_real)
            logits, k, v, *ids = forward_decode(
                params, fed, positions, held["kv"], block_tables, ctx_lens,
                **live)
            riding = [(rider, rider.take(ids)) for rider in family.riders]
            block, flags, conf = remasked(logits, tokens, decided,
                                         spec["per_pass"])
            with jax.named_scope("kv_write"):
                writes = commit & (jnp.arange(tokens.shape[0]) < n_real)
                rows = k.shape[1] * span                    # (L, R, B, ..)
                held = rows_written(
                    held, *block_slots_reserved(held, block_tables, ctx_lens,
                                                writes, span),
                    *(a.reshape(a.shape[0], rows, *a.shape[3:])
                      for a in (k, v)))
            packed = _ridden(jnp.concatenate([
                block.reshape(-1),
                jax.lax.bitcast_convert_type(conf, jnp.int32).reshape(-1),
                flags.astype(jnp.int32).reshape(-1)]),
                [rider.count(x) for rider, x in riding])
            pad = ((0, widest - block.shape[0]), (0, 0))
            carry = (jnp.pad(block, pad), jnp.pad(flags, pad))
            return held, ((logits, packed), carry, k, v, *ids)

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

        def fold_step(held, params, pages):
            # one closed window out of the pool: its folded rows over the
            # first of its own pages (every row is read before one is
            # written: the program is a function of the pool it was given)
            kf, vf = self.mod.fold_window(params, self.mcfg, held["kv"],
                                          pages)
            bs = held["kv"].shape[3]
            keep = kf.shape[1] // bs
            with jax.named_scope("eva_fold"):
                pool = write_rows(
                    held["kv"], jnp.repeat(pages[:keep], bs),
                    jnp.tile(jnp.arange(bs), keep), kf, vf)
            return {**held, "kv": pool}, None

        # bound to a name of its own: jaxlint pins a donating jit by the
        # name it is assigned to (lock_watchdog.DONATED)
        llm_fold_step = jax.jit(fold_step, donate_argnums=(0,))
        self._fold = llm_fold_step
        llm_prefill_step = jax.jit(prefill_step, donate_argnums=(0,))
        llm_decode_step = jax.jit(block_step if self.block else decode_step,
                                  donate_argnums=(0,))
        self._prefill = llm_prefill_step
        self._decode = llm_decode_step
        # the prefill body's two call forms: through the cache's holder,
        # donated, for a family that stages its state there; no holder for
        # any other
        self._prefill_holder = (lambda: self._state_cache().pool) \
            if kept.staged else _NoHolder
        self.staging_bytes = 0
        self._packed = None
        if self.chunk:
            # the staging is donated with the holder and comes back in the
            # program's result: the runner keeps it from chunk to chunk
            llm_prefill_chunk_step = jax.jit(prefill_chunk_step,
                                             donate_argnums=(0, 2))
            self._prefill_chunk = llm_prefill_chunk_step
            # made with the first chunk: a runner over shapes alone (a
            # compile for a described chip) holds none
            self.staging_spec = family.staging_spec
            self._staging = None
            self._chunk_choices: list = []
            self.staging_bytes = tree_bytes(self.staging_spec)
            # a routing module's choices, chunk behind chunk
            self._joined = jax.jit(
                lambda parts: jnp.concatenate(parts, axis=1))
        if self.chunk and kept.packed:
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
        placed = next(
            (w.sharding for w in weights if getattr(w, "committed", False)),
            None)
        self._no_ids = jax.device_put(np.zeros(widest, np.int32), placed)
        if self.block:
            # a pass with no pass before it: no row takes a block from it
            self._no_ids = tuple(jax.device_put(
                np.zeros((widest, self.block["block"]), t), placed)
                for t in (np.int32, bool))
        self.steps_enqueued = 0    # decode steps, this runner's life
        # the engine's cache: a bucket's scatter program is built with
        # the bucket's first prefill (None: a runner on its own)
        self._cache: Optional[PagedKVCache] = None
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

    @property
    def cache(self) -> Optional[PagedKVCache]:
        return self._cache

    @cache.setter
    def cache(self, cache: Optional[PagedKVCache]) -> None:
        """The engine's cache; one whose table shrinks is handed the fold
        program, which is built and run here once on pages out of range
        (read clamped, written nowhere): no window closes on a compile."""
        self._cache = cache
        if cache is not None and cache.fold_window:
            cache.folder = self.fold_windows
            pages = cache.fold_window // cache.block_size
            self.fold_windows(np.full(pages, cache.num_blocks, np.int32),
                              windows=0)

    def fold_windows(self, pages: np.ndarray, windows: int = 1) -> None:
        """Fold one closed window of the pool, ``pages`` its blocks in
        order: enqueued behind whatever wrote them, inside an
        ``llm.window.fold`` span that says what it folds (``rows_folded``:
        the window's rows x layers)."""
        cache = self._cache
        compiling = self._note_shape("fold", len(pages))
        if compiling is not _SEEN:
            register_program("llm.window.fold", self._fold, (
                cache.pool.abstract(), *abstract((self.params, pages))))
        with compiling, hot_span(
                "llm.window.fold", self.span_s, windows=windows,
                rows_folded=windows * cache.fold_window * self.kv_layers):
            cache._write(self._fold, self.params, pages)

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
        # a family stepped by blocks: whole blocks, and the last block's
        # B rows in the place of the last position's one
        span = self.block["block"] if self.block else 1
        if n % span:
            raise ValueError(
                f"{self.cfg.model} prefills whole blocks of {span} "
                f"positions, not {n}: what is left over opens the first "
                "block it decodes")
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
        out = self._pull("llm.prefill.pull", picked, span, logit_rows,
                         width=span)
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
        # what the planes say the chunk's real positions read (an index
        # plane: the positions scored and read), for the chunk's span
        first = index * c
        reads = {name: count for plane in (
            self.cache.planes if self.cache is not None else ())
            if plane.chunk_reads for name, count in plane.chunk_reads(
                first, min(first + c, n), self.cache).items()}
        reads.update(self._fold_reads(np.arange(first, min(first + c, n)),
                                      True))
        with compiling, hot_span("llm.prefill.chunk", self.span_s,
                                 chunk=index, tokens=n, **reads), \
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
        if self._packed is not None:
            # rows under two tables: one array a K and a V, the full
            # layers' rows one behind another and then each window layer's
            # run of the prompt's last positions, as the cache scatters them
            first, run = self._state_cache().window_run(n_tokens)
            ks, vs = self._packed(self._staging, first, bucket=tb,
                                  positions=run)
        else:
            ks, vs = staged_rows(self.family.kept, self._staging, tb)
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
               rows: Optional[np.ndarray] = None, wait: bool = True,
               decided: Optional[np.ndarray] = None,
               commit: Optional[np.ndarray] = None
               ) -> Tuple[Union[np.ndarray, Chosen, ChosenBlocks, Enqueued],
                          "jax.Array", "jax.Array"]:
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

        A family stepped by blocks (``self.block``): tokens (B, span), a
        row's open block; ``decided`` (B, span) bool, the positions that
        hold a token (None: all); ``commit`` (B,) bool, the rows whose pass
        writes the block's K/V at ``ctx_lens .. ctx_lens + span - 1`` (None:
        none); positions = ctx_lens, the committed positions before the
        block.  Logits are (B, span, V), new_k / new_v (L, bucket, span, KV,
        D), and with ``logit_rows`` the first result is ``ChosenBlocks``.
        """
        if self.block:
            tokens = np.asarray(tokens, np.int32)
            decided = np.ones(tokens.shape, bool) if decided is None \
                else np.asarray(decided, bool)
            commit = np.zeros(len(tokens), bool) if commit is None \
                else np.asarray(commit, bool)
        b = len(tokens)
        bb = _bucket(b, self.cfg.decode_batch_buckets)
        compiling = self._note_shape("decode", bb)
        pad = bb - b
        last_ids, src = self._no_ids, np.full(bb, -1, np.int32)
        if after is not None:
            last_ids, src[:b] = after.carry, rows
        if pad and self.block:
            decided = np.concatenate([decided, np.ones((pad,)
                                                       + decided.shape[1:],
                                                       bool)])
            commit = np.concatenate([commit, np.zeros(pad, bool)])
        if pad:
            with hot_span("llm.decode.tables", self.span_s):
                tokens = np.concatenate([tokens, np.zeros(
                    (pad,) + tokens.shape[1:], np.int32)])
                positions = np.concatenate([positions,
                                            np.zeros(pad, np.int32)])
                ctx_lens = np.concatenate([ctx_lens,
                                           np.zeros(pad, np.int32)])
                block_tables = np.concatenate(
                    [block_tables, np.zeros((pad, block_tables.shape[1]),
                                            np.int32)])
        # what the cache names for each row's table, an operand a plane
        # that asks one, and what the planes say the step reads (for the
        # pull's span): both in the table's order
        by_row, reads = [], {}
        for plane in kv_pool.planes:
            if plane.by_row:
                by_row.append(plane.by_row(self._state_cache(), block_tables,
                                           b))
            if plane.reads:
                reads.update(plane.reads(ctx_lens[:b], self._state_cache()))
        reads.update(self._fold_reads(ctx_lens[:b], False))
        if self.block:
            # a row's pages, walked once a pass for all its positions
            by_row += [decided, commit]
            reads["pages_read"] = self.kv_layers * int((-(
                -np.asarray(ctx_lens[:b], np.int64)
                // self.cfg.block_size)).sum())
        # dispatch holds the jitted call and ends at the ENQUEUE; pull
        # ends when the ids or the logits are on the host, so it holds the
        # wait for the step and nothing else: the pool stays where it is
        args = (self.params, tokens, positions, block_tables, ctx_lens,
                np.int32(b), last_ids, src, *by_row)
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

    def _fold_reads(self, seen, own: bool) -> dict:
        """What queries with ``seen`` positions behind them (``own``: and
        their own) read where sequences fold: the rows held beside the
        positions seen, from the step's or the chunk's own lengths
        (``kv_cache.fold_reads``); nothing where nothing folds."""
        kept = self.family.kept
        if not kept.fold_window:
            return {}
        reads = fold_reads(seen, own, kept.fold_window, kept.fold_chunk,
                           kept.kv_layers)
        if own:
            # the positions of a chunk the prompt fills are folded with it
            full = len(seen) == kept.fold_window
            reads["rows_folded"] = full * len(seen) * kept.kv_layers
        return reads

    def _window_reads(self, ctx_lens: np.ndarray) -> dict:
        """What a decode step's window layers read
        (``kv_cache.window_reads``, at this runner's sizes)."""
        return window_reads(ctx_lens, self.cfg.block_size, self.window,
                            self.window_layers)

    def pull_step(self, step: Enqueued) -> Union[np.ndarray, Chosen]:
        """Wait for an enqueued decode step and bring the host what its
        caller named (``decode``'s first result), inside an
        ``llm.decode.pull`` span that says which step it is."""
        width = _bucket(step.n, self.cfg.decode_batch_buckets)
        if self.block:
            return self._pull_blocks(step, width)
        return self._pull(
            "llm.decode.pull", step.picked, step.n, step.logit_rows,
            width=width, riders=self.family.layout, reads=step.reads,
            step=step.step)

    def _pull_blocks(self, step: Enqueued, width: int):
        """A pass over blocks for the host: all its logits, (n, B, V), for
        a caller that named no rows; else ``ChosenBlocks`` from the ONE
        array the program packed (ids, confidences' bits, flags, each
        ``width`` x B, and the riders behind them)."""
        logits, packed = step.picked
        n, span = step.n, self.block["block"]
        with hot_span("llm.decode.pull", self.span_s, step=step.step,
                      **step.reads) as pull:
            if step.logit_rows is None:
                pull.set(bytes=logits.nbytes)
                return np.asarray(logits)[:n]
            packed = np.asarray(packed)
            rode = _riders_read(packed, 3 * width * span, self.family.layout)
            ids, conf, flags = packed[:3 * width * span].reshape(
                3, width, span)
            chosen = ChosenBlocks(
                ids[:n], conf[:n].view(np.float32), flags[:n] != 0,
                {int(row): np.asarray(self._logits_row(logits,
                                                       np.int32(row)))
                 for row in step.logit_rows}, {**rode, **step.reads})
            pull.set(bytes=packed.nbytes + chosen.logits_nbytes, **rode)
            return chosen

    def _pull(self, span: str, picked, n: int,
              logit_rows: Optional[Sequence[int]], width: int = 1,
              riders: tuple = (), reads: dict = {}, **attrs):
        """A step's results for the host, inside ``span`` (whose ``bytes``
        is what crossed): all its logits, (n, V), for a caller that named
        no rows; else the n ids, the rows named, and what the step says of
        itself by name: the ``riders`` behind its ``width`` ids and the
        host's ``reads``, both told to the span."""
        logits, ids = picked
        with hot_span(span, self.span_s, **attrs, **reads) as pull:
            if logit_rows is None:
                pull.set(bytes=logits.nbytes)
                return np.asarray(logits)[:n]
            ids = np.asarray(ids)
            rode = _riders_read(ids, width, riders)
            chosen = Chosen(ids[:n], {
                int(row): np.asarray(self._logits_row(logits, np.int32(row)))
                for row in logit_rows}, {**rode, **reads})
            pull.set(bytes=ids.nbytes + chosen.logits_nbytes, **rode)
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
        around the first call of a (program, bucket), nothing after.  It
        is a set-up span: what jax says of tracing, lowering and compiling
        inside it is charged to ``llm.<program>`` and written on it."""
        key = (program, bucket)
        if key in self._shapes_seen:
            return _SEEN
        self._shapes_seen.add(key)
        self.compiles += 1
        logger.info("compiling %s program (total %d)", key, self.compiles)
        return setup_span("llm.compile", self.span_s, "llm." + program,
                          program=program, bucket=bucket)

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
