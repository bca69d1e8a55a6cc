"""LLMEngine: the continuous-batching loop over the paged KV cache.

One engine = one model on one replica process.  Requests enter through
``submit()`` (thread-safe, returns a token stream); a dedicated engine
thread runs ``step()`` forever: drain new requests, plan the iteration
(``scheduler.py``), execute a prefill or a bucketed decode batch
(``model_runner.py``), push sampled tokens to the per-request streams.
New KV goes into the block pool (``kv_cache.py``) on the device, where
the pool lives: the decode program writes its own token, prefill's K/V is
scattered by a second program.  A greedy token is chosen by the step
program too: what comes to the host is a token id a row, and the logits
of a row whose request samples (temperature > 0) and of no other.

The loop keeps one decode step in flight.  A greedy row's next token is
an id on the device (the step program feeds it to the next step there),
and a row's position and slot follow from how many tokens it has, not
from which: so an iteration enqueues decode step n+1 first and reads step
n's ids (and commits them: streams, finishes) after that enqueue, while
the device runs n+1.  What needs every token committed drains the step in
flight first (``_drain``, counted by cause): a cancel, an attached
sequence or a prefill (``admit``), a preemption (``pressure``), a step
with a row that samples, whose token is drawn here from pulled logits
(``sampled``), and nothing left to enqueue (``tail``).  DESIGN.md,
"Scheduler loop".

Blocks.  A model that generates by diffusion over blocks
(``runner.block``: ``models/llama.block_stepping``) is stepped a block of B
positions at a time, on the same loop.  A prompt's whole blocks are
prefilled under the block-causal mask and yield no token; what is left over
is the given part of the first open block.  A decode step is a *pass*: each
row's open block (``scheduler.OpenBlock``) through the model against the
sequence's pages.  A denoise pass writes no K/V and fixes some undecided
positions by the remasking rule, on the device; when none is undecided a
commit pass runs the final block once more and writes its K/V into the
slots reserved when the block opened (``PagedKVCache.append_block``), and
its tokens go on the stream, in order, cut at ``max_tokens`` and at a stop
token, when that pass is read.  Rows of one step stand at different passes:
a row's kind and flags are operands of one program.  A pass fixes a known
number of positions, so the loop knows which pass is a block's commit
without reading the one before it and keeps one pass in flight as it keeps
one step; the open block stays on the device between passes.  Preemption
folds only committed tokens into the prompt; the open block is lost with
its passes (``blocks_lost``).
Requests that sample, ``prefill_remote`` and ``attach`` are refused for
such a model.  DESIGN.md, "Block stepping".

Disaggregated prefill/decode rides the PR-4 data plane:
``prefill_remote()`` copies the filled blocks from the device into a
tmpfs export spool
(under /dev/shm when available, so publish is a page-cache write) served
by the engine's ``DataPlaneServer``; ``attach()`` on another engine
pulls them with pooled streamed ``DataPlanePool`` pulls (sendfile from
tmpfs on the holder side) and continues decoding WITHOUT re-running
prefill (the ``prefill_steps`` counter is the no-recompute oracle the
tests assert on).
"""

from __future__ import annotations

import os
import queue
import threading
import time
import uuid
from collections import Counter, deque
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from ray_tpu._private import rtlog
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.kv_cache import NoFreeBlocks, PagedKVCache
from ray_tpu.serve.llm.model_runner import (Chosen, Enqueued, ModelRunner,
                                            _bucket)
from ray_tpu.serve.llm.scheduler import (FAILED, FINISHED, RUNNING,
                                         IterationScheduler, OpenBlock, Plan,
                                         Sequence)
from ray_tpu.util import metrics_catalog as mcat
from ray_tpu.util import tracing
from ray_tpu.util.tracing import hot_span, setup_span

logger = rtlog.get("serve.llm.engine")

_DONE = "__llm_done__"
_ERR = "__llm_err__"
# the "first token" of a prompt that yields none (a model stepped by
# blocks): the sequence started, and nothing goes on its stream
_NO_TOKEN = -1


# What a decode step says of itself, by name (the attributes of
# ``llm.decode.pull``: a step's riders and host reads; and the cache's own
# counts at the step, ``PagedKVCache.held_counts``) -> the series it feeds
# and how: the one place that publishes them is ``mcat.tell_step``.
STEP_SERIES = {
    "experts_touched": ("rtpu_llm_moe_experts_touched", "observe"),
    "sparse_pages_read": ("rtpu_llm_sparse_pages_read", "inc"),
    "sparse_pages_held": ("rtpu_llm_sparse_pages_held", "inc"),
    "latent_pages_read": ("rtpu_llm_latent_pages_read", "inc"),
    "window_blocks_held": ("rtpu_llm_kv_window_blocks_held", "inc"),
    "window_blocks_held_unwindowed":
        ("rtpu_llm_kv_window_blocks_unwindowed", "inc"),
    "window_blocks_released":
        ("rtpu_llm_kv_window_blocks_released_total", "inc"),
    "state_rows_held": ("rtpu_llm_state_rows_held", "set"),
    "latent_blocks_held": ("rtpu_llm_latent_blocks_held", "set"),
    "positions_scored": ("rtpu_llm_index_positions_scored", "inc"),
    "positions_read": ("rtpu_llm_index_positions_read", "inc"),
    "index_blocks_held": ("rtpu_llm_index_blocks_held", "set"),
    "fold_blocks_held": ("rtpu_llm_kv_fold_blocks_held", "inc"),
    "fold_blocks_unfolded": ("rtpu_llm_kv_fold_blocks_unfolded", "inc"),
    "windows_folded": ("rtpu_llm_kv_windows_folded", "inc"),
}


def _new_seq_id() -> str:
    """Twelve characters that no reader takes for a number: a capture
    hands a span's attribute back as an int or a float where its text
    parses as one (``000123456789``, ``12e345678901``), and the spans name
    a sequence by this id."""
    return "s" + uuid.uuid4().hex[:11]


def _sampled_rows(samplings) -> List[int]:
    """The rows of a step whose logits the host needs: those whose
    request is not greedy (``Chosen.token`` asks the same property)."""
    return [i for i, sp in enumerate(samplings) if not sp.greedy]


class _InFlight(NamedTuple):
    """A decode step enqueued and not yet read: its tokens are not in any
    sequence's ``output`` yet."""

    step: Enqueued                   # what the runner's pull takes
    batch: List[Sequence]            # row i of the step is batch[i]
    rows: Dict[str, int]             # sequence id -> its row
    slots: Dict[str, tuple]          # the pool slots reserved for it
    # a step of a model stepped by blocks: sequence id -> (the block its
    # row passed over, whether the pass commits it)
    blocks: Dict[str, tuple] = {}


class RequestStream:
    """Iterator over one request's generated token ids."""

    def __init__(self, seq_id: str, q: "queue.Queue", engine=None):
        self.seq_id = seq_id
        self._q = q
        self._engine = engine
        self.finish_reason: Optional[str] = None

    def __iter__(self):
        while True:
            item = self._q.get()
            if isinstance(item, tuple):
                kind, payload = item
                if kind == _DONE:
                    self.finish_reason = payload
                    return
                raise RuntimeError(f"llm request failed: {payload}")
            yield item

    def poll(self, max_items: int = 16,
             timeout: float = 0.2) -> tuple:
        """Non-blocking-ish drain: wait up to ``timeout`` for the FIRST
        available token, then take whatever else is already queued (cap
        ``max_items``).  Returns (tokens, done) — the serve streaming
        path's bounded-occupancy pull."""
        out: List[int] = []
        try:
            item = self._q.get(timeout=timeout)
        except queue.Empty:
            return out, False
        while True:
            if isinstance(item, tuple):
                kind, payload = item
                if kind == _DONE:
                    self.finish_reason = payload
                    return out, True
                if out:
                    # deliver the tokens drained BEFORE the failure
                    # (parity with __iter__); the error marker goes
                    # back for the next poll — nothing follows it
                    self._q.put(item)
                    return out, False
                raise RuntimeError(f"llm request failed: {payload}")
            out.append(item)
            if len(out) >= max_items:
                return out, False
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return out, False

    def cancel(self) -> None:
        """Abandon the request: the engine frees its KV blocks and
        drops it from the batch at the next iteration."""
        if self._engine is not None:
            self._engine.cancel(self.seq_id)

    def tokens(self) -> List[int]:
        return list(self)


class LLMEngine:
    def __init__(self, cfg: EngineConfig, params=None, *,
                 start: bool = True):
        if cfg.prefill_len_buckets[-1] < cfg.max_model_len:
            raise ValueError(
                "largest prefill bucket must cover max_model_len "
                "(preempted sequences re-prefill their full context)")
        if cfg.decode_batch_buckets[-1] < cfg.max_num_seqs:
            raise ValueError(
                f"largest decode batch bucket "
                f"{cfg.decode_batch_buckets[-1]} < max_num_seqs "
                f"{cfg.max_num_seqs}: a full batch could never compile")
        from ray_tpu.serve.llm import weights as _weights
        _weights.reap_orphans()
        self.cfg = cfg
        self.runner = ModelRunner(cfg, params)
        # what a sequence of this model keeps, the cache holds: blocks, and
        # beside them whatever planes its module declares (kv_cache.PLANES)
        # (a set-up span: the fills of the device pool are programs too,
        # and it ends when they are enqueued)
        with setup_span("llm.cache.build", self.runner.span_s,
                        "llm.cache") as span:
            self.cache = PagedKVCache.for_engine(cfg, self.runner.family.kept)
            span.set(bytes=self.cache.pool.nbytes)
        self.runner.cache = self.cache
        self.sched = IterationScheduler(cfg.max_num_seqs,
                                        cfg.max_prefill_tokens,
                                        cfg.max_model_len)
        self._lock = threading.Lock()
        self._inbox: deque = deque()                 # guarded by: _lock
        self._attached: deque = deque()              # guarded by: _lock
        self._streams: Dict[str, queue.Queue] = {}   # guarded by: _lock
        self._cancels: set = set()                   # guarded by: _lock
        self._wake = threading.Event()
        self._stop = threading.Event()
        # decode_steps/preemptions/tokens_out are step-loop-owned
        # (read-only elsewhere; torn reads are benign ints).
        # prefill_steps has a second writer — prefill_remote() on the
        # caller's thread — so its += always runs under _lock.
        self.prefill_steps = 0
        self.decode_steps = 0
        self.preemptions = 0
        self.tokens_out = 0
        # queue wait, stamped where it ends (_do_prefill): first
        # admissions apart from re-admissions after a preemption
        self.admitted = 0
        self.queue_wait_s = 0.0
        self.requeue_wait_s = 0.0
        # pool blocks the decode steps' contexts held, and the block-
        # table entries those steps were compiled for: their ratio is
        # the share of the table a step has to read (loop-owned ints)
        self.attn_blocks_read = 0
        self.attn_blocks_table = 0
        # rows of recurrent state the compiled decode steps read and
        # wrote: the whole store each step, whatever the batch (loop-owned)
        self.state_rows_stepped = 0
        # what the decode steps said of themselves, summed by name over the
        # steps read (``Chosen.reads``: a routing model's experts touched,
        # over routed layers; a selecting model's pages read and held, over
        # live rows, sparse layers and KV heads; window layers' blocks read
        # and what full layers would have read; latent pages walked), and
        # ``steps``, how many were read; and the chunks of prompts run by a
        # model that prefills in chunks (each is also one of prefill_steps)
        # (loop-owned)
        self.step_reads: Counter = Counter()
        # the count that is told per routed layer: the layers it sums over
        route = self.runner.route_spec
        self._per = {"experts_touched": route["layers"]} if route else {}
        self.prefill_chunks = 0
        # tokens by where they were chosen (the step program's argmax for
        # a greedy request; ModelRunner.sample on a pulled row for any
        # other) and the bytes of logits pulled for the latter.
        # prefill_remote() counts too, so these += run under _lock
        self.sampled_on_device = 0
        self.sampled_on_host = 0
        self.logits_host_bytes = 0
        # the decode step enqueued and not yet read, and how often the
        # loop had one: steps enqueued behind another, steps read with
        # nothing behind them (by what made the loop wait), and rows of a
        # step that were stepped for a sequence its stop token had ended
        # a step before (loop-owned)
        self._inflight: Optional[_InFlight] = None
        self.decode_steps_ahead = 0
        self.decode_drains = dict(sampled=0, pressure=0, admit=0, tail=0)
        self.decode_rows_discarded = 0
        # a model stepped by blocks: blocks whose commit pass was read,
        # row-passes by kind, and blocks whose passes (or part of whose
        # tokens) were thrown away, by cause (loop-owned)
        self.blocks_committed = 0
        self.block_passes = dict(denoise=0, commit=0)
        self.blocks_lost = dict(preempt=0, stop=0, cut=0)
        # hot-span totals of the loop and the runner, name ->
        # [count, seconds] (tracing.hot_span); the names are a contract,
        # PERF.md section 3 lists each with the metric that reads it
        self.span_s = self.runner.span_s
        self._export_server = None
        self._export_spool: Optional[str] = None
        self._exports: deque = deque()               # guarded by: _lock
        self._pull_pool = None
        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    # ------------------------------------------------------------- lifecycle
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, name=f"llm-engine-{self.cfg.model_key()}",
            daemon=True)
        self._thread.start()

    def shutdown(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        with self._lock:
            streams = list(self._streams.values())
            self._streams.clear()
        for q in streams:           # unblock any readers
            q.put((_ERR, "engine shut down"))
        if self._export_server is not None:
            self._export_server.stop()
            self._export_server = None
        if self._pull_pool is not None:
            self._pull_pool.close_all()
            self._pull_pool = None
        if self._export_spool:
            import shutil
            shutil.rmtree(self._export_spool, ignore_errors=True)
            self._export_spool = None
        if self.runner.weights_key:
            from ray_tpu.serve.llm import weights
            weights.release(self.runner.weights_key)
        self.cache.close()

    # ------------------------------------------------------------ submission
    def submit(self, prompt: List[int],
               sampling: Optional[SamplingParams] = None) -> RequestStream:
        sampling = sampling or SamplingParams()
        if self.runner.block and not sampling.greedy:
            raise ValueError(
                f"{self.cfg.model} generates by diffusion over blocks and "
                "its remasking rule runs on the device over greedy "
                "candidates: a request that samples is not written")
        seq_id = _new_seq_id()
        with hot_span("llm.submit", self.span_s, seq=seq_id):
            seq = Sequence(seq_id=seq_id, prompt=[int(t) for t in prompt],
                           sampling=sampling)
            # request tracing: the submitter's span (serve replica method
            # / driver trace) parents every engine span for this sequence
            # — captured HERE because the engine loop thread has no context
            span = tracing.current_span()
            if span is not None and span.sampled:
                seq.trace = span
            q: queue.Queue = queue.Queue()
            with self._lock:
                # checked under the same lock shutdown() drains streams
                # under: a submit that slips in before the drain gets its
                # _ERR from the drain; one after it raises here — either
                # way no reader can block on a never-serviced queue
                if self._stop.is_set():
                    raise RuntimeError("engine shut down")
                self._streams[seq.seq_id] = q
                self._inbox.append(seq)
            self._wake.set()
        return RequestStream(seq.seq_id, q, self)

    def generate(self, prompt: List[int],
                 sampling: Optional[SamplingParams] = None) -> List[int]:
        return self.submit(prompt, sampling).tokens()

    def cancel(self, seq_id: str) -> None:
        """Request abandonment (thread-safe; applied at the next step)."""
        with self._lock:
            self._cancels.add(seq_id)
        self._wake.set()

    # ------------------------------------------------------------ engine loop
    def _loop(self) -> None:
        # every wait of this thread lies in an ``llm.idle`` span that says
        # why it waits, so between two ``llm.step`` spans nothing of the
        # loop's is dark (PERF.md section 3)
        while not self._stop.is_set():
            if not self._work_pending():
                with hot_span("llm.idle", self.span_s, cause="empty"):
                    self._wake.wait(timeout=0.2)
                self._wake.clear()
                continue
            try:
                if not self.step():
                    # work exists but nothing runnable this iteration
                    # (e.g. the waiting head cannot fit in the free
                    # list yet): don't busy-spin the core
                    with hot_span("llm.idle", self.span_s, cause="blocked"):
                        self._wake.wait(timeout=0.02)
                    self._wake.clear()
            except Exception:  # noqa: BLE001 - engine must survive a step
                logger.exception("engine step failed")
                with hot_span("llm.idle", self.span_s, cause="error"):
                    time.sleep(0.05)

    def _work_pending(self) -> bool:
        with self._lock:
            backlog = bool(self._inbox or self._attached)
        return backlog or self.sched.has_work()

    def step(self) -> bool:
        """One iteration: admit, (maybe) prefill, decode, publish.
        Returns False when nothing was runnable (loop backs off)."""
        spans = self.span_s
        with hot_span("llm.step", spans):
            with hot_span("llm.step.admit", spans):
                self._admit()
            with hot_span("llm.step.plan", spans):
                plan = self.sched.plan(self.cache.free_block_count(),
                                       self.cache.blocks_needed)
            from ray_tpu._private import flight_recorder
            if flight_recorder.enabled() and \
                    (plan.prefill is not None or plan.decode):
                flight_recorder.record(
                    "llm_step",
                    f"prefill={'1' if plan.prefill is not None else '0'} "
                    f"decode={len(plan.decode)} "
                    f"free={self.cache.free_block_count()}")
            if plan.prefill is not None and self.runner.chunk:
                # one chunk of the prompt, and behind it a decode step of
                # whoever runs (the prompt's own sequence from its last
                # chunk on): a long prompt stalls the live rows a chunk at
                # a time, not for the whole of it
                self._do_prefill_chunk(plan.prefill)
                if self.sched.running:
                    self._do_decode(list(self.sched.running))
            elif plan.prefill is not None:
                # the commit first, the prefill's enqueue after it: the
                # tokens of the step in flight are what clients wait for,
                # and behind the prefill they would wait it out
                self._drain("admit")
                self._do_prefill(plan.prefill)
            elif plan.decode:
                self._do_decode(plan.decode)
            with hot_span("llm.step.publish", spans):
                self._publish_metrics(plan)
        return plan.prefill is not None or bool(plan.decode)

    def _admit(self) -> None:
        """Cancels, attached sequences and the inbox into the scheduler."""
        with self._lock:
            settle = bool(self._cancels) or bool(
                self._attached and self.max_num_seqs_room() > 0)
        if settle:
            # a cancel frees and an attached sequence joins: both find
            # every running sequence with all its tokens
            self._drain("admit")
        self._drain_cancels()
        self._drain_attached()
        with self._lock:
            while self._inbox:
                seq = self._inbox.popleft()
                try:
                    self.sched.add(seq)
                except ValueError as e:
                    self._finish_locked(seq, FAILED, str(e))
        # a prompt whose blocks can NEVER fit (even with every other
        # sequence evicted) must fail now, not starve the waiting line
        while self.sched.waiting:
            head = self.sched.waiting[0]
            if self.cache.blocks_needed(head.ctx_len) + 1 \
                    <= self.cache.num_blocks:
                break
            self.sched.waiting.popleft()
            self._finish(head, FAILED,
                         f"prompt needs more KV blocks than the pool "
                         f"holds ({self.cache.num_blocks})")

    # ---------------------------------------------------------------- prefill
    def _do_prefill(self, seq: Sequence) -> None:
        t0 = time.time()    # the cluster timeline's clock: a start only
        with hot_span("llm.prefill", self.span_s, seq=seq.seq_id,
                      tokens=len(seq.prompt)) as span:
            tok = self._prefill_one(seq, span)
        self._started(seq, tok, t0, span)

    def _started(self, seq: Sequence, tok: Optional[int], t0: float,
                 span: hot_span) -> None:
        """A prefilled sequence joins the running ones with its first
        token (None: it did not start; ``_NO_TOKEN``: a model stepped by
        blocks, whose prompt yields none)."""
        if tok is None:
            return
        if seq.trace is not None:
            # per-sequence prefill span (explicit parent: the engine
            # loop thread never holds the request's context variable);
            # its duration is the hot span's own measurement
            tracing.emit_span("llm.prefill", seq.trace, t0, span.dur,
                              cat="llm", seq_id=seq.seq_id,
                              tokens=len(seq.prompt), model=self.cfg.model)
        self._first_token(seq, tok)
        self._count_tokens(len(seq.prompt), phase="prefill")

    def _first_token(self, seq: Sequence, tok: int) -> None:
        """``seq`` joins the running ones and its first token goes on its
        stream: there at the END of this ``llm.prefill.commit``, as a later
        one is at the end of the ``llm.decode.commit`` that names ``seq``."""
        if tok == _NO_TOKEN:
            # no token, so the commit names nobody (a token is on its stream
            # at the end of the commit that names its sequence)
            with hot_span("llm.prefill.commit", self.span_s):
                self.sched.start_running(seq)
            return
        with hot_span("llm.prefill.commit", self.span_s, seq=seq.seq_id):
            self.sched.start_running(seq)
            self._emit(seq, tok)
            self._maybe_finish(seq)

    def _do_prefill_chunk(self, seq: Sequence) -> None:
        """The next chunk of ``seq``'s prompt (a model that prefills in
        chunks).  The first takes the sequence's blocks and row and makes
        it the scheduler's ``prefilling``; the last reads the first token
        and commits the prompt as ``_do_prefill`` does."""
        runner = self.runner
        if self.sched.prefilling is not seq:
            try:
                self.cache.alloc_seq(seq.seq_id, seq.ctx_len)
            except NoFreeBlocks:
                self.sched.waiting.appendleft(seq)
                return
            self.sched.prefilling = seq
            seq.chunks_done, seq.chunk_flight = 0, None
            self._note_admission(seq)
        last = seq.chunks_done + 1 == runner.prefill_chunks(len(seq.prompt))
        if last:
            # the first token is read below: the step in flight first,
            # whose tokens clients wait for
            self._drain("admit")
            t0 = time.time()
            with hot_span("llm.prefill", self.span_s, seq=seq.seq_id,
                          tokens=len(seq.prompt),
                          chunks=seq.chunks_done + 1) as span:
                tok = self._prefill_one(seq, span)
            self._started(seq, tok, t0, span)
        else:
            self._run_chunk(seq)

    def _run_chunk(self, seq: Sequence) -> bool:
        """Enqueue ``seq``'s next chunk; False when it failed (the sequence
        is finished as failed and nothing is prefilling)."""
        try:
            seq.chunk_flight = self.runner.prefill_chunk(
                seq.prompt, seq.chunks_done, after=seq.chunk_flight)
        except Exception as e:  # noqa: BLE001 - surface to the caller
            self.sched.prefilling = None
            self.cache.free_seq(seq.seq_id)
            self._finish(seq, FAILED, f"prefill failed: {e!r}")
            return False
        seq.chunks_done += 1
        self.prefill_chunks += 1
        with self._lock:
            self.prefill_steps += 1
        if GLOBAL_CONFIG.metrics_enabled:
            mcat.get("rtpu_llm_prefill_chunks_total").inc(
                tags={"model": self.cfg.model})
        return True

    def _prefill_one(self, seq: Sequence, span: hot_span) -> Optional[int]:
        """Blocks, the model's prefill, the scatter into the pool and the
        first token; None when the sequence did not start.  For a model
        that prefills in chunks: the prompt's last chunk, the sequence
        having its blocks since its first."""
        if self.runner.chunk:
            if not self._run_chunk(seq):
                return None
            self.sched.prefilling = None
            try:
                chosen, ks, vs = self.runner.prefill_result(
                    len(seq.prompt), seq.chunk_flight,
                    _sampled_rows([seq.sampling]))
            except Exception as e:  # noqa: BLE001 - surface to the caller
                self.cache.free_seq(seq.seq_id)
                self._finish(seq, FAILED, f"prefill failed: {e!r}")
                return None
            finally:
                seq.chunk_flight = None
            with self._lock:
                self._count_chosen_locked(chosen)
            return self._scattered(seq, chosen, ks, vs, len(seq.prompt))
        # a model stepped by blocks prefills the prompt's whole blocks,
        # under the block-causal mask; what is left over opens the first
        # block it decodes
        n, prompt = seq.ctx_len, seq.prompt
        if self.runner.block:
            n -= n % self.runner.block["block"]
            prompt = prompt[:n]
        try:
            self.cache.alloc_seq(seq.seq_id, n)
        except NoFreeBlocks:
            # plan() checked free blocks, but be safe: requeue
            self.sched.waiting.appendleft(seq)
            return None
        span.set(bucket=_bucket(n, self.cfg.prefill_len_buckets),
                 queue_ms=round(1e3 * self._note_admission(seq), 3))
        if not n:
            return _NO_TOKEN            # a prompt shorter than a block
        try:
            chosen, ks, vs = self.runner.prefill(
                prompt, logit_rows=_sampled_rows([seq.sampling]))
        except Exception as e:  # noqa: BLE001 - surface to the caller
            self.cache.free_seq(seq.seq_id)
            self._finish(seq, FAILED, f"prefill failed: {e!r}")
            return None
        with self._lock:
            self.prefill_steps += 1
            if not self.runner.block:
                self._count_chosen_locked(chosen)
        return self._scattered(seq, chosen, ks, vs, n)

    def _scattered(self, seq: Sequence, chosen: Chosen, ks, vs,
                   n: int) -> int:
        """The K/V of the prompt's ``n`` prefilled positions into its
        blocks (and its state to its row); the first token."""
        # K/V never left the device: the scatter is the enqueue of a
        # second device program; with recurrent state it also commits the
        # prompt's to the sequence's row, which the span then names
        row = {"row": self.cache.state_row(seq.seq_id)} \
            if self.cache.state_rows else {}
        with hot_span("llm.prefill.scatter", self.span_s, **row):
            self.cache.scatter_prefill(seq.seq_id, ks, vs, n)
        if self.runner.block:
            seq.kv_len = n
            return _NO_TOKEN
        # sampling step = tokens generated so far RELATIVE TO THE
        # ORIGINAL prompt, so a preemption re-prefill (k tokens folded
        # into the prompt) draws the same rng stream position as the
        # pressure-free run — seeded sampling stays reproducible
        return chosen.token(0, seq.sampling, step=seq.generated)

    def _note_admission(self, seq: Sequence) -> float:
        """Queue wait ends here, where the prefill begins: seconds since
        the sequence joined the waiting line.  A first admission is
        counted apart from a re-admission after a preemption."""
        now = time.monotonic()
        wait = now - seq.queued_at
        if seq.admitted_at is None:
            seq.admitted_at = now
            self.admitted += 1
            self.queue_wait_s += wait
            if GLOBAL_CONFIG.metrics_enabled:
                mcat.get("rtpu_llm_queue_seconds").observe(
                    wait, tags={"model": self.cfg.model})
        else:
            self.requeue_wait_s += wait
        return wait

    # ----------------------------------------------------------------- decode
    def _do_decode(self, seqs: List[Sequence]) -> None:
        if self.runner.block:
            return self._do_decode_blocks(seqs)
        # a sequence whose token in flight is its last by length is known
        # to end at that token's commit: no row of this step is spent on it
        flight = self._inflight
        if flight is not None:
            seqs = [s for s in seqs if s.seq_id not in flight.rows
                    or s.generated + 1 < s.sampling.max_tokens]
        if not seqs:
            # every running sequence has its last token in flight
            self._drain("tail")
            return
        t0 = time.time()    # the cluster timeline's clock: a start only
        with hot_span("llm.decode", self.span_s) as span:
            batch = self._decode_batch(seqs, span)
        traced = next((s for s in batch if s.trace is not None), None)
        if traced is not None:
            # one span per decode ITERATION (the batch is the unit of
            # execution), parented to the first traced sequence in it;
            # its duration is the hot span's own measurement
            tracing.emit_span("llm.decode_step", traced.trace, t0,
                              span.dur, cat="llm", batch=len(batch),
                              seq_id=traced.seq_id, model=self.cfg.model)

    def _reserve_slots(self, seqs: List[Sequence]):
        """A pool slot for each sequence's new token, preempting under
        cache pressure: (slots by sequence id, the batch that remains)."""
        slots = {}
        batch = list(seqs)
        for seq in list(batch):
            while True:
                if seq not in self.sched.running:
                    break        # preempted while making room for others
                try:
                    slots[seq.seq_id] = self.cache.append_slot(seq.seq_id)
                    break
                except NoFreeBlocks:
                    if self._inflight is not None:
                        # a preemption folds output into prompt, which
                        # must hold every token; and the commit may free
                        # the blocks of a sequence that ends with it
                        self._drain("pressure")
                        continue
                    if not self._preempt_one(slots):
                        # unreachable: sched.running contains at least
                        # `seq` itself (checked at the loop top, same
                        # thread), so victim() always finds one — fail
                        # loudly rather than spin if that ever breaks
                        raise RuntimeError(
                            "no preemption victim with a growing "
                            "sequence running")
            # preemption may have evicted members of THIS batch
            batch = [s for s in batch if s in self.sched.running]
        return slots, batch

    def _decode_batch(self, seqs: List[Sequence],
                      span: hot_span) -> List[Sequence]:
        """One decode iteration: enqueue a step for ``seqs``, then read
        and commit the step that was in flight.  Returns the batch
        enqueued."""
        spans = self.span_s
        with hot_span("llm.decode.slots", spans):
            slots, batch = self._reserve_slots(seqs)
        if not batch:
            return batch
        flight = self._inflight
        behind = flight.rows if flight is not None else {}
        span.set(batch=len(batch), ahead=int(flight is not None),
                 seqs="|".join(s.seq_id for s in batch))
        with hot_span("llm.decode.tables", spans):
            maxb = self.cfg.max_blocks_per_seq
            tables = np.zeros((len(batch), maxb), np.int32)
            toks = np.zeros(len(batch), np.int32)
            src = np.full(len(batch), -1, np.int32)
            lens = np.zeros(len(batch), np.int32)
            for i, s in enumerate(batch):
                t = self.cache.table(s.seq_id)
                tables[i, :len(t)] = t
                # the token being processed is the last CHOSEN one: its
                # KV is not in the pool yet (this step writes it); both
                # its position and the valid pool length are the tokens
                # before it.  In flight it is not in ``output`` yet and
                # the step takes it from the device, by its row there
                src[i] = behind.get(s.seq_id, -1)
                if src[i] < 0:
                    toks[i] = s.output[-1] if s.output else s.prompt[-1]
                    lens[i] = s.ctx_len - 1
                else:
                    lens[i] = s.ctx_len
            # what the decode attention has to read against what the
            # compiled step's block tables can name (padded rows and
            # columns past a context included)
            span.set(kv_layers=self.cache.kv_layers,
                     state_layers=self.cache.state_layers)
            self.attn_blocks_read += int(
                (-(-self.cache.held_rows(lens)
                   // self.cfg.block_size)).sum())
            self.attn_blocks_table += maxb * _bucket(
                len(batch), self.cfg.decode_batch_buckets)
            if self.cache.state_rows:
                span.set(state_rows=len(batch))
                self.state_rows_stepped += self.cache.state_rows + 1
        sampled = _sampled_rows(s.sampling for s in batch)
        try:
            # the step writes each new token's K/V into its slot itself;
            # the K/V it also returns stay on the device, unread, and so
            # do the logits of every row whose request is greedy
            step, _, _ = self.runner.decode(
                toks, lens, self.cache.pool, tables, lens,
                logit_rows=sampled, wait=False, rows=src,
                after=None if flight is None else flight.step)
        except BaseException:
            # return every slot reserved for THIS step, or every later
            # append_slot is off by one and the cache silently corrupts
            self._return_slots(batch, slots)
            raise
        self.decode_steps += 1
        span.set(step=step.step)
        self._inflight = _InFlight(
            step, batch, {s.seq_id: i for i, s in enumerate(batch)}, slots)
        if flight is not None:
            # the device has this step queued behind that one: now read it
            self.decode_steps_ahead += 1
            self._commit(flight, span)
        if sampled:
            # its token is drawn here, from logits only this step has
            self._drain("sampled")
        return batch

    def _drain(self, cause: str) -> None:
        """Read and commit the decode step in flight, if there is one,
        with nothing enqueued behind it: the order the loop had before it
        kept one in flight, for what needs every token committed."""
        flight, self._inflight = self._inflight, None
        if flight is None:
            return
        self.decode_drains[cause] += 1
        with hot_span("llm.decode.drain", self.span_s, cause=cause) as span:
            self._commit(flight, span)

    def _commit(self, flight: _InFlight, span: hot_span) -> None:
        """Wait for ``flight``'s ids and give each sequence its token;
        ``span`` (the ``llm.decode`` or ``llm.decode.drain`` that reads the
        step) is told what the step says of itself, as its pull was."""
        if self.runner.block:
            return self._commit_blocks(flight, span)
        try:
            chosen = self.runner.pull_step(flight.step)
        except BaseException:
            # none of its tokens arrived, and a step behind it was fed
            # them: the slots of both go back
            for lost in (flight, self._inflight):
                if lost is not None:
                    self._return_slots(lost.batch, lost.slots)
            self._inflight = None
            raise
        reads = chosen.reads
        if reads:
            span.set(**reads)
        self.step_reads.update(reads, steps=1)
        if GLOBAL_CONFIG.metrics_enabled:
            mcat.tell_step({**reads, **self.cache.held_counts()}, STEP_SERIES,
                           {"model": self.cfg.model}, self._per)
        # the span says whose tokens it put on their streams, and of which
        # step: a token is on its stream at this span's END
        with hot_span("llm.decode.commit", self.span_s,
                      step=flight.step.step) as commit:
            emitted = []
            for i, s in enumerate(flight.batch):
                if s.state != RUNNING:
                    # ended by its stop token at the commit before this
                    # one, when this step was enqueued already: the row
                    # is nobody's, and free_seq took its slot back
                    continue
                self._emit(s, chosen.token(i, s.sampling, step=s.generated))
                self._maybe_finish(s)
                emitted.append(s.seq_id)
            discarded = len(flight.batch) - len(emitted)
            with self._lock:
                self._count_chosen_locked(chosen, discarded)
            commit.set(tokens=len(emitted), seqs="|".join(emitted))
        self.decode_rows_discarded += discarded
        self._count_tokens(len(emitted), phase="decode")

    # ---------------------------------------------------------------- blocks
    def _block_lost(self, cause: str) -> None:
        self.blocks_lost[cause] += 1
        if GLOBAL_CONFIG.metrics_enabled:
            mcat.get("rtpu_llm_blocks_lost").inc(
                tags={"model": self.cfg.model, "cause": cause})

    def _do_decode_blocks(self, seqs: List[Sequence]) -> None:
        # a sequence whose commit pass in flight ends it by length: no row
        # of this step is spent on a block it will never have
        flight, span = self._inflight, self.runner.block["block"]
        if flight is not None:
            def ends(s):
                ob, commits = flight.blocks.get(s.seq_id, (None, False))
                return commits and s.generated + span - ob.given \
                    >= s.sampling.max_tokens
            seqs = [s for s in seqs if not ends(s)]
        if not seqs:
            self._drain("tail")
            return
        with hot_span("llm.decode", self.span_s) as span_:
            self._block_batch(seqs, span_)

    def _open_blocks(self, seqs: List[Sequence]):
        """The slots of a whole block for every sequence that has none
        open, preempting under cache pressure as ``_reserve_slots`` does:
        (the blocks opened here by sequence id, the batch that remains)."""
        spec = self.runner.block
        span, opened = spec["block"], {}
        batch = list(seqs)
        for seq in list(batch):
            while seq.open_block is None and seq in self.sched.running:
                try:
                    grew = self.cache.append_block(seq.seq_id, span)
                except NoFreeBlocks:
                    if self._inflight is not None:
                        self._drain("pressure")
                    elif not self._preempt_one(opened):
                        raise RuntimeError(
                            "no preemption victim with a growing "
                            "sequence running")
                    continue
                # the tokens the prompt (or what was committed before a
                # preemption) puts in the block are decided; the rest are
                # fed the mask id
                given = (seq.prompt + seq.output)[seq.kv_len:]
                rest = span - len(given)
                seq.open_block = opened[seq.seq_id] = OpenBlock(
                    seq.kv_len, given + [spec["mask_id"]] * rest,
                    [True] * len(given) + [False] * rest, len(given), grew,
                    planned=len(given))
            batch = [s for s in batch if s in self.sched.running]
        return opened, batch

    def _block_batch(self, seqs: List[Sequence], span_: hot_span) -> None:
        """One pass: enqueue it for ``seqs``, each row at its own pass of
        its own block, then read and commit the pass that was in flight."""
        spans, spec = self.span_s, self.runner.block
        span = spec["block"]
        with hot_span("llm.decode.slots", spans):
            opened, batch = self._open_blocks(seqs)
        if not batch:
            return
        flight = self._inflight
        n = len(batch)
        with hot_span("llm.decode.tables", spans):
            maxb = self.cfg.max_blocks_per_seq
            tables = np.zeros((n, maxb), np.int32)
            toks = np.zeros((n, span), np.int32)
            decided = np.ones((n, span), bool)
            commit = np.zeros(n, bool)
            src = np.full(n, -1, np.int32)
            lens = np.zeros(n, np.int32)
            passed = {}
            for i, s in enumerate(batch):
                t = self.cache.table(s.seq_id)
                tables[i, :len(t)] = t
                ob = s.open_block
                lens[i] = ob.start
                commit[i] = ob.planned >= span
                passed[s.seq_id] = (ob, bool(commit[i]))
                # the pass in flight over this same block left it on the
                # device, ids and flags: taken from there by its row
                before = flight.blocks.get(s.seq_id) if flight else None
                if before is not None and before[0] is ob:
                    src[i] = flight.rows[s.seq_id]
                else:
                    toks[i], decided[i] = ob.ids, ob.decided
            commits = int(commit.sum())
            span_.set(batch=n, ahead=int(flight is not None), block=span,
                      denoise=n - commits, commit=commits,
                      seqs="|".join(s.seq_id for s in batch),
                      kv_layers=self.cache.kv_layers,
                      state_layers=self.cache.state_layers)
            self.attn_blocks_read += int(
                (-(-self.cache.held_rows(lens)
                   // self.cfg.block_size)).sum())
            self.attn_blocks_table += maxb * _bucket(
                n, self.cfg.decode_batch_buckets)
        try:
            step, _, _ = self.runner.decode(
                toks, lens, self.cache.pool, tables, lens, decided=decided,
                commit=commit, logit_rows=(), wait=False, rows=src,
                after=None if flight is None else flight.step)
        except BaseException:
            for s in batch:
                if s.seq_id in opened:
                    self.cache.rollback_block(s.seq_id, s.open_block.grew)
                    s.open_block = None
            raise
        self.decode_steps += 1
        span_.set(step=step.step)
        for s in batch:
            ob = s.open_block
            if ob.planned >= span:
                # its K/V is written by this pass: the next pass of this
                # sequence opens the block behind it
                s.kv_len, s.open_block = ob.start + span, None
            else:
                ob.planned = min(span, ob.planned + spec["per_pass"])
        self.block_passes["commit"] += commits
        self.block_passes["denoise"] += n - commits
        if GLOBAL_CONFIG.metrics_enabled:
            for kind, rows in (("denoise", n - commits), ("commit", commits)):
                mcat.get("rtpu_llm_block_passes").inc(
                    rows, tags={"model": self.cfg.model, "kind": kind})
        self._inflight = _InFlight(
            step, batch, {s.seq_id: i for i, s in enumerate(batch)}, {},
            passed)
        if flight is not None:
            self.decode_steps_ahead += 1
            self._commit(flight, span_)

    def _commit_blocks(self, flight: _InFlight, span: hot_span) -> None:
        """Wait for a pass's blocks: a denoise pass's rows bring the host
        their block as the rule left it; a commit pass's rows put the
        block's tokens on their streams, in order, cut at ``max_tokens``
        and at a stop token."""
        width = self.runner.block["block"]
        try:
            chosen = self.runner.pull_step(flight.step)
        except BaseException as e:
            # none of it arrived, and the pass behind was fed from it: the
            # sequences of both end here, their pages go back
            for lost in (flight, self._inflight):
                for s in lost.batch if lost is not None else ():
                    if s.state == RUNNING:
                        self.cache.free_seq(s.seq_id)
                        self.sched.finish(s, FAILED)
                        self._finish(s, FAILED, f"decode failed: {e!r}")
            self._inflight = None
            raise
        reads = chosen.reads
        if reads:
            span.set(**reads)
        self.step_reads.update(reads, steps=1)
        if GLOBAL_CONFIG.metrics_enabled:
            mcat.tell_step({**reads, **self.cache.held_counts()}, STEP_SERIES,
                           {"model": self.cfg.model}, self._per)
        with hot_span("llm.decode.commit", self.span_s,
                      step=flight.step.step) as commit:
            gave, discarded, blocks = [], 0, 0
            for i, s in enumerate(flight.batch):
                ob, commits = flight.blocks[s.seq_id]
                if s.state != RUNNING:
                    # ended by a stop token a commit before this one, when
                    # this pass was enqueued already: the row is nobody's
                    discarded += 1
                    continue
                if not commits:
                    ob.ids = [int(t) for t in chosen.ids[i]]
                    ob.decided = [bool(d) for d in chosen.decided[i]]
                    continue
                blocks += 1
                told = 0
                for tok in chosen.ids[i][ob.given:]:
                    self._emit(s, int(tok))
                    told += 1
                    if s.finish_reason() is not None:
                        break
                reason = s.finish_reason()
                if reason == "stop" and (told < width - ob.given
                                         or s.open_block is not None):
                    # the rest of its block, or the block a pass behind
                    # this one opened already
                    self._block_lost("stop")
                elif reason == "length" and told < width - ob.given:
                    self._block_lost("cut")
                self._maybe_finish(s)
                gave.append((s.seq_id, told))
            tokens = sum(told for _, told in gave)
            with self._lock:
                self.sampled_on_device += tokens
            commit.set(tokens=tokens, blocks=blocks,
                       seqs="|".join(sid for sid, _ in gave),
                       tokens_by_seq="|".join(str(told) for _, told in gave))
        self.blocks_committed += blocks
        if blocks and GLOBAL_CONFIG.metrics_enabled:
            tags = {"model": self.cfg.model}
            mcat.get("rtpu_llm_blocks_committed").inc(blocks, tags=tags)
            mcat.get("rtpu_llm_block_tokens").inc(tokens, tags=tags)
        self.decode_rows_discarded += discarded
        self._count_tokens(tokens, phase="decode")

    def _return_slots(self, batch: List[Sequence], slots: Dict) -> None:
        for s in batch:
            ent = slots.get(s.seq_id)
            if ent is not None:
                self.cache.rollback_slot(s.seq_id, ent[2])

    def _preempt_one(self, slots: Dict) -> bool:
        """Evict the scheduler's victim (latest arrival — possibly one
        that already reserved a slot this iteration, or even the
        sequence being grown); its entry in ``slots`` is invalidated so
        the caller's batch bookkeeping stays consistent."""
        victim = self.sched.victim()
        if victim is None:
            return False
        with hot_span("llm.preempt", self.span_s, seq=victim.seq_id,
                      ctx=victim.ctx_len):
            self._evict(victim, slots)
        return True

    def _evict(self, victim: Sequence, slots: Dict) -> None:
        logger.info("preempting %s under cache pressure (ctx=%d)",
                    victim.seq_id, victim.ctx_len)
        from ray_tpu._private import flight_recorder
        if flight_recorder.enabled():
            flight_recorder.record(
                "llm_preempt", f"{victim.seq_id} ctx={victim.ctx_len}")
        if victim.trace is not None:
            tracing.emit_span("llm.preempt", victim.trace, time.time(),
                              0.0, cat="llm", seq_id=victim.seq_id,
                              ctx=victim.ctx_len)
        self.cache.free_seq(victim.seq_id)
        slots.pop(victim.seq_id, None)
        if victim.open_block is not None:
            self._block_lost("preempt")
        self.sched.preempt(victim)
        self.preemptions += 1
        if GLOBAL_CONFIG.metrics_enabled:
            mcat.get("rtpu_llm_preemptions_total").inc(
                tags={"model": self.cfg.model})

    # --------------------------------------------------- prefill/decode split
    def _ensure_export_plane(self):
        from ray_tpu._private.data_plane import DataPlaneServer
        from ray_tpu.serve.llm.kv_cache import reap_orphan_export_spools
        with self._lock:
            if self._export_server is not None:
                return self._export_server
        # build OUTSIDE the lock: the orphan sweep (rmtree of a dead
        # predecessor's spool), mkdtemp, and the listener bind are all
        # I/O — _lock is a leaf guarding handoff state and must never
        # be held across blocking work (§4c discipline)
        import tempfile
        base = "/dev/shm" if os.path.isdir("/dev/shm") else None
        reap_orphan_export_spools(base)
        # pid in the name so a SIGKILLed publisher's spool is reapable
        # by the next engine on the node
        spool = tempfile.mkdtemp(
            prefix=f"rtpu_llm_export_{os.getpid()}_", dir=base)
        server = DataPlaneServer(spool, host="127.0.0.1",
                                 advertise_host="127.0.0.1")
        with self._lock:                # prefill_remote races are legal
            if self._export_server is None:
                self._export_spool = spool
                self._export_server = server
                return server
            winner = self._export_server
        server.stop()                   # lost the race: tear ours down
        import shutil
        shutil.rmtree(spool, ignore_errors=True)
        return winner

    def prefill_remote(self, prompt: List[int],
                       sampling: Optional[SamplingParams] = None) -> dict:
        """Run prefill here; publish the filled KV blocks on the data
        plane and return the manifest a decode engine ``attach()``es.

        Runs on the caller's thread (the engine loop keeps decoding its
        own batch meanwhile; cache alloc/free are thread-safe)."""
        from ray_tpu._private.data_plane import write_spool
        self._blocks_are_all_a_sequence_holds("prefill_remote")
        sampling = sampling or SamplingParams()
        if self._stop.is_set():
            raise RuntimeError("engine shut down")
        seq_id = "pf_" + uuid.uuid4().hex[:12]
        prompt = [int(t) for t in prompt]
        span = tracing.current_span()   # caller's thread context
        if span is not None and not span.sampled:
            span = None
        t0, p0 = time.time(), time.perf_counter()
        self.cache.alloc_seq(seq_id, len(prompt))
        try:
            chosen, ks, vs = self.runner.prefill(
                prompt, logit_rows=_sampled_rows([sampling]))
            with self._lock:
                self.prefill_steps += 1
                self._count_chosen_locked(chosen)
            self.cache.scatter_prefill(seq_id, ks, vs, len(prompt))
            first = chosen.token(0, sampling, step=0)
            srv = self._ensure_export_plane()
            oids = []
            for b in self.cache.table(seq_id):
                oid = f"llmkv_{seq_id}_{b}"
                write_spool(self._export_spool, oid,
                            self.cache.block_bytes(b))
                oids.append(oid)
            # bounded retention: exported manifests are consumed once
            # by the attaching decode engine; keep a window for late
            # attachers, evict beyond it so a long-lived prefill
            # replica cannot grow tmpfs without limit
            evict: List[str] = []
            with self._lock:
                self._exports.append(list(oids))
                while len(self._exports) > 64:
                    evict.extend(self._exports.popleft())
            for old in evict:
                srv.delete_local(old)
            self._count_tokens(len(prompt), phase="prefill")
            # the manifest carries the prefill-side SPAN (compact wire
            # form): attach() on the decode engine parents its tree to
            # it — the cross-process link between the two engines
            ctx = tracing.emit_span(
                "llm.prefill_remote", span, t0, time.perf_counter() - p0,
                cat="llm", tokens=len(prompt), blocks=len(oids),
                model=self.cfg.model) if span is not None else None
            return dict(addr=srv.advertise_addr, blocks=oids,
                        block_nbytes=self.cache.block_nbytes,
                        tokens=prompt, first_token=int(first),
                        model=self.cfg.model,
                        block_size=self.cfg.block_size,
                        trace=ctx.to_wire() if ctx is not None else None)
        except BaseException:
            if self._stop.is_set():
                # a shutdown racing this call closed the cache/export
                # plane under us: surface the contract error, not the
                # incidental TypeError/IO failure
                raise RuntimeError("engine shut down") from None
            raise
        finally:
            self.cache.free_seq(seq_id)

    def attach(self, manifest: dict,
               sampling: Optional[SamplingParams] = None) -> RequestStream:
        """Adopt a remotely-prefilled sequence: pull its KV blocks over
        the streamed data plane and continue decoding — no re-prefill."""
        from ray_tpu._private.data_plane import DataPlanePool
        self._blocks_are_all_a_sequence_holds("attach")
        if manifest["model"] != self.cfg.model:
            raise ValueError(f"manifest model {manifest['model']!r} != "
                             f"engine model {self.cfg.model!r}")
        if manifest["block_nbytes"] != self.cache.block_nbytes or \
                manifest["block_size"] != self.cfg.block_size:
            raise ValueError("KV block geometry mismatch")
        sampling = sampling or SamplingParams()
        # same admission contract submit() gets via IterationScheduler.add
        # — an attached sequence must not be able to outgrow the block
        # table width every decode program was compiled with
        if len(manifest["tokens"]) + sampling.max_tokens > \
                self.cfg.max_model_len:
            raise ValueError(
                f"manifest context {len(manifest['tokens'])} + "
                f"max_tokens {sampling.max_tokens} exceeds "
                f"max_model_len={self.cfg.max_model_len}")
        with self._lock:          # concurrent attach() races are legal
            if self._pull_pool is None:
                self._pull_pool = DataPlanePool()
            pool = self._pull_pool
        prompt = [int(t) for t in manifest["tokens"]]
        seq = Sequence(seq_id=_new_seq_id(), prompt=prompt,
                       sampling=sampling)
        # link the decode-side tree to the prefill-side one: the
        # manifest's span (prefill_remote on the other engine) parents
        # the attach span, which parents this sequence's decode spans.
        # Falls back to the caller's own span for untraced manifests.
        parent = tracing.SpanContext.from_wire(manifest.get("trace"),
                                               name="llm.prefill_remote")
        if parent is None:
            cur = tracing.current_span()
            parent = cur if cur is not None and cur.sampled else None
        t0, p0 = time.time(), time.perf_counter()
        self.cache.alloc_seq(seq.seq_id, len(prompt))
        tok = tracing.adopt(parent) if parent is not None else None
        try:
            # with the manifest span adopted, the block pulls' data.pull
            # spans (and their server-side serve_stream children on the
            # prefill engine) land inside the same tree
            table = self.cache.table(seq.seq_id)
            for b, oid in zip(table, manifest["blocks"]):
                raw = pool.pull(manifest["addr"], oid,
                                size=manifest["block_nbytes"])
                self.cache.load_block(b, raw)
        except BaseException:
            self.cache.free_seq(seq.seq_id)
            if self._stop.is_set():
                raise RuntimeError("engine shut down") from None
            raise
        finally:
            if tok is not None:
                tracing.restore(tok)
        if parent is not None:
            seq.trace = tracing.emit_span(
                "llm.attach", parent, t0, time.perf_counter() - p0,
                cat="llm",
                seq_id=seq.seq_id, blocks=len(manifest["blocks"]),
                tokens=len(prompt), model=self.cfg.model)
        q: queue.Queue = queue.Queue()
        released = False
        with self._lock:
            # same post-shutdown race submit() closes: a stream
            # registered after the drain would never be serviced
            if self._stop.is_set():
                released = True
            else:
                self._streams[seq.seq_id] = q
                self._attached.append((seq, manifest["first_token"]))
        if released:
            self.cache.free_seq(seq.seq_id)
            raise RuntimeError("engine shut down")
        self._wake.set()
        return RequestStream(seq.seq_id, q, self)

    def _blocks_are_all_a_sequence_holds(self, what: str) -> None:
        """The manifest of ``prefill_remote`` / ``attach`` carries blocks
        and nothing else: half a sequence, for a model with more."""
        for why in self.cache.unexported():
            raise NotImplementedError(f"{what}: {self.cfg.model} {why}")
        if self.runner.block:
            raise NotImplementedError(
                f"{what}: {self.cfg.model} is stepped by blocks, and a "
                "manifest carries a first token where such a prompt yields "
                "none and an open block may stand")

    def _drain_cancels(self) -> None:
        with self._lock:
            if not self._cancels:
                return
            cancelled = self._cancels
            self._cancels = set()
            for sid in cancelled:
                self._streams.pop(sid, None)    # nobody is reading
            self._inbox = deque(s for s in self._inbox
                                if s.seq_id not in cancelled)
            dropped = [it[0] for it in self._attached
                       if it[0].seq_id in cancelled]
            self._attached = deque(it for it in self._attached
                                   if it[0].seq_id not in cancelled)
        for seq in dropped:     # block free OUTSIDE _lock (leaf locks
            self.cache.free_seq(seq.seq_id)    # must never nest)
        part_way = self.sched.prefilling
        if part_way is not None and part_way.seq_id in cancelled:
            # its chunks so far are in the staging, which the next prompt
            # overwrites; blocks and row go back
            self.sched.prefilling = None
            part_way.chunk_flight = None
            self.cache.free_seq(part_way.seq_id)
            self.sched.finish(part_way, FINISHED)
        for seq in [s for s in self.sched.running
                    if s.seq_id in cancelled]:
            self.cache.free_seq(seq.seq_id)
            self.sched.finish(seq, FINISHED)
        for seq in [s for s in list(self.sched.waiting)
                    if s.seq_id in cancelled]:
            self.sched.drop_waiting(seq)

    def _drain_attached(self) -> None:
        # honor the same max_num_seqs gate plan() applies to prefill
        # admission: adopting more sequences than the largest decode
        # batch bucket would make every later _do_decode un-compilable
        room = self.max_num_seqs_room()
        if room <= 0:
            return
        items = []
        with self._lock:
            while self._attached and len(items) < room:
                items.append(self._attached.popleft())
        for seq, first in items:
            self._first_token(seq, int(first))

    def max_num_seqs_room(self) -> int:
        return self.cfg.max_num_seqs - len(self.sched.running)

    # ------------------------------------------------------------- completion
    def _emit(self, seq: Sequence, tok: int) -> None:
        now = time.monotonic()
        if seq.first_token_at is None:
            seq.first_token_at = now
            if GLOBAL_CONFIG.metrics_enabled:
                mcat.get("rtpu_llm_ttft_seconds").observe(
                    now - seq.arrival, tags={"model": self.cfg.model})
        seq.output.append(int(tok))
        self.tokens_out += 1
        with self._lock:
            q = self._streams.get(seq.seq_id)
        if q is not None:
            q.put(int(tok))

    def _maybe_finish(self, seq: Sequence) -> None:
        reason = seq.finish_reason()
        if reason is None:
            return
        # a sequence ended by its stop token may still have a row in the
        # step in flight, which will write the slot reserved for it and
        # step its row of recurrent state.  Blocks and row are free for
        # the next prefill all the same: every program takes the pool
        # donated and the device runs them in the order they were
        # enqueued, so that write lands before the new owner's scatter
        # (which commits the state row whole) and before any later step's
        self.cache.free_seq(seq.seq_id)
        self.sched.finish(seq, FINISHED)
        if GLOBAL_CONFIG.metrics_enabled and len(seq.output) > 1 and \
                seq.first_token_at is not None:
            tpot = (seq.finished_at - seq.first_token_at) / \
                (len(seq.output) - 1)
            mcat.get("rtpu_llm_tpot_seconds").observe(
                tpot, tags={"model": self.cfg.model})
        with self._lock:
            q = self._streams.pop(seq.seq_id, None)
        if q is not None:
            q.put((_DONE, reason))

    def _finish(self, seq: Sequence, state: str, err: str) -> None:
        with self._lock:
            self._finish_locked(seq, state, err)

    def _finish_locked(self, seq: Sequence, state: str, err: str) -> None:
        seq.state = state
        seq.error = err
        seq.finished_at = time.monotonic()
        q = self._streams.pop(seq.seq_id, None)
        if q is not None:
            q.put((_ERR, err))

    # ---------------------------------------------------------------- metrics
    def _count_tokens(self, n: int, phase: str) -> None:
        if GLOBAL_CONFIG.metrics_enabled:
            mcat.get("rtpu_llm_tokens_total").inc(
                n, tags={"model": self.cfg.model, "phase": phase})

    def _count_chosen_locked(self, chosen: Chosen,
                             discarded: int = 0) -> None:
        """One step's tokens by where they were chosen; the rows whose
        logits were pulled are the rows sampled here.  ``discarded``
        greedy rows gave nobody a token."""
        self.sampled_on_host += len(chosen.logits)
        self.sampled_on_device += len(chosen.ids) - len(chosen.logits) \
            - discarded
        self.logits_host_bytes += chosen.logits_nbytes

    def _publish_metrics(self, plan: Plan) -> None:
        if not GLOBAL_CONFIG.metrics_enabled:
            return
        tags = {"model": self.cfg.model}
        running = len(self.sched.running)
        mcat.get("rtpu_llm_sequences").set(
            running, tags={**tags, "state": "running"})
        mcat.get("rtpu_llm_sequences").set(
            len(self.sched.waiting), tags={**tags, "state": "waiting"})
        free = self.cache.free_block_count()
        mcat.get("rtpu_llm_kv_blocks").set(
            self.cfg.num_blocks - free, tags={**tags, "state": "used"})
        mcat.get("rtpu_llm_kv_blocks").set(free,
                                           tags={**tags, "state": "free"})
        mcat.get("rtpu_llm_batch_occupancy").set(
            running / max(1, self.cfg.max_num_seqs), tags=tags)

    # ------------------------------------------------------------------ stats
    def stats(self) -> dict:
        read = self.step_reads
        return dict(prefill_steps=self.prefill_steps,
                    decode_steps=self.decode_steps,
                    decode_steps_ahead=self.decode_steps_ahead,
                    decode_drains=dict(self.decode_drains),
                    decode_rows_discarded=self.decode_rows_discarded,
                    # a model stepped by blocks says three things more
                    **(dict(blocks_committed=self.blocks_committed,
                            block_passes=dict(self.block_passes),
                            blocks_lost=dict(self.blocks_lost))
                       if self.runner.block else {}),
                    preemptions=self.preemptions,
                    tokens_out=self.tokens_out,
                    running=len(self.sched.running),
                    # a prompt part-way through its chunks is not running
                    # yet: it waits for its first token
                    waiting=len(self.sched.waiting)
                    + (self.sched.prefilling is not None),
                    blocks_free=self.cache.free_block_count(),
                    compiles=self.runner.compiles,
                    param_bytes=self.runner.param_bytes,
                    kv_lane_pad_bytes=self.cache.lane_pad_bytes,
                    admitted=self.admitted,
                    queue_wait_s=self.queue_wait_s,
                    requeue_wait_s=self.requeue_wait_s,
                    kv_host_bytes=self.cache.host_bytes,
                    logits_host_bytes=self.logits_host_bytes,
                    sampled_on_device=self.sampled_on_device,
                    sampled_on_host=self.sampled_on_host,
                    attn_blocks_read=self.attn_blocks_read,
                    attn_blocks_table=self.attn_blocks_table,
                    state_bytes=self.cache.state_bytes,
                    state_rows=self.cache.state_rows,
                    state_rows_used=self.cache.state_rows_used(),
                    state_rows_stepped=self.state_rows_stepped,
                    state_commits=self.cache.state_commits,
                    kv_layers=self.cache.kv_layers,
                    state_layers=self.cache.state_layers,
                    experts_touched=read["experts_touched"],
                    routed_layer_steps=read["steps"] * self._per.get(
                        "experts_touched", 0),
                    prefill_chunks=self.prefill_chunks,
                    sparse_pages_read=read["sparse_pages_read"],
                    sparse_pages_held=read["sparse_pages_held"],
                    select_bytes=self.cache.select_bytes,
                    staging_bytes=self.runner.staging_bytes,
                    window_layers=self.cache.window_layers,
                    window_bytes=self.cache.window_bytes,
                    window_blocks=dict(zip(
                        ("held", "released", "unwindowed"),
                        self.cache.window_counts())),
                    window_blocks_read=read["window_blocks"],
                    window_blocks_unwindowed=read["window_blocks_unwindowed"],
                    latent_layers=self.cache.latent_layers,
                    latent_bytes=self.cache.latent_bytes,
                    latent_pages_read=read["latent_pages_read"],
                    index_layers=self.cache.index_layers,
                    index_bytes=self.cache.index_bytes,
                    positions_scored=read["positions_scored"],
                    positions_read=read["positions_read"],
                    **(dict(fold_window=self.cache.fold_window,
                            windows_folded=self.cache.windows_folded,
                            rows_read=read["rows_read"],
                            positions_seen=read["positions_seen"])
                       if self.cache.fold_window else {}),
                    span_s={k: list(v) for k, v in
                            list(self.span_s.items())})
