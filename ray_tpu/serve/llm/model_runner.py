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

The runner is model-family-agnostic: ``models/gpt2.py`` and
``models/llama.py`` each export ``forward_prefill`` / ``forward_decode``
(the decode step reads the paged pool through
``ops/paged_attention.py``); sampling (greedy / temperature / top-k)
happens host-side on the (B, V) logits.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Tuple

import numpy as np

from ray_tpu._private import rtlog
from ray_tpu._private.xla_watchdog import compile_budget
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams, \
    resolve_model
from ray_tpu.util.tracing import hot_span

logger = rtlog.get("serve.llm.runner")


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds largest bucket {buckets[-1]}")


class ModelRunner:
    """Owns params + the jitted, bucketed prefill/decode programs."""

    def __init__(self, cfg: EngineConfig, params=None):
        import jax

        self.cfg = cfg
        self.mod, self.mcfg = resolve_model(cfg)
        self.weights_key: str = ""      # set when the shm plane is used
        if params is None:
            params = self._load_params()
        self.params = params
        self.n_layer = self.mcfg.n_layer
        self.n_kv = getattr(self.mcfg, "n_kv_head", self.mcfg.n_head)
        self.head_dim = self.mcfg.head_dim
        self.vocab = self.mcfg.vocab_size
        self._prefill = jax.jit(partial(self.mod.forward_prefill,
                                        cfg=self.mcfg))
        self._decode = jax.jit(partial(self.mod.forward_decode,
                                       cfg=self.mcfg))
        self.compiles = 0          # observability: distinct programs built
        self._shapes_seen: set = set()
        # hot-span totals, name -> [count, seconds]; the engine shares
        # this dict with its own spans (LLMEngine.stats()["span_s"])
        self.span_s: dict = {}
        # XLA watchdog step regions (DESIGN.md §4q): one compile per
        # bucket for the runner's life, zero host transfers inside the
        # dispatch.  The post-dispatch np.asarray pulls are designed
        # syncs and sit OUTSIDE the regions.
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
    def prefill(self, token_ids) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
        """One prompt → (last-position logits (V,), k, v (L, T, KV, D)).

        The prompt is padded to its length bucket; KV for pad positions
        is garbage and never referenced (the block table fill stops at
        the true length)."""
        import jax.numpy as jnp
        n = len(token_ids)
        tb = _bucket(n, self.cfg.prefill_len_buckets)
        compiling = self._note_shape("prefill", tb)
        toks = np.zeros((1, tb), np.int32)
        toks[0, :n] = token_ids
        # last_pos is TRACED (one compile per bucket, not per length);
        # only the last real position's (1, V) logits come back to host
        last_pos = jnp.int32(n - 1)
        # dispatch ends at the ENQUEUE (the jitted call returns before
        # the device finishes); pull ends when the results are on the host
        with compiling, hot_span("llm.prefill.dispatch", self.span_s), \
                self._prefill_budget:
            logits, ks, vs = self._prefill(self.params, toks,
                                           last_pos=last_pos)
        with hot_span("llm.prefill.pull", self.span_s):
            logits = np.asarray(logits)[0]                       # (V,)
            ks = np.asarray(ks)[:, 0]                            # (L,T,KV,D)
            vs = np.asarray(vs)[:, 0]
        return logits, ks, vs

    # ----------------------------------------------------------------- decode
    def decode(self, tokens: np.ndarray, positions: np.ndarray,
               kv_pool: np.ndarray, block_tables: np.ndarray,
               ctx_lens: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
        """One iteration over a batch of sequences.

        tokens/positions/ctx_lens (B,); block_tables (B, MAXB);
        kv_pool — the cache's shm-backed ndarray, passed whole (the
        device copy is the CPU rig's stand-in for the pool living in
        HBM).  Returns (logits (B, V), new_k, new_v (L, B, KV, D));
        only the first B rows are real after bucket padding.
        """
        b = len(tokens)
        bb = _bucket(b, self.cfg.decode_batch_buckets)
        compiling = self._note_shape("decode", bb)
        pad = bb - b
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
        # dispatch holds the whole jitted call, the numpy pool argument's
        # way to the device included, and ends at the ENQUEUE; pull ends
        # when logits and the new K/V are on the host
        with compiling, hot_span("llm.decode.dispatch", self.span_s), \
                self._decode_budget:
            logits, ks, vs = self._decode(self.params, tokens,
                                          positions, kv_pool,
                                          block_tables, ctx_lens)
        with hot_span("llm.decode.pull", self.span_s):
            return (np.asarray(logits)[:b], np.asarray(ks)[:, :b],
                    np.asarray(vs)[:, :b])

    def _note_shape(self, program: str, bucket: int):
        """A context for the call that follows: an ``llm.compile`` span
        around the first call of a (program, bucket), nothing after."""
        key = (program, bucket)
        if key in self._shapes_seen:
            return contextlib.nullcontext()
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
        if sp.temperature <= 0.0:
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
