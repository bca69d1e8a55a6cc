"""What the harness has to know of the ``sdar`` family (SDAR-30B-A3B-Chat:
the Qwen3-MoE stack generating by diffusion over blocks): how a
configuration file's sizes (under their config.json names) name the
program's model configuration, which module of the program holds the model,
where its plain reference is, and HOW ITS SEQUENCES ARE STEPPED
(``stepping``; ``perfbench/README.md``, "A family that does not step by
tokens").

The file states one pipeline stage of a deployment: ``num_hidden_layers``
counts the layers HELD and ``published`` the model's; every width, all 128
experts of every layer, 8 a token, and the whole vocabulary are as
published.  What the catalog row does not give (the block length, the
number of denoise passes, the remasking rule, the mask id) is the file's
``generation`` group, each entry explained under ``assumed``.

Serving.  The family routes (``routed``): the job asks the program's runner
for the experts it chose and hands them to ``reference_logits(...,
choices=ids)``; the selection score is the softmax probability and the
weights are the reference's own probabilities of the program's set over
their sum (``reference/sdar_ref.py``).  And it steps by blocks: a decode
step of the program is one pass over a block of ``block_length`` positions
a row, so ``stepping`` hands the job ``BlockStepping``, whose ``check``
compares EVERY pass (each denoise pass and each commit pass of
``check_decode_steps`` whole blocks, and the prefill's last block) with one
plain forward over what that pass was fed, mask ids included: the K/V a
commit pass wrote is judged by the block after it.

The rehearsal (``--rehearse``) sets every serving cell's model to
``gpt2:tiny``; a configuration so shrunk (GPT-2's names present) is not
this family's any more: ``check_sizes``, ``routed``, ``stepping`` and
``reference_logits`` hand it to ``families/gpt2.py`` (``stepping`` answers
None: the job's own).  ``rehearsal/sdar.json`` carries this family's own toy
sizes, which ``tests/perfbench/test_perfbench_sdar.py`` runs through the
same job.
"""

from __future__ import annotations

import numpy as np

from perfbench.families import gpt2
from perfbench.reference import sdar_ref

# config.json key -> the attribute of the program's LlamaConfig
KEYS = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_positions",
    "hidden_size": "n_embd",
    "num_hidden_layers": "n_layer",
    "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head",
    "head_dim": "head_dim",
    "moe_intermediate_size": "ffn_dim",
    "num_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
}
# the file's ``generation`` group -> the attribute
GENERATION = {"block_length": "block_length",
              "denoising_steps": "denoising_steps",
              "mask_token_id": "mask_token_id"}
# the one remasking rule the program runs, under the file's name for it
RULE = "low_confidence_static"
# what the program's block is, and a file must not say otherwise
FIXED = {"model_type": "sdar_moe", "attention_bias": False,
         "hidden_act": "silu", "norm_topk_prob": True,
         "decoder_sparse_step": 1, "mlp_only_layers": [],
         "tie_word_embeddings": False, "rope_scaling": None,
         "use_sliding_window": False}


def module():
    from ray_tpu.models import llama
    return llama


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in gpt2.SIZE_KEYS if k != "vocab_size")


def sizes(config_file: dict) -> dict:
    """The reference's settings: the file's sizes and its block length."""
    out = {k: config_file[k] for k in KEYS}
    out["rope_theta"] = float(out["rope_theta"])
    out["block_length"] = config_file["generation"]["block_length"]
    return out


def check_sizes(config_file: dict, model_cfg) -> None:
    """The program's preset must have the file's sizes, the block the file
    describes and its generation settings, or the cell is not the
    configuration it says it is."""
    if shrunk(config_file):
        return gpt2.check_sizes(config_file, model_cfg)
    gen = config_file["generation"]
    want = {**{k: config_file[k] for k in KEYS},
            **{k: gen[k] for k in GENERATION},
            "remasking_strategy": gen["remasking_strategy"]}
    got = {**{k: getattr(model_cfg, attr) for k, attr in KEYS.items()},
           **{k: getattr(model_cfg, attr) for k, attr in GENERATION.items()},
           "remasking_strategy": RULE}
    differ = {k: (got[k], want[k]) for k in want
              if got[k] != want[k] and not (
                  k == "rope_theta" and float(got[k]) == float(want[k]))}
    block = (model_cfg.qk_norm and model_cfg.qk_norm_heads
             and model_cfg.norm_topk)
    if differ or not block:
        raise ValueError("the program's model and the configuration file "
                         f"differ in (program, file): {differ}"
                         + ("" if block else "; the preset lacks the "
                            "per-head QK-norm or the renormalised top-k"))
    other = {k: config_file.get(k, "absent") for k, v in FIXED.items()
             if config_file.get(k, "absent") != v}
    if other:
        raise ValueError(f"the program's SDAR block has {FIXED}, and the "
                         f"configuration file says {other}")


def routed(config_file: dict):
    """What the serving check has to be handed by the program: the chosen
    expert ids of every layer, int (layers, rows, k), each below
    ``experts``.  None: the configuration does not route (a rehearsal)."""
    if shrunk(config_file):
        return None
    return {"layers": config_file["num_hidden_layers"],
            "k": config_file["num_experts_per_tok"],
            "experts": config_file["num_experts"]}


def reference_logits(params, tokens, config_file: dict, choices=None):
    """Float32 logits (B, T, V) under the block-causal mask of the file's
    block length; under the program's ``choices`` (layers, B x T, k) ->
    (logits, audit): ``sdar_ref.logits``."""
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return sdar_ref.logits(params, tokens, sizes(config_file),
                           choices=choices)


def stepping(config_file: dict):
    """How the job steps this family's sequences: by blocks.  None for a
    shrunk (rehearsal) configuration, whose model steps by tokens."""
    if shrunk(config_file):
        return None
    return BlockStepping(config_file["generation"])


def _bucket(n: int, buckets) -> int:
    return next(b for b in sorted(buckets) if n <= b)


class BlockStepping:
    """A step is one pass over a block of ``block_length`` positions a row;
    K/V is written by a block's commit pass alone."""

    def __init__(self, generation: dict):
        self.span = generation["block_length"]
        self.mask = generation["mask_token_id"]

    def warm(self, served) -> None:
        """Every program the traffic will use, once: the prefill of each
        bucket the grid's prompts take in whole blocks (and its scatter,
        which the runner builds beside it), and the block step at each
        decode bucket.  A row's kind (denoise or commit) and its
        decided-flags are operands of that ONE program, so every
        combination the traffic meets runs what is built here; every page
        of the pool is touched."""
        from perfbench import traffic
        eng, ecfg = served.eng, served.ecfg
        eng.cache.pool.fill(0)
        for b in sorted({_bucket(max(p // self.span * self.span, self.span),
                                 ecfg.prefill_len_buckets)
                         for p, _ in traffic.length_grid(served.spec)}):
            eng.runner.prefill([0] * b)
        maxb = ecfg.max_blocks_per_seq
        for b in ecfg.decode_batch_buckets:
            if b <= _bucket(ecfg.max_num_seqs, ecfg.decode_batch_buckets):
                # rows that commit nothing: the pool is handed back as it was
                zeros = np.zeros(b, np.int32)
                eng.runner.decode(
                    np.zeros((b, self.span), np.int32), zeros, eng.cache.pool,
                    np.zeros((b, maxb), np.int32), zeros,
                    decided=np.zeros((b, self.span), bool), logit_rows=())

    def check(self, served, prompt: list, k: int) -> list:
        """The prompt's whole blocks through ``runner.prefill``, then ``k``
        whole blocks through ``runner.decode`` as the engine's loop drives
        them (slots reserved by block, every denoise pass until nothing is
        undecided, then the commit pass that writes the block), at the
        cell's buckets.  One ``Compared`` a pass: ``fed`` the committed ids
        and that pass's block as fed, mask ids where it was fed them;
        ``rows`` the block's positions (the prefill's last block's under
        ``"prefill"``); ``choices`` the experts chosen at every position of
        ``fed``, those of committed positions from the pass that wrote
        their K/V."""
        runner, cache = served.eng.runner, served.eng.cache
        span, mask, sid = self.span, self.mask, "pb_check"
        whole = len(prompt) // span * span
        if not whole:
            raise ValueError(f"a check prompt of {len(prompt)} tokens holds "
                             f"no whole block of {span}")
        maxb = served.ecfg.max_blocks_per_seq
        out = []

        def one(fed, phase, first, logits, chose):
            made = {"fed": list(fed),
                    "rows": [(phase, first + j, logits[j])
                             for j in range(span)]}
            if served.routed:
                made["choices"] = np.concatenate(chose, axis=1)
            out.append(made)

        cache.alloc_seq(sid, whole)
        try:
            logits, ks, vs = runner.prefill(prompt[:whole])
            held = [served._choices(whole)] if served.routed else []
            cache.scatter_prefill(sid, ks, vs, whole)
            done = list(prompt[:whole])
            one(done, "prefill", whole - span, logits, held)
            given = list(prompt[whole:])
            for _ in range(k):
                cache.append_block(sid, span)
                tables = np.zeros((1, maxb), np.int32)
                table = cache.table(sid)
                tables[0, :len(table)] = table
                at = np.asarray([len(done)], np.int32)
                ids = given + [mask] * (span - len(given))
                decided = [True] * len(given) + [False] * (span - len(given))
                given = []
                while True:
                    commits = all(decided)
                    chosen, _, _ = runner.decode(
                        np.asarray([ids], np.int32), at, cache.pool, tables,
                        at, decided=np.asarray([decided]),
                        commit=np.asarray([commits]), logit_rows=(0,))
                    chose = [served._choices(span)] if served.routed else []
                    fed = [t if d else mask for t, d in zip(ids, decided)]
                    one(done + fed, "decode", len(done), chosen.logits[0],
                        held + chose)
                    if commits:
                        done, held = done + fed, held + chose
                        break
                    ids = [int(t) for t in chosen.ids[0]]
                    decided = [bool(d) for d in chosen.decided[0]]
        finally:
            cache.free_seq(sid)
        return out
