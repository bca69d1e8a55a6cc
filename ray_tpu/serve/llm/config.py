"""Engine + sampling configuration for serve.llm."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class SamplingParams:
    """Per-request generation parameters.

    Greedy (temperature=0) is the default: deterministic output is what
    the engine tests and the prefill/decode-handoff equivalence checks
    rely on.  ``seed`` makes temperature>0 reproducible per request.
    """

    max_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0                   # 0 = full vocab
    stop_token: Optional[int] = None
    seed: int = 0

    @property
    def greedy(self) -> bool:
        """The next token is the logits' argmax: the step program's own
        choice serves it, and no logits need come to the host."""
        return self.temperature <= 0.0


@dataclass(frozen=True)
class EngineConfig:
    """Engine-level knobs (model, cache geometry, batching limits).

    ``model`` is "<family>:<preset>": a family of ``SERVED_FAMILIES``, a
    preset of its module's ``PRESETS`` (``gpt2:tiny``, ``gpt2:gpt2-124m``,
    ``llama:llama3-8b``, ``ling:tiny`` ...).
    """

    model: str = "gpt2:tiny"
    seed: int = 0
    # -- paged KV cache geometry ------------------------------------------
    block_size: int = 16             # tokens per KV block
    num_blocks: int = 128            # pool capacity, in blocks
    # -- iteration-level scheduler limits ---------------------------------
    max_num_seqs: int = 8            # max sequences decoded per step
    max_prefill_tokens: int = 512    # prompt-length admission cap
    max_model_len: int = 256         # context cap per sequence
    # -- XLA shape bucketing (bounds recompilation) -----------------------
    # decode batch is padded up to the nearest bucket; prefill prompt
    # length likewise.  Every bucket is one compiled program.
    decode_batch_buckets: Tuple[int, ...] = (1, 2, 4, 8, 16)
    prefill_len_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256, 512)
    # -- weights plane ----------------------------------------------------
    share_weights: bool = True       # publish/attach params via shm

    @property
    def max_blocks_per_seq(self) -> int:
        # the block-table width of every compiled decode program
        return -(-self.max_model_len // self.block_size)

    def model_key(self) -> str:
        return self.model.replace(":", "_").replace("/", "_")


# the families the engine serves: ``ray_tpu.models.<family>`` exports
# ``forward_prefill`` / ``forward_decode`` and what ``model_runner.py`` lists
SERVED_FAMILIES = ("gpt2", "llama", "falcon_h1", "lfm2", "minicpm_sala",
                   "afmoe", "ling")


def resolve_model(cfg: EngineConfig):
    """"<family>:<preset>" → (module, model cfg) from the in-tree zoo."""
    import importlib
    family, _, preset = cfg.model.partition(":")
    preset = preset or "tiny"
    if family not in SERVED_FAMILIES:     # outside input: no import of it
        raise ValueError(f"unknown model family {family!r} (expected "
                         f"{'|'.join(SERVED_FAMILIES)})")
    mod = importlib.import_module(f"ray_tpu.models.{family}")
    try:
        mcfg = mod.PRESETS[preset]()
    except KeyError:
        raise ValueError(f"unknown {family} preset {preset!r}; have "
                         f"{sorted(mod.PRESETS)}") from None
    return mod, mcfg
