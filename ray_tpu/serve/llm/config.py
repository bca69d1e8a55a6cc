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

    ``model`` is "<family>:<preset>" over the in-tree model zoo —
    ``gpt2:tiny``, ``gpt2:gpt2-124m``, ``llama:tiny``, ``llama:llama3-8b``,
    ``falcon_h1:tiny``, ``lfm2:tiny``, ``minicpm_sala:tiny``, ``afmoe:tiny``,
    ``ling:tiny`` … (the PRESETS of ``models/gpt2.py``, ``llama.py``,
    ``falcon_h1.py``, ``lfm2.py``, ``minicpm_sala.py``, ``afmoe.py``,
    ``ling.py``).
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


def resolve_model(cfg: EngineConfig):
    """"<family>:<preset>" → (module, model cfg) from the in-tree zoo."""
    family, _, preset = cfg.model.partition(":")
    preset = preset or "tiny"
    if family == "gpt2":
        from ray_tpu.models import gpt2 as mod
    elif family == "llama":
        from ray_tpu.models import llama as mod
    elif family == "falcon_h1":
        from ray_tpu.models import falcon_h1 as mod
    elif family == "lfm2":
        from ray_tpu.models import lfm2 as mod
    elif family == "minicpm_sala":
        from ray_tpu.models import minicpm_sala as mod
    elif family == "afmoe":
        from ray_tpu.models import afmoe as mod
    elif family == "ling":
        from ray_tpu.models import ling as mod
    else:
        raise ValueError(f"unknown model family {family!r} (expected "
                         "gpt2|llama|falcon_h1|lfm2|minicpm_sala|afmoe|ling)")
    try:
        mcfg = mod.PRESETS[preset]()
    except KeyError:
        raise ValueError(f"unknown {family} preset {preset!r}; have "
                         f"{sorted(mod.PRESETS)}") from None
    return mod, mcfg
