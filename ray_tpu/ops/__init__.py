"""ray_tpu.ops — TPU kernels and long-context attention (SURVEY.md §5.7).

The reference framework ships no kernels; these are greenfield TPU-first
components: causal attention behind one choice (XLA dense or the Pallas
flash kernel; latent attention hands it its parts unjoined, GPT-2 its
fused projection with the heads unsplit), paged decode attention, the two context-parallel
schedules (ring via ppermute, Ulysses via all-to-all), and the
decomposed collective matmuls that hide model-parallel
all-gather/reduce-scatter legs behind chunked compute (DESIGN.md §4m).
"""

from ray_tpu.ops.attention import (  # noqa: F401
    causal_attention, dense_attention, flash_runs, latent_causal_attention,
    unsplit_causal_attention, unsplit_heads_run,
)
from ray_tpu.ops.collective_matmul import (  # noqa: F401
    all_gather_matmul, matmul_reduce_scatter, ring_scan,
)
from ray_tpu.ops.flash_attention import flash_attention  # noqa: F401
from ray_tpu.ops.paged_attention import (  # noqa: F401
    paged_attention_decode,
)
from ray_tpu.ops.ring_attention import (  # noqa: F401
    ring_attention, ring_attention_sharded,
)
from ray_tpu.ops.ulysses import (  # noqa: F401
    ulysses_attention, ulysses_attention_sharded,
)

__all__ = [
    "causal_attention", "flash_runs", "dense_attention", "flash_attention",
    "latent_causal_attention", "unsplit_causal_attention",
    "unsplit_heads_run",
    "all_gather_matmul", "matmul_reduce_scatter", "ring_scan",
    "paged_attention_decode",
    "ring_attention", "ring_attention_sharded",
    "ulysses_attention", "ulysses_attention_sharded",
]
