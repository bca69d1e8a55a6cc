"""serve.llm — continuous-batching TPU inference engine (DESIGN.md §4g).

The two mechanisms that made production LLM serving viable, built on the
machinery this framework already has:

- **iteration-level (continuous) scheduling** per Orca (Yu et al.,
  OSDI '22): the batch is re-formed every decode step — new requests'
  prefills interleave with running decodes, finished sequences leave
  immediately, and the lowest-priority sequence is preempted (blocks
  freed, re-prefilled later) under cache pressure.
- **paged KV cache** per PagedAttention (Kwon et al., SOSP '23): the KV
  cache is fixed-size blocks in one pool on the device with a block table
  per sequence (``ops/paged_attention.py``), so memory is allocated in
  block grains, prefilled cache is exported/attached between replicas
  over the PR-4 streamed data plane instead of recomputed, and model
  weights are shared across same-node replicas through the shm plane
  (``serve/llm/weights.py``).

Entry points::

    from ray_tpu.serve import llm
    eng = llm.LLMEngine(llm.EngineConfig(model="gpt2:tiny"))
    for tok in eng.submit([1, 2, 3], llm.SamplingParams(max_tokens=16)):
        ...

    app = llm.llm_deployment(llm.EngineConfig(model="gpt2:tiny")).bind()
    handle = serve.run(app)          # streaming tokens per request
"""

from ray_tpu.serve.llm.config import EngineConfig, SamplingParams  # noqa: F401
from ray_tpu.serve.llm.engine import LLMEngine  # noqa: F401
from ray_tpu.serve.llm.deployment import (  # noqa: F401
    llm_deployment, naive_llm_deployment,
)

__all__ = ["EngineConfig", "SamplingParams", "LLMEngine",
           "llm_deployment", "naive_llm_deployment"]
