"""LLM inference engine: paged KV-cache continuous batching.

Ragged request lengths share ONE fixed-shape decode batch through a
paged KV cache (the Ragged Paged Attention design — fixed-size blocks
in a preallocated pool, per-request block tables, gather-by-block-table
attention), a prefill/decode scheduler interleaves chunked prefill with
decode steps so long prompts cannot stall in-flight streams, and a
latency-driven controller policy autoscales replicas from the live
``Router.latency_stats()`` p50/p99 feed.

Layout:

- ``kv_cache``  the paged block pool + per-request block tables
- ``model``     what a model family is to the engine (``Family``: its
  weights, its cache and ONE forward), the engine's two jitted programs
  and their host arrays written once over that forward, and the paged
  family's forward; ``hybrid``, ``latent``, ``linear``, ``mamba``: a
  cache and a forward each
- ``scheduler`` request lifecycle: bounded admission, chunked-prefill
  interleave, preemption on cache pressure, deadline sweep
- ``engine``    the engine loop + counters (``ENGINE_STAT_KEYS``)
- ``server``    the ``LLMEngineServer`` serve deployment class
- ``autoscale`` the latency-driven replica-count policy
"""

from ray_tpu.exceptions import CacheExhaustedError
from ray_tpu.serve.llm_engine.autoscale import LatencyPolicy
from ray_tpu.serve.llm_engine.engine import ENGINE_STAT_KEYS, LLMEngine
from ray_tpu.serve.llm_engine.kv_cache import PagedKVCache
from ray_tpu.serve.llm_engine.server import LLMEngineServer

__all__ = [
    "CacheExhaustedError", "ENGINE_STAT_KEYS", "LLMEngine",
    "LLMEngineServer", "LatencyPolicy", "PagedKVCache",
]
