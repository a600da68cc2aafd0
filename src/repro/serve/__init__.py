"""Production serve path: continuous batching, paged KV cache, SLO-aware
serving goodput.  (`repro.serve.jax_executor` — the per-slot real-model
executor — and `repro.serve.batched_executor` — the batched paged-decode
executor over the allocator's block tables — are imported lazily by
callers so this package stays importable without JAX, e.g. in the
numpy-only benchmark CI jobs.)"""
from repro.serve.engine import (NO_SLO, ContinuousServeEngine, ServeReport,
                                ServeRequest, ServeSLO, SimulatedExecutor,
                                run_static, synthetic_requests)
from repro.serve.kv_cache import (BLOCK_TOKENS, KVCacheStats,
                                  OutOfBlocksError, PagedKVCache)

__all__ = [
    "NO_SLO", "ContinuousServeEngine", "ServeReport", "ServeRequest",
    "ServeSLO", "SimulatedExecutor", "run_static", "synthetic_requests",
    "BLOCK_TOKENS", "KVCacheStats", "OutOfBlocksError", "PagedKVCache",
]
