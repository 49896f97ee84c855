"""Serving tier: KV-cached decoding + continuous batching.

Opens the inference workload over the training stack — every training
subsystem (amp dtypes, Pallas attention kernels, profiler) is reused,
nothing is forked:

    kv_cache   preallocated slot-paged KV cache pytree (bf16 default,
               in-place dynamic_update_slice writes, per-slot lengths)
    paging     vLLM-style paged cache: shared page pool + per-slot
               block tables (`PagedKVCache`), host free-list/ref-count
               `PageAllocator`, and the copy-on-write `PrefixStore`
               that shares materialized prompt pages across requests;
               optional int8 pools with per-(page, head) scales
    adapters   multi-LoRA `AdapterPool`: rank-padded packed adapter
               factors in fixed-shape paged device buffers (the
               `PageAllocator` idiom — ref-counts, LRU park on idle
               tenants, reclaim on pressure), host registry keyed by
               tenant; `ops/lora.py` contracts per-token deltas out of
               it inside the one mixed serving trace
    sampling   greedy / temperature / top-k / top-p, jit-able and
               seed-deterministic
    drafting   n-gram self-drafter for speculative decoding: proposes
               up to k continuation tokens per slot by suffix-matching
               the slot's own history (no draft model); pluggable hook
               protocol for learned drafters
    faults     deterministic chaos harness: seeded `FaultPlan`
               schedules (tick / nth-call / periodic / probabilistic)
               over the engine's failure sites — page allocation,
               device step, logits (NaN/Inf poisoning), host fetch —
               with the shared `NO_FAULTS` null plan on the hot path
    programs   `StepPrograms`: the ONE definition of the tick's
               compiled programs (fused chunk+decode, decode alone,
               whole-prompt prefill, speculative commit, page fork);
               speculation and an adapter pool add operands to the one
               body, and ``step_source=`` replicas share the object
    engine     continuous-batching serving loop: fixed slot grid,
               request queue, per-step admit/evict, and the chunked-
               prefill token-budget scheduler — ONE compiled mixed
               chunk+decode step per tick (plus a decode-only fast
               path), donated cache buffers, no prompt-length ceiling;
               ``paged=True`` swaps in the block-table cache
    router     multi-replica serving fabric: `ReplicaRouter` owns N
               engines behind one surface — prefix-affinity placement
               via the cross-replica `SharedPrefixRegistry`,
               least-loaded otherwise, replica failover with
               token-identical in-flight recovery (page-shipping
               migration on paged caches, prompt + emitted tokens as
               the replay fallback), disaggregated prefill/decode
               replica classes with per-class TTFT/TPOT, rolling
               drain/rejoin, fleet chaos sites, merged fleet telemetry

The model side lives in `models/gpt.py` (``cache=`` on `GPTModel`) and
`ops/flash_attention.py` (`flash_attention_decode`); this package owns
the cache layout and the serving loop. See docs/inference.md.
"""

from rocm_apex_tpu.inference.adapters import (  # noqa: F401
    BASE_ADAPTER_ID,
    AdapterPool,
)
from rocm_apex_tpu.inference.drafting import NGramDrafter  # noqa: F401
from rocm_apex_tpu.inference.engine import (  # noqa: F401
    FINISH_REASONS,
    GenerationResult,
    InferenceEngine,
    Request,
    SamplingParams,
    shard_tp1_params,
)
from rocm_apex_tpu.inference.faults import (  # noqa: F401
    NO_FAULTS,
    Fault,
    FaultInjected,
    FaultPlan,
)
from rocm_apex_tpu.inference.kv_cache import KVCache  # noqa: F401
from rocm_apex_tpu.inference.paging import (  # noqa: F401
    PageAllocator,
    PagedKVCache,
    PrefixStore,
)
from rocm_apex_tpu.inference.programs import StepPrograms  # noqa: F401
from rocm_apex_tpu.inference.router import (  # noqa: F401
    REPLICA_CLASSES,
    REPLICA_STATES,
    ReplicaRouter,
    SharedPrefixRegistry,
)
from rocm_apex_tpu.inference.sampling import (  # noqa: F401
    greedy,
    sample,
    top_k_logits,
    top_p_logits,
)

__all__ = [
    "AdapterPool",
    "BASE_ADAPTER_ID",
    "KVCache",
    "PagedKVCache",
    "PageAllocator",
    "PrefixStore",
    "InferenceEngine",
    "StepPrograms",
    "ReplicaRouter",
    "SharedPrefixRegistry",
    "REPLICA_STATES",
    "REPLICA_CLASSES",
    "shard_tp1_params",
    "NGramDrafter",
    "Fault",
    "FaultPlan",
    "FaultInjected",
    "NO_FAULTS",
    "FINISH_REASONS",
    "Request",
    "GenerationResult",
    "SamplingParams",
    "greedy",
    "sample",
    "top_k_logits",
    "top_p_logits",
]
