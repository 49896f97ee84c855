"""Continuous-batching generation engine over the KV-cached GPT.

The serving loop the ROADMAP's "heavy traffic" story needs: a fixed
grid of batch slots (the preallocated `KVCache`), a host-side request
queue, and per-step admit/evict — a finished sequence frees its slot
at the end of a step and a queued request claims it at the start of
the next, so the compiled programs never change shape while the set of
in-flight requests churns (the continuous-batching design of modern
LLM servers, compiled-program-friendly).

Prefill is CHUNKED by default (the Sarathi-Serve / Orca design point,
arXiv:2403.02310): each tick the scheduler packs up to
``prefill_token_budget`` pending prompt tokens — pieces of one or more
queued or partially-prefilled requests, tracked by a per-slot prefill
cursor — into one fixed-shape ``(budget,)`` buffer with per-token slot
ids and positions, and runs ONE compiled **mixed step** that

* attends the packed chunk against each slot's existing cache prefix
  plus intra-chunk causality (models/gpt.py chunk path: the packed
  varlen segments kernel merged with the chunk-width cache read),
* scatters the chunk's K/V into the cache at per-slot offsets
  (`KVCache.write_at` semantics), and
* advances the WHOLE decode grid in the same program,

so decodes never wait on a prefill (no head-of-line blocking), prompts
of ANY length stream through in budget-sized pieces (there is no
admit-time prompt-length ceiling — only the physical cache capacity),
and no padded ``(1, max_prompt_len, …)`` activation ever materializes.
Ticks with no pending prompt tokens take a decode-only fast path (the
same compiled decode program every tick). Fixed shapes mean exactly
ONE mixed-step trace for a whole serving run regardless of the prompt
mix — ``mixed_trace_count`` pins that invariant in tests.

``prefill_token_budget=None`` restores the legacy whole-prompt path
(one padded compiled prefill per request) as the A/B baseline the
serving bench measures against.

Inactive slots ride along as dead rows (their sampled tokens are
discarded and their lengths pinned) — uniform shapes beat ragged
dispatch, the same padded-slot trade the training stack's pipeline
microbatching makes.

Determinism: one engine-owned PRNG key, device state like the cache,
split once per compiled call INSIDE the call (`programs.py`); a fixed
seed replays the exact token stream for the same arrival
order regardless of wall-clock timing.
"""

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rocm_apex_tpu.inference.faults import NO_FAULTS, FaultInjected
from rocm_apex_tpu.inference.kv_cache import KVCache
from rocm_apex_tpu.inference.paging import (
    PageAllocator,
    PagedKVCache,
    PrefixStore,
)
from rocm_apex_tpu.inference.programs import FETCHED, StepPrograms
from rocm_apex_tpu.monitor.trace import (
    NULL_TRACER,
    gc_pauses,
    install_gc_hook,
    mint_trace_id,
    phase,
)
from rocm_apex_tpu.ops._pallas import on_tpu

__all__ = [
    "SamplingParams",
    "Request",
    "GenerationResult",
    "InferenceEngine",
    "FINISH_REASONS",
    "shard_tp1_params",
]

#: the phases of the tick's own clock (`InferenceEngine.step`), in the
#: order a tick meets them; ``rest`` is what lies outside the others
TICK_PHASES = (
    "admit", "pack", "table_push", "dispatch", "fetch", "commit", "rest",
)
# where the tick's account counts a tick and its wall time, by `program`
_ACCOUNT_KEYS = {
    "mixed": ("cum_ticks_mixed", "cum_ms_mixed"),
    "decode": ("cum_ticks_decode", "cum_ms_decode"),
}


def shard_tp1_params(model, params_tp1, mesh, sample_tokens=None):
    """Slice a tp=1 params pytree into the fake-replicated tp layout.

    The tensor-parallel layers draw INDEPENDENT per-rank values at
    init (rank-folded keys), so a tp>1 model initialized from the same
    seed does NOT compute the tp=1 function. Serving wants exactly
    that function: this helper takes the tp=1 checkpoint and, for each
    leaf, finds the one axis the tp model shards (by comparing against
    the tp model's abstract init shapes), slices the tp=1 weight into
    per-rank shards, and lays them out in the repo's fake-replicated
    idiom — global shape == local shape, each mesh device holding its
    own rank's slice (`check_vma=False` downstream). Replicated leaves
    (LayerNorms, position embeddings, biases of row-parallel layers)
    pass through unchanged on every rank.

    ``model`` is the tp>1 module (its cfg names the tensor axis and
    world size); ``mesh`` the initialized `parallel_state` mesh. The
    returned pytree is committed to the mesh devices, ready for
    `InferenceEngine(model, params)` or a training step.
    """
    from jax import shard_map

    P = jax.sharding.PartitionSpec
    axis = model.cfg.tensor_axis
    tp = mesh.shape[axis]
    if sample_tokens is None:
        sample_tokens = jnp.zeros((1, 8), jnp.int32)

    local_shapes = jax.eval_shape(
        shard_map(
            lambda t: model.init(jax.random.PRNGKey(0), t),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        ),
        sample_tokens,
    )

    def _stack(full, local):
        full_np = np.asarray(full)
        gshape, lshape = tuple(full_np.shape), tuple(local.shape)
        if gshape == lshape:
            return np.stack([full_np] * tp)
        diff = [
            i for i, (g, l) in enumerate(zip(gshape, lshape)) if g != l
        ]
        if len(gshape) != len(lshape) or len(diff) != 1 or any(
            gshape[i] != lshape[i] * tp for i in diff
        ):
            raise ValueError(
                f"cannot map tp=1 leaf {gshape} onto tp={tp} local "
                f"shape {lshape}"
            )
        ax = diff[0]
        return np.stack(
            np.split(full_np, tp, axis=ax)
        )

    stacked = jax.tree_util.tree_map(_stack, params_tp1, local_shapes)

    def _pick(tree):
        r = jax.lax.axis_index(axis)
        return jax.tree_util.tree_map(
            lambda s: jax.lax.dynamic_index_in_dim(
                s, r, 0, keepdims=False
            ),
            tree,
        )

    return jax.jit(
        shard_map(
            _pick, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )
    )(stacked)

#: every finish_reason a `GenerationResult` can carry — the lifecycle
#: contract documented in docs/inference.md "Failure semantics"
FINISH_REASONS = (
    "eos", "length", "capacity",  # normal completion paths
    "deadline", "cancelled", "error", "queue_full",  # robustness paths
)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Static sampling config — fixed per engine (it is baked into the
    compiled programs). ``temperature=0`` is greedy."""

    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int
    # enqueue wall time (perf_counter domain) — the anchor for the
    # queue-wait and TTFT percentiles in `stats()`
    enqueued_at: float = 0.0
    # lifecycle bounds (absolute perf_counter times; None = unbounded):
    # `deadline` is end-to-end (queue wait + serving), checked at tick
    # boundaries; `queue_deadline` is the admission TTL — a request
    # still queued past it is expired without ever taking a slot.
    deadline: Optional[float] = None
    queue_deadline: Optional[float] = None
    # multi-LoRA serving (engines built with adapter_pool=): the
    # registered adapter this request decodes under (0 = base model)
    # and the tenant it bills to (None on a base engine)
    adapter_id: int = 0
    tenant: Optional[str] = None
    # fleet-causal trace context: minted ONCE at admission (router or
    # first engine to see the request) and carried verbatim across
    # every migration/failover/handoff hop, so merged timelines group
    # a request's whole fleet lifeline under one id ("" = untraced).
    trace_id: str = ""


@dataclasses.dataclass
class GenerationResult:
    request_id: int
    prompt: List[int]
    tokens: List[int]  # generated ids (includes the eos when hit)
    finish_reason: str  # one of FINISH_REASONS


@dataclasses.dataclass
class _Slot:
    """Host-side bookkeeping for one leased cache slot."""

    req: Request
    generated: List[int]
    pos: int  # tokens materialized in the cache for this slot
    cursor: int = 0  # prefix tokens committed to the cache so far
    # tokens this slot must prefill before decoding. Normally the
    # prompt; for a PREEMPTED request re-admitted after its pages were
    # reclaimed it is prompt + generated[:-1] — the recompute-on-resume
    # semantics (the last generated token stays unwritten, exactly the
    # live-slot invariant pos == prompt + generated[:-1]).
    prefix: List[int] = dataclasses.field(default_factory=list)
    resumed: bool = False  # re-admitted after preemption mid-decode
    # per-request timeline anchors (perf_counter domain — the SAME
    # clock as `enqueued_at` and `stats()`): slot lease, first sampled
    # token, and the count of mixed ticks that carried this request's
    # prompt tokens. Host floats only — no device traffic.
    leased_at: float = 0.0
    first_token_at: float = 0.0
    chunks: int = 0
    # paged-cache bookkeeping (engine-paged mode only): page indices
    # this slot BORROWS from the prefix store (immutable until a
    # copy-on-write fork), the chain key of the last full prompt page
    # walked/registered, and how many full prompt pages that is.
    borrowed: Set[int] = dataclasses.field(default_factory=set)
    chain_key: Any = None
    reg_pages: int = 0
    # adapter-pool buffer slot this lease holds ONE admission ref on
    # (0 = base, no ref; -1 = already released — the teardown guard)
    adapter_slot: int = 0
    # window group only: the first page index this slot still maps there
    # (the pages before it lay behind its window and went back)
    window_head: int = 0

    @property
    def prefilling(self) -> bool:
        return self.cursor < len(self.prefix)


class InferenceEngine:
    """Continuous-batching serving loop for a `GPTModel`.

    ``model``/``params`` are the trained flax module and its variables
    (the same pytree `GPTModel.init` returns — serving reuses the
    training checkpoint directly). The cache dtype defaults to the
    model's compute dtype (bf16 under the O4/O5 recipe).

    ``prefill_token_budget`` (default 64) is the chunked-prefill
    scheduler knob: prompt tokens absorbed per tick, across requests.
    Larger budgets raise prefill throughput (fewer, wider chunks);
    smaller budgets cut time-to-first-token jitter for the decodes
    sharing the tick — see docs/inference.md for the trade.
    ``prefill_chunk`` optionally caps the tokens taken from ONE
    request per tick (a fairness knob inside the budget).
    ``prefill_token_budget=None`` selects the legacy whole-prompt
    prefill (one padded compiled call per request, pad width
    ``max_prompt_len``) — the A/B baseline; only this path has a
    prompt-length ceiling.

    ``tracer`` (a `monitor.Tracer`) opts into per-request serving
    timelines: each request gets its own track with
    enqueue → queue_wait → prefill_chunk spans (chunk token counts as
    args) → decode → finish, built from the SAME ``perf_counter``
    readings that feed ``stats()`` — export with
    ``tracer.export_chrome_trace(path)`` and the span boundaries
    reproduce the reported TTFT/queue-wait numbers. Default ``None``
    is the shared disabled tracer: call sites pay one attribute check,
    the compiled programs and the one-fetch-per-tick host↔device
    pattern are untouched.

    Whatever the tracer, every tick is one ``apex/engine.tick``
    profiler annotation (`monitor.trace.phase`) tiled by its phases
    ``engine.admit`` → ``engine.pack`` → ``engine.table_push`` →
    ``engine.dispatch`` → ``engine.fetch`` → ``engine.commit``, the
    tick's counts (program, the model's applies in it, decodes, chunk
    tokens, slots, pages, queue depth, admitted, finished) riding as
    the tick's metadata; `add_request` is ``apex/engine.enqueue``. A
    `jax.profiler` capture holds them on the device planes' clock;
    with none live they cost microseconds a tick (the counts are
    values the tick computes anyway; docs/observability.md says what
    reads each).
    An enabled tracer records the same phases on its ``engine``
    track.
    Per-request COMPLETION records (TTFT, TPOT, tokens, chunks, queue
    wait) accrue on ``completions`` unconditionally — pure host
    bookkeeping.

    ``paged=True`` swaps the contiguous per-slot cache for the
    block-table `PagedKVCache` (chunked scheduler required): device
    memory in use scales with LIVE tokens, writes scatter through the
    page table and reads gather through it
    (`flash_attention_decode_paged`). ``page_size`` tunes the
    fragmentation/indirection trade; ``num_pages`` caps the pool
    (default: worst-case slots × pages_per_slot — size it DOWN to
    realize the memory win; exhaustion backpressures token scheduling,
    it never crashes). ``kv_dtype=jnp.int8`` stores int8 pools with
    per-(page, head) fp32 scales (~half the cache bytes and decode
    DMA; greedy outputs stay parity-grade, see tests).
    ``prefix_sharing=True`` additionally ref-counts fully-written
    prompt pages in a `PrefixStore`: a later request with the same
    prompt prefix maps those pages instead of re-prefilling them
    (TTFT collapses for shared-system-prompt traffic) and pages fork
    copy-on-write only when the borrower would write into one.

    Multi-chip serving (``cfg.tensor_parallel_size > 1``; requires
    ``paged=True`` + chunked mode and an initialized
    `parallel_state` mesh): every step program runs under one
    `shard_map` over the tensor axis. The packed prefill chunk rides
    the sequence-parallel + collective-matmul layout (each chip holds
    ``budget/tp`` rows between the embedding scatter and the LM-head
    gather; TP-edge collectives fuse into ppermute rings), the decode
    grid stays plain tensor-parallel, and the paged pools keep GLOBAL
    heads laid out head-sharded (`NamedSharding`) so per-chip KV bytes
    drop by 1/tp (`per_chip_kv_bytes`) while host fetches — page
    shipping, debugging — see full-head arrays. Greedy outputs are
    token-identical to a tp=1 engine and ``mixed_trace_count`` stays 1.

    Robustness layer (docs/inference.md "Failure semantics"): per-
    request deadlines/queue TTLs (``add_request(timeout=, queue_ttl=)``,
    checked at tick boundaries), `cancel`, `drain`, a bounded
    admission queue (``max_queue`` — overflow sheds the NEWEST request
    with a ``queue_full`` result, never silently), a stall watchdog
    (``watchdog_timeout`` wall-seconds without token progress raises
    with the stuck slots named; ``watchdog_dump_path`` persists the
    engine state + tracer timeline first), device-step retry with
    capped exponential backoff (``max_step_retries``/
    ``step_retry_backoff``; exhaustion preempts-and-requeues the
    in-flight batch before surfacing), and per-slot quarantine of
    nonfinite logits (finish reason ``error``; ``flight_recorder``
    dumps the anomaly bundle). ``faults`` accepts a seeded
    `inference.faults.FaultPlan` — the chaos harness that injects
    failures at the page-allocation / device-step / logits /
    host-fetch sites deterministically; the default is the shared
    ``NO_FAULTS`` null plan. Every transition is a host-side slot-mask
    edit: ``mixed_trace_count`` stays 1 under any plan.
    """

    def __init__(
        self,
        model,
        params,
        *,
        num_slots: int = 8,
        max_prompt_len: Optional[int] = None,
        capacity: Optional[int] = None,
        eos_id: Optional[int] = None,
        sampling: Optional[SamplingParams] = None,
        seed: int = 0,
        cache_dtype: Any = None,
        prefill_token_budget: Optional[int] = 64,
        prefill_chunk: Optional[int] = None,
        tracer=None,
        paged: bool = False,
        page_size: int = 16,
        kv_dtype: Any = None,
        num_pages: Optional[int] = None,
        prefix_sharing: bool = False,
        spec_k: int = 0,
        drafter=None,
        spec_window: int = 64,
        faults=None,
        max_queue: Optional[int] = None,
        max_step_retries: int = 2,
        step_retry_backoff: float = 0.0,
        watchdog_timeout: Optional[float] = None,
        watchdog_dump_path: Optional[str] = None,
        flight_recorder=None,
        donate_buffers: Optional[bool] = None,
        registry=None,
        stats_retention: int = 4096,
        step_source: Optional["InferenceEngine"] = None,
        adapter_pool=None,
        tier_preemption: bool = False,
        retrace_policy: Optional[str] = None,
        timeseries=None,
    ):
        cfg = model.cfg
        tp = int(cfg.tensor_parallel_size or 1)
        self.tp = tp
        self._mesh = None
        if tp > 1:
            # Multi-chip serving: the fused mixed step runs under
            # shard_map over the tensor axis. The packed chunk rides
            # the PR-3 sequence-parallel layout (ring collectives from
            # ops/collective_matmul.py); the decode grid stays plain
            # tensor-parallel (its width-1 seq axis cannot shard); the
            # paged pools are laid out head-sharded so per-chip KV
            # bytes drop by 1/tp (see _cache_pspec).
            from rocm_apex_tpu.transformer import parallel_state

            if not parallel_state.model_parallel_is_initialized():
                raise ValueError(
                    "tp>1 serving needs parallel_state."
                    "initialize_model_parallel(tp, 1) before engine "
                    "construction (the shard_map mesh comes from it)"
                )
            if parallel_state.get_tensor_model_parallel_world_size() != tp:
                raise ValueError(
                    f"model cfg.tensor_parallel_size={tp} but the "
                    f"initialized mesh has tensor size "
                    f"{parallel_state.get_tensor_model_parallel_world_size()}"
                )
            self._mesh = parallel_state.get_mesh()
            if not paged:
                raise ValueError(
                    "tp>1 serving shards the PagedKVCache pools over "
                    "heads; set paged=True"
                )
            if prefill_token_budget is None:
                raise ValueError(
                    "tp>1 serving rides the chunked mixed step; set "
                    "prefill_token_budget"
                )
            if prefill_token_budget % tp != 0:
                raise ValueError(
                    f"prefill_token_budget={prefill_token_budget} must "
                    f"divide by tp={tp} (the chunk stream is "
                    f"sequence-scattered over the tensor axis)"
                )
            if cfg.num_attention_heads % tp != 0:
                raise ValueError(
                    f"num_attention_heads={cfg.num_attention_heads} "
                    f"must divide by tp={tp}"
                )
        self.model = model
        self.params = params
        # The served model DECLARES what its layers keep per request
        # (`cache_spec`) and the paged cache is built from that. A
        # recurrent state lives in the slot, not in pages: whatever
        # shares, ships or defers pages cannot carry it, so those
        # options are refused here and not at their first use. A LATENT
        # row lives in pages as K/V does, so a model that keeps one is
        # refused only what its own code does not do.
        spec = model.cache_spec()
        kinds = {layer["kind"] for layer in spec}
        self._stateful = "ssm" in kinds  # keeps per-slot state
        self._latent = "latent" in kinds
        # frees the pages its window layers' rows have left
        self._windowed = any(layer.get("window") for layer in spec)
        quantized = kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8
        refused = {}
        if self._stateful:
            keeps = "keeps a recurrent state per slot beside its paged K/V"
            refused = {
                "paged=False (its attention layers' K/V is paged; the "
                "contiguous cache has no per-slot state)": not paged,
                "prefix_sharing (a shared page holds K/V, not the state "
                "a borrower would need at the prefix's end)": prefix_sharing,
                "spec_k > 0 (a rejected draft row cannot be unwound from "
                "a state that was overwritten in place)": spec_k > 0,
                "tensor_parallel_size > 1": tp > 1,
                "adapter_pool": adapter_pool is not None,
                "kv_dtype=int8": quantized,
            }
        elif self._latent:
            keeps = "keeps latent rows in pages"
            refused = {
                "paged=False (the contiguous cache has no latent row)":
                    not paged,
                "spec_k > 0 (the commit program writes K and V pools; "
                "a latent block hands back no deferred rows)": spec_k > 0,
                "tensor_parallel_size > 1 (every head reads the one "
                "latent row: the pool has no head axis to shard)": tp > 1,
                "adapter_pool (its projections take no adapters)":
                    adapter_pool is not None,
                "kv_dtype=int8 (a latent pool has no int8 form)": quantized,
            }
        elif self._windowed:
            keeps = (
                "frees the pages that its window layers' rows have left "
                "while a request runs")
            refused = {
                "paged=False (the contiguous cache keeps every row of a "
                "slot)": not paged,
                "prefix_sharing (a registered page may be freed behind "
                "its borrower's window)": prefix_sharing,
                "spec_k > 0 (the commit program writes the global "
                "group's pools by layer; a window layer hands back no "
                "deferred rows)": spec_k > 0,
                "tensor_parallel_size > 1 (the window group's pools and "
                "table have no sharded layout)": tp > 1,
                "adapter_pool (its projections take no adapters)":
                    adapter_pool is not None,
                "kv_dtype=int8 (a windowed read has no int8 form)":
                    quantized,
            }
        for what, asked in refused.items():
            if asked:
                raise ValueError(
                    f"{type(model).__name__} {keeps}; it does not serve "
                    f"with {what}"
                )
        self.capacity = int(capacity or cfg.max_position_embeddings)
        if self.capacity > cfg.max_position_embeddings:
            raise ValueError(
                f"capacity {self.capacity} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}"
            )
        self.max_prompt_len = int(max_prompt_len or self.capacity)
        if not 0 < self.max_prompt_len <= self.capacity:
            raise ValueError(
                f"max_prompt_len {self.max_prompt_len} must be in "
                f"(0, capacity={self.capacity}]"
            )
        if prefill_token_budget is not None and prefill_token_budget < 1:
            raise ValueError(
                f"prefill_token_budget must be >= 1 (or None for the "
                f"whole-prompt path), got {prefill_token_budget}"
            )
        self.prefill_token_budget = (
            int(prefill_token_budget)
            if prefill_token_budget is not None else None
        )
        self.prefill_chunk = (
            int(prefill_chunk) if prefill_chunk is not None else None
        )
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        self.eos_id = eos_id
        self.sampling = sampling or SamplingParams()
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if self.spec_k > 0:
            if self.prefill_token_budget is None:
                raise ValueError(
                    "speculative decoding rides the chunked mixed step; "
                    "set prefill_token_budget (chunked mode) to use "
                    "spec_k"
                )
            if self.spec_k + 1 > self.prefill_token_budget:
                raise ValueError(
                    f"spec_k={self.spec_k} needs spec_k + 1 <= "
                    f"prefill_token_budget="
                    f"{self.prefill_token_budget} chunk rows (the "
                    f"verified span is the last token plus k drafts)"
                )
            if drafter is None:
                from rocm_apex_tpu.inference.drafting import NGramDrafter

                drafter = NGramDrafter(self.spec_k, window=spec_window)
        self._drafter = drafter if self.spec_k > 0 else None
        self._spec_window = int(
            getattr(self._drafter, "window", spec_window)
        )
        # ---- multi-LoRA serving (ISSUE 18) ---------------------------
        # adapter_pool: an `inference.adapters.AdapterPool` whose
        # packed device buffers the step programs (`programs.py`) gather
        # per-token deltas from (ops/lora.py). The pool is engine-owned
        # state like the KV cache: its buffers are donated through the
        # jits and re-bound every tick. Admission acquires one ref per
        # in-flight request (tier-ordered, acquire-or-skip — see
        # `_pick_queued`); every teardown path releases exactly once.
        self.adapter_pool = adapter_pool
        self.tier_preemption = bool(tier_preemption)
        # host-side per-tenant completion accounting (the chaos
        # isolation identity: sums across tenants == the global
        # counters) — keyed by TRUE tenant name, unlike the labeled
        # metric families which overflow into "other" at the cap
        self._tenant_counts: Dict[str, Dict[str, int]] = {}
        if adapter_pool is not None:
            if tp > 1:
                raise ValueError(
                    "adapter_pool serving is tp=1 only for now (the "
                    "segmented gather would need head-sharded adapter "
                    "buffers)"
                )
            if self.spec_k > 0:
                raise ValueError(
                    "adapter_pool does not compose with speculative "
                    "decoding yet (the drafter is base-model-only; a "
                    "per-adapter draft would be wrong for every "
                    "non-base slot)"
                )
            if self.prefill_token_budget is None:
                raise ValueError(
                    "adapter_pool rides the chunked mixed step; set "
                    "prefill_token_budget"
                )
            if (
                adapter_pool.num_layers != cfg.num_layers
                or adapter_pool.hidden != cfg.hidden_size
                or adapter_pool.out_dims["qkv"] != 3 * cfg.hidden_size
            ):
                raise ValueError(
                    f"adapter pool geometry (layers="
                    f"{adapter_pool.num_layers}, hidden="
                    f"{adapter_pool.hidden}, qkv_out="
                    f"{adapter_pool.out_dims['qkv']}) does not match "
                    f"the model (layers={cfg.num_layers}, hidden="
                    f"{cfg.hidden_size})"
                )
        self.paged = bool(paged)
        self.prefix_sharing = bool(prefix_sharing)
        self._allocator = None
        self._window_allocator = None
        self._window_pages_freed = 0  # in the tick under way
        self._store = None
        # preempted-request carryover: request_id -> (generated tokens,
        # first_token_at, chunk count) restored on re-admission
        self._preempted: Dict[int, Any] = {}
        # page-shipping migration: payloads handed to resume_request(pages=...)
        # wait here until the request leases a slot; fallbacks replay tokens
        self._shipped: Dict[int, Any] = {}
        if not self.paged:
            if prefix_sharing:
                raise ValueError("prefix_sharing requires paged=True")
            if quantized:
                raise ValueError("kv_dtype=int8 requires paged=True")
            self.cache = KVCache.for_model(
                cfg, num_slots, self.capacity, dtype=cache_dtype
            )
        else:
            if self.prefill_token_budget is None:
                raise ValueError(
                    "the paged cache serves the chunked-prefill "
                    "scheduler only (the legacy whole-prompt path "
                    "needs contiguous slot rows); set "
                    "prefill_token_budget"
                )
            self.cache = PagedKVCache.from_spec(
                # tp>1: GLOBAL head count in the pools; the NamedSharding
                # below splits dim 1 (heads) over the tensor axis, so
                # each chip physically holds 1/tp of the KV bytes while
                # host fetches (page shipping, debugging) still see
                # full-head arrays — shipped pages are tp-agnostic.
                model.cache_spec(), num_slots, self.capacity,
                page_size=page_size, num_pages=num_pages,
                dtype=(
                    kv_dtype if (kv_dtype is not None and not quantized)
                    else cache_dtype or cfg.dtype
                ),
                quantized=quantized,
                prefill_token_budget=self.prefill_token_budget,
            )
            if tp > 1:
                self.cache = jax.device_put(
                    self.cache, self._cache_sharding()
                )
            self._allocator = PageAllocator(self.cache.num_pages)
            if self.cache.window:
                # the window group: pools behind a table and an
                # allocator of their own (`_free_window_pages`)
                self._window_allocator = PageAllocator(
                    self.cache.window_pages)
                self._window_table = np.full(
                    (num_slots, self.cache.pages_per_slot),
                    self.cache.window_pages, np.int32,
                )
                self._window_table_dirty = False
            if prefix_sharing:
                self._store = PrefixStore(page_size)
                self._allocator.on_evict = self._store.unregister_page
            # host mirror of the page table (the host is the source of
            # truth; pushed to device once per tick when dirty)
            self._table = np.full(
                (num_slots, self.cache.pages_per_slot),
                self.cache.num_pages, np.int32,
            )
            self._table_dirty = False
        # the sampling key is device state like the cache: every step
        # program splits it inside itself and hands the new state back
        # (`_run_program` re-binds it); the host never splits it
        self._rng = self._replicated(jax.random.PRNGKey(seed))
        self._queue: collections.deque = collections.deque()
        self._slots: List[Optional[_Slot]] = [None] * num_slots
        self._next_id = 0
        self._zero_counters()
        # Per-request queue waits (enqueue -> slot lease) and TTFTs
        # (enqueue -> first token) feed the p50/p95 fields that surface
        # the head-of-line blocking the chunked scheduler removes.
        # Raw per-request samples keep EXACT percentiles while they
        # fit; `stats_retention` caps them (oldest drop) so a
        # long-lived engine has O(1) stats memory. The registry
        # histograms below never drop — once the rings wrap, stats()
        # switches to their bounded-error quantiles (see stats()).
        if stats_retention < 1:
            raise ValueError(
                f"stats_retention must be >= 1, got {stats_retention}"
            )
        self.stats_retention = int(stats_retention)
        self._queue_waits: collections.deque = collections.deque(
            maxlen=self.stats_retention
        )
        self._ttfts: collections.deque = collections.deque(
            maxlen=self.stats_retention
        )
        # per-request completion records (host-side; see `completions`)
        self._completions: collections.deque = collections.deque(
            maxlen=self.stats_retention
        )
        # Mergeable constant-memory telemetry (monitor/telemetry.py):
        # a private enabled registry by default so every engine can be
        # scraped / merged; pass monitor.NULL_REGISTRY to opt out
        # (stats() then serves the capped rings only). All observation
        # is host-side — the compiled programs gain ZERO equations
        # (pinned by tools/graphlint.py fingerprints).
        if registry is None:
            from rocm_apex_tpu.monitor.telemetry import MetricRegistry

            registry = MetricRegistry()
        self.registry = registry
        self._h_queue_wait = registry.histogram(
            "serve_queue_wait_ms",
            "Request queue wait (enqueue -> slot lease), ms.",
        )
        # multi-tenant engines label TTFT and the token counters by
        # tenant (the per-tenant SLO feed); unlabeled reads on these
        # families aggregate across series, so stats() and the base
        # bench consume both shapes identically. The cardinality cap
        # is honored by an explicit "other" overflow tenant (see
        # `_tenant_series`) — the serving hot path NEVER raises
        # CardinalityError.
        self._per_tenant = adapter_pool is not None
        if self._per_tenant:
            self._h_ttft = registry.histogram(
                "serve_ttft_ms",
                "Time to first token (enqueue -> first token), ms.",
                labelnames=("tenant",),
            )
            self._c_tokens = registry.counter(
                "serve_tokens_total",
                "Tokens of finished requests, by phase "
                "(prompt=ingested, generated=emitted) and tenant.",
                labelnames=("phase", "tenant"),
            )
            # pre-create the overflow series so the fallback can never
            # itself overflow, whatever max_label_sets is
            self._c_tokens.labels(phase="prompt", tenant="other")
            self._c_tokens.labels(phase="generated", tenant="other")
            self._h_ttft.labels(tenant="other")
            self._tenant_label_ok: Set[str] = {"other"}
            self._tenant_overflowed: Set[str] = set()
        else:
            self._h_ttft = registry.histogram(
                "serve_ttft_ms",
                "Time to first token (enqueue -> first token), ms.",
            )
            self._c_tokens = registry.counter(
                "serve_tokens_total",
                "Tokens of finished requests, by phase "
                "(prompt=ingested, generated=emitted).",
                labelnames=("phase",),
            )
        self._h_tpot = registry.histogram(
            "serve_tpot_ms",
            "Mean inter-token time after the first token, ms.",
        )
        self._h_e2e = registry.histogram(
            "serve_e2e_ms",
            "Request end-to-end latency (enqueue -> finish), ms.",
        )
        self._c_completions = registry.counter(
            "serve_completions_total",
            "Finished requests by terminal finish_reason.",
            labelnames=("finish_reason",),
        )
        self._g_queue_depth = registry.gauge(
            "serve_queue_depth", "Requests waiting for a slot."
        )
        self._g_slots_active = registry.gauge(
            "serve_slots_active", "Slots holding a live request."
        )
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # the collector's pauses as ``host.gc`` spans and totals: once a
        # process, whatever the tracer (the tick's account reads them)
        install_gc_hook()
        # ---- runtime retrace sentinel + sensor plane (ISSUE 19) ------
        # retrace_policy="count"|"raise" arms a RetraceSentinel at the
        # next reset_stats() (the bench contract's warmed-up-now
        # marker): a jax compile landing after that boundary is the
        # latency cliff the one-compiled-trace invariant forbids —
        # "count" observes it into xla_compiles_post_warmup_total,
        # "raise" fails the NEXT step() (never mid-compile). The
        # timeseries ring, when attached, samples the registry once
        # per `interval` from the step loop.
        self.retrace_sentinel = None
        if retrace_policy is not None:
            from rocm_apex_tpu.monitor.trace import RetraceSentinel

            self.retrace_sentinel = RetraceSentinel(
                registry, policy=retrace_policy, tracer=self.tracer
            )
        self.timeseries = timeseries
        # ---- robustness layer (ISSUE 12) -----------------------------
        # faults: the chaos harness (NO_FAULTS = the shared null plan —
        # call sites pay one `enabled` attribute check, the NULL_TRACER
        # idiom). All injection and all lifecycle transitions below are
        # host-side slot-mask edits: the compiled programs never change
        # shape and `mixed_trace_count` stays 1 under any plan.
        self.faults = faults if faults is not None else NO_FAULTS
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_queue = max_queue
        if max_step_retries < 0:
            raise ValueError(
                f"max_step_retries must be >= 0, got {max_step_retries}"
            )
        self.max_step_retries = int(max_step_retries)
        self.step_retry_backoff = float(step_retry_backoff)
        if watchdog_timeout is not None and watchdog_timeout <= 0:
            raise ValueError(
                f"watchdog_timeout must be > 0 seconds, got "
                f"{watchdog_timeout}"
            )
        self.watchdog_timeout = watchdog_timeout
        self.watchdog_dump_path = watchdog_dump_path
        self.flight_recorder = flight_recorder
        self._draining = False
        self._tick = 0  # step() count — the fault plans' tick domain
        # the tick's own clock: (phase, when it ended) of the tick
        # under way, appended where each phase ends
        self._laps: List[Tuple[str, float]] = []
        # queue_full results awaiting delivery through the next step()
        self._shed_results: List[GenerationResult] = []
        # stall watchdog anchors: last wall time token progress was
        # observed, and the counter snapshot that defines "progress"
        self._last_progress = time.perf_counter()
        self._progress_mark = (0, 0, 0)

        # cache buffers are DONATED: the step updates them in place on
        # TPU. On CPU (the test platform) the default is NO donation —
        # the fault-retry path (`_run_program`) re-runs a step from the
        # caller's still-live buffers, which donation would have
        # deleted. `donate_buffers` overrides the gate both ways (the
        # graph-contract linter lowers a donating engine to verify the
        # aliasing contract without being on TPU).
        if donate_buffers is None:
            donate_buffers = on_tpu()
        self.donate_buffers = bool(donate_buffers)
        # The compiled step programs, defined once in `programs.py`.
        # `spec` and `lora` are features this engine DERIVES (spec_k >
        # 0, an adapter pool), not options of their own: each adds
        # operands to the one tick body. Building the object traces
        # nothing (jit is lazy), so only what a tick runs is compiled.
        self.programs = StepPrograms(
            model, self.sampling, self.cache,
            budget=self.prefill_token_budget, spec_k=self.spec_k,
            adapter_buffers=(
                adapter_pool.buffers if adapter_pool is not None else None
            ),
            mesh=self._mesh,
            cache_pspec=self._cache_pspec() if tp > 1 else None,
            donate_buffers=self.donate_buffers,
        )
        if step_source is not None:
            self._adopt_steps(step_source)

    def _adopt_steps(self, src: "InferenceEngine") -> None:
        """Replica fast-path: run `src`'s step programs (and count on
        the trace counters they increment) instead of re-tracing
        identical ones. Used by ReplicaRouter: an N-replica fleet warms
        up once, not N times, and the shared counters keep every
        replica's `mixed_trace_count == 1` invariant honest. Refused
        (ValueError) unless the programs this engine would build are
        the ones `src` built."""
        src.programs.compatible_with(self.programs)
        self.programs = src.programs

    # `tests/benchmarks/test_compile_v5e.py` lowers these two for a
    # described v5e; everything else reads `engine.programs`
    @property
    def _mixed_fn(self):
        return self.programs.mixed_fn

    @property
    def _decode_fn(self):
        return self.programs.decode_fn

    # ------------------------------------------------------------------
    # tp>1 cache layout
    # ------------------------------------------------------------------

    def _cache_pspec(self):
        """PartitionSpec pytree matching the `PagedKVCache` structure:
        pools head-sharded over the tensor axis (dim 1 of
        ``(num_pages, heads, page_size, head_dim)``), int8 scales
        likewise (dim 1 of ``(num_pages, heads)``), table and lengths
        replicated. Used both as the shard_map cache spec and (through
        `_cache_sharding`) as the initial device layout."""
        P = jax.sharding.PartitionSpec
        axis = self.model.cfg.tensor_axis
        cache = self.cache

        def each(arrays, spec):
            return None if arrays is None else tuple(spec for _ in arrays)

        pool, scale = P(None, axis, None, None), P(None, axis)
        return PagedKVCache(
            k=each(cache.k, pool), v=each(cache.v, pool),
            k_scale=each(cache.k_scale, scale),
            v_scale=each(cache.v_scale, scale),
            page_table=P(), lengths=P(), page_size=cache.page_size,
        )

    def _replicated(self, x):
        """``x`` on the tp mesh's replicated layout (as it is where
        there is no mesh): the step pytree never mixes device
        assignments, and a state that comes back from a program goes in
        with the type it came back with (no second trace)."""
        if self._mesh is None:
            return x
        return jax.device_put(x, jax.sharding.NamedSharding(
            self._mesh, jax.sharding.PartitionSpec()))

    def _cache_sharding(self):
        """`NamedSharding` pytree for `jax.device_put` of the cache."""
        return jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(self._mesh, s),
            self._cache_pspec(),
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec),
        )

    def per_chip_kv_bytes(self) -> int:
        """Physical KV pool + scale bytes held by the most-loaded chip
        — the 1/tp audit number (a tp=1 engine reports the full pool).
        Walks `addressable_shards`, so it measures the layout the
        arrays actually have, not the intended spec."""
        per_dev: Dict[Any, int] = {}
        arrays = list(self.cache.k) + list(self.cache.v)
        for scales in (self.cache.k_scale, self.cache.v_scale):
            if scales is not None:
                arrays += list(scales)
        for a in arrays:
            for sh in a.addressable_shards:
                nbytes = sh.data.size * sh.data.dtype.itemsize
                per_dev[sh.device] = per_dev.get(sh.device, 0) + nbytes
        return max(per_dev.values()) if per_dev else 0

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    @property
    def num_slots(self) -> int:
        return len(self._slots)

    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def num_queued(self) -> int:
        return len(self._queue)

    @property
    def chunked(self) -> bool:
        return self.prefill_token_budget is not None

    @property
    def prefill_trace_count(self) -> int:
        return self.programs.traces["prefill"]

    @property
    def decode_trace_count(self) -> int:
        return self.programs.traces["decode"]

    @property
    def mixed_trace_count(self) -> int:
        return self.programs.traces["mixed"]

    def has_work(self) -> bool:
        return (
            bool(self._queue) or self.num_active > 0
            or bool(self._shed_results)
        )

    @property
    def draining(self) -> bool:
        """True once `drain()` was called: admission is closed."""
        return self._draining

    @property
    def tick_count(self) -> int:
        """Engine ticks so far — the `FaultPlan` tick domain."""
        return self._tick

    @property
    def completions(self) -> List[Dict[str, float]]:
        """Per-request completion records, one dict per finished
        request in finish order: ``request_id``, ``finish_reason``,
        ``prompt_tokens``, ``new_tokens``, ``chunks`` (mixed ticks
        that carried this prompt; 1 on the whole-prompt path),
        ``queue_wait_ms`` (enqueue → slot lease), ``ttft_ms``
        (enqueue → first token — the SAME values whose percentiles
        ``stats()`` reports), ``tpot_ms`` (mean inter-token time after
        the first), ``e2e_ms``. Jsonl-ready: route through
        `monitor.JsonlWriter.emit` (``bench.py serve --trace`` and
        ``examples/generate_gpt.py --trace`` do). Cleared by
        `reset_stats`; retention is capped at ``stats_retention``
        records (oldest drop) — the registry counters/histograms keep
        the full-traffic accounting in constant memory."""
        return list(self._completions)

    # -- telemetry recording (host-side only; one registry `enabled`
    # -- check per sample, the NULL_TRACER discipline) ----------------

    def _record_queue_wait(self, seconds: float) -> None:
        self._queue_waits.append(seconds)
        if self.registry.enabled:
            self._h_queue_wait.observe(1e3 * seconds)

    def _tenant_series(self, tenant: Optional[str]) -> str:
        """Metric label for a tenant, honoring ``max_label_sets``: the
        first sighting tries to create the tenant's series; once the
        registry cap trips, that tenant maps to the pre-created
        ``other`` overflow label forever. The serving hot path never
        raises `CardinalityError` — a tenant beyond the cap still has
        every token and TTFT accounted, just under ``other``."""
        if tenant is None:
            tenant = "base"
        if tenant in self._tenant_label_ok:
            return tenant
        if tenant in self._tenant_overflowed:
            return "other"
        from rocm_apex_tpu.monitor.telemetry import CardinalityError

        try:
            # the token family first: two series per tenant, so it
            # trips the cap before the single-series TTFT family
            self._c_tokens.labels(phase="prompt", tenant=tenant)
            self._c_tokens.labels(phase="generated", tenant=tenant)
            self._h_ttft.labels(tenant=tenant)
        except CardinalityError:
            self._tenant_overflowed.add(tenant)
            return "other"
        self._tenant_label_ok.add(tenant)
        return tenant

    def _record_ttft(
        self, seconds: float, tenant: Optional[str] = None
    ) -> None:
        self._ttfts.append(seconds)
        if self.registry.enabled:
            if self._per_tenant:
                self._h_ttft.observe(
                    1e3 * seconds, tenant=self._tenant_series(tenant)
                )
            else:
                self._h_ttft.observe(1e3 * seconds)

    def _record_completion(self, rec: Dict[str, float]) -> None:
        self._completions.append(rec)
        tenant = rec.get("tenant")
        if self.adapter_pool is not None:
            # host-side per-tenant accounting keyed by the TRUE tenant
            # name (never collapsed to "other"): the chaos isolation
            # identity sums these against the global counters
            tc = self._tenant_counts.setdefault(
                tenant or "base",
                {"completed": 0, "prompt_tokens": 0,
                 "generated_tokens": 0},
            )
            tc["completed"] += 1
            tc["prompt_tokens"] += int(rec["prompt_tokens"])
            tc["generated_tokens"] += int(rec["new_tokens"])
        if self.registry.enabled:
            self._c_completions.inc(
                finish_reason=rec["finish_reason"]
            )
            if self._per_tenant:
                label = self._tenant_series(tenant)
                self._c_tokens.inc(
                    rec["prompt_tokens"], phase="prompt", tenant=label
                )
                self._c_tokens.inc(
                    rec["new_tokens"], phase="generated", tenant=label
                )
            else:
                self._c_tokens.inc(
                    rec["prompt_tokens"], phase="prompt"
                )
                self._c_tokens.inc(
                    rec["new_tokens"], phase="generated"
                )
            self._h_e2e.observe(rec["e2e_ms"])
            if rec["new_tokens"] > 1:
                self._h_tpot.observe(rec["tpot_ms"])

    def tenant_stats(self) -> Dict[str, Dict[str, int]]:
        """Host-side per-tenant completion accounting (true tenant
        names — unlike the labeled metric families, never collapsed
        into ``other``): tenant -> {completed, prompt_tokens,
        generated_tokens}. Empty on engines without an adapter pool.
        The per-tenant sums partition the global counters: summing
        ``completed`` across tenants equals the completion-record
        count, and likewise for both token phases."""
        return {t: dict(c) for t, c in self._tenant_counts.items()}

    def stats(self) -> Dict[str, float]:
        """Serving telemetry as one flat name→scalar dict — the
        `monitor.MetricsLogger.log_step` input format (route the
        monotonic counters through its ``last_value`` set).

        Gauges: ``queue_depth``, ``slots_active``, ``slot_occupancy``.
        Counters: ``admitted``, ``evicted``, ``prompt_tokens``,
        ``generated_tokens``, ``decode_steps``, ``mixed_steps``.
        Derived: mean latency per prefill-carrying tick
        (``prefill_ms_avg`` — a whole-prompt admit in legacy mode, a
        mixed chunk+decode tick in chunked mode), mean decode-only
        tick latency, and tokens/sec over each phase's accumulated
        wall time. Per-request distributions: ``queue_wait_ms_p50/95``
        (enqueue → slot lease) and ``ttft_ms_p50/95`` (enqueue →
        first token) — the tails that surface head-of-line blocking,
        which the averages above hide.

        Stats memory is O(1): raw per-request samples are retained up
        to ``stats_retention`` (default 4096, oldest drop) and the
        percentiles are EXACT over them; once traffic exceeds the cap,
        percentiles switch to the engine registry's constant-memory
        log-bucket histograms (``serve_queue_wait_ms`` /
        ``serve_ttft_ms``), whose quantile estimates carry the
        documented relative error bound
        ``monitor.telemetry.Histogram.error_bound`` (~26% hard bound
        at 20 buckets/decade; typically <2% interpolated — see
        docs/observability.md "Telemetry & SLOs"). With a disabled
        registry (``monitor.NULL_REGISTRY``) the capped rings are the
        only source and percentiles describe the newest
        ``stats_retention`` requests.

        Paged-cache occupancy (zeros on the contiguous engine):
        ``pages_total``/``pages_used``/``page_occupancy`` (pages
        holding a live mapping — THE memory-win witness: it scales
        with live tokens, not slots × capacity), ``shared_page_ratio``
        (mapped table entries pointing at ref>1 pages),
        ``cow_forks``, ``prefix_hits``/``prefix_hit_tokens`` (admits
        that skipped re-prefilling a stored prefix, and the tokens
        skipped), ``page_stalls`` (tokens deferred by pool
        backpressure), ``preemptions`` (slots whose pages were
        reclaimed under pool deadlock — the request recomputes via
        chunked prefill on re-admission), ``page_ships`` /
        ``page_ship_fallbacks`` (migrations that landed their KV
        payload directly vs fell back to token replay).

        Speculative decoding (zeros at ``spec_k == 0``):
        ``tokens_drafted``/``tokens_accepted`` (drafter proposals
        scheduled into the chunk vs. proposals the verify step
        emitted), ``acceptance_rate`` (their ratio), ``rollbacks``
        (spans with at least one rejected draft). Every drafted token
        is one or the other: ``drafted - accepted`` is exactly the
        rolled-back row count.

        The tick's own account since `reset_stats` (they also ride on
        every ``engine.tick`` span: docs/observability.md):
        ``cum_ticks_mixed``/``cum_ticks_decode`` and
        ``cum_ms_mixed``/``cum_ms_decode`` (ticks and their wall time
        by the program they ran), ``cum_gap_ms`` (the serving loop's
        time between two ticks while the engine had work),
        ``cum_gc_n``/``cum_gc_ms``/``gc_max_ms`` (the collector's
        pauses), ``slow_ms``/``slow_tick`` (the slowest tick, wall plus
        gap; `slowest_tick` names its program and phases)."""
        prefill_ticks = (
            self._mixed_steps if self.chunked else self._admitted
        )
        prefill_ms = (
            1e3 * self._prefill_seconds / prefill_ticks
            if prefill_ticks else 0.0
        )
        decode_ms = (
            1e3 * self._decode_seconds / self._decode_steps
            if self._decode_steps else 0.0
        )
        decode_generated = self._generated_tokens - self._admitted

        def _pct_ms(ring, hist, q):
            # exact percentile while the capped ring still holds every
            # sample; bounded-error histogram quantile once it wrapped
            if self.registry.enabled and hist.count() > len(ring):
                return float(hist.percentile(q))
            if not ring:
                return 0.0
            return 1e3 * float(np.percentile(np.asarray(ring), q))

        # page-occupancy counters (zeros when not paged, so one
        # MetricsLogger schema serves both engines)
        pages_total = float(self.pages_total)
        pages_used = float(self.pages_used)
        shared_ratio = 0.0
        if self.paged:
            sentinel = self.cache.num_pages
            mapped = self._table[self._table != sentinel]
            if mapped.size:
                shared = sum(
                    1 for p in mapped
                    if self._allocator.refcount(int(p)) > 1
                )
                shared_ratio = shared / mapped.size
        paged_stats = {
            "pages_total": pages_total,
            "pages_used": pages_used,
            "page_occupancy": (
                pages_used / pages_total if pages_total else 0.0
            ),
            "shared_page_ratio": shared_ratio,
            "cow_forks": float(self._cow_forks),
            "prefix_hits": float(self._prefix_hits),
            "prefix_hit_tokens": float(self._prefix_hit_tokens),
            "page_stalls": float(self._page_stalls),
            "preemptions": float(self._preemptions),
            "page_ships": float(self._page_ships),
            "page_ship_fallbacks": float(self._page_ship_fallbacks),
        }
        # multi-LoRA pool economics (zeros without an adapter pool):
        # uploads/evictions/revivals witness the park-reclaim cycle,
        # adapter_stalls counts admission skips under residency
        # backpressure, tier_* the SLO-driven admission actions
        if self.adapter_pool is not None:
            snap = self.adapter_pool.snapshot()
            adapter_stats = {
                "adapters_registered": float(snap["registered"]),
                "adapters_resident": float(snap["resident"]),
                "adapter_uploads": float(snap["uploads"]),
                "adapter_evictions": float(snap["evictions"]),
                "adapter_revivals": float(snap["revivals"]),
            }
        else:
            adapter_stats = {
                "adapters_registered": 0.0,
                "adapters_resident": 0.0,
                "adapter_uploads": 0.0,
                "adapter_evictions": 0.0,
                "adapter_revivals": 0.0,
            }
        adapter_stats.update(
            adapter_stalls=float(self._adapter_stalls),
            tier_preemptions=float(self._tier_preemptions),
            tier_sheds=float(self._tier_sheds),
        )
        return {
            **paged_stats,
            **adapter_stats,
            # robustness counters (docs/inference.md "Failure
            # semantics"): every lifecycle transition is accounted —
            # completed + shed + quarantined + cancelled + expired
            # equals submitted, never a silent drop
            "cancelled": float(self._cancelled),
            "deadline_exceeded": float(self._deadline_exceeded),
            "quarantined": float(self._quarantined),
            "step_retries": float(self._step_retries),
            "shed": float(self._shed),
            "watchdog_fires": float(self._watchdog_fires),
            "evacuated": float(self._evacuated),
            "tokens_drafted": float(self._tokens_drafted),
            "tokens_accepted": float(self._tokens_accepted),
            "acceptance_rate": (
                self._tokens_accepted / self._tokens_drafted
                if self._tokens_drafted else 0.0
            ),
            "rollbacks": float(self._rollbacks),
            "queue_depth": float(self.num_queued),
            "slots_active": float(self.num_active),
            "slot_occupancy": self.num_active / self.num_slots,
            "admitted": float(self._admitted),
            "evicted": float(self._evicted),
            "prompt_tokens": float(self._prompt_tokens),
            "generated_tokens": float(self._generated_tokens),
            "decode_steps": float(self._decode_steps),
            "mixed_steps": float(self._mixed_steps),
            "prefill_ms_avg": prefill_ms,
            "decode_ms_avg": decode_ms,
            "prefill_tokens_per_sec": (
                self._prompt_tokens / self._prefill_seconds
                if self._prefill_seconds > 0 else 0.0
            ),
            "decode_tokens_per_sec": (
                decode_generated / self._decode_seconds
                if self._decode_seconds > 0 else 0.0
            ),
            "queue_wait_ms_p50": _pct_ms(
                self._queue_waits, self._h_queue_wait, 50
            ),
            "queue_wait_ms_p95": _pct_ms(
                self._queue_waits, self._h_queue_wait, 95
            ),
            "ttft_ms_p50": _pct_ms(self._ttfts, self._h_ttft, 50),
            "ttft_ms_p95": _pct_ms(self._ttfts, self._h_ttft, 95),
            **self.account_totals(),
            "slow_ms": self._account["slow_ms"],
            "slow_tick": float(self._account["slow_tick"]),
        }

    def account_totals(self) -> Dict[str, float]:
        """The tick account's sums since `reset_stats`, under the names
        they ride on ``engine.tick`` (`_tick_account`). A dozen numbers
        the tick keeps anyway: safe to read from another thread at any
        rate (`start_exporter` shows them on ``/varz``), where `stats()`
        walks every request and every mapped page."""
        return {
            k: v for k, v in self._account.items()
            if not k.startswith("slow_")}

    def slowest_tick(self) -> Dict[str, Any]:
        """The slowest tick since `reset_stats`, wall plus the gap
        before it: ``slow_ms``, ``slow_tick`` (its `tick_count`),
        ``slow_program`` and ``slow_phases`` (that tick's gap and phase
        durations, names and microseconds joined with spaces: put a
        stall down to admit, pack, table_push, dispatch, fetch, commit,
        the rest of the tick or the loop around it)."""
        return {
            k: v for k, v in self._account.items() if k.startswith("slow_")}

    def _zero_counters(self) -> None:
        """The monotonic counters and wall-time sums `stats()` reports,
        named in ONE place: the constructor and `reset_stats` both
        start from here. The latencies include the result fetch, which
        waits for the device (the Timers rule), so they are true
        end-to-end numbers, not dispatch times."""
        self._admitted = self._evicted = 0
        self._prompt_tokens = self._generated_tokens = 0
        self._prefill_seconds = self._decode_seconds = 0.0
        self._decode_steps = self._mixed_steps = 0
        # paging
        self._cow_forks = self._prefix_hits = self._prefix_hit_tokens = 0
        self._page_stalls = self._preemptions = 0
        self._page_ships = self._page_ship_fallbacks = 0
        # speculation: every drafted token ends up either accepted
        # (emitted) or rolled back
        self._tokens_drafted = self._tokens_accepted = self._rollbacks = 0
        # robustness
        self._cancelled = self._deadline_exceeded = self._quarantined = 0
        self._step_retries = self._shed = self._watchdog_fires = 0
        self._evacuated = 0
        # adapters
        self._adapter_stalls = 0
        self._tier_preemptions = self._tier_sheds = 0
        # the tick's own account (`_tick_account`), under the names it
        # rides on ``engine.tick``: ticks and their wall time by
        # `program`, the serving loop's time between two ticks, the
        # collector's pauses, and the slowest tick (wall + gap) with its
        # phases. ONE dict, updated in place and handed to the span as
        # it stands, so a tick builds nothing to report it.
        self._account: Dict[str, Any] = {
            "cum_ticks_mixed": 0, "cum_ticks_decode": 0,
            "cum_ms_mixed": 0.0, "cum_ms_decode": 0.0,
            "cum_gap_ms": 0.0,
            "cum_gc_ms": 0.0, "cum_gc_n": 0, "gc_max_ms": 0.0,
            "slow_ms": 0.0, "slow_tick": -1, "slow_program": "none",
            "slow_phases": "",
        }
        # the collector's totals at the last tick's reading
        self._gc_seen = gc_pauses()[:2]
        # when the last `step()` returned, where it left work behind
        self._returned_at: Optional[float] = None

    def reset_stats(self) -> None:
        """Zero the telemetry counters and per-request distributions.
        Compiled programs, trace counters, and cache state are
        untouched — benchmarks warm the compiles up on the same engine,
        then measure a clean window."""
        self._zero_counters()
        self._queue_waits.clear()
        self._ttfts.clear()
        self._completions.clear()
        # zero the ENGINE's registry series in place (a shared
        # registry's other families are untouched)
        if self.registry.enabled:
            for metric in (
                self._h_queue_wait, self._h_ttft, self._h_tpot,
                self._h_e2e, self._c_completions, self._c_tokens,
                self._g_queue_depth, self._g_slots_active,
            ):
                metric.clear()
            if self._per_tenant:
                # clear() dropped every tenant series, including the
                # pre-created overflow — rebuild the overflow series
                # and forget the sighting sets so re-creation replays
                # the same cap-honoring first-sighting protocol
                self._c_tokens.labels(phase="prompt", tenant="other")
                self._c_tokens.labels(phase="generated", tenant="other")
                self._h_ttft.labels(tenant="other")
                self._tenant_label_ok = {"other"}
                self._tenant_overflowed = set()
        self._tenant_counts.clear()
        # the watchdog's progress snapshot tracks counters just zeroed
        self._progress_mark = (0, 0, 0)
        self._last_progress = time.perf_counter()
        if self.retrace_sentinel is not None:
            # reset_stats() IS the bench contract's warmed-up-now
            # marker (warm generate(), reset, measure a clean window)
            # — arm the sentinel here: compiles from now on are the
            # retraces the one-compiled-trace invariant forbids
            self.retrace_sentinel.arm()

    def cache_bytes(self) -> int:
        """Device bytes held by the KV cache (pools/buffers + scales +
        tables + lengths — every leaf of the cache pytree). The paged
        A/B's memory line: contiguous = slots × capacity rows up
        front; paged = the page pool you sized (int8 ~halves it)."""
        return sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(self.cache)
        )

    def add_request(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        request_id: Optional[int] = None,
        *,
        timeout: Optional[float] = None,
        queue_ttl: Optional[float] = None,
        adapter_id: int = 0,
        tenant: Optional[str] = None,
        trace_id: Optional[str] = None,
    ) -> int:
        """Queue a prompt; returns the request id. The request is
        admitted into a cache slot by a later `step` when a slot is
        free; its prompt then streams through the prefill budget. The
        only length bound is the physical cache: a prompt must fit in
        ``capacity`` rows. (The legacy whole-prompt path additionally
        needs the prompt to fit its ``max_prompt_len`` pad width.)

        ``timeout`` (seconds) is the request's END-TO-END deadline —
        queue wait included — and ``queue_ttl`` bounds the queue wait
        alone; both are checked at tick boundaries and expire the
        request with ``finish_reason='deadline'`` (in-flight work is
        torn down through the ordinary eviction path, so pages and
        slots are released correctly).

        With ``max_queue`` set, a request arriving at a full queue is
        SHED, never silently dropped: it still gets an id, a
        ``queue_full`` result is delivered by the next `step()` (so
        `generate` callers see it), and the ``shed`` counter ticks.
        After `drain()` admission is closed and this raises.

        ``adapter_id`` selects a LoRA adapter registered in the
        engine's `AdapterPool` (0 = base model, always valid); the
        request's ``tenant`` defaults to the adapter's registered
        tenant and labels its telemetry. On a full queue with an
        adapter pool, shedding is TIER-AWARE: an arrival outranking
        the lowest-tier queued request sheds that victim (newest
        within the tier) instead of itself — paying tenants keep
        their queue positions under overload (``tier_sheds``)."""
        if self._draining:
            raise RuntimeError(
                "engine is draining: admission is closed "
                "(drain() was called)"
            )
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if len(prompt) > self.capacity:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the cache "
                f"capacity {self.capacity} (rows per slot)"
            )
        if not self.chunked and len(prompt) > self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the whole-prompt "
                f"pad width max_prompt_len={self.max_prompt_len}; the "
                f"default chunked engine (prefill_token_budget) "
                f"streams prompts of any length"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if timeout is not None and timeout <= 0:
            raise ValueError(f"timeout must be > 0 s, got {timeout}")
        if queue_ttl is not None and queue_ttl <= 0:
            raise ValueError(f"queue_ttl must be > 0 s, got {queue_ttl}")
        adapter_id = int(adapter_id)
        if adapter_id != 0:
            if self.adapter_pool is None:
                raise ValueError(
                    f"adapter_id={adapter_id} but the engine has no "
                    f"adapter_pool"
                )
            if not self.adapter_pool.known(adapter_id):
                raise KeyError(f"unknown adapter_id {adapter_id}")
        if tenant is None and self.adapter_pool is not None:
            tenant = self.adapter_pool.tenant_of(adapter_id)
        if request_id is None:
            request_id = self._next_id
        self._next_id = max(self._next_id, request_id) + 1
        # fleet-causal context: mint at first admission, carry a
        # caller-supplied id verbatim (the router mints once per
        # admitted request and every hop re-presents the same id)
        if trace_id is None:
            trace_id = mint_trace_id()
        now = time.perf_counter()
        if (
            self.max_queue is not None
            and len(self._queue) >= self.max_queue
        ):
            # bounded admission: shed-NEWEST (the queued requests keep
            # their positions — fairness under overload), accounted in
            # the completion records and delivered as a queue_full
            # result through the next step(). With an adapter pool the
            # shed is TIER-AWARE: when the arrival outranks the
            # lowest-tier queued request, THAT victim (newest within
            # its tier) is shed instead and the arrival takes its
            # place at the tail.
            victim_req, victim_idx = None, None
            if self.adapter_pool is not None:
                inc_tier = self.adapter_pool.tier_of(adapter_id)
                min_tier, min_idx = inc_tier, None
                for i, q in enumerate(self._queue):
                    t = self.adapter_pool.tier_of(q.adapter_id)
                    if t <= min_tier and t < inc_tier:
                        min_tier, min_idx = t, i
                if min_idx is not None:
                    victim_idx = min_idx
                    victim_req = self._queue[min_idx]
            if victim_req is not None:
                del self._queue[victim_idx]
                self._tier_sheds += 1
                shed_id = victim_req.request_id
                shed_prompt = victim_req.prompt
                shed_tenant = victim_req.tenant
                shed_trace = victim_req.trace_id
            else:
                shed_id, shed_prompt, shed_tenant, shed_trace = (
                    request_id, prompt, tenant, trace_id
                )
            self._shed += 1
            self._record_completion({
                "request_id": shed_id,
                "finish_reason": "queue_full",
                "prompt_tokens": len(shed_prompt),
                "new_tokens": 0,
                "chunks": 0,
                "queue_wait_ms": 0.0,
                "ttft_ms": 0.0,
                "tpot_ms": 0.0,
                "e2e_ms": 0.0,
                "tenant": shed_tenant,
            })
            self._shed_results.append(GenerationResult(
                request_id=shed_id, prompt=list(shed_prompt),
                tokens=[], finish_reason="queue_full",
            ))
            if self.tracer.enabled:
                self.tracer.instant(
                    "shed", ts=now, track=f"req{shed_id}",
                    queue_depth=len(self._queue),
                    request_id=shed_id, trace_id=shed_trace,
                )
            if victim_req is None:
                return request_id
        # the arrival, on the clock of the tick that will lease it a
        # slot (`engine.admit` carries the same id)
        with phase(
            "engine.enqueue", request_id=request_id,
            prompt_tokens=len(prompt),
        ):
            req = Request(
                request_id, prompt, max_new_tokens,
                enqueued_at=now,
                deadline=(
                    (now + timeout) if timeout is not None else None
                ),
                queue_deadline=(
                    (now + queue_ttl) if queue_ttl is not None else None
                ),
                adapter_id=adapter_id,
                tenant=tenant,
                trace_id=trace_id,
            )
            self._queue.append(req)
        if self.tracer.enabled:
            self.tracer.instant(
                "enqueue", ts=req.enqueued_at,
                track=f"req{request_id}",
                prompt_tokens=len(prompt), max_new_tokens=max_new_tokens,
                request_id=request_id, trace_id=trace_id,
            )
        return request_id

    def step(self) -> List[GenerationResult]:
        """One engine tick. Chunked mode: admit queued requests into
        free slots (bookkeeping only), pack up to the token budget of
        pending prompt tokens, and run ONE compiled mixed
        chunk+decode step (decode-only fast path when nothing is
        prefilling). Legacy mode: one compiled whole-prompt prefill
        per admit, then the decode step. Returns the requests that
        finished this tick (their slots are already free for the
        next) — including any shed (``queue_full``) and expired
        (``deadline``) requests, so every submitted request yields
        exactly one result."""
        entered = time.perf_counter()
        gap = 0.0 if self._returned_at is None else entered - self._returned_at
        self._laps = []
        number = self._tick
        with self.tracer.phase(
            "engine.tick", track="engine", tick=number
        ) as tick:
            # read before the tick maps or frees a page
            pages_used = self.pages_used
            window = {}
            if self._window_allocator is not None:
                window = dict(
                    window_pages_used=self._window_allocator.pages_used,
                    window_pages_total=self._window_allocator.num_pages)
                self._window_pages_freed = 0
            out, leased = self._admit_phase()
            if self.chunked:
                finished, counts = self._step_chunked()
            else:
                finished, counts = self._step_whole()
            out.extend(finished)
            # the tick's counts, all from values it computed anyway
            # (docs/observability.md says what reads each); the legacy
            # mode admits in `_step_whole` and counts its own
            counts.setdefault("admitted", leased)
            if window:  # pages that went back behind windows in this tick
                window["window_pages_freed"] = self._window_pages_freed
            gc_n, gc_us = self._tick_account(
                number, counts.get("program", "none"), entered, gap)
            # handed over only while somebody keeps them (a profiler
            # capture is live, or the tracer's ring records the span):
            # thirty keywords cost a tick more than the account itself
            if tick.is_enabled():
                tick.set_metadata(
                    **counts,
                    finished=len(out),
                    queue_depth=len(self._queue),
                    slots=self.num_slots,
                    budget=self.prefill_token_budget or 0,
                    pages_used=pages_used,
                    pages_total=self.pages_total,
                    **window,
                    # the tick's account (docs/observability.md)
                    gap_us=int(1e6 * gap),
                    gc_us=gc_us,
                    gc_n=gc_n,
                    cum_prefill_tokens=self._prompt_tokens,
                    cum_generated=self._generated_tokens,
                    **self._account,
                )
        # the loop's gap is counted from here, where the loop has a
        # reason to come straight back
        self._returned_at = time.perf_counter() if self.has_work() else None
        return out

    def _tick_account(
        self, number: int, program: str, entered: float, gap: float,
    ) -> Tuple[int, int]:
        """Close the tick's books in `_account`, which rides on
        ``engine.tick`` as it stands beside the tick's counts: the sums
        since `reset_stats` by the tick's ``program`` (`account_totals`)
        and the slowest tick so far (`slowest_tick`). ``gap`` is from
        the previous `step()`'s return to this one's entry, 0 where
        that one left no work behind. Returns this tick's ``gc_n`` and
        ``gc_us``: the collections since the previous tick's reading.
        The last tick a capture holds so carries the whole run's totals
        to a reader that has nothing but the capture.

        The tick's own clock is read where each phase ends
        (``self._laps``): what has passed since the last lap (or the
        tick's entry) is that phase's, so the laps tile the tick. They
        are summed by name only for a tick that becomes the slowest."""
        now = time.perf_counter()
        wall = now - entered
        account = self._account
        count, seconds, longest = gc_pauses()
        gc_n, gc_us = count - self._gc_seen[0], 0
        if gc_n:
            gc_s = seconds - self._gc_seen[1]
            self._gc_seen = count, seconds
            gc_us = int(1e6 * gc_s)
            account["cum_gc_n"] += gc_n
            account["cum_gc_ms"] += 1e3 * gc_s
            # one collection's pause; where several fell between two
            # ticks, their sum, capped by the process's longest
            account["gc_max_ms"] = max(
                account["gc_max_ms"], 1e3 * min(gc_s, longest))
        if program in _ACCOUNT_KEYS:
            ticks, ms = _ACCOUNT_KEYS[program]
            account[ticks] += 1
            account[ms] += 1e3 * wall
        if gap:
            account["cum_gap_ms"] += 1e3 * gap
        if 1e3 * (wall + gap) > account["slow_ms"]:
            phases, at = dict.fromkeys(TICK_PHASES, 0.0), entered
            for name, t in (*self._laps, ("rest", now)):
                phases[name] += t - at
                at = t
            account.update(
                slow_ms=1e3 * (wall + gap),
                slow_tick=number,
                slow_program=program,
                slow_phases=f"gap {int(1e6 * gap)} " + " ".join(
                    f"{name} {int(1e6 * s)}" for name, s in phases.items()),
            )
        return gc_n, gc_us

    def _admit_phase(self) -> Tuple[List[GenerationResult], int]:
        """The tick's ``engine.admit`` phase: the watchdog, the shed
        and expired requests' results, and the lease of free slots to
        queued requests. Returns those results and how many requests
        were leased a slot; their ids ride on the span, the ids
        ``engine.enqueue`` carries."""
        with self.tracer.phase("engine.admit", track="engine") as admit:
            now = time.perf_counter()
            self._check_watchdog(now)
            out: List[GenerationResult] = []
            if self._shed_results:
                out.extend(self._shed_results)
                self._shed_results = []
            out.extend(self._expire_deadlines(now))
            leased = self._admit_free_slots(now) if self.chunked else []
            if leased:
                admit.set_metadata(
                    request_ids=" ".join(str(i) for i in leased)
                )
        self._laps.append(("admit", time.perf_counter()))
        return out, len(leased)

    def _close_tick(self) -> None:
        """The end of every tick, inside its ``engine.commit`` phase:
        tick count, progress mark, gauges, the sensor plane's sample
        and the retrace sentinel's check."""
        self._tick += 1
        self._note_progress()
        if self.registry.enabled:
            # live occupancy gauges for the async /metrics scrape
            # (host-side sets; the compiled programs are untouched)
            self._g_queue_depth.set(self.num_queued)
            self._g_slots_active.set(self.num_active)
        if self.timeseries is not None:
            self.timeseries.tick()
        if self.retrace_sentinel is not None:
            # tick-boundary enforcement — under policy="raise" a
            # post-warmup compile fails HERE, never inside the jax
            # callback mid-compile
            self.retrace_sentinel.check()

    def cancel(self, request_id: int) -> Optional[GenerationResult]:
        """Cancel one request, wherever it is in its lifecycle, and
        return its partial result (``finish_reason='cancelled'``, the
        tokens generated so far) — or None if the id is unknown or
        already finished. In-flight work tears down through the
        ordinary eviction path, so the slot frees and its pages
        release with the PR-7 allocator invariants intact (CoW
        refcounts drop, store-registered prefix pages park). A
        preempted request's carried tokens are returned too. Host
        bookkeeping only — the compiled programs never see a cancel
        (the next tick simply runs without the slot)."""
        now = time.perf_counter()
        for req in self._queue:
            if req.request_id == request_id:
                self._queue.remove(req)
                self._cancelled += 1
                return self._finalize_queued(req, "cancelled", now)
        for slot, st in enumerate(self._slots):
            if st is not None and st.req.request_id == request_id:
                self._cancelled += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "cancel", ts=now, track=f"req{request_id}",
                        slot=slot, generated=len(st.generated),
                        request_id=request_id,
                        trace_id=st.req.trace_id,
                    )
                return self._evict(slot, st, "cancelled")
        return None

    def drain(self, shed_queue: bool = False) -> List[GenerationResult]:
        """Graceful shutdown: close admission (`add_request` raises
        from here on), run the engine until all accepted work
        finishes, and return those results. ``shed_queue=True``
        additionally cancels the still-QUEUED requests up front
        (finish_reason ``cancelled``) so only the in-flight slots run
        to completion — the SIGTERM fast path. Stats counters and
        tracer events are all emitted by the time this returns; the
        caller flushes them (``stats()`` / ``export_chrome_trace``).
        Bounded by the stall watchdog like any other stepping.

        Idempotent: a second drain on an already-draining (or already
        drained) engine just runs any remaining work dry and returns
        those results — no error, no duplicate drain markers — so a
        supervisor and a signal handler can both call it. The return
        path is `reopen()`."""
        already = self._draining
        self._draining = True
        now = time.perf_counter()
        if self.tracer.enabled and not already:
            self.tracer.instant(
                "drain_begin", ts=now, track="engine",
                queued=self.num_queued, active=self.num_active,
            )
        out: List[GenerationResult] = []
        if shed_queue:
            while self._queue:
                req = self._queue.popleft()
                self._cancelled += 1
                out.append(self._finalize_queued(req, "cancelled", now))
        while self.has_work():
            out.extend(self.step())
        if self.tracer.enabled and not already:
            self.tracer.instant(
                "drain_end", track="engine", finished=len(out),
            )
        return out

    def reopen(self) -> None:
        """Rejoin after `drain()` or a quarantine: reset the lifecycle
        latches (drain flag, watchdog-fire count, progress anchors) so
        admission reopens on the SAME engine — compiled programs,
        cache, and prefix store survive, nothing retraces. The state
        must be provably clean or this raises `RuntimeError`: no
        leased slot, empty queue, no preempted carryover, no
        undelivered shed results, and (paged) an all-sentinel block
        table with the allocator's free-list/refcount invariants
        intact. Callers that want the clean state first use
        `evacuate()` / `drain()`; parked prefix pages are FINE — they
        are the reusable prefix cache, not a leak."""
        dirty = []
        if any(st is not None for st in self._slots):
            dirty.append(f"{self.num_active} leased slot(s)")
        if self._queue:
            dirty.append(f"{len(self._queue)} queued request(s)")
        if self._preempted:
            dirty.append(
                f"{len(self._preempted)} preempted carryover(s)"
            )
        if self._shed_results:
            dirty.append(
                f"{len(self._shed_results)} undelivered shed result(s)"
            )
        if self.paged:
            sentinel = self.cache.num_pages
            mapped = int((self._table != sentinel).sum())
            if self._window_allocator is not None:
                mapped += int(
                    (self._window_table != self.cache.window_pages).sum())
            if mapped:
                dirty.append(f"{mapped} mapped page-table entries")
        if dirty:
            raise RuntimeError(
                "reopen() on a dirty engine: " + ", ".join(dirty)
                + " — drain() or evacuate() first"
            )
        if self.paged:
            # the allocator's own invariants (free-list / refcounts /
            # parked set) must hold before we accept traffic again
            for allocator in self._allocators():
                allocator.assert_consistent()
        self._draining = False
        self._watchdog_fires = 0
        self._progress_mark = (
            self._prompt_tokens, self._generated_tokens, self._evicted,
        )
        self._last_progress = time.perf_counter()
        if self.tracer.enabled:
            self.tracer.instant("reopen", track="engine")

    def outstanding(self) -> List[Dict[str, Any]]:
        """Snapshot of every request this engine currently OWNS —
        in-flight slots (slot order), then the queue (queue order) —
        as migration records: ``request_id``, ``prompt``,
        ``max_new_tokens``, ``generated`` (tokens emitted so far),
        ``enqueued_at``/``deadline``/``queue_deadline`` (absolute
        perf_counter times), ``first_token_at``, ``chunks``. A
        prompt + its ``generated`` tokens IS the request's migration
        format (the vLLM recompute transition): feed a record to
        another engine's `resume_request` and greedy decode continues
        token-identically. Pure read — engine state is untouched."""
        recs: List[Dict[str, Any]] = []

        def _rec(req: Request, generated, first_at, chunks):
            recs.append({
                "request_id": req.request_id,
                "prompt": list(req.prompt),
                "max_new_tokens": req.max_new_tokens,
                "generated": list(generated),
                "enqueued_at": req.enqueued_at,
                "deadline": req.deadline,
                "queue_deadline": req.queue_deadline,
                "first_token_at": first_at,
                "chunks": chunks,
                "adapter_id": req.adapter_id,
                "tenant": req.tenant,
                "trace_id": req.trace_id,
            })

        for st in self._slots:
            if st is not None:
                _rec(st.req, st.generated, st.first_token_at, st.chunks)
        for req in self._queue:
            carried = self._preempted.get(req.request_id)
            if carried is not None:
                _rec(req, carried[0], carried[1], carried[2])
            else:
                _rec(req, [], 0.0, 0)
        return recs

    def evacuate(self, ship_pages: bool = False) -> List[Dict[str, Any]]:
        """Hand EVERY owned request off for migration: snapshot
        `outstanding()`, then release all slots and pages and empty
        the queue, leaving the engine provably clean for `reopen()`.
        The records are returned to the caller (the router), which
        re-owns their delivery — no completion is recorded here, so a
        migrated request still finishes exactly once, on whichever
        engine ultimately runs it. Store-registered prefix pages park
        (they remain a valid cross-request cache); private pages
        free. Host bookkeeping only — except with ``ship_pages=True``
        on a paged cache, where each slot-held record additionally
        carries its materialized KV page blocks (``rec["pages"]``, the
        `_export_slot_pages` payload): feed the whole record to another
        engine's `resume_request(pages=...)` and the destination skips
        the recompute prefill, token-identically."""
        self._refuse_page_shipping(ship_pages)
        recs = self.outstanding()
        by_id = {rec["request_id"]: rec for rec in recs}
        for slot in range(self.num_slots - 1, -1, -1):
            st = self._slots[slot]
            if st is None:
                continue
            if self.paged:
                if ship_pages:
                    payload = self._export_slot_pages(st, slot)
                    if payload is not None:
                        by_id[st.req.request_id]["pages"] = payload
                self._release_slot_pages(st, slot)
            self._release_adapter(st)
            self._slots[slot] = None
            if self.tracer.enabled:
                self.tracer.instant(
                    "evacuate", track=f"req{st.req.request_id}",
                    slot=slot, generated=len(st.generated),
                    request_id=st.req.request_id,
                    trace_id=st.req.trace_id,
                )
        if self.paged:
            self._push_table()
        self._queue.clear()
        self._preempted.clear()
        self._shipped.clear()
        self._evacuated += len(recs)
        return recs

    def _refuse_page_shipping(self, asked: bool) -> None:
        if asked and self._stateful:
            raise ValueError(
                f"{type(self.model).__name__} keeps a recurrent state per "
                f"slot: shipped pages would arrive without it (evacuate "
                f"and resume by tokens; the prefill recomputes the state)"
            )
        if asked and self._latent:
            raise ValueError(
                f"{type(self.model).__name__} keeps latent rows in pages: "
                f"the shipped payload is the K and V pools' and does not "
                f"carry them (evacuate and resume by tokens)"
            )
        if asked and self._windowed:
            raise ValueError(
                f"{type(self.model).__name__} frees the pages that its "
                f"window layers' rows have left: the shipped payload is a "
                f"slot's whole page list in the global group's pools and "
                f"carries no window group (evacuate and resume by tokens)"
            )

    def evacuate_request(
        self, request_id: int, ship_pages: bool = False,
    ) -> Optional[Dict[str, Any]]:
        """Hand off ONE owned request (the disaggregation handoff
        primitive: a prefill-class replica evacuates a request the
        moment its prompt is materialized and the router re-lands it on
        a decode-class replica). Same contract as `evacuate()` scoped
        to a single request: the returned record — with its KV pages
        attached when ``ship_pages`` and the request holds a slot — is
        the caller's to deliver; this engine forgets the request
        entirely. Returns None when the request is not owned here."""
        self._refuse_page_shipping(ship_pages)
        for slot in range(self.num_slots):
            st = self._slots[slot]
            if st is None or st.req.request_id != request_id:
                continue
            rec: Dict[str, Any] = {
                "request_id": st.req.request_id,
                "prompt": list(st.req.prompt),
                "max_new_tokens": st.req.max_new_tokens,
                "generated": list(st.generated),
                "enqueued_at": st.req.enqueued_at,
                "deadline": st.req.deadline,
                "queue_deadline": st.req.queue_deadline,
                "first_token_at": st.first_token_at,
                "chunks": st.chunks,
                "adapter_id": st.req.adapter_id,
                "tenant": st.req.tenant,
                "trace_id": st.req.trace_id,
            }
            if self.paged:
                if ship_pages:
                    payload = self._export_slot_pages(st, slot)
                    if payload is not None:
                        rec["pages"] = payload
                self._release_slot_pages(st, slot)
                self._push_table()
            self._release_adapter(st)
            self._slots[slot] = None
            self._evacuated += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "evacuate", track=f"req{request_id}",
                    slot=slot, generated=len(st.generated),
                    request_id=request_id,
                    trace_id=st.req.trace_id,
                )
            return rec
        for i, req in enumerate(self._queue):
            if req.request_id != request_id:
                continue
            carried = self._preempted.pop(request_id, None)
            generated, first_at, chunks = carried or ([], 0.0, 0)
            del self._queue[i]
            self._shipped.pop(request_id, None)
            self._evacuated += 1
            return {
                "request_id": req.request_id,
                "prompt": list(req.prompt),
                "max_new_tokens": req.max_new_tokens,
                "generated": list(generated),
                "enqueued_at": req.enqueued_at,
                "deadline": req.deadline,
                "queue_deadline": req.queue_deadline,
                "first_token_at": first_at,
                "chunks": chunks,
                "adapter_id": req.adapter_id,
                "tenant": req.tenant,
                "trace_id": req.trace_id,
            }
        return None

    def resume_request(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        request_id: int,
        *,
        generated: Sequence[int] = (),
        enqueued_at: Optional[float] = None,
        deadline: Optional[float] = None,
        queue_deadline: Optional[float] = None,
        first_token_at: float = 0.0,
        chunks: int = 0,
        pages: Optional[Dict[str, Any]] = None,
        adapter_id: int = 0,
        tenant: Optional[str] = None,
        trace_id: Optional[str] = None,
    ) -> int:
        """Admit a request MIGRATED from another engine, carrying the
        tokens it already emitted (an `outstanding()`/`evacuate()`
        record). Re-admission recomputes prompt + generated[:-1]
        through the ordinary chunked prefill — the PR-8 preemption
        carryover — so greedy decode continues bitwise-identically
        and no carried token is ever re-emitted. Deadlines are
        ABSOLUTE (same perf_counter domain): a migrated request keeps
        its original SLA clock. Unlike `add_request`, a full queue
        never sheds a resumed request — it was already admitted once;
        shedding it here would double-account it.

        ``pages`` (a record's ``rec["pages"]`` from
        ``evacuate(ship_pages=True)``) upgrades the resume to
        page-shipping: when the request leases a slot, the payload's
        KV blocks land directly in this engine's pool and the prefill
        cursor starts past them — only the final prefix token recomputes.
        The payload is best-effort: if it cannot be imported (geometry
        mismatch, pool pressure, or an injected ``page_ship`` fault)
        admission silently falls back to the token-replay path above,
        with identical greedy output."""
        self._refuse_page_shipping(pages is not None)
        if self._draining:
            raise RuntimeError(
                "engine is draining: admission is closed "
                "(drain() was called)"
            )
        prompt = [int(t) for t in prompt]
        generated = [int(t) for t in generated]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if len(prompt) > self.capacity:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the cache "
                f"capacity {self.capacity} (rows per slot)"
            )
        if generated and not self.chunked:
            raise ValueError(
                "resume with carried tokens needs the chunked engine "
                "(prefill_token_budget): the recompute prefix "
                "prompt + generated[:-1] streams through the budget"
            )
        if len(generated) >= max_new_tokens:
            raise ValueError(
                f"carried {len(generated)} tokens >= max_new_tokens="
                f"{max_new_tokens}: the request already finished"
            )
        adapter_id = int(adapter_id)
        if adapter_id != 0:
            if self.adapter_pool is None:
                raise ValueError(
                    f"adapter_id={adapter_id} but the engine has no "
                    f"adapter_pool"
                )
            if not self.adapter_pool.known(adapter_id):
                raise KeyError(f"unknown adapter_id {adapter_id}")
        if tenant is None and self.adapter_pool is not None:
            tenant = self.adapter_pool.tenant_of(adapter_id)
        now = time.perf_counter()
        self._next_id = max(self._next_id, request_id) + 1
        # carry the hop's trace context verbatim; mint only if this
        # request was never traced (a bare resume outside the router)
        if not trace_id:
            trace_id = mint_trace_id()
        req = Request(
            request_id, prompt, max_new_tokens,
            enqueued_at=enqueued_at if enqueued_at is not None else now,
            deadline=deadline,
            queue_deadline=queue_deadline,
            adapter_id=adapter_id,
            tenant=tenant,
            trace_id=trace_id,
        )
        if generated:
            self._preempted[request_id] = (
                list(generated), first_token_at or now, int(chunks),
            )
        if pages is not None and self.paged:
            self._shipped[request_id] = pages
        self._queue.append(req)
        if self.tracer.enabled:
            self.tracer.instant(
                "resume", ts=now, track=f"req{request_id}",
                carried=len(generated),
                request_id=request_id, trace_id=trace_id,
            )
        return request_id

    def prefix_match_tokens(self, prompt: Sequence[int]) -> int:
        """How many of ``prompt``'s tokens this engine's `PrefixStore`
        already holds materialized (0 without prefix sharing). Pure
        read — the router's prefix-affinity signal: route a prompt to
        the replica that can skip the most prefill."""
        if self._store is None:
            return 0
        return self._store.match([int(t) for t in prompt])[1]

    @property
    def pages_used(self) -> int:
        """Pages holding a live mapping (0 on the contiguous cache) —
        the memory-pressure term of least-loaded placement."""
        return sum(a.pages_used for a in self._allocators())

    @property
    def pages_total(self) -> int:
        """Pages of every group (0 on the contiguous cache)."""
        return sum(a.num_pages for a in self._allocators())

    def _allocators(self) -> List[PageAllocator]:
        """The page groups' allocators: the global group's, then the
        window group's where the model declares one."""
        return [
            a for a in (self._allocator, self._window_allocator)
            if a is not None]

    @property
    def progress_marker(self) -> Tuple[int, int, int]:
        """(prompt_tokens, generated_tokens, evicted) — the same
        signals the stall watchdog watches, for an EXTERNAL
        zero-progress detector (the router's stall probe)."""
        return (
            self._prompt_tokens, self._generated_tokens, self._evicted,
        )

    #: consecutive zero-progress ticks `generate()` tolerates before
    #: diagnosing a stall (a backstop when no wall-clock watchdog is
    #: configured; page-stall backpressure either recovers within a
    #: tick or two or raises the pool-deadlock diagnosis long before)
    _GENERATE_STALL_TICKS = 1000

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int,
    ) -> List[GenerationResult]:
        """Convenience batch API: queue every prompt, run the serving
        loop dry, return results in prompt order. The loop is BOUNDED:
        the engine's wall-clock watchdog (``watchdog_timeout``) fires
        through `step()`, and even without one a run of
        ``_GENERATE_STALL_TICKS`` consecutive ticks with no token
        progress and nothing finished raises a diagnostic RuntimeError
        naming the stuck slot(s) instead of spinning forever."""
        ids = [self.add_request(p, max_new_tokens) for p in prompts]
        done = {}
        stale = 0
        mark = (
            self._prompt_tokens, self._generated_tokens, self._evicted,
        )
        while self.has_work():
            results = self.step()
            for r in results:
                done[r.request_id] = r
            work = (
                self._prompt_tokens, self._generated_tokens,
                self._evicted,
            )
            if results or work != mark:
                stale, mark = 0, work
                continue
            stale += 1
            if stale >= self._GENERATE_STALL_TICKS:
                raise RuntimeError(
                    f"generate() stalled: {stale} consecutive ticks "
                    f"without token progress; {self._stall_diagnosis()}"
                    f" (set watchdog_timeout for a wall-clock bound)"
                )
        return [done[i] for i in ids]

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    # -- paged-cache host bookkeeping ----------------------------------

    def _page_registered(self, page: int) -> bool:
        return self._store is not None and self._store.is_registered(page)

    def _map_page(self, slot: int, idx: int, page: int) -> None:
        self._table[slot, idx] = page
        self._table_dirty = True

    def _push_table(self) -> None:
        """Sync the host page-table mirror to the device pytree (once
        per tick, only when the mapping changed)."""
        if self._table_dirty:
            self.cache = self.cache.replace(
                page_table=self._replicated(jnp.asarray(self._table)))
            self._table_dirty = False
        if self._window_allocator is not None and self._window_table_dirty:
            self.cache = self.cache.replace(
                window_table=jnp.asarray(self._window_table))
            self._window_table_dirty = False

    def _export_slot_pages(self, st: _Slot, slot: int):
        """Snapshot the slot's mapped KV pages as a migration payload —
        the pool IS the transfer format. One batched host fetch pulls
        the per-layer page blocks (and int8 scale rows) for the pages
        covering ``st.pos`` materialized rows; the payload plus the
        `outstanding()` record is everything a destination engine needs
        to resume without re-prefilling. Pools are head-FULL even at
        tp>1 (the cache shards a full-head pool over the mesh), so a
        payload exported at any tp imports at any other tp. Returns
        None when the slot holds no rows — the caller ships nothing and
        the request replays."""
        ps = self.cache.page_size
        rows = int(st.pos)
        if rows <= 0:
            return None
        sentinel = self.cache.num_pages
        n = -(-rows // ps)  # ceil: partial last page ships whole
        pages = [int(p) for p in self._table[slot, :n]]
        if any(p == sentinel for p in pages):
            return None
        idx = jnp.asarray(pages, jnp.int32)
        payload: Dict[str, Any] = {
            "rows": rows,
            "page_size": int(ps),
            "quantized": bool(self.cache.quantized),
            "dtype": str(self.cache.k[0].dtype),
            "k": [pool[idx] for pool in self.cache.k],
            "v": [pool[idx] for pool in self.cache.v],
        }
        if self.cache.quantized:
            payload["k_scale"] = [s[idx] for s in self.cache.k_scale]
            payload["v_scale"] = [s[idx] for s in self.cache.v_scale]
        return jax.device_get(payload)

    def _import_shipped_pages(self, st: _Slot, slot: int, payload) -> bool:
        """Land a shipped KV payload directly in this engine's pool:
        allocate destination pages, scatter the page blocks in, map the
        slot's table rows, and start the cursor past the shipped rows.
        The LAST prefix token is never trusted from the wire — it
        replays through the ordinary chunk path so the fused step
        re-derives the slot's device lengths and decode feed exactly as
        a replay-resume would (greedy output is identical either way;
        the rewritten row holds the same values it shipped with).

        Returns False — and counts a fallback — whenever the payload
        cannot be used verbatim: the ``page_ship`` fault site fires
        (transfer dropped mid-flight), the geometry disagrees
        (page_size/dtype/quantization/pool shape), or the local
        allocator is out of pages. The caller then simply admits the
        request on the token-replay path; nothing was mapped, so
        neither allocator can leak."""
        track = f"req{st.req.request_id}"
        if self.faults.enabled and self.faults.fire(
            "page_ship", tick=self._tick, slot=slot,
        ) is not None:
            # injected transfer loss: the payload never arrived —
            # fall back to replay, exactly like a real dropped ship
            self._page_ship_fallbacks += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "page_ship_dropped", track=track, slot=slot,
                )
            return False
        cache = self.cache
        ps = cache.page_size
        rows = int(payload.get("rows", 0))
        target = min(rows, len(st.prefix) - 1)
        if target <= 0:
            return False
        k_bufs = payload.get("k", ())
        v_bufs = payload.get("v", ())
        compatible = (
            int(payload.get("page_size", -1)) == ps
            and bool(payload.get("quantized")) == cache.quantized
            and payload.get("dtype") == str(cache.k[0].dtype)
            and len(k_bufs) == cache.num_layers
            and len(v_bufs) == cache.num_layers
            and all(
                tuple(b.shape[1:]) == tuple(cache.k[0].shape[1:])
                for b in list(k_bufs) + list(v_bufs)
            )
        )
        n = len(k_bufs[0]) if compatible else 0
        if not compatible or n < -(-rows // ps) or n > cache.pages_per_slot:
            self._page_ship_fallbacks += 1
            return False
        got = self._allocator.alloc(n)
        if got is None:
            # pool pressure at admission: replaying is strictly better
            # than holding the slot hostage waiting for pages
            self._page_ship_fallbacks += 1
            return False
        dst = jnp.asarray(got, jnp.int32)
        k = tuple(
            pool.at[dst].set(jnp.asarray(buf))
            for pool, buf in zip(cache.k, k_bufs)
        )
        v = tuple(
            pool.at[dst].set(jnp.asarray(buf))
            for pool, buf in zip(cache.v, v_bufs)
        )
        k_scale, v_scale = cache.k_scale, cache.v_scale
        if cache.quantized:
            k_scale = tuple(
                s.at[dst].set(jnp.asarray(buf))
                for s, buf in zip(cache.k_scale, payload["k_scale"])
            )
            v_scale = tuple(
                s.at[dst].set(jnp.asarray(buf))
                for s, buf in zip(cache.v_scale, payload["v_scale"])
            )
        self.cache = cache.replace(
            k=k, v=v, k_scale=k_scale, v_scale=v_scale,
        )
        if self._mesh is not None:
            # eager scatters may drop the head sharding; restore the
            # canonical layout so the donated step inputs stay put
            self.cache = jax.device_put(
                self.cache, self._cache_sharding()
            )
        for i, page in enumerate(got):
            self._map_page(slot, i, page)
        st.cursor = target
        st.pos = target
        self._page_ships += 1
        if self.tracer.enabled:
            self.tracer.instant(
                "page_ship_import", track=track, slot=slot,
                pages=n, rows=target,
            )
        return True

    def _ensure_writable(self, st: _Slot, slot: int, idx: int) -> bool:
        """Page index ``idx`` of ``slot`` is mapped and privately
        owned after this call — allocating a fresh page for an
        unmapped entry, or copy-on-write-forking a BORROWED
        (prefix-shared) page the slot is about to write into. Returns
        False when the pool cannot supply a page: the caller
        backpressures (the token simply is not scheduled this tick;
        nothing crashes, nothing clamps)."""
        if self.faults.enabled and self.faults.fire(
            "page_alloc", tick=self._tick, slot=slot, page_idx=idx,
        ) is not None:
            # injected allocator failure: indistinguishable from a
            # genuinely exhausted pool — the caller backpressures
            return False
        if self._window_allocator is not None and (
            self._window_table[slot, idx] == self.cache.window_pages
        ):
            # the window group's page of the same positions; a global
            # page mapped while this one is wanting stays the slot's
            got = self._window_allocator.alloc(1)
            if got is None:
                return False
            self._window_table[slot, idx] = got[0]
            self._window_table_dirty = True
        sentinel = self.cache.num_pages
        page = int(self._table[slot, idx])
        track = f"req{st.req.request_id}"
        if page == sentinel:
            got = self._allocator.alloc(1)
            if got is None:
                return False
            self._map_page(slot, idx, got[0])
            if self.tracer.enabled:
                self.tracer.instant(
                    "page_alloc", track=track,
                    page=got[0], page_idx=idx, slot=slot,
                )
            return True
        if idx in st.borrowed:
            got = self._allocator.alloc(1)
            if got is None:
                return False
            dst = got[0]
            # device copy first (one compiled program for every fork),
            # then remap: the sharers keep reading the source page —
            # their bytes are never touched
            self.cache = self.programs.fork(
                self.cache, jnp.int32(page), jnp.int32(dst)
            )
            self._allocator.decref(page, park=self._page_registered(page))
            st.borrowed.discard(idx)
            self._map_page(slot, idx, dst)
            self._cow_forks += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "cow_fork", track=track,
                    src=page, dst=dst, page_idx=idx, slot=slot,
                )
        return True

    def _secure_prefill_pages(self, st: _Slot, slot: int, n: int) -> int:
        """Make pages for prompt positions ``[cursor, cursor + n)``
        writable; returns how many of the n tokens actually have a
        page (possibly 0 — free-list exhaustion backpressure)."""
        ps = self.cache.page_size
        secured_end = st.cursor
        first = st.cursor // ps
        last = (st.cursor + n - 1) // ps
        for idx in range(first, last + 1):
            if not self._ensure_writable(st, slot, idx):
                self._page_stalls += 1
                break
            secured_end = min(st.cursor + n, (idx + 1) * ps)
        return secured_end - st.cursor

    def _register_full_pages(self, st: _Slot, slot: int) -> None:
        """Advance the slot's prefix chain over every page that is now
        FULL of prompt tokens: freshly-owned pages register in the
        store (immutable from here on — appends only land past them);
        borrowed pages just advance the chain key they were matched
        from."""
        ps = self.cache.page_size
        prompt = st.req.prompt
        while ((st.reg_pages + 1) * ps <= st.cursor
               and (st.reg_pages + 1) * ps <= len(prompt)):
            idx = st.reg_pages
            tokens = prompt[idx * ps:(idx + 1) * ps]
            if idx in st.borrowed:
                st.chain_key = self._store.chain_key(
                    st.chain_key, tokens
                )
            else:
                st.chain_key = self._store.register(
                    st.chain_key, tokens, int(self._table[slot, idx])
                )
            st.reg_pages += 1

    def _release_slot_pages(self, st: _Slot, slot: int) -> None:
        """Eviction: drop this slot's page references. Store-registered
        pages PARK (reclaimable prefix cache — a later request with
        the same prefix revives them for free); private pages free."""
        sentinel = self.cache.num_pages
        for idx in range(self._table.shape[1]):
            page = int(self._table[slot, idx])
            if page == sentinel:
                continue
            self._allocator.decref(
                page, park=self._page_registered(page)
            )
            self._table[slot, idx] = sentinel
        self._table_dirty = True
        st.borrowed.clear()
        if self._window_allocator is not None:
            row = self._window_table[slot]
            for page in row[row != self.cache.window_pages]:
                self._window_allocator.decref(int(page))
            row[:] = self.cache.window_pages
            self._window_table_dirty = True

    def _free_window_pages(self) -> None:
        """After a tick commits: unmap and free every page of the window
        group that lies wholly behind what a slot's NEXT row can attend.
        That row sits at ``st.pos`` (the next chunk's first row while
        the prompt is prefilled, else the next decode row) and attends
        from ``st.pos + 1 - window``; every later row attends later
        keys. The global group's pages stay until the slot ends."""
        window, ps = self.cache.window, self.cache.page_size
        sentinel = self.cache.window_pages
        for slot, st in enumerate(self._slots):
            if st is None:
                continue
            behind = (st.pos + 1 - window) // ps  # pages wholly behind
            for idx in range(st.window_head, behind):
                page = int(self._window_table[slot, idx])
                if page != sentinel:
                    self._window_allocator.decref(page)
                    self._window_table[slot, idx] = sentinel
                    self._window_table_dirty = True
                    self._window_pages_freed += 1
            st.window_head = max(st.window_head, behind)

    def _release_adapter(self, st: _Slot) -> None:
        """Drop an in-flight request's adapter residency ref, exactly
        once per lease (``adapter_slot = -1`` marks the lease closed,
        so overlapping teardown paths under failure recovery cannot
        double-release). The pool slot PARKS at refcount zero — the
        tenant's next request revives the bytes for free."""
        if self.adapter_pool is None or st.adapter_slot < 0:
            return
        self.adapter_pool.release(st.req.adapter_id)
        st.adapter_slot = -1

    def _preempt_for_pages(self) -> None:
        """Break a pool deadlock by preempting slots — youngest lease
        first (least recompute lost, and it frees the most recently
        allocated pages) — until at least one page is available.
        Only slots that actually hold table mappings are candidates
        (preempting a pageless slot frees nothing). A preempted
        request keeps its generated tokens and timeline anchors in
        ``_preempted`` and rejoins the HEAD of the queue; re-admission
        recomputes prompt + generated through the ordinary chunked
        prefill (determinism: greedy output is unchanged). If every
        mapped slot is drained and the pool is still empty (pages
        pinned elsewhere), the original deadlock diagnosis raises."""
        sentinel = self.cache.num_pages
        while any(a.available < 1 for a in self._allocators()):
            victim, vslot = None, -1
            for slot, st in enumerate(self._slots):
                if st is None:
                    continue
                if not any(
                    int(p) != sentinel for p in self._table[slot]
                ) and not (
                    self._window_allocator is not None
                    and (self._window_table[slot]
                         != self.cache.window_pages).any()
                ):
                    continue
                if victim is None or st.leased_at >= victim.leased_at:
                    victim, vslot = st, slot
            # preemption is only productive if ANOTHER in-flight slot
            # remains to consume the freed pages: the victim rejoins
            # the queue HEAD, so preempting the sole request would
            # re-admit it straight into the same wall — a livelock,
            # not a recovery (the num_pages=1 unservable-pool case)
            if sum(s is not None for s in self._slots) <= 1:
                victim = None
            if victim is None:
                raise RuntimeError(
                    "paged KV pool deadlock: every in-flight request "
                    "is stalled waiting for pages, no decode can run "
                    "to free any, and no slot holds reclaimable pages "
                    f"(pages={self.pages_total}, used="
                    f"{self.pages_used}); size num_pages "
                    "for the expected live tokens, or admit less "
                    "concurrency"
                )
            self._release_slot_pages(victim, vslot)
            self._release_adapter(victim)
            self._slots[vslot] = None
            self._preempted[victim.req.request_id] = (
                list(victim.generated), victim.first_token_at,
                victim.chunks,
            )
            self._queue.appendleft(victim.req)
            self._preemptions += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "preempt", track=f"req{victim.req.request_id}",
                    slot=vslot, generated=len(victim.generated),
                    request_id=victim.req.request_id,
                    trace_id=victim.req.trace_id,
                )

    def _guard_capacity(self, active) -> None:
        """The host-side replacement for the cache's silent
        clamp-at-capacity: a live slot about to DECODE at a position
        >= capacity is an engine invariant violation (the scheduler
        must have evicted it with finish_reason='capacity' already) —
        raise with the slot id instead of wedging the length and
        silently re-sampling from a stale last row."""
        for slot, st in enumerate(self._slots):
            if st is None or not active[slot]:
                continue
            if st.pos >= self.capacity:
                raise RuntimeError(
                    f"slot {slot} (request {st.req.request_id}) would "
                    f"write cache position {st.pos} >= capacity "
                    f"{self.capacity}: the engine must evict a "
                    f"sequence before its length hits capacity "
                    f"(finish_reason='capacity'), never clamp a live "
                    f"write"
                )

    def _pick_queued(self) -> Optional[Tuple[Request, int]]:
        """Pick the next admissible queued request. Without an adapter
        pool: plain FIFO. With one, admission is TIER-ORDERED (highest
        tier first, FIFO within a tier) and ACQUIRE-OR-SKIP: the
        candidate's adapter must take a residency ref NOW — if every
        pool slot is pinned by in-flight work the candidate is skipped
        (``adapter_stalls``; token-level backpressure, retried next
        tick once a finishing request drops a ref — never a deadlock)
        and a lower-tier request whose adapter IS available admits
        instead. Returns ``(request, adapter buffer slot)`` with the
        ref already held; the caller owns releasing it."""
        if not self._queue:
            return None
        if self.adapter_pool is None:
            return self._queue.popleft(), 0
        order = sorted(
            range(len(self._queue)),
            key=lambda i: (
                -self.adapter_pool.tier_of(
                    self._queue[i].adapter_id
                ),
                i,
            ),
        )
        for i in order:
            req = self._queue[i]
            aslot = self.adapter_pool.acquire(req.adapter_id)
            if aslot is None:
                self._adapter_stalls += 1
                continue
            del self._queue[i]
            return req, aslot
        return None

    def _admit_free_slots(self, now: float) -> List[int]:
        """Lease free slots to queued requests (host bookkeeping; the
        prefill work itself is scheduled by the caller) and return the
        ids of those leased. With prefix
        sharing, a prompt that extends an already-materialized page
        chain maps those pages by REFERENCE and starts its prefill
        cursor past them — the shared tokens are never re-prefilled.

        With ``tier_preemption`` and a fully-occupied engine, a queued
        request outranking the lowest-tier in-flight one preempts that
        victim (youngest lease within the tier; at most one per tick)
        through the PR-8 requeue path — tokens kept, cache recomputed
        on re-admission, greedy output unchanged."""
        if (
            self.tier_preemption
            and self.adapter_pool is not None
            and self._queue
            and all(s is not None for s in self._slots)
        ):
            top = max(
                self.adapter_pool.tier_of(q.adapter_id)
                for q in self._queue
            )
            victim, vslot, vtier = None, -1, 0
            for slot, st in enumerate(self._slots):
                t = self.adapter_pool.tier_of(st.req.adapter_id)
                if (
                    victim is None or t < vtier
                    or (t == vtier and st.leased_at >= victim.leased_at)
                ):
                    victim, vslot, vtier = st, slot, t
            if top > vtier:
                if self.paged:
                    self._release_slot_pages(victim, vslot)
                self._release_adapter(victim)
                self._slots[vslot] = None
                self._preempted[victim.req.request_id] = (
                    list(victim.generated), victim.first_token_at,
                    victim.chunks,
                )
                self._queue.appendleft(victim.req)
                self._tier_preemptions += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "tier_preempt",
                        track=f"req{victim.req.request_id}",
                        slot=vslot, tier=vtier, over=top,
                        request_id=victim.req.request_id,
                        trace_id=victim.req.trace_id,
                    )
        leased: List[int] = []
        for slot in range(self.num_slots):
            if self._slots[slot] is not None or not self._queue:
                continue
            picked = self._pick_queued()
            if picked is None:
                # nothing admissible this tick (adapter residency
                # backpressure) — no point probing the other slots
                break
            req, aslot = picked
            self._admitted += 1
            leased.append(req.request_id)
            self._record_queue_wait(now - req.enqueued_at)
            st = _Slot(
                req=req, generated=[], pos=0, cursor=0,
                prefix=list(req.prompt), leased_at=now,
                adapter_slot=aslot,
            )
            carried = self._preempted.pop(req.request_id, None)
            if carried is not None:
                # preempted request: restore its tokens and recompute
                # the cache via ordinary chunked prefill of
                # prompt + generated[:-1] (the last generated token
                # stays unwritten — the live-slot invariant — so the
                # slot rejoins the decode grid exactly where it left
                # off; greedy output is identical to an unpreempted
                # run). TTFT/chunk anchors carry over: the first token
                # was already delivered before preemption.
                generated, first_at, chunks = carried
                st.generated = list(generated)
                st.first_token_at = first_at
                st.chunks = chunks
                if generated:
                    st.prefix = list(req.prompt) + list(generated[:-1])
                    st.resumed = True
            self._slots[slot] = st
            shipped = self._shipped.pop(req.request_id, None)
            if shipped is not None and self._import_shipped_pages(
                st, slot, shipped
            ):
                # page-shipping landed: the cursor already covers the
                # shipped rows, which is at least what a local prefix
                # match could offer — skip the store consult entirely
                if self.tracer.enabled:
                    self.tracer.add_span(
                        "queue_wait", req.enqueued_at, now,
                        track=f"req{req.request_id}", slot=slot,
                        request_id=req.request_id,
                        trace_id=req.trace_id,
                    )
                continue
            if self._store is not None:
                pages, matched, partial, key = self._store.match(
                    req.prompt
                )
                if matched > 0:
                    for idx, page in enumerate(pages):
                        self._allocator.ref(page)
                        self._map_page(slot, idx, page)
                        st.borrowed.add(idx)
                    st.cursor = matched
                    st.pos = matched
                    st.chain_key = key
                    st.reg_pages = len(pages) - (1 if partial else 0)
                    self._prefix_hits += 1
                    self._prefix_hit_tokens += matched
                    if self.tracer.enabled:
                        self.tracer.instant(
                            "prefix_hit", track=f"req{req.request_id}",
                            tokens=matched, pages=len(pages),
                            partial_tokens=partial, slot=slot,
                            request_id=req.request_id,
                            trace_id=req.trace_id,
                        )
            if self.tracer.enabled:
                self.tracer.add_span(
                    "queue_wait", req.enqueued_at, now,
                    track=f"req{req.request_id}", slot=slot,
                    request_id=req.request_id, trace_id=req.trace_id,
                )
        return leased

    # -- robustness internals ------------------------------------------

    def _fetch(self, values):
        """``engine.fetch``: ONE batched `device_get` (= the device
        sync) — never a per-request scalar pull."""
        with self.tracer.phase("engine.fetch", track="engine"):
            values = jax.device_get(values)
        self._laps.append(("fetch", time.perf_counter()))
        return values

    def _run_program(self, name: str, operands, fetch: bool = True):
        """The one way a tick reaches the device: run step program
        ``name`` of `self.programs` on ``operands`` (host arrays in the
        program's positional order, between the engine's state and the
        key) and return ``(values, counters, kept, t0, t1)``.

        ONE call into the runtime on the way in, the jitted program,
        and no eager device operation. ``engine.dispatch`` is that call
        until it returns: the `numpy` operands go to it as they are (its
        own argument path puts them on the device) and the sampling key
        is split INSIDE the program, which hands the new key state back
        beside the cache. ``engine.fetch`` is the one `device_get` of
        the program's leading `FETCHED` outputs (sampled tokens,
        nonfinite flags) and of the new cache's tick counters (None
        where it keeps none) — left to the caller with ``fetch=False``,
        for one fetch over several calls. ``kept`` is what else the
        program returned and stays on the device (the speculative
        chunk's K/V; else None); ``t0``/``t1`` bracket the device call
        for the caller's books.

        The call and its fetch retry with capped exponential backoff
        (the ``device_step`` and ``host_fetch`` fault sites fail them
        on purpose). What the program returns to be kept — the key
        state, the DONATED cache and a pool's adapter buffers — is
        re-bound only on success, so a retry re-runs against the
        pre-step state and replays the same key: bitwise-deterministic
        recovery on CPU, where buffers are not donated; on TPU a genuine
        mid-step failure consumes the donated cache and the retry
        surfaces that. On exhaustion every in-flight slot
        preempts-and-requeues (`_requeue_in_flight`), then the failure
        propagates — and the key has NOT advanced (when the host split
        it, before ISSUE 29, a failed tick consumed one split)."""
        program = getattr(self.programs, name)
        pool = self.adapter_pool
        state = (self.cache,) if pool is None else (self.cache, pool.buffers)
        n = FETCHED[name]
        attempt = 0
        t0 = time.perf_counter()
        while True:
            try:
                if self.faults.enabled and self.faults.fire(
                    "device_step", tick=self._tick,
                ) is not None:
                    raise FaultInjected(
                        f"injected device_step fault (tick {self._tick})"
                    )
                with self.tracer.phase("engine.dispatch", track="engine"):
                    out = program(
                        self.params, *state, *operands, self._rng)
                    # between the device call and the value fetch
                    if self.faults.enabled and self.faults.fire(
                        "host_fetch", tick=self._tick,
                    ) is not None:
                        raise FaultInjected(
                            f"injected host_fetch fault (tick {self._tick})"
                        )
                self._laps.append(("dispatch", time.perf_counter()))
                values = out[:n], out[n + 1].counters if self.paged else None
                if fetch:
                    values = self._fetch(values)
                break
            except Exception:
                if attempt >= self.max_step_retries:
                    self._requeue_in_flight()
                    raise
                attempt += 1
                self._step_retries += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "step_retry", track="engine", attempt=attempt,
                    )
                if self.step_retry_backoff > 0:
                    time.sleep(min(
                        self.step_retry_backoff * (2 ** (attempt - 1)),
                        1.0,
                    ))
        self._rng, self.cache, *rest = out[n:]
        if pool is not None:
            pool.buffers = rest.pop(0)
        return (*values, rest[0] if rest else None,
                t0, time.perf_counter())

    def _requeue_in_flight(self) -> None:
        """Device-step retries exhausted: hand every in-flight request
        back to the queue through the PR-8 preempt path before the
        failure surfaces, so a caller that catches it finds a
        consistent engine (slots free, pages released, requests
        queued) and the next successful tick recomputes everything.
        Reverse slot order + appendleft keeps the original slot order
        at the queue head. Pages registered in the prefix store by
        COMPLETED ticks are valid and park as usual; the failed
        tick's writes never registered (registration is deferred past
        the device call) so no junk page can be matched later."""
        for slot in range(self.num_slots - 1, -1, -1):
            st = self._slots[slot]
            if st is None:
                continue
            if self.paged:
                self._release_slot_pages(st, slot)
            self._release_adapter(st)
            self._slots[slot] = None
            if st.generated:
                self._preempted[st.req.request_id] = (
                    list(st.generated), st.first_token_at, st.chunks,
                )
            self._queue.appendleft(st.req)
            self._preemptions += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "requeue", track=f"req{st.req.request_id}",
                    slot=slot, generated=len(st.generated),
                    request_id=st.req.request_id,
                    trace_id=st.req.trace_id,
                )
        if self.paged:
            self._push_table()

    def _expire_deadlines(self, now: float) -> List[GenerationResult]:
        """Tick-boundary deadline sweep: queued requests past their
        queue TTL or end-to-end deadline expire without a slot;
        in-flight requests past their deadline tear down through the
        ordinary eviction (slot + pages released). Both finish with
        reason ``deadline``."""
        out: List[GenerationResult] = []
        if self._queue:
            keep: collections.deque = collections.deque()
            for req in self._queue:
                expired = (
                    (req.queue_deadline is not None
                     and now > req.queue_deadline)
                    or (req.deadline is not None and now > req.deadline)
                )
                if expired:
                    self._deadline_exceeded += 1
                    out.append(
                        self._finalize_queued(req, "deadline", now)
                    )
                else:
                    keep.append(req)
            self._queue = keep
        for slot, st in enumerate(self._slots):
            if st is None or st.req.deadline is None:
                continue
            if now > st.req.deadline:
                self._deadline_exceeded += 1
                out.append(self._evict(slot, st, "deadline"))
        return out

    def _finalize_queued(
        self, req: Request, reason: str, now: float
    ) -> GenerationResult:
        """Finish a request that never (re)took a slot — expired in
        queue, cancelled in queue, or shed by drain. A PREEMPTED
        request waiting to resume returns the tokens it already
        generated (they were delivered work; dropping them would
        un-deliver it)."""
        carried = self._preempted.pop(req.request_id, None)
        tokens = list(carried[0]) if carried is not None else []
        self._record_completion({
            "request_id": req.request_id,
            "finish_reason": reason,
            "prompt_tokens": len(req.prompt),
            "new_tokens": len(tokens),
            "chunks": carried[2] if carried is not None else 0,
            "queue_wait_ms": 1e3 * (now - req.enqueued_at),
            "ttft_ms": 0.0,
            "tpot_ms": 0.0,
            "e2e_ms": 1e3 * (now - req.enqueued_at),
            "tenant": req.tenant,
        })
        if self.tracer.enabled:
            self.tracer.instant(
                "finish", ts=now, track=f"req{req.request_id}",
                reason=reason, request_id=req.request_id,
                trace_id=req.trace_id,
            )
        return GenerationResult(
            request_id=req.request_id, prompt=list(req.prompt),
            tokens=tokens, finish_reason=reason,
        )

    def _quarantine(
        self, slot: int, st: _Slot, why: str
    ) -> GenerationResult:
        """Fault isolation: nonfinite logits on ONE slot evict that
        slot only (``finish_reason='error'``) — the tick's other slots
        already got their tokens from the same fetch, bitwise
        identical to a fault-free run (the poison/flag path adds
        ``+0.0`` to their logits and nothing else). The flight
        recorder, when wired, dumps a ``nonfinite/slot<i>`` bundle for
        the postmortem."""
        self._quarantined += 1
        rid = st.req.request_id
        if self.tracer.enabled:
            self.tracer.instant(
                "quarantine", track=f"req{rid}", slot=slot, why=why,
                request_id=rid, trace_id=st.req.trace_id,
            )
        if self.flight_recorder is not None:
            self.flight_recorder.record(
                self._tick, {f"nonfinite/slot{slot}": 1.0},
                request_id=rid, pos=st.pos,
                generated=len(st.generated),
            )
        return self._evict(slot, st, "error")

    def _note_progress(self) -> None:
        """Token progress = prompt tokens absorbed, tokens generated,
        or slots evicted — the signals the stall watchdog watches."""
        work = (
            self._prompt_tokens, self._generated_tokens, self._evicted,
        )
        if work != self._progress_mark:
            self._progress_mark = work
            self._last_progress = time.perf_counter()

    def _check_watchdog(self, now: float) -> None:
        if self.watchdog_timeout is None or not self.has_work():
            return
        stalled = now - self._last_progress
        if stalled <= self.watchdog_timeout:
            return
        self._watchdog_fires += 1
        diag = self._stall_diagnosis()
        if self.tracer.enabled:
            self.tracer.instant(
                "watchdog", track="engine", stalled_seconds=stalled,
            )
        if self.watchdog_dump_path is not None:
            # the postmortem bundle: engine state as json, plus the
            # tracer timeline next to it when tracing is on
            import json

            with open(self.watchdog_dump_path, "w") as f:
                json.dump({
                    "event": "watchdog",
                    "stalled_seconds": stalled,
                    "tick": self._tick,
                    "diagnosis": diag,
                    "stats": self.stats(),
                }, f, indent=2)
            if self.tracer.enabled:
                self.tracer.export_chrome_trace(
                    self.watchdog_dump_path + ".trace.json"
                )
        raise RuntimeError(
            f"serving watchdog: no token progress for {stalled:.2f}s "
            f"(watchdog_timeout={self.watchdog_timeout}s); {diag}"
        )

    def _stall_diagnosis(self) -> str:
        """Name the stuck slot(s) — the diagnostic the watchdog and
        the bounded `generate()` raise with."""
        parts = []
        for slot, st in enumerate(self._slots):
            if st is None:
                continue
            phase = "prefilling" if st.prefilling else "decoding"
            parts.append(
                f"slot {slot}: request {st.req.request_id} {phase} "
                f"pos={st.pos} cursor={st.cursor}/{len(st.prefix)} "
                f"generated={len(st.generated)}"
            )
        if not parts:
            parts.append("no slots leased")
        return (
            f"queue_depth={self.num_queued}, "
            f"draining={self._draining}; " + "; ".join(parts)
        )

    def _step_chunked(
        self,
    ) -> Tuple[List[GenerationResult], Dict[str, Any]]:
        finished: List[GenerationResult] = []
        # (admission ran in `_admit_phase`, under ``engine.admit``)
        with self.tracer.phase("engine.pack", track="engine"):
            budget = self.prefill_token_budget
            S = self.num_slots
            one_pass = self.programs.one_pass
            chunk_tokens = np.zeros((budget,), np.int32)
            # slot id == num_slots marks padding: the scatter drops it and
            # the segment mask keeps pads talking only to each other
            chunk_slots = np.full((budget,), S, np.int32)
            chunk_pos = np.zeros((budget,), np.int32)
            # speculative mode only: who COMMITS in-trace. Prefill rows
            # commit like always; speculative rows keep the pad sentinel
            # (the host commits their accepted prefix post-verification)
            commit_slots = np.full((budget,), S, np.int32)
            lengths_before = np.zeros((S,), np.int32)
            lengths_after = np.zeros((S,), np.int32)
            # logits poison: zeros on the fault-free path (the compiled
            # programs add it unconditionally — x + 0.0 — so the fault-free
            # tokens are bitwise identical and the trace never changes);
            # a `logits` fault poisons ONE slot's rows with NaN/Inf
            chunk_poison = np.zeros((budget,), np.float32)
            dec_poison = np.zeros((S,), np.float32)
            # per-row adapter BUFFER slots (multi-LoRA): pad rows stay 0 =
            # base = zero factors, so padding is exact with or without
            # adapters in the batch
            pool = self.adapter_pool
            chunk_adp = dec_adp = None
            if pool is not None:
                chunk_adp = np.zeros((budget,), np.int32)
                dec_adp = np.zeros((S,), np.int32)
            poison_slot = -1
            poison_val = 0.0
            if self.faults.enabled:
                flt = self.faults.fire("logits", tick=self._tick)
                if flt is not None:
                    pay = (
                        flt.payload if isinstance(flt.payload, dict)
                        else {"slot": flt.payload}
                    )
                    s = pay.get("slot")
                    poison_slot = int(s) if s is not None else 0
                    poison_val = float(pay.get("value", float("nan")))
                    if 0 <= poison_slot < S:
                        dec_poison[poison_slot] = poison_val
            # (slot, chunk index of last prompt token, fed-to-decode flag)
            completions = []
            packed = []  # (slot, tokens, start_pos) — tracer span payload
            # paged prefix registration deferred past the device call (a
            # failed step must not leave never-written pages registered)
            reg_pending = []
            # speculative bookkeeping: (slot, first chunk row, drafted
            # count, draft tokens, pre-draft position)
            spec_entries = []
            used = 0
            prefill_used = 0

            drafts_np = counts_np = None
            t_d0 = t_d1 = 0.0
            if self.spec_k > 0:
                # one batched drafter call per tick, covering every
                # decoding slot (jitted inside the drafter; numpy in/out)
                W = self._spec_window
                hist = np.full((S, W), -1, np.int32)
                hist_len = np.zeros((S,), np.int32)
                any_decoding = False
                for slot, s in enumerate(self._slots):
                    if s is None or not s.generated or s.prefilling:
                        continue
                    any_decoding = True
                    h = (s.req.prompt + s.generated)[-W:]
                    hist[slot, W - len(h):] = h
                    hist_len[slot] = len(h)
                if any_decoding:
                    t_d0 = time.perf_counter()
                    drafts_np, counts_np = self._drafter(hist, hist_len)
                    t_d1 = time.perf_counter()

            # the budget goes to the prefilling slots in the order they
            # were LEASED (the queue's order: within one tick's admissions
            # the slot index), not by slot index: a long prompt in a high
            # slot does not wait behind every newcomer in a lower one, and
            # which slot a request happened to get decides nothing (by
            # slot index a saturated run's tokens/s spread twice as
            # widely over seeds: PERF.md, PR 35)
            grants = {}
            left = budget
            for slot in sorted(
                (i for i, s in enumerate(self._slots)
                 if s is not None and s.prefilling),
                key=lambda i: (self._slots[i].leased_at, i),
            ):
                if left <= 0:
                    break
                st = self._slots[slot]
                n = min(left, len(st.prefix) - st.cursor)
                if self.prefill_chunk is not None:
                    n = min(n, self.prefill_chunk)
                if self.paged:
                    # pool backpressure: only tokens whose pages exist
                    # (or could be allocated / CoW-forked) are
                    # scheduled; a starved slot just waits for
                    # evictions to free pages
                    n = self._secure_prefill_pages(st, slot, n)
                if n > 0:
                    grants[slot] = n
                    left -= n

            # slot order keeps the packed segment ids non-decreasing (the
            # varlen kernel's contract); a slot contributes either prefill
            # rows or a speculative span (from what the prompts left of
            # the budget), never both
            for slot in range(S):
                st = self._slots[slot]
                if st is not None:
                    lengths_before[slot] = st.pos
                    lengths_after[slot] = st.pos
                if st is None:
                    continue
                if st.prefilling:
                    n = grants.get(slot, 0)
                    if n <= 0:
                        continue
                    chunk_tokens[used:used + n] = st.prefix[
                        st.cursor:st.cursor + n
                    ]
                    chunk_slots[used:used + n] = slot
                    commit_slots[used:used + n] = slot
                    chunk_pos[used:used + n] = np.arange(
                        st.cursor, st.cursor + n
                    )
                    if chunk_adp is not None:
                        chunk_adp[used:used + n] = st.adapter_slot
                    packed.append((slot, n, st.cursor))
                    st.cursor += n
                    st.pos = st.cursor
                    st.chunks += 1
                    lengths_after[slot] = st.cursor
                    self._prompt_tokens += n
                    if self.paged and self._store is not None:
                        reg_pending.append((st, slot))
                    if not st.prefilling and not st.resumed:
                        # the completing prompt's first sampled token is
                        # fed straight into the fused decode — UNLESS that
                        # decode write has nowhere to land: a prompt that
                        # exactly fills capacity (the old silent
                        # clamp-at-capacity; the host evicts it right
                        # after the first token instead) or a paged slot
                        # whose next page the pool cannot supply yet (it
                        # decodes on a later tick). A RESUMED (preempted)
                        # request completing its recomputed prefix emits
                        # nothing here — its tokens already exist; it
                        # rejoins the decode grid below this same tick
                        # (the next, under the one-pass body).
                        # The ONE-PASS body (`StepPrograms.one_pass`)
                        # feeds nothing: every completion emits its first
                        # token here and decodes from the next tick, so
                        # no page is reserved for a fused write and no
                        # stall is counted for one.
                        fed = not one_pass and st.cursor < self.capacity
                        if fed and self.paged:
                            fed = self._ensure_writable(
                                st, slot, st.cursor // self.cache.page_size
                            )
                            if not fed:
                                self._page_stalls += 1
                        completions.append((slot, used + n - 1, fed))
                    used += n
                    prefill_used += n
                    continue
                # ---- speculative span: [last generated token, k drafts].
                # The last token needs its decode row scored anyway; the
                # drafts ride the same packed chunk, so acceptance costs
                # no extra trace. Clamps: drafter confidence, spec_k, the
                # remaining budget (one row is the last token itself),
                # capacity (every accepted token + bonus needs a cache
                # row), and max_new (finishing mid-span is handled, but
                # drafting past the request's end is wasted budget).
                if drafts_np is None or not st.generated:
                    continue
                n = min(
                    int(counts_np[slot]), self.spec_k, left - 1,
                    self.capacity - st.pos - 1,
                    st.req.max_new_tokens - len(st.generated) - 1,
                )
                if n < 1:
                    continue
                if self.paged and not self._ensure_writable(
                    st, slot, st.pos // self.cache.page_size
                ):
                    # pool exhausted even for the last token's row: fall
                    # through to the decode grid, which hits the same wall
                    # and stalls the slot for the tick
                    continue
                drafts = [int(t) for t in drafts_np[slot, :n]]
                chunk_tokens[used] = st.generated[-1]
                chunk_tokens[used + 1:used + 1 + n] = drafts
                chunk_slots[used:used + n + 1] = slot
                chunk_pos[used:used + n + 1] = np.arange(
                    st.pos, st.pos + n + 1
                )
                spec_entries.append((slot, used, n, drafts, st.pos))
                self._tokens_drafted += n
                used += n + 1
                left -= n + 1

            if poison_slot >= 0:
                # poison the faulted slot's chunk rows too (a prompt
                # completion or speculative span must quarantine the same
                # way a decode row does)
                chunk_poison[chunk_slots == poison_slot] = poison_val

            # decode grid: slots whose prompt completed in an EARLIER tick
            # (a slot finishing prefill this tick gets its first token from
            # the chunk logits below and starts decoding next tick; a slot
            # with a speculative span this tick advances via the accept
            # walk instead)
            active = np.array(
                [s is not None and bool(s.generated) and not s.prefilling
                 for s in self._slots],
                dtype=bool,
            )
            for slot, _, _, _, _ in spec_entries:
                active[slot] = False
            if one_pass:
                # no slot has rows in both parts of the one apply: a
                # preempted request whose recomputed prefix ends in this
                # chunk rejoins the grid in the next tick
                for slot, _, _ in packed:
                    active[slot] = False
            self._guard_capacity(active)
            if self.paged:
                for slot, st in enumerate(self._slots):
                    if not active[slot]:
                        continue
                    if not self._ensure_writable(
                        st, slot, st.pos // self.cache.page_size
                    ):
                        # stall THIS slot's decode for the tick; everyone
                        # else advances (fixed shapes: the row just rides
                        # along dead)
                        active[slot] = False
                        self._page_stalls += 1
            dec_tokens = np.array(
                [s.generated[-1] if s is not None and s.generated else 0
                 for s in self._slots],
                np.int32,
            )

            # the two-apply body reads it as "feed this row's token to
            # the grid"; the one-pass body as "the head's row of the slot"
            completion_idx = np.full((S,), -1, np.int32)
            for slot, idx, fed in completions:
                completion_idx[slot] = idx if fed or one_pass else -1
            if dec_adp is not None:
                # only rows the fused decode actually emits carry their
                # adapter slot; dead rows stay 0 so a pure-base tick's
                # `active` skip condition sees all-zero ids exactly
                for slot, st in enumerate(self._slots):
                    if st is None:
                        continue
                    if active[slot] or completion_idx[slot] >= 0:
                        dec_adp[slot] = st.adapter_slot
            if (
                self.paged and used == 0 and not active.any()
                and completions == [] and self.has_work()
            ):
                # pool deadlock: every in-flight request is stalled
                # waiting for pages and no decode can run to free any.
                # Preempt-and-requeue (the vLLM recompute policy)
                # instead of stalling forever or raising: the youngest
                # page-holding slot gives its pages back and its
                # request rejoins the queue head; on re-admission its
                # prompt + generated tokens are recomputed through the
                # ordinary chunked prefill.
                self._preempt_for_pages()
        self._laps.append(("pack", time.perf_counter()))
        if self.paged:
            with self.tracer.phase("engine.table_push", track="engine"):
                self._push_table()
            self._laps.append(("table_push", time.perf_counter()))

        chunk_out = dec_out = chunk_bad = dec_bad = None
        chunk_kv = layer_counts = None
        program = "none"
        # a feature's operands ride where `StepPrograms.mixed_operands`
        # puts them: the adapter ids of an engine with a pool, the
        # `commit_slots` of a speculative one
        dec_extra = () if pool is None else (dec_adp,)
        if used > 0 or (self.spec_k > 0 and active.any()):
            # speculative engines ALWAYS run the mixed program, even on
            # draft-free ticks: the decode-only fast path reads
            # device-resident lengths, which the host-side accept walk
            # outruns — here the host cursors ride in as arguments
            # every tick, and one program means mixed_trace_count == 1
            # at any k. The SAME fused chunk+decode program serves any
            # adapter mix — ids are data, so adapter add / park /
            # reclaim churn never retraces.
            program = "spec" if self.spec_k > 0 else "mixed"
            chunk_extra = (
                ((commit_slots,) if self.spec_k > 0 else ())
                + (() if pool is None else (chunk_adp,))
            )
            fetched, layer_counts, chunk_kv, t0, t1 = self._run_program(
                "mixed", (
                    chunk_tokens, chunk_slots, chunk_pos, *chunk_extra,
                    lengths_before, lengths_after, completion_idx,
                    dec_tokens, active, *dec_extra,
                    chunk_poison, dec_poison,
                ),
            )
            chunk_out, dec_out, chunk_bad, dec_bad = fetched
            if prefill_used > 0:
                self._prefill_seconds += t1 - t0
                self._mixed_steps += 1
            else:  # a speculative tick with no prompt token in it
                self._decode_seconds += t1 - t0
            if active.any() or completions or spec_entries:
                self._decode_steps += 1
        elif active.any():
            program = "decode"
            (dec_out, dec_bad), layer_counts, _, t0, t1 = (
                self._run_program(
                    "decode",
                    (dec_tokens, active, *dec_extra, dec_poison),
                )
            )
            self._decode_seconds += t1 - t0
            self._decode_steps += 1
        if self.tracer.enabled and packed:
            # the request lifelines: a prompt's chunks, over the
            # device call that absorbed them
            for slot, n, start_pos in packed:
                st = self._slots[slot]
                self.tracer.add_span(
                    "prefill_chunk", t0, t1,
                    track=f"req{st.req.request_id}",
                    tokens=n, start_pos=start_pos, slot=slot,
                )

        with self.tracer.phase("engine.commit", track="engine"):
            # the tick's counts as the device step saw them, before
            # the evictions below: decode-grid rows that emit a token
            # (those of earlier ticks' prompts and the fused second
            # token of a prompt completed in this one), leased slots
            counts = {
                "program": program,
                # applies of the model in the tick's program
                "model_passes": (
                    0 if program == "none"
                    else 1 if program == "decode" or one_pass else 2),
                "chunk_tokens": used,
                "prefill_tokens": prefill_used,
                "decodes": int(active.sum()) + sum(
                    1 for _, _, fed in completions if fed
                ),
                "slots_busy": self.num_active,
            }
            if layer_counts is not None:
                counts.update(zip(
                    self.cache.counter_names,
                    (int(c) for c in layer_counts),
                ))
            # the device step committed: NOW the tick's full prompt pages
            # may register in the prefix store (see reg_pending above)
            for st, slot in reg_pending:
                self._register_full_pages(st, slot)

            now2 = time.perf_counter()
            for slot, idx, fed in completions:
                st = self._slots[slot]
                if one_pass:
                    idx = slot  # the chunk's fetched values are per slot
                if chunk_bad is not None and chunk_bad[idx]:
                    # fault isolation: only THIS slot quarantines; every
                    # other slot's tokens came out of the same fetch,
                    # bitwise identical to a fault-free tick
                    finished.append(self._quarantine(
                        slot, st, "nonfinite logits at prompt completion",
                    ))
                    continue
                st.generated.append(int(chunk_out[idx]))
                self._generated_tokens += 1
                st.first_token_at = now2
                self._record_ttft(now2 - st.req.enqueued_at)
                done = self._finish_reason(st)
                if done is not None:
                    # any fused decode output for this slot is discarded
                    # with the eviction (dead-row junk)
                    finished.append(self._evict(slot, st, done))
                    continue
                if not fed:
                    # no fused decode ran for this slot (the one-pass
                    # body, the at-capacity edge already evicted above,
                    # or a paged page stall): the second token arrives
                    # on a later tick
                    continue
                if dec_bad is not None and dec_bad[slot]:
                    finished.append(self._quarantine(
                        slot, st, "nonfinite logits in fused decode",
                    ))
                    continue
                # the mixed step fed the first token straight into the
                # decode grid: the SECOND token arrives in the same tick
                # (the whole-prompt admit-tick cadence, without the pad)
                st.pos += 1
                st.generated.append(int(dec_out[slot]))
                self._generated_tokens += 1
                done = self._finish_reason(st)
                if done is not None:
                    finished.append(self._evict(slot, st, done))
            if dec_out is not None:
                for slot, st in enumerate(self._slots):
                    if st is None or not active[slot]:
                        continue
                    if dec_bad is not None and dec_bad[slot]:
                        finished.append(self._quarantine(
                            slot, st, "nonfinite logits in decode",
                        ))
                        continue
                    st.pos += 1  # the input token was written this step
                    st.generated.append(int(dec_out[slot]))
                    self._generated_tokens += 1
                    done = self._finish_reason(st)
                    if done is not None:
                        finished.append(self._evict(slot, st, done))

            # ---- speculative accept walk. Every packed span was sampled
            # under the target model (row j conditioned on the drafts before
            # it), so for the point-mass drafter the exact rejection rule
            # (arXiv 2302.01318) degenerates to: accept draft j iff the
            # model's own sample at row j equals it; the first disagreeing
            # row's sample is the corrected "bonus" token — m accepted
            # drafts always yield m+1 emitted tokens. Rejected rows simply
            # never commit: their K/V exists only in the trace's packed
            # per-layer output, so rollback is "don't write", not "undo" —
            # shared pages and int8 scales are untouchable by construction.
            if spec_entries:
                any_commit = False
                commit_np = np.full((budget,), S, np.int32)
                commit_pos_np = np.zeros((budget,), np.int32)
                for slot, r0, n, drafts, pos0 in spec_entries:
                    st = self._slots[slot]
                    if chunk_bad is not None and chunk_bad[
                        r0:r0 + n + 1
                    ].any():
                        # the whole span's K/V stays uncommitted (rollback
                        # = "never written"), so quarantining the slot
                        # cannot leave poisoned rows in shared pages
                        finished.append(self._quarantine(
                            slot, st,
                            "nonfinite logits in speculative span",
                        ))
                        continue
                    out = chunk_out[r0:r0 + n + 1]
                    m = 0
                    while m < n and int(out[m]) == drafts[m]:
                        m += 1
                    if self.paged and m > 0:
                        # accepted tokens become cache writes: clamp the
                        # accept length to pages the pool can actually
                        # supply (CoW-forking shared ones as usual)
                        ps = self.cache.page_size
                        for j in range(1, m + 1):
                            if not self._ensure_writable(
                                st, slot, (pos0 + j) // ps
                            ):
                                self._page_stalls += 1
                                m = j - 1
                                break
                    emit = drafts[:m] + [int(out[m])]
                    accepted = 0
                    done = None
                    for i, tok in enumerate(emit):
                        st.pos += 1
                        st.generated.append(int(tok))
                        self._generated_tokens += 1
                        if i < m:
                            accepted += 1
                            self._tokens_accepted += 1
                        done = self._finish_reason(st)
                        if done is not None:
                            break
                    if n - accepted > 0:
                        self._rollbacks += 1
                    if self.tracer.enabled:
                        track = f"req{st.req.request_id}"
                        self.tracer.add_span(
                            "draft", t_d0, t_d1, track=track, tokens=n,
                        )
                        self.tracer.add_span(
                            "verify", t0, t1, track=track,
                            drafted=n, accepted=accepted, slot=slot,
                        )
                        if n - accepted > 0:
                            self.tracer.instant(
                                "rollback", track=track,
                                rejected=n - accepted,
                            )
                    if done is not None:
                        # evicted slot: its uncommitted rows just die with
                        # the lease (paged pages are derefed by the evict)
                        finished.append(self._evict(slot, st, done))
                        continue
                    # commit the span's written prefix: the last token's
                    # row r0 (it was never in the cache — the scatter
                    # dropped it in-trace) plus the m accepted draft rows.
                    # The bonus token is NOT written: it is the slot's new
                    # trailing unwritten token, exactly like normal decode.
                    commit_np[r0:r0 + m + 1] = slot
                    commit_pos_np[r0:r0 + m + 1] = np.arange(
                        pos0, pos0 + m + 1
                    )
                    any_commit = True
                if any_commit:
                    if self.paged:
                        self._push_table()  # CoW forks from the clamp above
                    self.cache = self.programs.commit(
                        self.cache, chunk_kv,
                        jnp.asarray(commit_np), jnp.asarray(commit_pos_np),
                    )
            if self._window_allocator is not None:
                self._free_window_pages()
            self._close_tick()
        self._laps.append(("commit", time.perf_counter()))
        return finished, counts

    def _step_whole(
        self,
    ) -> Tuple[List[GenerationResult], Dict[str, Any]]:
        """Legacy whole-prompt prefill (the A/B baseline): one padded
        compiled prefill per admitted request — every other slot's
        decode WAITS on it (the head-of-line blocking the chunked
        scheduler removes) — then one decode step for the grid."""
        finished: List[GenerationResult] = []
        t_admit = time.perf_counter()
        pending = []  # (slot, device first-token)
        prefilled = 0  # prompt tokens
        for slot in range(self.num_slots):
            if self._slots[slot] is not None or not self._queue:
                continue
            req = self._queue.popleft()
            self._record_queue_wait(t_admit - req.enqueued_at)
            if self.tracer.enabled:
                self.tracer.add_span(
                    "queue_wait", req.enqueued_at, t_admit,
                    track=f"req{req.request_id}", slot=slot,
                )
            toks = np.zeros((1, self.max_prompt_len), np.int32)
            toks[0, : len(req.prompt)] = req.prompt
            # not fetched here: every admit of the tick shares ONE
            # fetch below
            (tok,), _, _, _, _ = self._run_program(
                "prefill", (toks, slot, len(req.prompt)), fetch=False,
            )
            self._admitted += 1
            self._prompt_tokens += len(req.prompt)
            prefilled += len(req.prompt)
            self._slots[slot] = _Slot(
                req=req, generated=[], pos=len(req.prompt),
                cursor=len(req.prompt), prefix=list(req.prompt),
                leased_at=t_admit, chunks=1,
            )
            pending.append((slot, tok))
        if pending:
            # ONE batched value fetch for every admit this tick (the
            # device sync) — the per-request int(tok) pull serialized
            # host and device once per admitted request
            first_toks = self._fetch([t for _, t in pending])
            now = time.perf_counter()
            self._prefill_seconds += now - t_admit
            for (slot, _), tok in zip(pending, first_toks):
                st = self._slots[slot]
                st.generated.append(int(tok))
                self._generated_tokens += 1
                st.first_token_at = now
                self._record_ttft(now - st.req.enqueued_at)
                if self.tracer.enabled:
                    self.tracer.add_span(
                        "prefill", st.leased_at, now,
                        track=f"req{st.req.request_id}",
                        tokens=len(st.req.prompt), slot=slot,
                    )
                done = self._finish_reason(st)
                if done is not None:
                    finished.append(self._evict(slot, st, done))

        # ---- decode ---------------------------------------------------
        active = np.array(
            [s is not None for s in self._slots], dtype=bool
        )
        self._guard_capacity(active)
        toks = None
        if active.any():
            tokens = np.array(
                [s.generated[-1] if s is not None else 0
                 for s in self._slots],
                np.int32,
            )
            poison = np.zeros((self.num_slots,), np.float32)
            (toks, bad_h), _, _, t0, t1 = self._run_program(
                "decode", (tokens, active, poison),
            )
            self._decode_seconds += t1 - t0
            self._decode_steps += 1
        with self.tracer.phase("engine.commit", track="engine"):
            counts = {
                "program": "whole",
                "model_passes": len(pending) + int(toks is not None),
                "chunk_tokens": 0,
                "prefill_tokens": prefilled,
                "decodes": int(active.sum()),
                "slots_busy": int(active.sum()),
                "admitted": len(pending),
            }
            for slot, state in enumerate(self._slots):
                if state is None or toks is None:  # no decode ran
                    continue
                if bad_h[slot]:
                    # a genuine model blow-up on one slot quarantines
                    # it on the legacy path too (the chaos harness's
                    # injection sites thread the chunked scheduler)
                    finished.append(self._quarantine(
                        slot, state, "nonfinite logits in decode",
                    ))
                    continue
                state.pos += 1  # the input token was written this step
                state.generated.append(int(toks[slot]))
                self._generated_tokens += 1
                done = self._finish_reason(state)
                if done is not None:
                    finished.append(self._evict(slot, state, done))
            self._close_tick()
        self._laps.append(("commit", time.perf_counter()))
        return finished, counts

    def _finish_reason(self, state: _Slot) -> Optional[str]:
        if (
            self.eos_id is not None
            and state.generated[-1] == self.eos_id
        ):
            return "eos"
        if len(state.generated) >= state.req.max_new_tokens:
            return "length"
        if state.pos >= self.capacity:
            # the next decode would need cache position `pos`; the
            # slot is full — forced eviction, never a clamped write
            return "capacity"
        return None

    def _evict(
        self, slot: int, state: _Slot, reason: str
    ) -> GenerationResult:
        self._slots[slot] = None
        self._evicted += 1
        if self.paged:
            self._release_slot_pages(state, slot)
        self._release_adapter(state)
        finished_at = time.perf_counter()
        req = state.req
        n_new = len(state.generated)
        # a request torn down BEFORE its first token (cancel/deadline/
        # quarantine mid-prefill) has no TTFT anchor — clamp to the
        # teardown time so the record stays sane
        first_at = state.first_token_at or finished_at
        # the jsonl-ready per-request completion record: the same
        # perf_counter anchors the tracer spans and `stats()` use, so
        # the three reports can never disagree about one request
        self._record_completion({
            "request_id": req.request_id,
            "finish_reason": reason,
            "prompt_tokens": len(req.prompt),
            "new_tokens": n_new,
            "chunks": state.chunks,
            "queue_wait_ms": 1e3 * (state.leased_at - req.enqueued_at),
            "ttft_ms": 1e3 * (first_at - req.enqueued_at),
            "tpot_ms": (
                1e3 * (finished_at - first_at)
                / max(n_new - 1, 1)
            ),
            "e2e_ms": 1e3 * (finished_at - req.enqueued_at),
            "tenant": req.tenant,
        })
        if self.tracer.enabled:
            track = f"req{req.request_id}"
            self.tracer.add_span(
                "decode", first_at, finished_at,
                track=track, tokens=n_new, slot=slot,
                request_id=req.request_id, trace_id=req.trace_id,
            )
            self.tracer.instant(
                "finish", ts=finished_at, track=track, reason=reason,
                request_id=req.request_id, trace_id=req.trace_id,
            )
        return GenerationResult(
            request_id=req.request_id,
            prompt=list(req.prompt),
            tokens=list(state.generated),
            finish_reason=reason,
        )
