"""Paged KV cache: block tables, int8 per-page scales, prefix sharing.

The contiguous `KVCache` leases one ``capacity``-row lane per slot, so
HBM scales with ``max_length × slots`` whether or not the tokens exist
— ROADMAP's "real ceiling on concurrent users". This module is the
vLLM-style answer (PagedAttention, arXiv 2309.06180), three
independently A/B-able rungs:

1. **Block tables** — all slots draw fixed-size pages from ONE shared
   pool; a ``(num_slots, pages_per_slot)`` int32 table maps each
   slot's logical positions onto pool pages. Memory in use scales
   with LIVE tokens; the decode read walks the pages a slot has live
   and no other (`flash_attention_decode_paged`: one grid step a slot
   and head block, the slot's own pages copied from the pool two
   ahead).
2. **int8 per-page quantization** — pools store int8 with one fp32
   scale per (page, head) (EQuARX's per-chunk-scale design, arXiv
   2506.17615, applied to cache bytes): cache HBM and decode DMA
   halve; dequantization happens inside the kernels' fp32
   accumulators (ops/paging.py owns the write-side requantize math).
3. **Copy-on-write prefix sharing** — `PrefixStore` hashes chains of
   page-aligned prompt blocks; a request whose prompt extends an
   already-materialized chain maps the shared pages by reference
   (no re-prefill — TTFT collapses for shared-system-prompt traffic)
   and `paged_fork` copies a page only when the borrower would WRITE
   into it.

Split of responsibilities: `PageAllocator`/`PrefixStore` are pure
host-side bookkeeping (no jax); `PagedKVCache` is the device pytree
whose write/advance methods keep the contiguous cache's signatures —
the engine (engine.py) is the only place the two halves meet, and
models/gpt.py keeps consuming a duck-typed cache pytree (it shares
the scatter/view math via ops/paging.py, never this package).
"""

import collections
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from rocm_apex_tpu.ops.paging import (
    paged_fork,
    paged_scatter,
    quantized_paged_scatter,
)

__all__ = [
    "PageAllocator", "PrefixStore", "PagedKVCache", "window_pages_per_slot",
]


def window_pages_per_slot(window: int, page_size: int, budget: int,
                          capacity: int) -> int:
    """The most pages of a window group one slot holds: while a chunk of
    ``budget`` rows is prefilled its first row still attends ``window -
    1`` keys before it, so ``window - 1 + budget`` positions are live,
    which straddle one page more than they fill; never more than the
    context's own pages."""
    live = window - 1 + budget
    return min(-(-live // page_size) + 1, -(-capacity // page_size))


class PageAllocator:
    """Host-side free-list + ref-count bookkeeping for the page pool.

    Pages are integers in ``[0, num_pages)``. A mapped page holds one
    ref per slot whose table points at it (prefix sharing = ref > 1).
    When the last ref drops the page either returns to the free list
    or — if it is registered in a `PrefixStore` — is PARKED on a
    reclaimable LRU: its bytes stay valid so a later request with the
    same prefix can revive it for free, but allocation pressure may
    reclaim it at any time (``on_evict`` fires so the store entry is
    dropped in the same motion). Allocation NEVER raises on
    exhaustion: ``alloc`` returns None and the engine backpressures
    (the request waits in prefill; nothing crashes).
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = num_pages
        self._free: collections.deque = collections.deque(range(num_pages))
        self._ref = [0] * num_pages
        # insertion order = LRU order (parked pages re-park at the end)
        self._parked: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict()
        )
        # called with the page id when a PARKED page is reclaimed for a
        # fresh allocation (the engine unregisters it from the store)
        self.on_evict = None

    @property
    def available(self) -> int:
        return len(self._free) + len(self._parked)

    @property
    def pages_used(self) -> int:
        """Pages currently holding a reference (live mappings only —
        parked prefix-cache pages are reclaimable, not 'used')."""
        return self.num_pages - self.available

    def alloc(self, n: int = 1) -> Optional[List[int]]:
        """n fresh pages (ref = 1 each), or None if fewer than n are
        available — all-or-nothing, so a partial grab never deadlocks
        two half-satisfied requests."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if self.available < n:
            return None
        out = []
        for _ in range(n):
            if self._free:
                page = self._free.popleft()
            else:
                page, _ = self._parked.popitem(last=False)  # LRU
                if self.on_evict is not None:
                    self.on_evict(page)
            self._ref[page] = 1
            out.append(page)
        return out

    def ref(self, page: int) -> None:
        """Add a reference — reviving the page off the parked LRU if a
        prefix match picked it up there."""
        if self._ref[page] == 0:
            if page not in self._parked:
                raise ValueError(
                    f"page {page} is free, not shareable; alloc() it"
                )
            del self._parked[page]
        self._ref[page] += 1

    def decref(self, page: int, park: bool = False) -> None:
        """Drop one reference. At zero the page returns to the free
        list, or parks on the reclaimable LRU when ``park`` (the
        engine parks store-registered pages). Refs can never go
        negative — that is a corrupted table, not a recoverable
        state."""
        if self._ref[page] <= 0:
            raise RuntimeError(
                f"page {page} decref below zero (double free)"
            )
        self._ref[page] -= 1
        if self._ref[page] == 0:
            if park:
                self._parked[page] = None
            else:
                self._free.append(page)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def snapshot(self) -> Dict[str, int]:
        """Counters for leak checks: a drained engine must return to
        the baseline snapshot (every page free or parked, no refs)."""
        return {
            "free": len(self._free),
            "parked": len(self._parked),
            "available": self.available,
            "refs": sum(self._ref),
        }

    def assert_consistent(self) -> None:
        """The PR-7 allocator invariants, as one assertable check —
        the robustness tests run it after EVERY teardown path
        (cancel, deadline, quarantine, preempt, requeue, drain):

        * free, parked, and referenced pages partition the pool
          (no page in two states, none lost);
        * no parked or free page holds a reference;
        * no referenced page sits on the free list or the parked LRU.

        Raises AssertionError naming the corrupted page otherwise."""
        free = set(self._free)
        parked = set(self._parked)
        assert len(free) == len(self._free), (
            f"free list holds duplicates: {sorted(self._free)}"
        )
        assert not (free & parked), (
            f"pages both free and parked: {sorted(free & parked)}"
        )
        for page in range(self.num_pages):
            refs = self._ref[page]
            assert refs >= 0, f"page {page} has negative refs ({refs})"
            if page in free or page in parked:
                assert refs == 0, (
                    f"page {page} is free/parked with refs={refs}"
                )
            else:
                assert refs > 0, (
                    f"page {page} leaked: not free, not parked, "
                    f"refs=0"
                )


class _StoreEntry:
    __slots__ = ("key", "parent", "tokens", "page")

    def __init__(self, key, parent, tokens, page):
        self.key = key
        self.parent = parent
        self.tokens = tokens
        self.page = page


class PrefixStore:
    """Chain-hash registry of immutable, fully-written prompt pages.

    A page is registerable once it holds ``page_size`` PROMPT tokens
    (appends only ever land past a full page, so its bytes are final;
    pages mixing prompt and generated tokens are never registered).
    The key of a page is the chain ``(parent_key, its page_size token
    ids)`` — two requests share a page only if their ENTIRE token
    history up to that page matches, which is exactly the condition
    under which the K/V bytes are identical (absolute positions).

    `match` walks a prompt down the chain: full-page hits map by
    reference; after the last full hit, the longest token-level prefix
    of any CHILD page is matched PARTIALLY — the borrower reads the
    shared page's first j rows and must copy-on-write before its own
    tokens land in that page. At least one prompt token is always left
    unmatched (the final token must run through the model to produce
    the first sampled logits).
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self._by_chain: Dict[Any, _StoreEntry] = {}
        self._children: Dict[Any, Set[_StoreEntry]] = {}
        self._by_page: Dict[int, _StoreEntry] = {}
        # optional pub/sub hooks, called as ``hook(chain_key, page)``
        # when a registration appears/disappears in THIS store — the
        # router's cross-replica SharedPrefixRegistry subscribes here.
        # Chain keys are pure token tuples, so a subscriber can index
        # them without holding any store state.
        self.on_register = None
        self.on_unregister = None

    def __len__(self) -> int:
        return len(self._by_page)

    def is_registered(self, page: int) -> bool:
        return page in self._by_page

    def register(
        self, parent_key, tokens: Sequence[int], page: int
    ):
        """Register a full page (its ``page_size`` token ids) under
        ``parent_key`` (None for the first page of a prompt); returns
        the new chain key for the NEXT page's parent. First
        registration wins: a duplicate chain keeps the existing page
        (the caller's page simply stays private)."""
        tokens = tuple(int(t) for t in tokens)
        if len(tokens) != self.page_size:
            raise ValueError(
                f"register needs exactly page_size={self.page_size} "
                f"tokens, got {len(tokens)}"
            )
        key = (parent_key, tokens)
        if key in self._by_chain:
            return key
        entry = _StoreEntry(key, parent_key, tokens, page)
        self._by_chain[key] = entry
        self._children.setdefault(parent_key, set()).add(entry)
        self._by_page[page] = entry
        if self.on_register is not None:
            self.on_register(key, page)
        return key

    def chain_key(self, parent_key, tokens: Sequence[int]):
        """The key `register` would produce — lets a slot continue a
        chain it is re-walking without registering anything."""
        return (parent_key, tuple(int(t) for t in tokens))

    def unregister_page(self, page: int) -> None:
        entry = self._by_page.pop(page, None)
        if entry is None:
            return
        del self._by_chain[entry.key]
        if self.on_unregister is not None:
            self.on_unregister(entry.key, page)
        kids = self._children.get(entry.parent)
        if kids is not None:
            kids.discard(entry)
            if not kids:
                del self._children[entry.parent]
        # orphaned descendants (their parent chain is gone) can no
        # longer be matched — drop them so they do not pin pages
        for child in list(self._children.get(entry.key, ())):
            self.unregister_page(child.page)

    def match(
        self, prompt: Sequence[int]
    ) -> Tuple[List[int], int, int, Any]:
        """Longest shared prefix of ``prompt`` already materialized.

        Returns ``(pages, matched_tokens, partial_tokens, chain_key)``:
        the shared pages in order, how many prompt tokens they cover
        (``< len(prompt)``), how many of those are a PARTIAL borrow of
        the last page (0 = every matched page is fully covered), and
        the chain key of the last FULL page matched (the parent under
        which the borrower registers its next full page).
        """
        ps = self.page_size
        limit = len(prompt) - 1  # leave >= 1 token to prefill
        pages: List[int] = []
        key = None
        m = 0
        while m + ps <= limit:
            entry = self._by_chain.get(
                (key, tuple(int(t) for t in prompt[m:m + ps]))
            )
            if entry is None:
                break
            pages.append(entry.page)
            key = entry.key
            m += ps
        best = None
        best_len = 0
        rest = [int(t) for t in prompt[m:limit]]
        if rest:
            for child in self._children.get(key, ()):
                n = 0
                for a, b in zip(child.tokens, rest):
                    if a != b:
                        break
                    n += 1
                if n > best_len:
                    best, best_len = child, n
        if best is not None:
            pages.append(best.page)
            m += best_len
        return pages, m, best_len, key


@struct.dataclass
class PagedKVCache:
    """Device half of the paged cache; a jit-friendly pytree.

    ``k``/``v``: per-layer POOLS, ``(num_pages, heads_local,
    page_size, head_dim)`` (heads ahead of the page rows so a
    (page, head) tile is the trailing-two-dims block the Pallas paged
    kernel fetches natively). ``k_scale``/``v_scale``: per-layer
    ``(num_pages, heads_local)`` fp32 when the pools are int8, else
    None. ``page_table``: ``(num_slots, pages_per_slot)`` int32 —
    unmapped entries hold the sentinel ``num_pages`` (writes there
    drop; the host engine owns the mapping and mirrors it).
    ``lengths`` as in `KVCache`.

    `write`/`write_at` keep the contiguous cache's signatures — the
    indirection is resolved inside (ops/paging.py) — so the model's
    cached attention calls the same protocol either way.

    The engine builds the cache from what the served model DECLARES
    each layer keeps per request (`from_spec` over
    ``model.cache_spec()``). ``k``/``v`` hold one pool per layer that
    ATTENDS, in layer order, so the page methods and the engine's
    allocator do not care how many layers that is. ``ssm``/``conv``
    hold, per layer that SCANS, the slots' recurrent state ``(slots,
    state dim, heads * head dim)`` and the rows that last entered its
    convolution ``(slots, d_conv - 1, conv dim)``: neither lives in
    pages (a slot's state is overwritten in place), so nothing that
    shares, ships or defers pages can carry it, and the engine refuses
    those options for a model that declares such a layer. A slot's
    state needs no reset call: ``lengths`` is the engine's own cursor,
    and the model starts a slot whose length is 0 from zero.
    ``latent`` holds one pool per LATENT-attention block, in block
    order: ``(num_pages, 1, page_size, width)``, a position's row being
    the block's compressed K/V latent (``rank`` values) then its rotated
    positional key (``rope`` values), shared by all of the block's
    heads, then zeros up to ``width`` (`ops/mla.py::latent_width`: a
    multiple of 128 lanes, or the chip would store the pool with its
    rows along the lanes). A latent pool lives behind the same page
    table as K/V and is forked with it; a model may declare latent
    blocks and no K/V layer at all.

    ``counters`` (None unless a layer declares ``counters``): the
    tick's sums over layers and over the step program's applies, named
    by ``counter_names``; `start_tick` zeroes them and the engine
    fetches them with the tick's tokens. ``routes`` (None unless a
    layer declares ``route_words``, a model's debug option): one more
    pool, ``(num_pages, 1, page_size, lanes)`` uint32 behind the same
    page table, a position's row holding layer after layer the words
    of the mask of experts its router chose.

    A K/V layer may declare a sliding ``window``: it attends the
    ``window`` keys that end at a row's own and no further back. The
    pools of such layers (``window_k``/``window_v``, in layer order)
    form a group of their own: ``(window_pages, heads, page_size,
    head_dim)`` behind ``window_table`` (the shape of ``page_table``;
    unmapped entries hold ``window_pages``), with an allocator of its
    own on the host. The engine unmaps and frees a page of this group
    once it lies wholly behind every key the slot's next row can
    attend, while the request runs, so a slot holds a window's worth of
    them and not its whole context; ``k``/``v`` then hold the layers
    that attend every earlier key (the GLOBAL group). ``window`` is the
    group's length in keys, 0 where no layer declares one.
    """

    k: Tuple[jnp.ndarray, ...]
    v: Tuple[jnp.ndarray, ...]
    k_scale: Optional[Tuple[jnp.ndarray, ...]]
    v_scale: Optional[Tuple[jnp.ndarray, ...]]
    page_table: jnp.ndarray
    lengths: jnp.ndarray
    page_size: int = struct.field(pytree_node=False, default=16)
    ssm: Tuple[jnp.ndarray, ...] = ()
    conv: Tuple[jnp.ndarray, ...] = ()
    routes: Optional[jnp.ndarray] = None
    counters: Optional[jnp.ndarray] = None
    latent: Tuple[jnp.ndarray, ...] = ()
    window_k: Tuple[jnp.ndarray, ...] = ()
    window_v: Tuple[jnp.ndarray, ...] = ()
    window_table: Optional[jnp.ndarray] = None
    window: int = struct.field(pytree_node=False, default=0)

    COUNTER_NAMES = (
        "moe_assignments", "moe_experts_touched", "moe_load_max",
        "state_slots_live", "moe_zero_assignments", "latent_rows_read",
    )
    # a cache with a window group counts two more: the cached positions
    # the decode grid attended over after the window's bound, and before
    WINDOW_COUNTER_NAMES = ("kv_rows_read", "kv_rows_cached")

    @property
    def counter_names(self) -> Tuple[str, ...]:
        """The names of ``counters``' entries, in order."""
        return self.COUNTER_NAMES + (
            self.WINDOW_COUNTER_NAMES if self.window else ())

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def create(
        cls,
        num_layers: int,
        num_slots: int,
        capacity: int,
        num_heads: int,
        head_dim: int,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        dtype: Any = jnp.bfloat16,
        quantized: bool = False,
        validate_tpu_layout: Optional[bool] = None,
    ) -> "PagedKVCache":
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        pool_dtype = jnp.int8 if quantized else dtype
        if validate_tpu_layout is None:
            validate_tpu_layout = jax.default_backend() == "tpu"
        if validate_tpu_layout:
            # TPU-silicon constraint (deferred from the paged-kernel
            # PR): the paged flash kernels DMA (page, head) tiles whose
            # second-minor dim is page_size, so it must be a sublane
            # multiple for the pool dtype — 8 rows × 4 bytes packed,
            # i.e. 8 for fp32, 16 for bf16, 32 for int8. A non-multiple
            # page relayouts every pool tile on each read.
            sublanes = 32 // jnp.dtype(pool_dtype).itemsize
            if page_size % sublanes != 0:
                raise ValueError(
                    f"page_size={page_size} is not a sublane multiple "
                    f"for {jnp.dtype(pool_dtype).name} pools: the TPU "
                    f"paged kernels need page_size % {sublanes} == 0 "
                    f"(8 for fp32, 16 for bf16, 32 for int8)"
                )
        pages_per_slot = -(-capacity // page_size)  # ceil
        if num_pages is None:
            # worst-case default: every slot full — safe, but the
            # memory win comes from sizing num_pages to expected LIVE
            # tokens (see docs/inference.md)
            num_pages = num_slots * pages_per_slot
        shape = (num_pages, num_heads, page_size, head_dim)
        scales = (
            tuple(
                jnp.zeros((num_pages, num_heads), jnp.float32)
                for _ in range(num_layers)
            )
            if quantized else None
        )
        return cls(
            k=tuple(jnp.zeros(shape, pool_dtype) for _ in range(num_layers)),
            v=tuple(jnp.zeros(shape, pool_dtype) for _ in range(num_layers)),
            k_scale=scales,
            v_scale=None if scales is None else tuple(
                jnp.zeros((num_pages, num_heads), jnp.float32)
                for _ in range(num_layers)
            ),
            page_table=jnp.full(
                (num_slots, pages_per_slot), num_pages, jnp.int32
            ),
            lengths=jnp.zeros((num_slots,), jnp.int32),
            page_size=page_size,
        )

    @classmethod
    def for_model(
        cls,
        cfg,
        num_slots: int,
        capacity: Optional[int] = None,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        dtype: Any = None,
        quantized: bool = False,
        full_heads: bool = False,
    ) -> "PagedKVCache":
        """Paged cache sized for a `GPTConfig`-shaped config (same
        duck-typing as `KVCache.for_model`; heads are the LOCAL
        per-TP-rank count). ``full_heads=True`` keeps the GLOBAL head
        count instead — the tp>1 serving engine builds the pools at
        full heads and lays them out with a head-sharded
        `NamedSharding`, so each chip holds 1/tp of the heads while
        host-side fetches still see full-head arrays (which is what
        makes shipped pages tp-agnostic)."""
        tp = 1 if full_heads else (cfg.tensor_parallel_size or 1)
        return cls.create(
            cfg.num_layers,
            num_slots,
            capacity or cfg.max_position_embeddings,
            cfg.num_attention_heads // tp,
            cfg.head_dim,
            page_size=page_size,
            num_pages=num_pages,
            dtype=dtype if dtype is not None else cfg.dtype,
            quantized=quantized,
        )

    @classmethod
    def from_spec(
        cls,
        spec: Sequence[Dict[str, Any]],
        num_slots: int,
        capacity: int,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        dtype: Any = jnp.bfloat16,
        quantized: bool = False,
        prefill_token_budget: Optional[int] = None,
    ) -> "PagedKVCache":
        """The cache of a model whose layers declare what they keep, a
        list with one entry per thing kept (a layer with two attention
        blocks declares two): ``dict(kind="kv", heads=, head_dim=)`` for
        one that attends over K and V (the GLOBAL head count: the engine
        shards the pools itself), ``dict(kind="latent", rank=, rope=)``
        for one that attends over a shared latent row, ``dict(kind="ssm",
        state=, conv=, state_dtype=)`` for one that scans; any entry may
        add ``counters=True`` and ``route_words=<n>``. Something has to
        live in pages (``kv`` or ``latent``): the engine schedules by
        them.

        A ``kv`` entry may add ``window=<keys>``: those layers' pools
        form the window group (one window length serves them all).
        ``num_pages`` is then the global group's count or the pair
        ``(global, window)``; a window group given no count gets its
        worst case, `window_pages_per_slot` a slot (it never stalls)."""
        from rocm_apex_tpu.ops.mla import latent_width

        window_pages = None
        if isinstance(num_pages, (tuple, list)):
            num_pages, window_pages = num_pages

        by_kind = {"kv": [], "latent": [], "ssm": []}
        for entry in spec:
            if entry["kind"] not in by_kind:
                raise ValueError(
                    f"a layer declares kind {entry['kind']!r}; the cache "
                    f"knows {sorted(by_kind)}")
            by_kind[entry["kind"]].append(entry)
        ssm_layers = by_kind["ssm"]
        windowed = [s for s in by_kind["kv"] if s.get("window")]
        kv_layers = [s for s in by_kind["kv"] if not s.get("window")]
        windows = {int(s["window"]) for s in windowed}
        if len(windows) > 1:
            raise ValueError(
                f"K/V layers declare windows {sorted(windows)}; one "
                f"window group serves one length")
        if windowed and quantized:
            raise ValueError("a window group has no int8 form")
        if window_pages is not None and not windowed:
            raise ValueError(
                "num_pages names a window group's pages and no layer "
                "declares a window")
        if not by_kind["kv"] and not by_kind["latent"]:
            raise ValueError(
                "the engine schedules by pages: a model that keeps "
                "neither K/V nor latent rows has none to schedule by")
        shapes = {(s["heads"], s["head_dim"]) for s in by_kind["kv"]}
        if len(shapes) > 1:
            raise ValueError(
                f"K/V layers differ in (heads, head_dim): {sorted(shapes)}; "
                f"one pool shape serves them all")
        heads, head_dim = shapes.pop() if shapes else (1, 1)
        if quantized and by_kind["latent"]:
            raise ValueError("a latent pool has no int8 form")
        cache = cls.create(
            len(kv_layers), num_slots, capacity, heads, head_dim,
            page_size=page_size, num_pages=num_pages, dtype=dtype,
            quantized=quantized,
        )
        pages = (
            num_slots * cache.pages_per_slot if num_pages is None
            else num_pages)
        words = sum(s.get("route_words", 0) for s in spec)
        if windowed:
            window = windows.pop()
            if window_pages is None:
                window_pages = min(pages, num_slots * window_pages_per_slot(
                    window, page_size, prefill_token_budget or capacity,
                    capacity))
            shape = (window_pages, heads, page_size, head_dim)
            cache = cache.replace(
                window_k=tuple(jnp.zeros(shape, dtype) for _ in windowed),
                window_v=tuple(jnp.zeros(shape, dtype) for _ in windowed),
                window_table=jnp.full(
                    (num_slots, cache.pages_per_slot), window_pages,
                    jnp.int32),
                window=window)
        return cache.replace(
            latent=tuple(
                jnp.zeros(
                    (pages, 1, page_size, latent_width(s["rank"], s["rope"])),
                    dtype)
                for s in by_kind["latent"]),
            ssm=tuple(
                jnp.zeros((num_slots,) + tuple(s["state"]), s["state_dtype"])
                for s in ssm_layers),
            conv=tuple(
                jnp.zeros((num_slots,) + tuple(s["conv"]), dtype)
                for s in ssm_layers),
            routes=jnp.zeros(
                (pages, 1, page_size, -(-words // 128) * 128),
                jnp.uint32) if words else None,
            counters=jnp.zeros((len(cache.counter_names),), jnp.int32)
            if any(s.get("counters") for s in spec) else None,
        )

    def count(self, **sums) -> "PagedKVCache":
        """The tick's counters with ``sums`` (by their `counter_names`)
        added in; ``moe_load_max`` is a running maximum."""
        new = list(self.counters)
        for name, value in sums.items():
            i = self.counter_names.index(name)
            new[i] = (
                jnp.maximum(new[i], value) if name == "moe_load_max"
                else new[i] + value)
        return self.replace(counters=jnp.stack(new).astype(jnp.int32))

    def start_tick(self) -> "PagedKVCache":
        """The tick's counters from zero (a cache that keeps none is
        returned as it is)."""
        if self.counters is None:
            return self
        return self.replace(counters=jnp.zeros_like(self.counters))

    # ------------------------------------------------------------------
    # shape facts
    # ------------------------------------------------------------------

    @property
    def num_layers(self) -> int:
        return len(self.k)

    @property
    def num_slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    @property
    def num_pages(self) -> int:
        """Pages of the group behind ``page_table`` (a model whose K/V
        layers all declare a window keeps that table for its scheduling
        alone, over as many pages as the window group has)."""
        pools = self.k or self.latent or self.window_k
        return pools[0].shape[0]

    @property
    def window_pages(self) -> int:
        """Pages of the window group (0 where there is none)."""
        return self.window_k[0].shape[0] if self.window_k else 0

    @property
    def capacity(self) -> int:
        """Rows addressable per slot. May exceed a requested capacity
        that does not divide page_size (the engine's host bound stays
        authoritative)."""
        return self.pages_per_slot * self.page_size

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def cache_bytes(self) -> int:
        """Device bytes this cache actually allocates (pools + scales
        + table + lengths) — the number the bench's cache-bytes line
        reports against the contiguous equivalent."""
        total = 0
        extra = tuple(
            a for a in (self.routes, self.counters) if a is not None)
        if self.window_table is not None:
            extra += (self.window_table,)
        for arrs in (self.k, self.v, self.k_scale or (), self.v_scale or (),
                     self.latent, self.ssm, self.conv, self.window_k,
                     self.window_v, extra):
            for a in arrs:
                total += a.size * a.dtype.itemsize
        total += self.page_table.size * self.page_table.dtype.itemsize
        total += self.lengths.size * self.lengths.dtype.itemsize
        return total

    # ------------------------------------------------------------------
    # functional updates (all jit-safe)
    # ------------------------------------------------------------------

    def _scatter(self, layer, slots, positions, k_new, v_new):
        k = list(self.k)
        v = list(self.v)
        if self.quantized:
            ks = list(self.k_scale)
            vs = list(self.v_scale)
            k[layer], ks[layer] = quantized_paged_scatter(
                self.k[layer], self.k_scale[layer], self.page_table,
                slots, positions, k_new,
            )
            v[layer], vs[layer] = quantized_paged_scatter(
                self.v[layer], self.v_scale[layer], self.page_table,
                slots, positions, v_new,
            )
            return self.replace(
                k=tuple(k), v=tuple(v),
                k_scale=tuple(ks), v_scale=tuple(vs),
            )
        k[layer] = paged_scatter(
            self.k[layer], self.page_table, slots, positions, k_new
        )
        v[layer] = paged_scatter(
            self.v[layer], self.page_table, slots, positions, v_new
        )
        return self.replace(k=tuple(k), v=tuple(v))

    def write(self, layer: int, k_new: jnp.ndarray, v_new: jnp.ndarray
              ) -> "PagedKVCache":
        """`KVCache.write` semantics — ``(num_slots, t, heads, hd)``
        new rows land at each slot's current length — scattered
        through the page table. Positions at/past capacity DROP
        (where the contiguous cache clamped onto its last row, a
        paged write must never land in somebody else's page); lengths
        do not advance here."""
        num_slots, t = k_new.shape[0], k_new.shape[1]
        slots = jnp.repeat(jnp.arange(num_slots, dtype=jnp.int32), t)
        positions = (
            self.lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        ).reshape(-1)
        h, hd = k_new.shape[2], k_new.shape[3]
        return self._scatter(
            layer, slots, positions,
            k_new.reshape(num_slots * t, h, hd),
            v_new.reshape(num_slots * t, h, hd),
        )

    def write_at(
        self,
        layer: int,
        slots: jnp.ndarray,
        positions: jnp.ndarray,
        k_new: jnp.ndarray,
        v_new: jnp.ndarray,
    ) -> "PagedKVCache":
        """`KVCache.write_at` semantics (packed chunk at explicit
        per-token destinations; pad tokens carry slot id >= num_slots
        and drop) routed through the page table. The drop path is
        what lets speculative drafts defer their commit: a rejected
        draft row is simply never scattered, so it can never have
        touched a shared (CoW) page or grown an int8 page scale —
        the engine's post-verification commit re-issues only the
        accepted rows."""
        return self._scatter(layer, slots, positions, k_new, v_new)

    def advance(self, t: int, active: Optional[jnp.ndarray] = None
                ) -> "PagedKVCache":
        """`KVCache.advance` semantics. The clamp only keeps idle
        slots from drifting — the ENGINE is responsible for never
        letting a live request reach capacity (it raises host-side
        with the slot id; see `InferenceEngine`), and the paged write
        path independently drops at-capacity writes instead of
        clamping them into a live page."""
        new = jnp.minimum(self.lengths + t, self.capacity)
        if active is not None:
            new = jnp.where(active, new, self.lengths)
        return self.replace(lengths=new)

    def reset_slot(self, slot) -> "PagedKVCache":
        """Forget a slot's length. The page-table row is HOST state —
        the engine sentinels its mirror and pushes it with the next
        step (stale device entries are unreachable meanwhile: every
        read is bounded by lengths)."""
        return self.replace(
            lengths=jax.lax.dynamic_update_slice(
                self.lengths, jnp.zeros((1,), jnp.int32), (slot,)
            )
        )

    def fork_page(self, src, dst) -> "PagedKVCache":
        """Copy-on-write device half: duplicate page ``src`` onto
        ``dst`` in every pool behind the page table (K/V and their
        scales, latent rows, the routing log). ``src``/``dst`` may be
        traced — the engine jits this once and calls it for every
        fork."""
        def fork(pools):
            return tuple(paged_fork(b, src, dst) for b in pools)

        cache = self.replace(
            k=fork(self.k), v=fork(self.v), latent=fork(self.latent),
            routes=None if self.routes is None
            else paged_fork(self.routes, src, dst))
        if not self.quantized:
            return cache
        return cache.replace(
            k_scale=tuple(s.at[dst].set(s[src]) for s in self.k_scale),
            v_scale=tuple(s.at[dst].set(s[src]) for s in self.v_scale),
        )
