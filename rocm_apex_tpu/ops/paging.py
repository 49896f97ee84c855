"""Device-side paged KV-cache primitives: block-table indirection math.

The paged cache (rocm_apex_tpu/inference/paging.py) replaces the
contiguous per-slot ``(num_slots, capacity, heads, head_dim)`` buffers
with fixed-size PAGES drawn from one shared pool — vLLM's
PagedAttention layout (arXiv 2309.06180) — so HBM scales with LIVE
tokens instead of ``slots × capacity``. This module owns the pure-jnp
transforms every consumer shares:

* ``paged_scatter`` / ``quantized_paged_scatter`` — the write path:
  tokens land at host-resolved ``(slot, position)`` destinations,
  routed through the ``(num_slots, pages_per_slot)`` page table to
  ``(page, offset)`` pool rows. Invalid destinations (padding slots,
  positions at/past capacity, unmapped table entries) carry the
  out-of-range page sentinel and are DROPPED by the scatter — a paged
  write can never clamp into a live (possibly SHARED) page the way the
  contiguous cache's dynamic_update_slice clamped at capacity.
* ``paged_view`` — the reference read path: gather the pool through
  the table back into the contiguous ``(num_slots, capacity, …)``
  layout (+ dequantization). The jnp attention fallback reads this
  view, which makes paged-vs-contiguous parity BIT-exact there; the
  flash path instead copies each slot's live pages in-kernel
  (`flash_attention_decode_paged`: a grid step a slot and head block,
  a loop over the pages that slot has live) and never materializes it.
* ``paged_fork`` — the copy-on-write primitive: duplicate one page's
  rows (pool + scales) so a prefix-sharing slot can diverge without
  touching its sharers' bytes.

int8 quantization is per-(page, head): one fp32 scale covers a page's
``page_size`` tokens per head (the EQuARX per-chunk-scale design,
arXiv 2506.17615, applied to cache bytes — halves both HBM and the
decode DMA). Scales only GROW; when a write raises a page's scale the
page's existing int8 rows are requantized in the same scatter
(``q' = round(q · old/new)``, ratio ≤ 1 so no overflow), so every row
of a page is always consistent with the page's current scale.

Pool layout is ``(num_pages, heads, page_size, head_dim)`` — heads
AHEAD of the page rows (the ISSUE sketch writes (num_pages, page_size,
heads, head_dim)) so a single (page, head) tile is the pool's LAST TWO
dims: the Pallas paged-decode kernel copies a page's ``(head block,
page_size, head_dim)`` slab as one contiguous piece, whole tiles that
Mosaic lays out natively, instead of a sublane-degenerate ``(1,
page_size, 1, head_dim)`` slice.

A WRITE IS A TILE GROUP, NOT A ROW. In that layout one token's write
is one row of each head's ``(page_size, head_dim)`` tile, and on a TPU a
16-bit row is HALF of a packed 32-bit sublane (tiling ``T(8,128)(2,1)``:
rows ``2i`` and ``2i+1`` share words). XLA's scatter does not write
half-sublanes: given ``pool.at[pages, :, offs].set(x)`` it re-laid the
WHOLE pool to ``(page, row, head, head_dim)``, scattered, and re-laid it
back, twice 168 MB of traffic per pool per write, 25 ms of a 45 ms
decode tick at the 1.3B serving geometry (PERF.md, PR 25).
``paged_scatter`` therefore writes at the granularity the pool is
stored in: ``G = gcd(page_size, 32 // itemsize)`` rows (16 bf16, 8 fp32;
the number `PagedKVCache.create` validates ``page_size`` against on a
TPU). The pool is VIEWED as ``(pages, heads, page_size // G, G,
head_dim)``, a bitcast; each token's ``(heads, G, head_dim)`` group is
gathered, the rows this call writes into it are replaced, and whole
groups are scattered back, which the compiler does in place in the
stored layout (`tests/L0/test_paged_write_compiled.py` holds the
compiled serving programs to that). On the small pages of the CPU suite
the gcd shrinks ``G``, down to 1, which is the row scatter: one path,
chosen by shape and dtype. `quantized_paged_scatter` still writes rows
after its page-level rewrite (int8 pools: no benchmark cell runs them).

This module lives in ``ops`` (not ``inference``) so the model layer
can share it: models/gpt.py consumes any cache pytree without
importing the inference package (the PR-1 layering rule), but both
sides must agree byte-for-byte on the scatter/view math.
"""

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "paged_destinations",
    "paged_scatter",
    "quantized_paged_scatter",
    "paged_view",
    "paged_fork",
]


def paged_destinations(
    page_table: jnp.ndarray,
    slots: jnp.ndarray,
    positions: jnp.ndarray,
    page_size: int,
    num_pages: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Resolve per-token ``(slot, position)`` to ``(page, offset)``.

    Invalid tokens — slot outside ``[0, num_slots)``, position outside
    ``[0, capacity)``, or an unmapped table entry (the host fills
    unallocated entries with ``num_pages``) — come back with
    ``page == num_pages``: the scatter sentinel ``mode="drop"``
    discards. Valid ``page`` values are clamped into range only via
    the table contents themselves (the host owns the mapping).
    """
    num_slots, pages_per_slot = page_table.shape
    capacity = pages_per_slot * page_size
    valid = (
        (slots >= 0)
        & (slots < num_slots)
        & (positions >= 0)
        & (positions < capacity)
    )
    sl = jnp.clip(slots, 0, num_slots - 1)
    pos = jnp.clip(positions, 0, capacity - 1)
    pages = jnp.where(valid, page_table[sl, pos // page_size], num_pages)
    return pages, pos % page_size


# jitted: a serving program calls this for K and V of every layer with
# the same shapes, so the body is traced and lowered once per program
# and not 2 x layers times (set-up is mostly JAX tracing: PERF.md)
@jax.jit
def paged_scatter(
    pool: jnp.ndarray,
    page_table: jnp.ndarray,
    slots: jnp.ndarray,
    positions: jnp.ndarray,
    x: jnp.ndarray,
) -> jnp.ndarray:
    """Scatter ``x`` (tokens, heads, head_dim) into the pool at the
    table-resolved destinations. Exact (no quantization): the stored
    bytes equal the contiguous cache's ``.at[slot, pos].set`` bytes,
    which is what makes paged-vs-contiguous greedy parity exact.

    The write is made in whole TILE GROUPS of ``G`` rows, not in rows
    (module docstring): each token gathers the ``(heads, G, head_dim)``
    group its row lives in, replaces the rows that THIS call writes
    into that group, all of them, and scatters the group back. Several
    tokens of one group (a chunk's run of positions) therefore scatter
    identical bytes, whichever lands last. Destinations are taken to be
    distinct, as for the row scatter; dropped tokens carry the page
    sentinel and write nothing. ``G`` follows from the pool's dtype and
    page size; at ``G == 1`` this IS the row scatter."""
    num_pages, heads, page_size, head_dim = pool.shape
    pages, offs = paged_destinations(
        page_table, slots, positions, page_size, num_pages
    )
    g = math.gcd(page_size, 32 // pool.dtype.itemsize)
    grp, row = offs // g, offs % g
    view = pool.reshape(num_pages, heads, page_size // g, g, head_dim)
    # hit[token, source, r]: the source's row is row r of the token's
    # group (a dropped source's page is the sentinel, which no live
    # token has)
    cell = pages * (page_size // g) + grp
    hit = (cell[:, None] == cell[None, :])[:, :, None] & (
        row[None, :, None] == jnp.arange(g, dtype=row.dtype)
    )
    written = jnp.any(hit, axis=1)
    src = jnp.argmax(hit, axis=1)  # (tokens, g)
    new = x.astype(pool.dtype)[src].transpose(0, 2, 1, 3)
    old = view[jnp.clip(pages, 0, num_pages - 1), :, grp]
    groups = jnp.where(written[:, None, :, None], new, old)
    view = view.at[pages, :, grp].set(groups, mode="drop")
    return view.reshape(pool.shape)


def quantized_paged_scatter(
    pool: jnp.ndarray,
    scale: jnp.ndarray,
    page_table: jnp.ndarray,
    slots: jnp.ndarray,
    positions: jnp.ndarray,
    x: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8 write with per-(page, head) fp32 scales.

    ``pool`` int8 ``(num_pages, heads, page_size, head_dim)``;
    ``scale`` fp32 ``(num_pages, heads)``; ``x`` float
    ``(tokens, heads, head_dim)``. Three phases, all one fused scatter
    chain under jit:

    1. scatter-max the incoming per-token absmax into the touched
       pages' scales (scales never shrink — a page's scale is the max
       absmax it has ever held);
    2. requantize the touched pages' EXISTING rows by
       ``old_scale / new_scale`` (1.0 exactly for untouched pages and
       for touched pages whose scale did not move, so the common
       steady-state write rewrites bytes unchanged);
    3. quantize the new tokens with the new scale and scatter them.

    Duplicate destination pages (several chunk tokens in one page) are
    safe: every duplicate writes the identical requantized content.
    Invalid tokens are dropped by the same sentinel as `paged_scatter`.
    """
    num_pages, _, page_size, _ = pool.shape
    pages, offs = paged_destinations(
        page_table, slots, positions, page_size, num_pages
    )
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)  # (tokens, heads)
    contrib = jnp.zeros_like(scale).at[pages].max(absmax, mode="drop")
    new_scale = jnp.maximum(scale, contrib / 127.0)
    safe = jnp.where(new_scale > 0.0, new_scale, 1.0)
    ratio = jnp.where(new_scale > 0.0, scale / safe, 1.0)

    pg = jnp.clip(pages, 0, num_pages - 1)
    old_rows = pool[pg].astype(jnp.float32)  # (tokens, heads, ps, hd)
    resc = jnp.round(old_rows * ratio[pg][:, :, None, None])
    pool = pool.at[pages].set(resc.astype(pool.dtype), mode="drop")
    q = jnp.clip(jnp.round(xf / safe[pg][:, :, None]), -127.0, 127.0)
    pool = pool.at[pages, :, offs].set(q.astype(pool.dtype), mode="drop")
    return pool, new_scale


def paged_view(
    pool: jnp.ndarray,
    page_table: jnp.ndarray,
    scale: Optional[jnp.ndarray] = None,
    out_dtype=None,
) -> jnp.ndarray:
    """Gather the pool through the table into the CONTIGUOUS layout:
    ``(num_slots, pages_per_slot · page_size, heads, head_dim)``.

    The jnp reference attention reads this (bit-identical to the
    contiguous cache when unquantized; dequantized to fp32 when
    ``scale`` is given). Unmapped entries (sentinel ``num_pages``)
    clamp onto the last pool page — harmless garbage, because every
    attention read is bounded by the slot's live length. This
    materializes O(slots·capacity) — the FLASH path must not call it
    (`flash_attention_decode_paged` gathers page tiles in-kernel);
    it exists for the jnp fallback and for tests/debug dumps.
    """
    num_pages, heads, page_size, head_dim = pool.shape
    num_slots, pages_per_slot = page_table.shape
    tab = jnp.clip(page_table, 0, num_pages - 1)
    g = pool[tab]  # (slots, P, heads, ps, hd)
    if scale is not None:
        g = g.astype(jnp.float32) * scale[tab][:, :, :, None, None]
    g = g.transpose(0, 1, 3, 2, 4).reshape(
        num_slots, pages_per_slot * page_size, heads, head_dim
    )
    if out_dtype is not None:
        g = g.astype(out_dtype)
    return g


def paged_fork(
    pool: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
) -> jnp.ndarray:
    """Copy page ``src``'s rows onto page ``dst`` — the device half of
    copy-on-write (the host remaps the forking slot's table entry and
    the ref counts). ``src``/``dst`` may be traced scalars: one
    compiled program serves every fork."""
    return pool.at[dst].set(pool[src])
