"""The paged K/V decode kernel as it stood before PR 39, kept for the
tests alone: `grid=(slots x head blocks x row blocks, walk)` over the
WORST-case page count, the pools blocked by scalar-prefetch index maps
that hold a block index past a slot's live prefix. The kernel that
replaced it (`ops/flash_attention.py::flash_attention_decode_paged`: one
grid step a (slot, head block, row block), a loop over the slot's own
live pages copied two ahead) reads the same pages in the same order
under the same masks with the same arithmetic, so
`test_paged_decode_walk.py` holds its outputs EQUAL to this one's.
Copied from commit 6281e14, names changed, docstring cut."""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocm_apex_tpu.ops._pallas import pallas_call
from rocm_apex_tpu.ops.flash_attention import (
    _PREC,
    DECODE_BLOCK_T,
    LN2,
    LOG2E,
    NEG_INF,
    PAGED_VMEM_LIMIT,
    _paged_grid_row,
    _paged_head_block,
    _round_up,
)
from rocm_apex_tpu.ops import flash_attention as _fa


def _fixed_grid_kernel(
    scale, hb, nhb, ps, num_pages, block_t, quantized, row_blocks, bound,
    tab_ref, len_ref, src_ref, *rest,
):
    """Online-softmax decode against a PAGED cache for grid point
    (b, j): b = (slot, head block, row block), slot-major, and j walks
    the slot's page list. One step takes ``hb`` heads of ONE page: the
    K and V tiles are the `(hb, page_size, head_dim)` slab of the pool
    as it is stored, fetched by the scalar-prefetch index maps through
    the page table, so the kernel sees exactly the pages the slot owns.
    What the contiguous `_decode_kernel` still DMAs (its skip is
    compute-only) never leaves HBM here: a step past the slot's live
    prefix, and every step of a slot with nothing to read, holds the
    block index of the step before it, and Pallas elides the DMA of a
    repeated block index. Each head runs the accumulation of
    `_decode_kernel` (base-2 online softmax, natural-log lse at the
    boundary) over its own rows of the head-major scratch.

    ``quantized`` adds per-(page, head) fp32 dequantization: int8
    tiles are scaled into the score/value dots from SMEM-resident
    scale tables (``hb`` scalar reads a step). ``src_ref`` is only the
    index maps' (`flash_attention_decode_paged`).

    ``bound`` (None, ``"slot"`` or ``"rows"``) is a LOWER bound on the
    positions read, a sliding window's: a fourth prefetched vector gives
    each slot's first position, step j takes the page ``first // ps +
    j`` (the pages before it are never fetched) and the first live page
    is masked from the bound on; with ``"rows"`` each query row masks
    from a bound of its own (one more block, ``(block_t, 1)``)."""
    del src_ref
    first_ref = lo_ref = None
    if bound is not None:
        first_ref, rest = rest[0], rest[1:]
    q_ref, k_ref, v_ref, *rest = rest
    if bound == "rows":
        lo_ref, rest = rest[0], rest[1:]
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    slot, hblk, _ = _paged_grid_row(b, nhb, row_blocks)
    head0 = hblk * hb
    ln = len_ref[slot]
    # (without a bound the first position of step j's page stays the
    # `j * ps` it was, written where it was: the older callers' programs
    # are held to what they traced)
    if bound is not None:
        first = first_ref[slot]
        row0 = jax.lax.mul(
            jax.lax.add(jax.lax.div(first, jnp.int32(ps)), j),
            jnp.int32(ps))

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        col = (j * ps if bound is None else row0) + (
            jax.lax.broadcasted_iota(jnp.int32, (block_t, ps), 1)
        )
        if bound is not None:
            lo = first if bound == "slot" else lo_ref[...]
            seen = jnp.logical_and(col < ln, col >= lo)
        if quantized:
            # j is inside the live prefix here, so this is the page the
            # index map fetched
            page = jnp.minimum(tab_ref[slot, j], num_pages - 1)

        def _head(h, carry):
            q = q_ref[0, h, 0]  # (block_t, d)
            k = k_ref[0, h]  # (ps, d)
            v = v_ref[0, h]
            if quantized:
                k = (
                    k.astype(jnp.float32) * ks_ref[page, head0 + h]
                ).astype(q.dtype)
                v = (
                    v.astype(jnp.float32) * vs_ref[page, head0 + h]
                ).astype(q.dtype)
            s = jax.lax.dot_general(
                (q * jnp.asarray(scale * LOG2E, q.dtype)), k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )
            s = jnp.where(col < ln if bound is None else seen, s, NEG_INF)
            m_prev = m_scr[h, :, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(s, axis=1, keepdims=True)
            )
            p = jnp.exp2(s - m_new)
            corr = jnp.exp2(m_prev - m_new)
            l_new = l_scr[h, :, :1] * corr + jnp.sum(
                p, axis=1, keepdims=True
            )
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot(
                p.astype(v.dtype), v,
                preferred_element_type=jnp.float32, precision=_PREC,
            )
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
            return carry

        # one traced body for all heads of the block: a Python loop
        # traces and lowers it hb times at every call site (16 heads x
        # 72 sites: 115 s of the serving cell's set-up, PERF.md PR 27)
        jax.lax.fori_loop(0, hb, _head, 0)

    # pages wholly past the live prefix: no compute AND no fetch (the
    # index map held their DMA on an already-resident block)
    pl.when((j * ps if bound is None else row0) < ln)(_body)

    @pl.when(j == nj - 1)
    def _finish():
        # every step writes its own output block, live or not: a dead
        # slot's rows are zeros at the -inf tier, which the chunk
        # read's log-sum-exp merge weighs to exactly zero
        l = l_scr[:, :, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, :, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0, :, 0] = jnp.where(
            l > 0.0,
            (m_scr[:, :, :1] + jnp.log2(safe_l)) * LN2,
            NEG_INF,
        )


def fixed_grid_decode_paged(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    return_lse: bool = False,
    _row_blocks: int = 1,
    window: Optional[int] = None,
    q_positions: Optional[jnp.ndarray] = None,
):
    """The parent's wrapper: same arguments, same outputs."""
    bh, t, d0 = q.shape
    num_pages, nh, ps, dp = k_pool.shape
    num_slots, pages_per_slot = page_table.shape
    if dp != d0:
        raise ValueError(
            f"pool head_dim {dp} != query head_dim {d0}"
        )
    if bh % (num_slots * nh * _row_blocks):
        raise ValueError(
            f"q rows {bh} must be num_slots {num_slots} * pool "
            f"heads {nh} (slot-major) times a whole number of query "
            f"heads per pool head"
        )
    group = bh // (num_slots * nh * _row_blocks)
    if group > 1:
        fold = max(
            f for f in range(1, group + 1)
            if group % f == 0 and (f == 1 or f * t <= _fa.GROUP_FOLD_ROWS)
        )
        out = fixed_grid_decode_paged(
            q.reshape(bh // fold, fold * t, d0), k_pool, v_pool,
            page_table, kv_lengths, scale, k_scale, v_scale,
            return_lse=True, _row_blocks=group // fold, window=window,
            q_positions=(
                None if q_positions is None
                else jnp.tile(q_positions, fold)),
        )
        o, lse = out[0].reshape(bh, t, d0), out[1].reshape(bh, t)
        return (o, lse) if return_lse else o
    rb = _row_blocks
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale or neither")
    s = scale if scale is not None else 1.0 / np.sqrt(d0)
    d = _round_up(d0, 128)
    block_t = _round_up(t, DECODE_BLOCK_T)
    hb = _paged_head_block(
        nh, ps, d, block_t, k_pool.dtype.itemsize, q.dtype.itemsize,
        quantized,
    )
    nhb = nh // hb
    qp = jnp.pad(q, ((0, 0), (0, block_t - t), (0, d - d0))).reshape(
        num_slots, nh, rb, block_t, d
    )
    kp = jnp.pad(k_pool, ((0, 0), (0, 0), (0, 0), (0, d - d0)))
    vp = jnp.pad(v_pool, ((0, 0), (0, 0), (0, 0), (0, d - d0)))
    table = jnp.asarray(page_table, jnp.int32)
    bound, walk, first = None, pages_per_slot, None
    if window is not None:
        if quantized:
            raise ValueError("a windowed read has no int8 form")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        bound = "slot" if q_positions is None else "rows"
        # a window's keys lie in this many pages at most
        walk = min(pages_per_slot, (window + ps - 2) // ps + 1)
        first = jnp.maximum(
            jnp.asarray(kv_lengths, jnp.int32)
            + (0 if q_positions is None else 1) - window, 0)
    # the table bounds the read: no slot reads past its mapped pages, so
    # a slot that owns none has nothing to read whatever length it
    # carries (the engine's dead rows carry the capacity sentinel)
    is_mapped = table < num_pages
    if window is not None:
        # the pages before the bound count as mapped: nothing reads them
        is_mapped = jnp.logical_or(
            is_mapped,
            jnp.arange(pages_per_slot, dtype=jnp.int32)[None, :]
            < (first // ps)[:, None])
    mapped = jnp.sum(
        jnp.cumprod(is_mapped.astype(jnp.int32), axis=1), axis=1
    )
    lens = jnp.minimum(jnp.asarray(kv_lengths, jnp.int32), mapped * ps)
    # the slot whose block a slot's steps hold: itself when it has
    # something to read, else the last live slot before it, else the
    # first live slot after it (the last slot when nothing is live)
    idx = jnp.arange(num_slots, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(lens > 0, idx, -1))
    after = jax.lax.cummin(
        jnp.where(lens > 0, idx, num_slots - 1), reverse=True
    )
    src = jnp.where(before >= 0, before, after)

    def _row_map(b, j, *_):
        return (*_paged_grid_row(b, nhb, rb), 0, 0)

    def _page_map(b, j, tab, lens, src, *first):
        # a repeated block index is not refetched. Past a slot's live
        # prefix: its last live page. A slot with nothing to read: the
        # block of the step before its first (the LAST block of the
        # live slot before it), else the block of the step after its
        # last (the FIRST block of the live slot after it). Plain lax
        # primitives: an index map is lowered at every call site.
        slot, hblk, _ = _paged_grid_row(b, nhb, rb)
        held = src[slot]
        dead = lens[slot] == 0
        before = jnp.logical_and(dead, held >= slot)
        last_page = jax.lax.max(
            jax.lax.div(lens[held] + (ps - 1), jnp.int32(ps)), 1
        ) - 1
        page0 = jnp.int32(0)
        if first:  # a window: the walk starts at the bound's page
            page0 = jax.lax.div(first[0][held], jnp.int32(ps))
            j = jax.lax.add(page0, j)
        jeff = jax.lax.select(
            before, page0,
            jax.lax.select(dead, last_page, jax.lax.min(j, last_page)),
        )
        if nhb > 1:
            hblk = jax.lax.select(
                before, jnp.int32(0),
                jax.lax.select(dead, jnp.int32(nhb - 1), hblk),
            )
        return (jax.lax.min(tab[held, jeff], num_pages - 1), hblk, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hb, 1, block_t, d), _row_map),
        pl.BlockSpec((1, hb, ps, d), _page_map),
        pl.BlockSpec((1, hb, ps, d), _page_map),
    ]
    ins = [qp, kp, vp]
    prefetch = [table, lens, src]
    if window is not None:
        prefetch.append(first)
        if bound == "rows":
            # each row's own bound; the rows that pad the block read all
            lo = jnp.maximum(
                jnp.asarray(q_positions, jnp.int32) + 1 - window, 0)
            in_specs.append(
                pl.BlockSpec((block_t, 1), lambda b, j, *_: (0, 0)))
            ins.append(jnp.pad(lo, (0, block_t - t)).reshape(block_t, 1))
    if quantized:
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        in_specs += [smem, smem]
        ins += [
            jnp.asarray(k_scale, jnp.float32),
            jnp.asarray(v_scale, jnp.float32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # the page table stays FIRST and two-dimensional: the trace's
        # readers tell this kernel by it
        num_scalar_prefetch=len(prefetch),
        grid=(num_slots * nhb * rb, walk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, hb, 1, block_t, d), _row_map),
            pl.BlockSpec((1, hb, 1, block_t, 1), _row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, block_t, 128), jnp.float32),
            pltpu.VMEM((hb, block_t, 128), jnp.float32),
            pltpu.VMEM((hb, block_t, d), jnp.float32),
        ],
    )
    o, lse = pallas_call(
        functools.partial(
            _fixed_grid_kernel, s, hb, nhb, ps, num_pages, block_t,
            quantized, rb, bound,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct(qp.shape[:-1] + (1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=PAGED_VMEM_LIMIT
        ),
    )(*prefetch, *ins)
    o = o.reshape(bh, block_t, d)[:, :t, :d0]
    if return_lse:
        return o, lse.reshape(bh, block_t)[:, :t]
    return o
