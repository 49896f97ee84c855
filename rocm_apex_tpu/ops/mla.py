"""Latent attention (MLA) over a paged pool of LATENT rows, and rotary
positions.

A latent-attention layer keeps, per position, ONE row shared by all of
its heads: the normalised compressed K/V latent ``c'`` (``rank`` values)
followed by the rotated positional key ``k_r`` (``rope`` values). The
full keys and values are ``[k_n, v] = c' W_ukv`` per head, so a decode
step need never build them: with ``W_uk`` taken into the query and
``W_uv`` into the output (the ABSORBED form) every head's score is
``[q_n W_uk^T, q_r] . [c', k_r]`` and its context the probability-
weighted ``c'``, which ``W_uv`` then takes to the head's values. That is
multi-query attention with one key row of ``rank + rope`` values whose
first ``rank`` are also the value row.

`mla_decode_paged` is that read through a page table: the pool is
``(num_pages, 1, page_size, width)``, the layout
`ops/paging.py::paged_scatter` writes (one "head"; ``width`` is
`latent_width`: the row, then zeros up to whole lane tiles, which the
query meets with zeros of its own). A page meets ALL query heads of a
row at once, so it is fetched once for the 64 heads that read it: about
120 operations a byte read where the K/V kernel
(`flash_attention_decode_paged`) does one. The caller's ``n`` rows each
bring their own page list: the decode grid's rows are the engine's
slots; a packed chunk's rows are its tokens, each with its slot's list
and pre-chunk length (`models/latent.py`).

How the kernel walks: ONE grid step a query row, and inside it a loop
over the ``ceil(length / page_size)`` pages the row has live, in order.
The pool is not blocked by the grid; it stays in device memory and each
live page is copied into one of three page buffers. A fetch cursor runs
through the live pages of all rows, two pages ahead of the products, so
two copies are always in flight while a third page is multiplied: a
row's first pages were asked for by the live rows before it, no row but
the call's first begins by waiting, and the copies follow one another
at the memory's own rate (on a v5e 0.79 us for a page of 512 x 640
bfloat16; 0.96 us a page all told, a row's step and its division
included). A row with nothing to read (an idle slot, a chunk row with
no cached prefix) costs its one step (0.45 us), copies nothing, and
writes zeros and a log-sum-exp of -1e30. So a call costs its live pages
plus a step a row, whatever ``n x pages_per_row`` is.

Rotary positions (`rotary`): pairs interleaved, ``(x[2i], x[2i+1])``
rotated by ``pos * theta ** (-2i / d)``, angles in float32.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocm_apex_tpu.ops._pallas import pallas_call
from rocm_apex_tpu.ops.flash_attention import LN2, LOG2E, NEG_INF, _PREC

__all__ = ["latent_width", "rotary", "bounded_lengths", "mla_decode_paged"]


def latent_width(rank: int, rope: int) -> int:
    """Values a latent pool stores per position: ``rank + rope`` rounded
    up to whole 128-lane tiles, the tail zero. A last dimension that is
    no multiple of 128 (576 as published) makes the chip lay the pool
    out with its ROWS along the lanes, and every call of the kernel
    copies it back (seen in the program compiled for a described v5e)."""
    return -(-(rank + rope) // 128) * 128


def rotary(x, positions, theta, pairing="interleaved"):
    """``x`` (T, ..., d) rotated at ``positions`` (T,), base ``theta``:
    pair i turns by ``position * theta ** (-2i / d)``. ``pairing`` says
    which two values pair i is: ``"interleaved"`` (x[2i], x[2i + 1]) or
    ``"halves"`` (x[i], x[i + d/2]). Float32 in and out of the rotation,
    the result in ``x``'s dtype."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if pairing == "halves":
        xf = x.astype(jnp.float32)
        a, b = xf[..., :d // 2], xf[..., d // 2:]
        out = jnp.concatenate(
            [a * cos - b * sin, a * sin + b * cos], axis=-1)
        return out.astype(x.dtype)
    if pairing != "interleaved":
        raise ValueError(f"unknown rotary pairing {pairing!r}")
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# pages of the pool the kernel holds at once: the one being multiplied
# and two on their way (with one on its way the memory idles between
# copies: 1.0 us a page on a v5e where two in flight give the copy's own
# 0.79; a third gives no more)
_BUFFERS = 3


def _kernel(scale, rank, ps, num_pages, tab_ref, len_ref, live_ref, q_ref,
            pool_ref, o_ref, lse_ref, buf, sem, cur, m_scr, l_scr, acc_scr):
    """Grid point ``row``: all heads of that query row against the pages
    its length covers, one after another, by the online softmax of
    `flash_attention.py::_decode_paged_kernel` (base 2; natural-log lse
    at the boundary). The pool stays where it is. A fetch cursor
    ``cur`` = (row, page of its list, pages asked for, pages awaited)
    runs through the live pages of ALL rows, ``_BUFFERS - 1`` pages
    ahead of the products: whoever multiplies a page first asks for the
    next one the cursor points at, so a row's first pages are on their
    way before its step begins and the copies follow one another
    without a gap. A row with nothing to read copies nothing and loops
    over nothing."""
    row = pl.program_id(0)
    n = pl.num_programs(0)
    ln = len_ref[row]

    @pl.when(jax.lax.eq(row, 0))
    def _reset():
        cur[0] = live_ref[0]
        cur[1] = 0
        cur[2] = 0
        cur[3] = 0

    # scalar arithmetic in plain `lax` primitives: an operator on a
    # traced scalar is a nested `pjit`, and the kernel is traced anew at
    # every call site of a served program (two a layer and program)
    def ask(_, carry):
        r, j, asked = cur[0], cur[1], cur[2]

        @pl.when(jax.lax.lt(r, n))
        def _start():
            slot = jax.lax.rem(asked, _BUFFERS)
            pltpu.make_async_copy(
                pool_ref.at[jax.lax.min(tab_ref[r, j], num_pages - 1), 0],
                buf.at[slot], sem.at[slot]).start()
            j1 = jax.lax.add(j, 1)
            last = jax.lax.ge(jax.lax.mul(j1, ps), len_ref[r])
            cur[0] = jax.lax.select(last, live_ref[jax.lax.add(r, 1)], r)
            cur[1] = jax.lax.select(last, jnp.int32(0), j1)
            cur[2] = jax.lax.add(asked, 1)

        return carry

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def _page(j, carry):
        awaited = cur[3]
        # as many asks as bring the cursor `_BUFFERS` pages past the
        # last page awaited: all of them at the call's first page, one
        # at every other
        jax.lax.fori_loop(jax.lax.sub(cur[2], awaited), _BUFFERS, ask, 0)
        slot = jax.lax.rem(awaited, _BUFFERS)
        cur[3] = jax.lax.add(awaited, 1)
        # the wait reads the semaphore and the size alone
        pltpu.make_async_copy(
            pool_ref.at[0, 0], buf.at[slot], sem.at[slot]).wait()
        q = q_ref[0]  # (heads, width)
        c = buf[slot]  # (ps, width)
        s = jax.lax.dot_general(
            q * jnp.asarray(scale * LOG2E, q.dtype), c,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        col = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < ln, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(c.dtype), c[:, :rank],
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, jax.lax.div(ln + (ps - 1), jnp.int32(ps)), _page, 0)

    # every row writes its block, live or not: a row with nothing to
    # read gives zeros at the -inf tier, which a log-sum-exp merge
    # weighs to exactly zero
    l = l_scr[:, :1]
    safe_l = jnp.where(l > 0.0, l, 1.0)
    o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(
        l > 0.0, (m_scr[:, :1] + jnp.log2(safe_l)) * LN2, NEG_INF)


def bounded_lengths(page_table, lengths, num_pages, ps):
    """(table, lengths): no row reads past the pages its list maps before the first
    sentinel, whatever length it carries (the engine's dead decode rows
    carry the capacity sentinel and map no page)."""
    table = jnp.asarray(page_table, jnp.int32)
    mapped = jnp.sum(
        jnp.cumprod((table < num_pages).astype(jnp.int32), axis=1), axis=1)
    return table, jnp.minimum(jnp.asarray(lengths, jnp.int32), mapped * ps)


def mla_decode_paged(q, pool, page_table, lengths, scale, rank):
    """Absorbed latent attention of ``n`` query rows, each over its own
    page list.

    ``q`` (n, heads, width): per head ``[q_n W_uk^T, q_r, 0...]``.
    ``pool`` (num_pages, 1, page_size, width): rows ``[c', k_r, 0...]``;
    on a chip a page is copied in whole tiles, so ``width`` is whole
    128-lane tiles (`latent_width`) and ``page_size`` whole sublane
    tiles of the pool's dtype (16 rows of bfloat16).
    ``page_table`` (n, pages_per_row) int32, unmapped entries
    ``num_pages``; ``lengths`` (n,): row i attends positions ``[0,
    lengths[i])`` of its list, bounded by the pages the list maps.
    Returns ``(o, lse)``: o (n, heads, rank) in ``q``'s dtype, the
    probability-weighted ``c'`` (zeros where nothing was read), and lse
    (n, heads) float32 in natural log (-1e30 where nothing was read).
    """
    n, heads, d = q.shape
    num_pages, one, ps, d2 = pool.shape
    if one != 1 or d2 != d:
        raise ValueError(
            f"latent pool {pool.shape} does not hold one row of {d} a "
            f"position")
    table, lens = bounded_lengths(page_table, lengths, num_pages, ps)
    # live[i]: the first row at or after i with something to read (n:
    # none), which is where the fetch cursor goes from row i - 1
    live = jax.lax.cummin(
        jnp.where(
            jnp.pad(lens, (0, 1)) > 0, jnp.arange(n + 1, dtype=jnp.int32), n),
        reverse=True)

    def _row_map(i, tab, lens, live):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, heads, d), _row_map),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, rank), _row_map),
            pl.BlockSpec((1, heads, 1), _row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((_BUFFERS, ps, d), pool.dtype),
            pltpu.SemaphoreType.DMA((_BUFFERS,)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, rank), jnp.float32),
        ],
    )
    o, lse = pallas_call(
        functools.partial(_kernel, float(scale), rank, ps, num_pages),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, heads, rank), q.dtype),
            jax.ShapeDtypeStruct((n, heads, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 1024 * 1024),
    )(table, lens, live, q, pool)
    return o, lse[..., 0]
