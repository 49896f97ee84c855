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
query meets with zeros of its own), and a grid step
takes ALL query heads of one row against one page, so a page is fetched
once for the 64 heads that read it: about 120 operations a byte read
where the K/V kernel (`flash_attention_decode_paged`) does one. The
caller's ``n`` rows each bring their own page list: the decode grid's
rows are the engine's slots; a packed chunk's rows are its tokens, each
with its slot's list and pre-chunk length (`models/latent.py`).

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


def rotary(x, positions, theta):
    """``x`` (T, ..., d) rotated at ``positions`` (T,): interleaved
    pairs, base ``theta``; float32 in and out of the rotation, the
    result in ``x``'s dtype."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _kernel(scale, rank, ps, tab_ref, len_ref, src_ref, q_ref, c_ref,
            o_ref, lse_ref, m_scr, l_scr, acc_scr):
    """Grid point (row, j): all heads of query row ``row`` against page
    j of the row's list, by the online softmax of
    `flash_attention.py::_decode_paged_kernel` (base 2; natural-log lse
    at the boundary). A step past the row's live prefix, and every step
    of a row with nothing to read, holds the block of the step before
    it (`_page_map`), so nothing is fetched for it."""
    del tab_ref, src_ref
    row = pl.program_id(0)
    j = pl.program_id(1)
    ln = len_ref[row]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * ps < ln)
    def _body():
        q = q_ref[0]  # (heads, width)
        c = c_ref[0, 0]  # (ps, width)
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

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # every row writes its block, live or not: a row with nothing
        # to read gives zeros at the -inf tier, which a log-sum-exp
        # merge weighs to exactly zero
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
    ``pool`` (num_pages, 1, page_size, width): rows ``[c', k_r, 0...]``.
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
    pages_per_row = page_table.shape[1]
    table, lens = bounded_lengths(page_table, lengths, num_pages, ps)
    # the row whose block a row's steps hold: itself when it has
    # something to read, else the last live row before it, else the
    # first live row after it (`flash_attention_decode_paged`)
    idx = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(lens > 0, idx, -1))
    after = jax.lax.cummin(jnp.where(lens > 0, idx, n - 1), reverse=True)
    src = jnp.where(before >= 0, before, after)

    def _row_map(i, j, tab, lens, src):
        return (i, 0, 0)

    def _page_map(i, j, tab, lens, src):
        held = src[i]
        dead = lens[i] == 0
        first = jnp.logical_and(dead, held >= i)
        last_page = jax.lax.max(
            jax.lax.div(lens[held] + (ps - 1), jnp.int32(ps)), 1) - 1
        jeff = jax.lax.select(
            first, jnp.int32(0),
            jax.lax.select(dead, last_page, jax.lax.min(j, last_page)))
        return (jax.lax.min(tab[held, jeff], num_pages - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, pages_per_row),
        in_specs=[
            pl.BlockSpec((1, heads, d), _row_map),
            pl.BlockSpec((1, 1, ps, d), _page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, rank), _row_map),
            pl.BlockSpec((1, heads, 1), _row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, rank), jnp.float32),
        ],
    )
    o, lse = pallas_call(
        functools.partial(_kernel, float(scale), rank, ps),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, heads, rank), q.dtype),
            jax.ShapeDtypeStruct((n, heads, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024),
    )(table, lens, src, q, pool)
    return o, lse[..., 0]
