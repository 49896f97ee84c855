"""Packed-native varlen flash attention: segment-id masking, O(total).

The reference FMHA kernels operate DIRECTLY on the packed token stream
(reference: apex/contrib/fmha/fmha.py:33-56 — qkv ``(total, 3, h, d)``
with ``cu_seqlens`` prefix offsets; kernels
apex/contrib/csrc/fmha/fmha_api.cpp:432). The first TPU rebuild
scattered into a padded ``(b, max_s, …)`` batch, so compute and HBM
scaled with ``b·max_s``; this module is the packed-native design point:

* operands stay on the token axis — ``(h, total, d)``, every
  allocation O(total);
* masking is by SEGMENT ID: token i attends token j iff
  ``seg[i] == seg[j]`` (+ the global causal triangle, which equals
  within-segment causality because packed segments are contiguous and
  ordered). The mask test lives in `_masked_scores` (flash_attention.py)
  next to every other masking rule;
* whole (q-block, k-block) pairs whose segment RANGES do not overlap
  are skipped via per-block min/max segment ids in SMEM — segments are
  sorted along the stream, so MXU compute scales with Σ len_i² (plus
  block granularity), not total². Note the skip is inside the kernel
  body: Pallas still prefetches the K/V tiles of skipped pairs, so HBM
  fetch traffic remains O(tp²·d/block) per head — moving the skip to
  the index-map/scalar-prefetch level (re-pointing skipped fetches at
  the previous block) is the known next step if bandwidth ever binds
  here before compute.

Padding tokens carry segment id −1: they only match each other, and
their rows are never consumed (the fmha-level gather reads real tokens
only — same unspecified-row contract as `flash_attention_varlen`).
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocm_apex_tpu.ops._pallas import pallas_call
from rocm_apex_tpu.ops.flash_attention import (
    LN2,
    LOG2E,
    NEG_INF,
    _PREC,
    _masked_scores,
    _round_up,
)

__all__ = [
    "flash_attention_segments",
    "flash_attention_segments_with_lse",
    "flash_attention_chunk_paged",
]

DEFAULT_BLOCK = 512


def _overlap(causal, block_q, block_k, qi, ki,
             qmin_ref, qmax_ref, kmin_ref, kmax_ref):
    """Does block pair (qi, ki) contain any unmasked position?"""
    hit = (kmin_ref[ki] <= qmax_ref[qi]) & (kmax_ref[ki] >= qmin_ref[qi])
    if causal:
        hit &= qi * block_q + block_q - 1 >= ki * block_k
    return hit


def _seg_fwd_kernel(
    causal, scale, block_q, block_k, window,
    q_ref, k_ref, v_ref, sq_ref, sk_ref,
    qmin_ref, qmax_ref, kmin_ref, kmax_ref,
    o_ref, lse_ref, m_scr, l_scr, acc_scr,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = _masked_scores(
            causal, scale, k.shape[0] * pl.num_programs(2), block_q,
            block_k, q, k, None, None, b, qi, ki, seg=(sq_ref, sk_ref),
            window=window,
        )
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # _masked_scores returns BASE-2 scores (flash_attention.py)
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32, precision=_PREC,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    pl.when(
        _overlap(causal, block_q, block_k, qi, ki,
                 qmin_ref, qmax_ref, kmin_ref, kmax_ref)
    )(_body)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:, :1] + jnp.log2(safe_l)) * LN2


def _seg_dkv_kernel(
    causal, scale, block_q, block_k,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
    qmin_ref, qmax_ref, kmin_ref, kmax_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = _masked_scores(
            causal, scale, k.shape[0] * pl.num_programs(1), block_q,
            block_k, q, k, None, None, b, qi, ki, seg=(sq_ref, sk_ref),
        )
        p = jnp.exp2(s - lse * LOG2E)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )

    pl.when(
        _overlap(causal, block_q, block_k, qi, ki,
                 qmin_ref, qmax_ref, kmin_ref, kmax_ref)
    )(_body)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _seg_dq_kernel(
    causal, scale, block_q, block_k,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
    qmin_ref, qmax_ref, kmin_ref, kmax_ref,
    dq_ref, dq_scr,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = _masked_scores(
            causal, scale, k.shape[0] * pl.num_programs(2), block_q,
            block_k, q, k, None, None, b, qi, ki, seg=(sq_ref, sk_ref),
        )
        p = jnp.exp2(s - lse * LOG2E)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32, precision=_PREC,
        )

    pl.when(
        _overlap(causal, block_q, block_k, qi, ki,
                 qmin_ref, qmax_ref, kmin_ref, kmax_ref)
    )(_body)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _prepare(q, seg, block_q, block_k):
    h, total, d0 = q.shape
    d = _round_up(d0, 128)
    block_q = min(block_q, _round_up(total, 128))
    block_k = min(block_k, _round_up(total, 128))
    # one padded length serves both grid axes (self-attention: q and k
    # are the same token stream); the lcm keeps tp divisible by BOTH
    # block sizes when the smaller does not divide the larger
    # (e.g. block_q=512, block_k=768)
    block = math.lcm(block_q, block_k)
    tp = _round_up(total, block)
    segp = jnp.pad(
        seg.astype(jnp.int32), (0, tp - total), constant_values=-1
    ).reshape(tp, 1)
    # per-block segment ranges for the SMEM skip test (segments are
    # sorted, so [min, max] is exact coverage)
    qmin = jnp.min(segp.reshape(tp // block_q, block_q), axis=1)
    qmax = jnp.max(segp.reshape(tp // block_q, block_q), axis=1)
    kmin = jnp.min(segp.reshape(tp // block_k, block_k), axis=1)
    kmax = jnp.max(segp.reshape(tp // block_k, block_k), axis=1)
    return d, block_q, block_k, tp, segp, (qmin, qmax, kmin, kmax)


def _pad3(x, tp, d):
    h, total, d0 = x.shape
    return jnp.pad(x, ((0, 0), (0, tp - total), (0, d - d0)))


def _seg_fwd(q, k, v, seg, causal, scale, block_q, block_k, window=None):
    h, total, d0 = q.shape
    if window is not None and not causal:
        raise ValueError("a window is a causal one")
    # grouped K/V heads (forward only): query head b reads K/V head
    # b // g; with g = 1 the index maps are what they were
    hk = k.shape[0]
    if h % hk or v.shape[0] != hk:
        raise ValueError(f"{h} query heads over {hk}/{v.shape[0]} K/V heads")
    g = h // hk
    kv_map = (
        (lambda b, i, j: (b, j, 0)) if g == 1
        else (lambda b, i, j: (b // g, j, 0))
    )
    d, block_q, block_k, tp, segp, ranges = _prepare(q, seg, block_q, block_k)
    qp, kp, vp = (_pad3(x, tp, d) for x in (q, k, v))
    qmin, qmax, kmin, kmax = ranges
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    o, lse = pallas_call(
        functools.partial(
            _seg_fwd_kernel, causal, scale, block_q, block_k, window),
        grid=(h, tp // block_q, tp // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((block_q, 1), lambda b, i, j: (i, 0)),
            pl.BlockSpec((block_k, 1), lambda b, i, j: (j, 0)),
            smem, smem, smem, smem,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, tp, d), q.dtype),
            jax.ShapeDtypeStruct((h, tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )(qp, kp, vp, segp, segp, qmin, qmax, kmin, kmax)
    return o[:, :total, :d0], lse[:, :total, 0]


def _seg_bwd(q, k, v, seg, o, lse, do, causal, scale, block_q, block_k):
    h, total, d0 = q.shape
    d, block_q, block_k, tp, segp, ranges = _prepare(q, seg, block_q, block_k)
    qmin, qmax, kmin, kmax = ranges
    qp, kp, vp = (_pad3(x, tp, d) for x in (q, k, v))
    dop = _pad3(do, tp, d)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lsep = jnp.pad(
        lse[..., None], ((0, 0), (0, tp - total), (0, 0)),
        constant_values=-NEG_INF,
    )
    deltap = jnp.pad(delta[..., None], ((0, 0), (0, tp - total), (0, 0)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    ins = (qp, kp, vp, dop, lsep, deltap, segp, segp,
           qmin, qmax, kmin, kmax)

    def specs(q_of, k_of):
        return [
            pl.BlockSpec((1, block_q, d), lambda b, a, c: (b, q_of(a, c), 0)),
            pl.BlockSpec((1, block_k, d), lambda b, a, c: (b, k_of(a, c), 0)),
            pl.BlockSpec((1, block_k, d), lambda b, a, c: (b, k_of(a, c), 0)),
            pl.BlockSpec((1, block_q, d), lambda b, a, c: (b, q_of(a, c), 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, a, c: (b, q_of(a, c), 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, a, c: (b, q_of(a, c), 0)),
            pl.BlockSpec((block_q, 1), lambda b, a, c: (q_of(a, c), 0)),
            pl.BlockSpec((block_k, 1), lambda b, a, c: (k_of(a, c), 0)),
            smem, smem, smem, smem,
        ]

    dk, dv = pallas_call(
        functools.partial(_seg_dkv_kernel, causal, scale, block_q, block_k),
        grid=(h, tp // block_k, tp // block_q),
        in_specs=specs(q_of=lambda j, i: i, k_of=lambda j, i: j),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, tp, d), k.dtype),
            jax.ShapeDtypeStruct((h, tp, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )(*ins)
    dq = pallas_call(
        functools.partial(_seg_dq_kernel, causal, scale, block_q, block_k),
        grid=(h, tp // block_q, tp // block_k),
        in_specs=specs(q_of=lambda i, j: i, k_of=lambda i, j: j),
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, tp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(*ins)
    return (
        dq[:, :total, :d0],
        dk[:, :total, :d0],
        dv[:, :total, :d0],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_segments(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
) -> jnp.ndarray:
    """Flash attention over a PACKED token stream.

    ``q/k/v``: (heads, total, head_dim) — the packed concatenation of
    all sequences; ``segment_ids``: (total,) int32, non-decreasing,
    one id per sequence. Token i attends token j iff their ids match
    (``causal`` additionally applies the packed-order triangle, which
    is within-segment causality). All allocations are O(total); block
    pairs with disjoint segment ranges are skipped in-kernel.

    Output rows are specified for every real token (all tokens belong
    to some segment); differentiable in q/k/v.
    """
    if k.shape[0] != q.shape[0]:
        raise ValueError(
            "grouped K/V heads are forward-only "
            "(flash_attention_segments_with_lse): the backward kernels "
            "walk one K/V head per query head")
    o, _ = _seg_fwd(
        q, k, v, segment_ids, causal,
        scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]),
        block_q, block_k,
    )
    return o


def flash_attention_segments_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    window: Optional[int] = None,
):
    """Forward-only packed attention returning ``(o, lse)``.

    Same masking contract as `flash_attention_segments`; ``lse`` is
    (heads, total) in natural log — the merge operand the
    chunked-prefill path needs to combine this INTRA-CHUNK piece with
    the per-slot cache-prefix piece
    (`flash_attention_decode(..., return_lse=True)`) by log-sum-exp
    weights. No vjp: inference never differentiates this variant.
    ``window`` (causal only): a token also attends no further back than
    the ``window`` tokens that end at its own.
    """
    return _seg_fwd(
        q, k, v, segment_ids, causal,
        scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]),
        block_q, block_k, window,
    )


def flash_attention_chunk_paged(
    q: jnp.ndarray,
    k_chunk: jnp.ndarray,
    v_chunk: jnp.ndarray,
    segment_ids: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Chunked-prefill attention against a PAGED cache prefix.

    The mixed-step read in one op: a packed chunk of prompt pieces
    attends (A) its own stream under segment-causal masking (this
    module's kernel — tokens of different slots never talk) and (B)
    each token's slot's PRE-CHUNK cache prefix, read THROUGH the page
    table (`flash_attention_decode_paged` — pages actually live bound
    the DMA, int8 pools dequantize in-kernel via the per-(page, head)
    scales); the two pieces merge by log-sum-exp weights, exactly the
    contiguous chunk path's merge in models/gpt.py.

    ``q``/``k_chunk``/``v_chunk``: (heads, budget, head_dim) — the
    chunk's FRESH projections (piece A reads them at full precision;
    quantization only ever touches prefix reads). ``segment_ids``:
    (budget,) per-token slot ids, ``num_slots`` marking padding.
    ``k_pool``/``v_pool``/``page_table``/``kv_lengths``/scales as in
    `flash_attention_decode_paged` (lengths are each slot's
    pre-chunk materialized length). Returns fp32
    (budget, heads, head_dim) — token-major, output-projection-ready.
    Forward only (serving never differentiates).

    ``window`` with ``positions`` (budget,), each token's position in
    its sequence: a layer with a sliding window. A token attends the
    ``window`` keys that end at its own, in the chunk and in its slot's
    prefix alike; the prefix pages wholly before a slot's first row's
    bound are not fetched and need not be mapped.
    """
    if (window is None) != (positions is None):
        raise ValueError("pass window and positions together or neither")
    from rocm_apex_tpu.ops.flash_attention import (
        flash_attention_decode_paged,
    )

    nh, budget, d0 = q.shape
    num_slots = page_table.shape[0]
    s = scale if scale is not None else 1.0 / np.sqrt(d0)
    o_a, lse_a = flash_attention_segments_with_lse(
        q, k_chunk, v_chunk, segment_ids, causal=True, scale=s,
        window=window,
    )
    # every slot scores the WHOLE chunk against its prefix (chunk-width
    # cache read, not per-token width); each token keeps its own slot's
    # row below
    qB = jnp.broadcast_to(
        q[None], (num_slots, nh, budget, d0)
    ).reshape(num_slots * nh, budget, d0)
    o_b, lse_b = flash_attention_decode_paged(
        qB, k_pool, v_pool, page_table, kv_lengths, s,
        k_scale=k_scale, v_scale=v_scale, return_lse=True,
        window=window, q_positions=positions,
    )
    o_b = o_b.reshape(num_slots, nh, budget, d0)
    lse_b = lse_b.reshape(num_slots, nh, budget)
    slot_c = jnp.clip(segment_ids, 0, num_slots - 1)
    tok = jnp.arange(budget)
    o_b = o_b[slot_c, :, tok]  # (budget, nh, hd)
    lse_b = lse_b[slot_c, :, tok]  # (budget, nh)
    o_a = o_a.transpose(1, 0, 2)  # (budget, nh, hd)
    lse_a = lse_a.transpose(1, 0)
    m = jnp.maximum(lse_a, lse_b)
    w_a = jnp.exp(lse_a - m)
    w_b = jnp.exp(lse_b - m)
    return (
        w_a[..., None] * o_a.astype(jnp.float32)
        + w_b[..., None] * o_b.astype(jnp.float32)
    ) / (w_a + w_b)[..., None]


def _fas_fwd(q, k, v, segment_ids, causal, scale, block_q, block_k):
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    o, lse = _seg_fwd(q, k, v, segment_ids, causal, s, block_q, block_k)
    return o, (q, k, v, segment_ids, o, lse)


def _fas_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, segment_ids, o, lse = res
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    dq, dk, dv = _seg_bwd(
        q, k, v, segment_ids, o, lse, do, causal, s, block_q, block_k
    )
    seg_ct = np.zeros(segment_ids.shape, jax.dtypes.float0)
    return dq, dk, dv, seg_ct


flash_attention_segments.defvjp(_fas_fwd, _fas_bwd)
