"""Pallas flash attention (fwd + bwd), the framework's fused-attention core.

TPU-native replacement for the reference's pre-flash fused attention
kernels — FMHA (reference: apex/contrib/csrc/fmha/, packed varlen
seqs <= 512) and fast_multihead_attn (reference:
apex/contrib/csrc/multihead_attn/, fused QKV+softmax+dropout+outproj,
seqlen-bounded smem tiles) — and for the megatron scaled-masked softmax
path (reference: csrc/megatron/, seqlen <= 2048 ceiling). Flash
attention is the idiomatic TPU design (SURVEY.md §5 long-context): the
(s, s) score matrix never materializes, so there is no sequence-length
ceiling and HBM traffic is O(s·d) instead of O(s²).

Algorithm: FlashAttention-2 online softmax. Forward walks kv blocks
innermost, carrying (m, l, acc) in VMEM scratch across the sequential
TPU grid; backward recomputes probabilities blockwise from the saved
row log-sum-exp — one kernel for dk/dv (kv blocks outer), one for dq
(q blocks outer).

Layout: (batch*heads, seq, head_dim), head_dim <= 256. ``bias`` is an
optional additive (batch*heads | 1, sq, sk) tensor (-inf = masked) —
the general form of the reference's padding/additive masks; ``causal``
applies the upper-triangular mask in-kernel (no bias tensor needed).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocm_apex_tpu.ops._pallas import pallas_call

__all__ = [
    "flash_attention",
    "flash_attention_varlen",
    "flash_attention_decode",
    "flash_attention_decode_paged",
    "flash_attention_with_lse",
    "flash_attention_dropout",
    "flash_attention_qkv",
    "flash_attention_qkv_dropout",
    "flash_attention_qkv_bias",
    "flash_attention_qkv_bias_dropout",
]

# Large blocks keep the sequential TPU grid short (per-step overhead is
# the dominant cost at small blocks) while staying well inside VMEM:
# q/k/v (1024, d) + the (1024, 1024) fp32 score tile ~ 5.5 MiB at
# d=128. Swept on v5e (s=1024, d=128, fwd+bwd): (1024, 1024) beats
# (512, 1024) by 16% and (512, 512) by 30%.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
# Scores are kept in BASE 2 inside every kernel: scale·log2(e) folds
# into the (block, head_dim) q tile before the MXU dot — one multiply
# over d columns instead of block_k — and the softmax runs on exp2
# (the VPU's native exponential; exp(x) would spend an extra full-tile
# multiply folding log2e back in). lse converts to natural log at the
# kernel boundary, so the public API (and the ring-attention lse
# combine) is unchanged.
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453
# Kernel dots PIN native MXU precision rather than inheriting
# jax_default_matmul_precision: Mosaic rejects non-native precisions on
# bf16 operands outright ("Bad lhs type" under 'highest'), so a global
# precision override would crash every bf16 training path. Like any
# hand-written kernel (cuDNN flash attention under torch's matmul
# flags), these kernels define their own numerics: bf16 operands on the
# MXU with fp32 accumulation.
_PREC = jax.lax.Precision.DEFAULT


def _round_up(x, m):
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _keep_mask(seed_ref, rate, b, qi, ki, shape):
    """Deterministic per-(batch, q-block, k-block) keep mask; the same
    seeding in forward and both backward kernels regenerates identical
    bits (the flash-dropout recompute trick — no mask is stored)."""
    # single combined scalar (multi-arg prng_seed does not lower on all
    # backends). The coordinates are folded through murmur3-style
    # multiply-rotate-xor rounds rather than an affine combination:
    # affine seeds collide across (b, qi, ki) triples at large grids
    # (e.g. qi ~ b-stride aliasing), which would correlate dropout
    # masks between blocks exactly in the long-context regime.
    def _mix(h, k):
        k = k * jnp.uint32(0xCC9E2D51)
        k = (k << 15) | (k >> 17)
        k = k * jnp.uint32(0x1B873593)
        h = h ^ k
        h = (h << 13) | (h >> 19)
        return h * jnp.uint32(5) + jnp.uint32(0xE6546B64)

    h = seed_ref[0].astype(jnp.uint32)
    for coord in (b, qi, ki):
        h = _mix(h, coord.astype(jnp.uint32))
    # fmix32 avalanche so low-bit coordinate differences reach all bits
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    # mask to 31 bits first: u32->s32 conversion is only
    # defined-behavior in XLA's ConvertElementType for in-range values,
    # and a scalar bitcast is rejected by current Mosaic ('tpu.bitcast'
    # on non-vector operands); one seed bit of entropy is immaterial
    pltpu.prng_seed((h & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32))
    bits = pltpu.prng_random_bits(shape)
    thresh = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return bits.astype(jnp.uint32) >= thresh


def _masked_scores(
    causal, scale, sk_real, block_q, block_k,
    q, k, bias_ref, len_ref, b, qi, ki, seg=None, window=None,
):
    """The masked BASE-2 score block for grid point (b, qi, ki) —
    shared by ALL FOUR kernels (fwd, dkv, dq, dbias). Masking semantics
    live here and only here: a change applied to one kernel but not the
    others would silently desynchronize forward and backward
    probabilities.

    Returns log2-domain scores: `exp2(s - m)` reproduces the natural-
    domain softmax exactly (scale·log2e is folded into the q tile —
    the narrow operand — before the dot)."""
    # native-dtype MXU operands (bf16 in / fp32 accumulate); an
    # explicit fp32 upcast here would fall off the fast MXU path.
    # q·(scale·log2e) rounds in q's dtype — the same 2^-8-tier relative
    # rounding the bf16 operands already carry into the MXU
    s = jax.lax.dot_general(
        (q * jnp.asarray(scale * LOG2E, q.dtype)), k,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_PREC,
    )
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32) * LOG2E
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    if sk_real % block_k != 0:
        s = jnp.where(col < sk_real, s, NEG_INF)
    if len_ref is not None:
        # per-row real key length (varlen): in-kernel bound, the
        # flash-grade replacement for a materialized (s, s) mask
        s = jnp.where(col < len_ref[b], s, NEG_INF)
    if seg is not None:
        # packed-stream segment masking: token i attends token j only
        # within the same segment (flash_attention_segments)
        sq_ids, sk_ids = seg
        s = jnp.where(
            sq_ids[...] == sk_ids[...].reshape(1, -1), s, NEG_INF
        )
    if causal:
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        s = jnp.where(row >= col, s, NEG_INF)
        if window is not None:
            # a sliding window over the stream: row i sees the `window`
            # keys that end at its own (rows of one packed segment are
            # contiguous, so stream distance is position distance)
            s = jnp.where(row - col < window, s, NEG_INF)
    return s


def _fwd_kernel(
    causal, scale, sk_real, block_q, block_k, has_bias, dropout_rate,
    has_lengths, q_ref, k_ref, v_ref, *refs, has_qkv_bias=False,
):
    refs = list(refs)
    qb_ref = refs.pop(0) if has_qkv_bias else None
    kb_ref = refs.pop(0) if has_qkv_bias else None
    vb_ref = refs.pop(0) if has_qkv_bias else None
    bias_ref = refs.pop(0) if has_bias else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    len_ref = refs.pop(0) if has_lengths else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
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
        if has_qkv_bias:
            # fused projection bias (same bf16 add the matmul epilogue
            # would have performed); (1, hd) row broadcasts over block
            q = q + qb_ref[0]
            k = k + kb_ref[0]
            v = v + vb_ref[0]
        s = _masked_scores(
            causal, scale, sk_real, block_q, block_k,
            q, k, bias_ref, len_ref, b, qi, ki,
        )

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        # the softmax normalizer uses the UNdropped probabilities;
        # dropout zeroes entries of the normalized matrix (torch order:
        # softmax -> dropout -> @v)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(
                seed_ref, dropout_rate, b, qi, ki, (block_q, block_k)
            )
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v,
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        # natural-log lse at the boundary (base-2 internally)
        lse_ref[0] = (m_scr[:, :1] + jnp.log2(safe_l)) * LN2


def _fwd(q, k, v, bias, causal, scale, block_q, block_k,
         dropout_rate=0.0, dropout_seed=None, kv_lengths=None):
    bh, sq, d0 = q.shape
    sk = k.shape[1]
    # lane-align head_dim (zero feature columns are inert in q@k^T and
    # produce zero output columns, sliced away below)
    d = _round_up(d0, 128)
    block_q = min(block_q, _round_up(sq, 128))
    block_k = min(block_k, _round_up(sk, 128))
    sq_p, sk_p = _round_up(sq, block_q), _round_up(sk, block_k)
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, d - d0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, d - d0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, d - d0)))
    grid = (bh, sq_p // block_q, sk_p // block_k)

    ins = [qp, kp, vp]
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    has_bias = bias is not None
    if has_bias:
        # bias leading dim: 1 (shared), batch (shared across heads), or
        # batch*heads — all handled by integer-dividing the bh index
        nb = bias.shape[0]
        if bh % nb != 0:
            raise ValueError(f"bias batch {nb} must divide batch*heads {bh}")
        hp = bh // nb
        bp = jnp.pad(
            bias.astype(jnp.float32),
            ((0, 0), (0, sq_p - sq), (0, sk_p - sk)),
        )
        ins.append(bp)
        in_specs.append(
            pl.BlockSpec((1, block_q, block_k), lambda b, i, j: (b // hp, i, j))
        )
    if dropout_rate > 0.0:
        ins.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    has_lengths = kv_lengths is not None
    if has_lengths:
        ins.append(jnp.asarray(kv_lengths, jnp.int32))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    o, lse = pallas_call(
        functools.partial(
            _fwd_kernel, causal, scale, sk, block_q, block_k, has_bias,
            dropout_rate, has_lengths,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )(*ins)
    return o[:, :sq, :d0], lse[:, :sq, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(
    causal, scale, sk_real, block_q, block_k, has_bias, dropout_rate,
    has_lengths, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
):
    refs = list(refs)
    bias_ref = refs.pop(0) if has_bias else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    len_ref = refs.pop(0) if has_lengths else None
    (dk_ref, dv_ref, dk_scr, dv_scr) = refs
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
            causal, scale, sk_real, block_q, block_k,
            q, k, bias_ref, len_ref, b, qi, ki,
        )
        p = jnp.exp2(s - lse * LOG2E)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        if dropout_rate > 0.0:
            # identical regeneration of the forward's keep mask
            keep = _keep_mask(
                seed_ref, dropout_rate, b, qi, ki, (block_q, block_k)
            )
            inv = 1.0 / (1.0 - dropout_rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            p_drop = p
        dv_scr[...] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        # unscaled ds: the outer q·k scale is applied once to the
        # accumulated (block, d) result at finish, not per score tile
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    causal, scale, sk_real, block_q, block_k, has_bias, dropout_rate,
    has_lengths, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
):
    refs = list(refs)
    bias_ref = refs.pop(0) if has_bias else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    len_ref = refs.pop(0) if has_lengths else None
    (dq_ref, dq_scr) = refs
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
            causal, scale, sk_real, block_q, block_k,
            q, k, bias_ref, len_ref, b, qi, ki,
        )
        p = jnp.exp2(s - lse * LOG2E)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        if dropout_rate > 0.0:
            keep = _keep_mask(
                seed_ref, dropout_rate, b, qi, ki, (block_q, block_k)
            )
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        # unscaled ds; the scale lands on the accumulated dq at finish
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot(
            ds.astype(k.dtype), k,
            preferred_element_type=jnp.float32, precision=_PREC,
        )

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dbias_kernel(
    causal, scale, sk_real, block_q, block_k, hp, dropout_rate,
    has_lengths, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    bias_ref, *refs,
):
    """dbias[n] = sum over the hp heads sharing bias row n of ds.

    Grid (nb, q, kv, h) with the head-group dim INNERMOST: the output
    bias block (n, i, j) is revisited on consecutive grid steps, so the
    VMEM scratch accumulates across heads and writes back once — no
    O(bh·s²) intermediate ever reaches HBM (only the O(nb·s²) gradient
    the caller asked for).
    """
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    len_ref = refs.pop(0) if has_lengths else None
    dbias_ref, db_scr = refs
    n = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    h = pl.program_id(3)
    b = n * hp + h

    @pl.when(h == 0)
    def _init():
        db_scr[...] = jnp.zeros_like(db_scr)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = _masked_scores(
            causal, scale, sk_real, block_q, block_k,
            q, k, bias_ref, len_ref, b, qi, ki,
        )
        p = jnp.exp2(s - lse * LOG2E)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        if dropout_rate > 0.0:
            keep = _keep_mask(
                seed_ref, dropout_rate, b, qi, ki, (block_q, block_k)
            )
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        # d loss / d bias_block == d loss / d s == ds without the
        # outer scale (bias adds to s AFTER the q·k scaling)
        db_scr[...] += p * (dp - delta)

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(h == hp - 1)
    def _finish():
        dbias_ref[0] = db_scr[...].astype(dbias_ref.dtype)


def _bwd(causal, scale, block_q, block_k, res, do, dlse=None,
         dropout_rate=0.0, dropout_seed=None, kv_lengths=None,
         compute_dbias=True):
    q, k, v, bias, o, lse = res
    bh, sq, d0 = q.shape
    sk = k.shape[1]
    d = _round_up(d0, 128)
    block_q = min(block_q, _round_up(sq, 128))
    block_k = min(block_k, _round_up(sk, 128))
    sq_p, sk_p = _round_up(sq, block_q), _round_up(sk, block_k)

    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (bh, sq)
    if dlse is not None:
        # lse cotangent: d lse / d s = p, so ds = p*(dp - delta + dlse)
        # — dlse folds into delta with opposite sign
        delta = delta - dlse.astype(jnp.float32)
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, d - d0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, d - d0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, d - d0)))
    dop = jnp.pad(do, ((0, 0), (0, sq_p - sq), (0, d - d0)))
    # padded q rows: lse = +inf would give p = exp(-inf)=0; NEG_INF keeps
    # exp(s - lse) = exp(finite - (-inf)) … use a large finite so p ~ 0
    lsep = jnp.pad(
        lse[..., None], ((0, 0), (0, sq_p - sq), (0, 0)),
        constant_values=-NEG_INF,
    )
    deltap = jnp.pad(delta[..., None], ((0, 0), (0, sq_p - sq), (0, 0)))

    common_ins = [qp, kp, vp, dop, lsep, deltap]
    has_bias = bias is not None
    if has_bias:
        nb = bias.shape[0]
        if bh % nb != 0:
            raise ValueError(f"bias batch {nb} must divide batch*heads {bh}")
        hp = bh // nb
        bp = jnp.pad(
            bias.astype(jnp.float32),
            ((0, 0), (0, sq_p - sq), (0, sk_p - sk)),
        )

    # dk/dv: grid (bh, kv, q) — q innermost
    def _kv_specs():
        specs = [
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ]
        if has_bias:
            specs.append(
                pl.BlockSpec(
                    (1, block_q, block_k), lambda b, j, i: (b // hp, i, j)
                )
            )
        if dropout_rate > 0.0:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        if has_lengths:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        return specs

    has_lengths = kv_lengths is not None
    ins = common_ins + ([bp] if has_bias else [])
    if dropout_rate > 0.0:
        ins.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))
    if has_lengths:
        ins.append(jnp.asarray(kv_lengths, jnp.int32))
    dk, dv = pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal, scale, sk, block_q, block_k, has_bias,
            dropout_rate, has_lengths,
        ),
        grid=(bh, sk_p // block_k, sq_p // block_q),
        in_specs=_kv_specs(),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_p, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk_p, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )(*ins)

    # dq: grid (bh, q, kv) — kv innermost
    def _q_specs():
        specs = [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ]
        if has_bias:
            specs.append(
                pl.BlockSpec(
                    (1, block_q, block_k), lambda b, i, j: (b // hp, i, j)
                )
            )
        if dropout_rate > 0.0:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        if has_lengths:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        return specs

    dq = pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal, scale, sk, block_q, block_k, has_bias,
            dropout_rate, has_lengths,
        ),
        grid=(bh, sq_p // block_q, sk_p // block_k),
        in_specs=_q_specs(),
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(*ins)

    dbias = None
    if has_bias and not compute_dbias:
        # constant-mask caller (compute_dbias=False): no kernel launch,
        # no O(nb·s²) gradient buffer — explicit, not DCE-dependent
        dbias = jnp.zeros_like(bias)
    elif has_bias:
        # dbias: grid (nb, q, kv, heads-per-bias-row), head dim
        # innermost so the output block accumulates in VMEM. XLA DCEs
        # this whole call when the caller does not differentiate bias.
        def _db_specs():
            specs = [
                pl.BlockSpec(
                    (1, block_q, d), lambda n, i, j, h: (n * hp + h, i, 0)
                ),
                pl.BlockSpec(
                    (1, block_k, d), lambda n, i, j, h: (n * hp + h, j, 0)
                ),
                pl.BlockSpec(
                    (1, block_k, d), lambda n, i, j, h: (n * hp + h, j, 0)
                ),
                pl.BlockSpec(
                    (1, block_q, d), lambda n, i, j, h: (n * hp + h, i, 0)
                ),
                pl.BlockSpec(
                    (1, block_q, 1), lambda n, i, j, h: (n * hp + h, i, 0)
                ),
                pl.BlockSpec(
                    (1, block_q, 1), lambda n, i, j, h: (n * hp + h, i, 0)
                ),
                pl.BlockSpec(
                    (1, block_q, block_k), lambda n, i, j, h: (n, i, j)
                ),
            ]
            if dropout_rate > 0.0:
                specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            if has_lengths:
                specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            return specs

        dbias_p = pallas_call(
            functools.partial(
                _bwd_dbias_kernel, causal, scale, sk, block_q, block_k,
                hp, dropout_rate, has_lengths,
            ),
            grid=(nb, sq_p // block_q, sk_p // block_k, hp),
            in_specs=_db_specs(),
            out_specs=pl.BlockSpec(
                (1, block_q, block_k), lambda n, i, j, h: (n, i, j)
            ),
            out_shape=jax.ShapeDtypeStruct((nb, sq_p, sk_p), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((block_q, block_k), jnp.float32)
            ],
        )(*ins)
        dbias = dbias_p[:, :sq, :sk].astype(bias.dtype)
    return (
        dq[:, :sq, :d0],
        dk[:, :sk, :d0],
        dv[:, :sk, :d0],
        dbias,
    )


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    compute_dbias: bool = False,
) -> jnp.ndarray:
    """Flash attention over (batch*heads, seq, head_dim) operands.

    ``bias`` additive (bh | 1, sq, sk); ``causal`` in-kernel triangular
    mask; ``scale`` defaults to 1/sqrt(head_dim). Differentiable in
    q/k/v, and in bias when ``compute_dbias=True``: learned additive
    biases (ALiBi slopes, relative position) train correctly — dbias is
    computed by a dedicated kernel summing ds over each bias row's head
    group.

    PERFORMANCE NOTE: ``compute_dbias`` defaults to False because the
    common bias is a constant mask (padding/causal combinations) whose
    gradient nobody reads — and the dbias kernel materializes an
    O(bh·sq·sk) fp32 buffer that an EAGER (non-jit) differentiated call
    pays for even when the cotangent is discarded. Under the default
    the bias cotangent is exact zeros with no kernel launch and no
    quadratic buffer. Training a LEARNED bias requires the explicit
    ``compute_dbias=True`` opt-in; forgetting it is loud (the bias
    never moves), not silently slow.
    """
    o, _ = _fwd(
        q, k, v, bias, causal,
        scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]),
        block_q, block_k,
    )
    return o


def _fa_fwd(q, k, v, bias, causal, scale, block_q, block_k, compute_dbias):
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    o, lse = _fwd(q, k, v, bias, causal, s, block_q, block_k)
    return o, (q, k, v, bias, o, lse)


def _fa_bwd(causal, scale, block_q, block_k, compute_dbias, res, do):
    s = scale if scale is not None else 1.0 / np.sqrt(res[0].shape[-1])
    return _bwd(
        causal, s, block_q, block_k, res, do, compute_dbias=compute_dbias
    )


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_varlen(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jnp.ndarray:
    """`flash_attention` with a per-row real key length.

    ``kv_lengths`` is (batch*heads,) int32: row b attends keys
    ``[0, kv_lengths[b])``. The bound is enforced in-kernel via an iota
    compare against an SMEM scalar — the flash-grade form of a padding
    mask, with no (sq, sk) bias tensor in HBM (reference capability:
    apex/contrib/fmha packed-varlen kernels, cu_seqlens semantics).
    Rows whose length is 0 produce unspecified output (callers drop
    padded rows). Differentiable in q/k/v.
    """
    o, _ = _fwd(
        q, k, v, None, causal,
        scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]),
        block_q, block_k, kv_lengths=kv_lengths,
    )
    return o


def _fav_fwd(q, k, v, kv_lengths, causal, scale, block_q, block_k):
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    o, lse = _fwd(
        q, k, v, None, causal, s, block_q, block_k, kv_lengths=kv_lengths
    )
    return o, (q, k, v, o, lse, kv_lengths)


def _fav_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, o, lse, kv_lengths = res
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    dq, dk, dv, _ = _bwd(
        causal, s, block_q, block_k, (q, k, v, None, o, lse), do,
        kv_lengths=kv_lengths,
    )
    len_ct = np.zeros(kv_lengths.shape, jax.dtypes.float0)
    return (dq, dk, dv, len_ct)


flash_attention_varlen.defvjp(_fav_fwd, _fav_bwd)


# ---------------------------------------------------------------------------
# KV-cache decode: forward-only single-token attention
# ---------------------------------------------------------------------------


# Decode queries are one real token padded to ONE input tile of rows
# (16 covers the bf16 sublane minimum; fp32's 8 divides it) — 8x less
# MXU work per k block than riding the general forward's 128-row
# minimum q block.
DECODE_BLOCK_T = 16
# grouped K/V heads: query heads of one group fold into the paged decode
# kernel's row axis while the folded block stays at or under this many
# rows (a 512-row block of scores against a 512-row page is 1 MiB)
GROUP_FOLD_ROWS = 512


def _decode_kernel(
    scale, sk_real, block_t, block_k, has_lse,
    q_ref, k_ref, v_ref, len_ref, o_ref, *rest,
):
    """Online-softmax decode step for grid point (b, ki). Mirrors
    `_fwd_kernel`'s accumulation exactly (same `_masked_scores`, same
    base-2 domain) minus everything decode never needs: causal
    masking, bias, dropout, and the backward. ``has_lse`` adds the
    natural-log lse output the chunked-prefill merge consumes
    (models/gpt.py combines the prefix piece with the intra-chunk
    piece by log-sum-exp weights)."""
    if has_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

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
            False, scale, sk_real, block_t, block_k,
            q, k, None, len_ref, b, jnp.int32(0), ki,
        )
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v,
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # key blocks wholly past this row's live prefix are skipped — the
    # preallocated cache tail costs no MXU work for short sequences
    # (the block DMA still lands; skipping it too needs manual HBM
    # copies, left for a paged-cache PR)
    pl.when(ki * block_k < len_ref[b])(_body)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        if has_lse:
            # rows with an empty live prefix carry lse = -inf-tier so a
            # downstream log-sum-exp merge weighs them to exactly zero
            lse_ref[0] = jnp.where(
                l > 0.0,
                (m_scr[:, :1] + jnp.log2(safe_l)) * LN2,
                NEG_INF,
            )


def flash_attention_decode(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    scale: Optional[float] = None,
    block_k: int = DEFAULT_BLOCK_K,
    return_lse: bool = False,
):
    """Decode/chunk attention against a preallocated KV cache.

    ``q`` is (batch*heads, t, head_dim) — t == 1 is the single-token
    decode step; t > 1 is the chunked-prefill read, where every query
    row of a batch row shares that row's bound (the slot's prefix).
    ``k``/``v`` are (batch*heads, capacity, head_dim) cache buffers
    whose live prefix per row is ``kv_lengths`` (int32 — row b attends
    keys ``[0, kv_lengths[b])``; rows with length 0 emit zeros, and
    lse = -inf-tier so a log-sum-exp merge drops them). Forward only —
    inference never differentiates — so no vjp is defined.
    ``return_lse`` returns ``(o, lse)`` with lse (batch*heads, t) in
    natural log, the merge operand for combining this prefix piece
    with an intra-chunk piece (`flash_attention_segments_with_lse`).
    The q block is one tile of ``round_up(t, 16)`` rows instead of the
    general kernel's 128, and key blocks past a row's live prefix skip
    their MXU work entirely.
    """
    bh, t, d0 = q.shape
    sk = k.shape[1]
    s = scale if scale is not None else 1.0 / np.sqrt(d0)
    d = _round_up(d0, 128)
    block_t = _round_up(t, DECODE_BLOCK_T)
    block_k = min(block_k, _round_up(sk, 128))
    sk_p = _round_up(sk, block_k)
    qp = jnp.pad(q, ((0, 0), (0, block_t - t), (0, d - d0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, d - d0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, d - d0)))

    o, lse = pallas_call(
        functools.partial(_decode_kernel, s, sk, block_t, block_k, True),
        grid=(bh, sk_p // block_k),
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_t, 1), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, block_t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, block_t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 128), jnp.float32),
            pltpu.VMEM((block_t, 128), jnp.float32),
            pltpu.VMEM((block_t, d), jnp.float32),
        ],
    )(qp, kp, vp, jnp.asarray(kv_lengths, jnp.int32))
    if return_lse:
        return o[:, :t, :d0], lse[:, :t, 0]
    return o[:, :t, :d0]


# ---------------------------------------------------------------------------
# paged KV-cache decode: page-table-gather read path
# ---------------------------------------------------------------------------


# pages of each pool the paged decode kernel holds at once: the one being
# multiplied and two on their way (`ops/mla.py` timed the forms on a
# v5e: with one on its way the memory idles between copies, a third in
# flight gives no more)
PAGED_BUFFERS = 3

# a head block of at most this many heads is lowered with its heads
# side by side. A larger block loops one head at a time: in groups of
# four GPT's sixteen would run 19% faster (216 us a call against 266 on
# a v5e), but a loop over unrolled groups costs 0.2 s of lowering at
# each of that cell's 48 decode call sites, 15% of its set-up (PERF.md,
# PR 39)
PAGED_HEADS_UNROLLED = 4

# What one grid step of the paged decode kernel may hold in VMEM: the
# `PAGED_BUFFERS` page buffers of K and of V for its head block, the
# query/output/lse blocks (double-buffered by the pipeline) and the
# accumulators of those heads, and their float32 scores (counted for
# every head, though they run a few at a time). The head block is the
# largest divisor of the pool's heads that stays inside it
# (`_paged_head_block`); the call's own scoped-VMEM limit leaves Mosaic
# its relayout room above it (a v5e core has 128 MiB).
PAGED_VMEM_BUDGET = 16 << 20
PAGED_VMEM_LIMIT = 32 << 20


def _paged_block_bytes(hb, ps, d, block_t, kv_itemsize, q_itemsize,
                       quantized):
    """VMEM bytes of one grid step that takes ``hb`` heads."""
    kv = 2 * PAGED_BUFFERS * hb * ps * d * kv_itemsize  # K and V buffers
    if quantized:
        kv += 2 * hb * ps * d * q_itemsize  # their dequantized tiles
    per_row = (
        2 * 2 * d * q_itemsize  # q and o blocks, double-buffered
        + 2 * 128 * 4  # the lse block (lane-padded), double-buffered
        + (2 * 128 + d) * 4  # m, l and acc scratch
        + ps * 4  # float32 scores
    )
    return kv + hb * block_t * per_row


def _paged_head_block(nh, ps, d, block_t, kv_itemsize, q_itemsize,
                      quantized):
    """Heads a grid step takes: from the shapes alone, never a user's
    choice. One head is always allowed (the same kernel, head axis 1)."""
    return max(
        h for h in range(1, nh + 1)
        if nh % h == 0 and (
            h == 1
            or _paged_block_bytes(
                h, ps, d, block_t, kv_itemsize, q_itemsize, quantized
            ) <= PAGED_VMEM_BUDGET
        )
    )


def _paged_grid_row(b, nhb, row_blocks):
    """Grid step b of the paged kernel as (slot, head block, row block):
    slot-major, row blocks innermost (grouped heads: they share a K/V
    head block). b is never negative, so truncating division, and none
    where a factor is 1."""
    r = 0
    if row_blocks > 1:
        b, r = (
            jax.lax.div(b, jnp.int32(row_blocks)),
            jax.lax.rem(b, jnp.int32(row_blocks)),
        )
    if nhb == 1:
        return b, 0, r
    return (
        jax.lax.div(b, jnp.int32(nhb)), jax.lax.rem(b, jnp.int32(nhb)), r
    )


def _decode_paged_kernel(
    scale, hb, nhb, ps, num_pages, block_t, quantized, row_blocks, bound,
    tab_ref, len_ref, live_ref, *rest,
):
    """Online-softmax decode against a PAGED cache for grid step b =
    (slot, head block, row block), slot-major: ``hb`` heads of that
    slot's query rows against the pages the slot has live, one after
    another. The pools stay where they are; a page's `(hb, page_size,
    head_dim)` K slab and V slab, as the pool stores them, are copied
    into one of `PAGED_BUFFERS` buffers each. A fetch cursor ``cur`` =
    (grid step, page of its slot's list, pages asked for, pages awaited)
    runs through the live pages of ALL steps, ``PAGED_BUFFERS - 1``
    pages ahead of the products (`ops/mla.py::_kernel` is the pattern):
    whoever multiplies a page first asks for the next one the cursor
    points at, so a step's first pages are on their way before it begins
    and the copies follow one another without a gap. A step with nothing
    to read (a slot that maps no page, a length of 0) copies nothing and
    loops over nothing. Each head runs the accumulation of
    `_decode_kernel` (base-2 online softmax, natural-log lse at the
    boundary) over its own rows of the head-major scratch; the heads of
    a block of at most `PAGED_HEADS_UNROLLED` are lowered side by side.

    ``quantized`` adds per-(page, head) fp32 dequantization: int8
    tiles are scaled into the score/value dots from SMEM-resident
    scale tables (``hb`` scalar reads a page). ``live_ref[b]`` is the
    first grid step at or after b with something to read (the grid's
    length where none has), which is where the cursor goes from b - 1.

    ``bound`` (None, ``"slot"`` or ``"rows"``) is a LOWER bound on the
    positions read, a sliding window's: a fourth prefetched vector gives
    each slot's first position, the loop starts at the page ``first //
    ps`` (the pages before it are never fetched) and the first live page
    is masked from the bound on; with ``"rows"`` each query row masks
    from a bound of its own (one more block, ``(block_t, 1)``)."""
    first_ref = lo_ref = None
    if bound is not None:
        first_ref, rest = rest[0], rest[1:]
    q_ref, k_ref, v_ref, *rest = rest
    if bound == "rows":
        lo_ref, rest = rest[0], rest[1:]
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, lse_ref, k_buf, v_buf, sem, cur, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    n = pl.num_programs(0)
    slot, hblk, _ = _paged_grid_row(b, nhb, row_blocks)
    ln = len_ref[slot]
    if bound is not None:
        first = first_ref[slot]
    if quantized:
        head0 = jax.lax.mul(hblk, hb)

    # scalar arithmetic in plain `lax` primitives: an operator on a
    # traced scalar is a nested `pjit`, and the kernel is traced anew at
    # every call site of a served program (72 in the GPT cell's two)
    def page0(r):
        """The first page grid step r reads (r may be the grid's end)."""
        if bound is None:
            return jnp.int32(0)
        s = _paged_grid_row(
            jax.lax.min(r, jax.lax.sub(n, 1)), nhb, row_blocks)[0]
        return jax.lax.div(first_ref[s], jnp.int32(ps))

    def slabs(page, head):
        """The K and V slab of ``page`` for the ``hb`` heads from
        ``head`` on."""
        if nhb == 1:
            return k_ref.at[page], v_ref.at[page]
        heads = pl.ds(head, hb)
        return k_ref.at[page, heads], v_ref.at[page, heads]

    @pl.when(jax.lax.eq(b, 0))
    def _reset():
        cur[0] = live_ref[0]
        cur[1] = page0(live_ref[0])
        cur[2] = 0
        cur[3] = 0

    def ask(_, carry):
        r, j, asked = cur[0], cur[1], cur[2]

        @pl.when(jax.lax.lt(r, n))
        def _start():
            at = jax.lax.rem(asked, PAGED_BUFFERS)
            s, hk, _ = _paged_grid_row(r, nhb, row_blocks)
            k_src, v_src = slabs(
                jax.lax.min(tab_ref[s, j], num_pages - 1),
                jax.lax.mul(hk, hb) if nhb > 1 else 0)
            pltpu.make_async_copy(k_src, k_buf.at[at], sem.at[0, at]).start()
            pltpu.make_async_copy(v_src, v_buf.at[at], sem.at[1, at]).start()
            j1 = jax.lax.add(j, 1)
            last = jax.lax.ge(jax.lax.mul(j1, ps), len_ref[s])
            nxt = live_ref[jax.lax.add(r, 1)]
            cur[0] = jax.lax.select(last, nxt, r)
            cur[1] = jax.lax.select(last, page0(nxt), j1)
            cur[2] = jax.lax.add(asked, 1)

        return carry

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def _page(j, carry):
        awaited = cur[3]
        # as many asks as bring the cursor `PAGED_BUFFERS` pages past
        # the last page awaited: all of them at the call's first page,
        # one at every other
        jax.lax.fori_loop(
            jax.lax.sub(cur[2], awaited), PAGED_BUFFERS, ask, 0)
        at = jax.lax.rem(awaited, PAGED_BUFFERS)
        cur[3] = jax.lax.add(awaited, 1)
        # the waits read the semaphore and the size alone
        k_any, v_any = slabs(0, 0)
        pltpu.make_async_copy(k_any, k_buf.at[at], sem.at[0, at]).wait()
        pltpu.make_async_copy(v_any, v_buf.at[at], sem.at[1, at]).wait()
        col = jax.lax.mul(j, ps) + (
            jax.lax.broadcasted_iota(jnp.int32, (block_t, ps), 1)
        )
        if bound is not None:
            lo = first if bound == "slot" else lo_ref[...]
            seen = jnp.logical_and(col < ln, col >= lo)
        if quantized:
            page = jax.lax.min(tab_ref[slot, j], num_pages - 1)

        def _head(h, carry):
            q = q_ref[0, h, 0]  # (block_t, d)
            k = k_buf[at, h]  # (ps, d)
            v = v_buf[at, h]
            if quantized:
                k = (
                    k.astype(jnp.float32) * ks_ref[page, jax.lax.add(head0, h)]
                ).astype(q.dtype)
                v = (
                    v.astype(jnp.float32) * vs_ref[page, jax.lax.add(head0, h)]
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
        # 72 sites: 115 s of the serving cell's set-up, PERF.md PR 27).
        # A block of few heads is unrolled at LOWERING: side by side,
        # one head's products run beside the next head's softmax and a
        # page's arithmetic hides under its copy, which one head after
        # another does not (PERF.md PR 39)
        jax.lax.fori_loop(
            0, hb, _head, 0, unroll=hb <= PAGED_HEADS_UNROLLED)
        return carry

    # the slot's own live pages, in order: none where it has nothing to
    # read (the bounds are traced, so an empty range loops over nothing)
    jax.lax.fori_loop(
        page0(b),
        jax.lax.div(jax.lax.add(ln, ps - 1), jnp.int32(ps)), _page, 0)

    # every step writes its own output block, live or not: a dead
    # slot's rows are zeros at the -inf tier, which the chunk read's
    # log-sum-exp merge weighs to exactly zero
    l = l_scr[:, :, :1]
    safe_l = jnp.where(l > 0.0, l, 1.0)
    o_ref[0, :, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
    lse_ref[0, :, 0] = jnp.where(
        l > 0.0,
        (m_scr[:, :, :1] + jnp.log2(safe_l)) * LN2,
        NEG_INF,
    )


def flash_attention_decode_paged(
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
    """`flash_attention_decode` reading through a block table.

    ``q`` is (num_slots·heads, t, head_dim), slot-major (row
    ``s·heads + n`` holds slot s, head n — the layout the model's
    head-flatten produces). ``k_pool``/``v_pool`` are the shared page
    pools, (num_pages, heads, page_size, head_dim); ``page_table`` is
    (num_slots, pages_per_slot) int32 mapping each slot's page list
    into the pool (unmapped entries carry the ``num_pages`` sentinel
    and are never fetched); ``kv_lengths`` is (num_slots,) int32 —
    slot s attends cache positions ``[0, kv_lengths[s])``, and no
    further than the pages its table row maps before the first
    sentinel: a slot that owns no page reads nothing, whatever length
    it carries (the engine's dead rows carry the capacity sentinel),
    and emits zeros at the -inf tier.

    ONE grid step a (slot, head block): the step loops over the pages
    the slot has live, in order, and takes a BLOCK OF HEADS of each. All
    heads of a page are contiguous in the pool, so the K and V tiles
    are one `(hb, page_size, head_dim)` slab each. The pools are not
    blocked by the grid; they stay in device memory, and a fetch cursor
    copies each live slab into one of `PAGED_BUFFERS` buffers, running
    two pages ahead of the products through the live pages of ALL
    steps: a slot's first pages were asked for by the live slots before
    it, and only the call's first page is waited for from its start
    (`ops/mla.py::mla_decode_paged` is the pattern, timed on a v5e in
    PERF.md, PR 31 and PR 39). ``hb`` follows the shapes of the call
    (the largest divisor of the pool's heads whose blocks and page
    buffers fit ``PAGED_VMEM_BUDGET``: all 16 heads of a 512-row bf16
    page at the decode step, 4 under a 256-row chunk), so the grid is
    ``num_slots · heads / hb`` steps whatever ``pages_per_slot`` is. HBM
    reads are the pages actually live, the paged answer to the
    contiguous kernel's fixed-capacity tail DMA: no page past a slot's
    live prefix is asked for, and a slot with NOTHING to read (length
    0, or no mapped page) copies nothing, loops over nothing and costs
    its one step and its small query/output blocks. A live page is read
    whole, however few of its rows are live.

    GROUPED K/V heads: ``q`` may hold ``g`` query heads per pool head,
    (num_slots·heads·g, t, head_dim) with query head ``n·g + i``
    reading pool head n. The g heads of a group differ only in their
    query rows, so they are folded into the kernel's row axis (a free
    reshape: as many as keep a row block at or under
    ``GROUP_FOLD_ROWS``) and a K/V page is fetched once for all of
    them; what does not fold takes further grid steps, row blocks of
    the same head block. With g = 1 nothing changes.

    ``k_scale``/``v_scale`` ((num_pages, heads) fp32) switch the pools
    to int8 with per-(page, head) dequantization inside the kernel's
    inner loop (the cache-bytes half of the EQuARX trade). Forward
    only, like every decode read. ``return_lse`` as in
    `flash_attention_decode` (rows with an empty prefix carry
    -inf-tier lse so a log-sum-exp merge drops them).

    ``window`` (a layer with a sliding window; None reads from position
    0 and traces what it always did) bounds the read from below. Without
    ``q_positions`` every row of slot s is the decode row at position
    ``kv_lengths[s] - 1`` and attends ``[max(0, kv_lengths[s] - window),
    kv_lengths[s])``: the ``window`` keys that end at its own. With
    ``q_positions`` (t,) row r sits at that position (a packed chunk
    scored against each slot's prefix: a slot's earliest row is at
    ``kv_lengths[s]``) and attends ``[max(0, q_positions[r] + 1 -
    window), kv_lengths[s])``. Either way the pages that lie wholly
    before a slot's bound are neither fetched nor need to be mapped (the
    cache frees them: their table entries hold the sentinel), the first
    live page is masked from the bound on, and a slot's loop starts at
    its bound's page.
    """
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
            if group % f == 0 and (f == 1 or f * t <= GROUP_FOLD_ROWS)
        )
        out = flash_attention_decode_paged(
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
    bound = first = None
    if window is not None:
        if quantized:
            raise ValueError("a windowed read has no int8 form")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        bound = "slot" if q_positions is None else "rows"
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
    # live[b]: the first grid step at or after b with something to read
    # (the grid's length: none), which is where the fetch cursor goes
    # from step b - 1; a slot's steps (its head and row blocks) read
    # alike
    steps = num_slots * nhb * rb
    reads = lens > (0 if window is None else first // ps * ps)
    if nhb * rb > 1:
        reads = jnp.repeat(reads, nhb * rb)
    live = jax.lax.cummin(
        jnp.where(
            jnp.pad(reads, (0, 1)),
            jnp.arange(steps + 1, dtype=jnp.int32), steps),
        reverse=True)

    def _row_map(b, *_):
        return (*_paged_grid_row(b, nhb, rb), 0, 0)

    pool = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, hb, 1, block_t, d), _row_map), pool, pool]
    ins = [qp, kp, vp]
    prefetch = [table, lens, live]
    if window is not None:
        prefetch.append(first)
        if bound == "rows":
            # each row's own bound; the rows that pad the block read all
            lo = jnp.maximum(
                jnp.asarray(q_positions, jnp.int32) + 1 - window, 0)
            in_specs.append(
                pl.BlockSpec((block_t, 1), lambda b, *_: (0, 0)))
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
        grid=(steps,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, hb, 1, block_t, d), _row_map),
            pl.BlockSpec((1, hb, 1, block_t, 1), _row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((PAGED_BUFFERS, hb, ps, d), kp.dtype),
            pltpu.VMEM((PAGED_BUFFERS, hb, ps, d), vp.dtype),
            pltpu.SemaphoreType.DMA((2, PAGED_BUFFERS)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((hb, block_t, 128), jnp.float32),
            pltpu.VMEM((hb, block_t, 128), jnp.float32),
            pltpu.VMEM((hb, block_t, d), jnp.float32),
        ],
    )
    o, lse = pallas_call(
        functools.partial(
            _decode_paged_kernel, s, hb, nhb, ps, num_pages, block_t,
            quantized, rb, bound,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct(qp.shape[:-1] + (1,), jnp.float32),
        ],
        # the cursor's state goes from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=PAGED_VMEM_LIMIT,
        ),
    )(*prefetch, *ins)
    o = o.reshape(bh, block_t, d)[:, :t, :d0]
    if return_lse:
        return o, lse.reshape(bh, block_t)[:, :t]
    return o


# ---------------------------------------------------------------------------
# packed-QKV path: zero-relayout attention
# ---------------------------------------------------------------------------
#
# The (batch*heads, seq, head_dim) layout forces callers to transpose
# the fused QKV projection output (B, S, nh, 3·hd) into head-major
# form and back — on the 134M GPT bench those relayouts (split + 2
# transposes + context transpose, plus the non-contiguous residual
# adds they induce) cost ~8 ms/step. The packed path instead reads
# q/k/v tiles STRAIGHT OUT of the projection output via BlockSpec
# index maps and writes the context back in (B, S, nh, hd) layout,
# bitcast-compatible with the (B, S, H) input of the output projection.
# No transpose, no split, no concat appears anywhere in the forward
# graph.
#
# WHO TAKES IT is written once, in `packed_heads_per_step`; the model's
# call site (`models/gpt.py::ParallelAttention`, `will_pack`) asks it and
# adds only what the kernels cannot see (no cache, no context-parallel
# axis, and a mask that is absent or a (B, S) key row). Mosaic takes a
# last-dimension block only in multiples of 128 lanes, and a head's
# [q|k|v] columns are 3·hd wide, so a grid step takes G heads with
# G·3·hd % 128 == 0:
#
# * hd % 128 == 0: G = 1, any sequence length. One tile covering the
#   sequence runs the direct-softmax forward and the merged backward
#   below; longer sequences run the general online-softmax kernels over
#   (1, block, hd) columns of the same buffer.
# * hd == 64 and an even head count: G = 2, ONE TILE only (a sequence of
#   at most `DEFAULT_BLOCK_Q` rows). The step's (1, S, 384) block is
#   [q0 k0 | v0 q1 | k1 v1]; the two heads are sliced out of it in VMEM
#   and run one after the other; the context leaves as one lane-dense
#   (1, S, 128) block [o0|o1] and the cotangent as one (1, S, 384) block
#   in the projection's own layout. Nothing is padded to 128 in HBM.
#
# Anything else (an odd head count, another head width, heads of 64 over
# more than one tile) is the caller's to send down the head-major path.


def packed_heads_per_step(nh, hd, seq, block_q=DEFAULT_BLOCK_Q,
                          block_k=DEFAULT_BLOCK_K):
    """Heads one grid step of the packed path takes for ``nh`` heads of
    ``hd`` over ``seq`` rows, or None where the packed path does not
    apply (the one statement of its conditions: see the section's
    comment)."""
    if hd % 128 == 0:
        return 1
    if hd == 64 and nh % 2 == 0 and _one_tile(seq, block_q, block_k):
        return 2
    return None


def _one_tile(seq, block_q, block_k):
    """The block that covers ``seq`` rows in one (block, block) tile, or
    None where the blocks asked for leave more than one."""
    block_q = min(block_q, _round_up(seq, 128))
    block_k = min(block_k, _round_up(seq, 128))
    if block_q == block_k and _round_up(seq, block_q) == block_q:
        return block_q
    return None


def _pad_seq(x, rows):
    """``x`` (B, S, width) zero-padded to ``rows`` along S; no `pad` in
    the graph where there is nothing to pad."""
    if x.shape[1] == rows:
        return x
    return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, 0)))


def _key_row(key_mask, rows):
    """The (B, S) key keep-mask (nonzero = attend) as the additive
    float32 (B, 1, rows) row the kernels add to every score row: 2 KB a
    sequence where a (S, S) bias tile is 1 MiB. It rides the kernels'
    ``bias_ref`` operand, so the masking itself stays in
    `_masked_scores`. Columns past S need no entry (the kernels bound
    them by ``sk_real``)."""
    row = jnp.where(key_mask != 0, 0.0, NEG_INF).astype(jnp.float32)
    if row.shape[1] != rows:
        row = jnp.pad(row, ((0, 0), (0, rows - row.shape[1])))
    return row[:, None, :]


def _along_lanes(col):
    """A (rows, 1) float32 column as a (1, rows) row, through an aligned
    2-d transpose. The one-tile kernels save the log-sum-exp this way:
    as a (B·nh, S, 1) array the chip tiles it (8, 128), 128 times its
    bytes in HBM (64 MiB a layer at 16 x 16 heads x 512) moved 4 bytes
    at a 512-byte stride; as (B·nh, 1, S) it is 0.5 MiB of whole rows."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[:1]


def _along_sublanes(row):
    """`_along_lanes` undone: a (1, rows) row as a (rows, 1) column."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]


def _flat_head(step, heads, h):
    """The flat batch·head index of head ``h`` of grid step ``step``:
    what keys a head's dropout stream, whatever the heads a step."""
    if heads == 1:
        return step
    return jax.lax.add(jax.lax.mul(step, heads), h)


def _head_columns(x, h, hd):
    """(q, k, v) of head ``h`` out of a step's [q|k|v]-per-head block."""
    at = 3 * h * hd
    return (
        x[:, at:at + hd], x[:, at + hd:at + 2 * hd],
        x[:, at + 2 * hd:at + 3 * hd],
    )


def _fwd_single_kernel(
    causal, scale, sk_real, block, hd, heads, dropout_rate,
    has_qkv_bias, has_key_row, x_ref, *refs,
):
    """Single-tile forward over a step's ``heads`` heads: the
    online-softmax carry (m/l scratch, correction multiplies, init /
    finish phases) degenerates when one (block, block) tile covers the
    whole sequence — each head's row softmax is computed directly. Same
    masking via `_masked_scores`, same dropout stream as the general
    kernel (keyed by the flat batch·head index)."""
    refs = list(refs)
    b_ref = refs.pop(0) if has_qkv_bias else None
    key_ref = refs.pop(0) if has_key_row else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    o_ref, lse_ref = refs
    step = pl.program_id(0)
    zero = jnp.int32(0)
    x = x_ref[0]
    if has_qkv_bias:
        # fused projection bias (same bf16 add the matmul epilogue
        # would have performed); the (1, G·3·hd) row broadcasts
        x = x + b_ref[0]
    for h in range(heads):
        head = _flat_head(step, heads, h)
        q, k, v = _head_columns(x, h, hd)
        s = _masked_scores(
            causal, scale, sk_real, block, block,
            q, k, key_ref, None, head, zero, zero,
        )
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp2(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(
                seed_ref, dropout_rate, head, zero, zero, (block, block)
            )
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        safe_l = jnp.where(l > 0.0, l, 1.0)
        acc = jax.lax.dot(
            p.astype(v.dtype), v,
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        o_ref[0, :, h * hd:(h + 1) * hd] = (acc / safe_l).astype(
            o_ref.dtype
        )
        lse_ref[h] = _along_lanes((m + jnp.log2(safe_l)) * LN2)


def _step_maps(nh, heads):
    """Index maps of the one-tile kernels' 1-d grid, one step a group of
    ``heads`` heads: the group's block of a (B, rows, groups·width)
    buffer, its row of a (groups, 1, width) buffer, its sequence's row
    of a (B, 1, rows) buffer, and its own row of a per-step buffer.
    `lax.div`/`rem`: `//` and `%` trace through `sign` and a nested
    `pjit` at every call site."""
    groups = nh // heads

    def of_batch(b):
        return jax.lax.div(b, groups)

    def of_group(b):
        return jax.lax.rem(b, groups)

    return (
        lambda b: (of_batch(b), 0, of_group(b)),
        lambda b: (of_group(b), 0, 0),
        lambda b: (of_batch(b), 0, 0),
        lambda b: (b, 0, 0),
    )


def _optional_operands(nh, heads, width, block, qkv_bias, key_mask,
                       dropout_rate, dropout_seed):
    """The one-tile kernels' optional operands with their specs, in the
    order both kernels take them: the projection bias, the key row, the
    dropout seed."""
    _, of_group, of_batch, _ = _step_maps(nh, heads)
    ins, specs = [], []
    if qkv_bias is not None:
        # middle singleton dim so the (1, width) tile equals the
        # array's last-two dims (Mosaic block divisibility rule)
        ins.append(qkv_bias.reshape(nh // heads, 1, width))
        specs.append(pl.BlockSpec((1, 1, width), of_group))
    if key_mask is not None:
        ins.append(_key_row(key_mask, block))
        specs.append(pl.BlockSpec((1, 1, block), of_batch))
    if dropout_rate > 0.0:
        ins.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    return ins, specs


def _fwd_packed(qkv, causal, scale, block_q, block_k,
                dropout_rate=0.0, dropout_seed=None, qkv_bias=None,
                key_mask=None):
    B, S, nh, three_hd = qkv.shape
    hd = three_hd // 3
    heads = packed_heads_per_step(nh, hd, S, block_q, block_k)
    if three_hd != 3 * hd or heads is None:
        raise ValueError(
            "packed path needs qkv (B, S, nh, 3*hd) with hd % 128 == 0, "
            "or hd == 64 with an even nh and a sequence of one tile; "
            f"got {qkv.shape}"
        )
    # Pallas TPU tiles the LAST TWO dims, so the head lives in the flat
    # last axis of the (B, S, nh*3*hd) view (free reshape of the
    # projection output)
    qkv3 = qkv.reshape(B, S, nh * three_hd)
    has_qkv_bias = qkv_bias is not None
    has_key_row = key_mask is not None
    block = _one_tile(S, block_q, block_k)
    if block is not None:
        # one tile covers the sequence: direct softmax, no online carry
        width = heads * three_hd
        of_block, _, _, of_step = _step_maps(nh, heads)
        more, more_specs = _optional_operands(
            nh, heads, width, block, qkv_bias, key_mask, dropout_rate,
            dropout_seed,
        )
        o, lse = pallas_call(
            functools.partial(
                _fwd_single_kernel, causal, scale, S, block, hd, heads,
                dropout_rate, has_qkv_bias, has_key_row,
            ),
            grid=(B * nh // heads,),
            in_specs=[pl.BlockSpec((1, block, width), of_block)]
            + more_specs,
            out_specs=[
                pl.BlockSpec((1, block, heads * hd), of_block),
                pl.BlockSpec((heads, 1, block), of_step),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, block, nh * hd), qkv.dtype),
                jax.ShapeDtypeStruct((B * nh, 1, block), jnp.float32),
            ],
        )(_pad_seq(qkv3, block), *more)
        return o[:, :S], lse

    block_q = min(block_q, _round_up(S, 128))
    block_k = min(block_k, _round_up(S, 128))
    # each grid dim rounds against ITS OWN block size (a shared
    # round_up(max(bq,bk)) would silently drop tail blocks when the
    # other block size does not divide it); the single padded buffer
    # covers the larger of the two
    sq_p = _round_up(S, block_q)
    sk_p = _round_up(S, block_k)
    pad = max(sq_p, sk_p)
    # hd-sized block column (head*3 + {0,1,2}) of the flat view
    qkv_p = _pad_seq(qkv3, pad)
    ins = [qkv_p, qkv_p, qkv_p]
    in_specs = [
        pl.BlockSpec(
            (1, block_q, hd), lambda b, i, j: (b // nh, i, (b % nh) * 3)
        ),
        pl.BlockSpec(
            (1, block_k, hd),
            lambda b, i, j: (b // nh, j, (b % nh) * 3 + 1),
        ),
        pl.BlockSpec(
            (1, block_k, hd),
            lambda b, i, j: (b // nh, j, (b % nh) * 3 + 2),
        ),
    ]
    if has_qkv_bias:
        b2 = qkv_bias.reshape(nh * 3, 1, hd)
        ins += [b2, b2, b2]
        in_specs += [
            pl.BlockSpec((1, 1, hd), lambda b, i, j: ((b % nh) * 3, 0, 0)),
            pl.BlockSpec(
                (1, 1, hd), lambda b, i, j: ((b % nh) * 3 + 1, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, hd), lambda b, i, j: ((b % nh) * 3 + 2, 0, 0)
            ),
        ]
    if has_key_row:
        # the general kernels' additive-bias operand, one row high
        ins.append(_key_row(key_mask, sk_p))
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // nh, 0, j))
        )
    if dropout_rate > 0.0:
        ins.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    o, lse = pallas_call(
        functools.partial(
            _fwd_kernel, causal, scale, S, block_q, block_k, has_key_row,
            dropout_rate, False, has_qkv_bias=has_qkv_bias,
        ),
        grid=(B * nh, sq_p // block_q, sk_p // block_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (1, block_q, hd), lambda b, i, j: (b // nh, i, b % nh)
            ),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, sq_p, nh * hd), qkv.dtype),
            jax.ShapeDtypeStruct((B * nh, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )(*ins)
    return o[:, :S], lse[:, :S]


def _bwd_merged_kernel(
    causal, scale, sk_real, block, hd, heads, dropout_rate,
    has_qkv_bias, has_key_row, x_ref, do_ref, lse_ref, o_ref, *refs,
):
    """Single-tile fused backward: dq + dk + dv of a step's ``heads``
    heads in ONE kernel pass.

    Used when one (block, block) tile covers the whole sequence (the
    common training regime, e.g. s=1024 blocks 1024²). The split dkv/dq
    kernels each recompute the score and dp matrices and each re-read
    q/k/v/do from HBM — 7 MXU matmuls and 2x input traffic. This kernel
    shares those intermediates (5 matmuls, one read) and writes the
    cotangents STRAIGHT INTO the packed projection layout: dqkv_ref is
    the step's (1, block, heads·3·hd) column of the (B, S, nh·3·hd)
    qkv-projection cotangent, so the 3-way concat the split path needs
    disappears entirely. delta = rowsum(do·o) is also computed here from
    the o tile (a few VPU ops on data already in VMEM) instead of as a
    separate XLA reduction pass over the full (B, S, nh, hd) product in
    HBM."""
    refs = list(refs)
    b_ref = refs.pop(0) if has_qkv_bias else None
    key_ref = refs.pop(0) if has_key_row else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    if has_qkv_bias:
        dqkv_ref, dbias_ref = refs
    else:
        (dqkv_ref,) = refs
    step = pl.program_id(0)
    zero = jnp.int32(0)  # qi = ki = 0: the single block
    x = x_ref[0]
    if has_qkv_bias:
        # the saved residual is the PRE-bias projection output; the
        # probability recompute needs the biased operands
        x = x + b_ref[0]
    do_all = do_ref[0]
    dod = do_all.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
    for h in range(heads):
        head = _flat_head(step, heads, h)
        q, k, v = _head_columns(x, h, hd)
        do = do_all[:, h * hd:(h + 1) * hd]
        delta = jnp.sum(
            dod[:, h * hd:(h + 1) * hd], axis=-1, keepdims=True
        )
        s = _masked_scores(
            causal, scale, sk_real, block, block,
            q, k, key_ref, None, head, zero, zero,
        )
        p = jnp.exp2(s - _along_sublanes(lse_ref[h]) * LOG2E)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        if dropout_rate > 0.0:
            keep = _keep_mask(
                seed_ref, dropout_rate, head, zero, zero, (block, block)
            )
            inv = 1.0 / (1.0 - dropout_rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            p_drop = p
        dv = jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        # unscaled ds: the q·k scale is applied to the (block, d) dq/dk
        # results, not the (block, block) score tile
        ds = (p * (dp - delta)).astype(q.dtype)
        dk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        ) * scale
        dq = jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32, precision=_PREC,
        ) * scale
        for j, g in enumerate((dq, dk, dv)):
            at = (3 * h + j) * hd
            dqkv_ref[0, :, at:at + hd] = g.astype(dqkv_ref.dtype)
            if has_qkv_bias:
                # fp32 per-(batch, head) bias-grad partials while the
                # cotangent tiles are still in VMEM — replaces a full
                # XLA reduction pass over the (B, S, nh, 3hd) dqkv
                # buffer in HBM (whose producer is this opaque kernel,
                # so XLA cannot fuse it)
                dbias_ref[0, :, at:at + hd] = jnp.sum(
                    g, axis=0, keepdims=True
                )


def _bwd_packed_merged(causal, scale, block, res, do,
                       dropout_rate=0.0, dropout_seed=None,
                       qkv_bias=None, key_mask=None):
    """Single-tile packed backward: see `_bwd_merged_kernel`.

    With ``qkv_bias`` also returns the (nh*3*hd,) fp32 bias cotangent
    (summed over batch from the kernel's per-(batch, group) partials)."""
    qkv, o, lse = res
    B, S, nh, three_hd = qkv.shape
    hd = three_hd // 3
    heads = packed_heads_per_step(nh, hd, S, block, block)
    width = heads * three_hd
    of_block, _, _, of_step = _step_maps(nh, heads)

    ins = [
        _pad_seq(qkv.reshape(B, S, nh * three_hd), block),
        _pad_seq(do, block),
        lse,
        _pad_seq(o, block),
    ]
    in_specs = [
        pl.BlockSpec((1, block, width), of_block),
        pl.BlockSpec((1, block, heads * hd), of_block),
        pl.BlockSpec((heads, 1, block), of_step),
        pl.BlockSpec((1, block, heads * hd), of_block),
    ]
    has_qkv_bias = qkv_bias is not None
    has_key_row = key_mask is not None
    more, more_specs = _optional_operands(
        nh, heads, width, block, qkv_bias, key_mask, dropout_rate,
        dropout_seed,
    )
    ins += more
    in_specs += more_specs

    out_specs = pl.BlockSpec((1, block, width), of_block)
    out_shape = jax.ShapeDtypeStruct((B, block, nh * three_hd), qkv.dtype)
    if has_qkv_bias:
        out_specs = [out_specs, pl.BlockSpec((1, 1, width), of_step)]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((B * nh // heads, 1, width), jnp.float32),
        ]

    out = pallas_call(
        functools.partial(
            _bwd_merged_kernel, causal, scale, S, block, hd, heads,
            dropout_rate, has_qkv_bias, has_key_row,
        ),
        grid=(B * nh // heads,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
    )(*ins)
    if has_qkv_bias:
        dqkv, dbias_part = out
        dbias = jnp.sum(
            dbias_part.reshape(B, nh * three_hd), axis=0
        )
        return dqkv[:, :S].reshape(B, S, nh, three_hd), dbias
    return out[:, :S].reshape(B, S, nh, three_hd)


def _bwd_packed(causal, scale, block_q, block_k, res, do,
                dropout_rate=0.0, dropout_seed=None, qkv_bias=None,
                key_mask=None):
    qkv, o, lse = res  # qkv (B,S,nh,3hd), o (B,S,nh*hd), lse (B*nh,S,1)
    B, S, nh, three_hd = qkv.shape
    hd = three_hd // 3
    block = _one_tile(S, block_q, block_k)
    if block is not None:
        # one tile covers the sequence: fused dq+dk+dv kernel, no concat
        return _bwd_packed_merged(
            causal, scale, block, res, do,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            qkv_bias=qkv_bias, key_mask=key_mask,
        )
    block_q = min(block_q, _round_up(S, 128))
    block_k = min(block_k, _round_up(S, 128))
    sq_p = _round_up(S, block_q)
    sk_p = _round_up(S, block_k)
    if qkv_bias is not None:
        # multi-tile fallback: biased operands via the pre-add (the
        # kernels then see the same values), dbias via an XLA reduce.
        # PRECISION: the reduce sums dqkv AFTER it is rounded to the
        # qkv dtype (bf16), whereas the single-tile merged path
        # accumulates fp32 partials in VMEM before casting — bias-grad
        # error here grows ~sqrt(B*S)·2^-8 relative. Acceptable for a
        # fallback (bias grads are O(B*S) sums either way and feed an
        # fp32 master update); emit fp32 partials from the split
        # kernels if large-B*S bias fidelity ever matters.
        qkv = qkv + qkv_bias.reshape(nh, three_hd).astype(qkv.dtype)
        dqkv = _bwd_packed(
            causal, scale, block_q, block_k, (qkv, o, lse), do,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            key_mask=key_mask,
        )
        return dqkv, jnp.sum(
            dqkv.astype(jnp.float32), axis=(0, 1)
        ).reshape(-1)
    pad = max(sq_p, sk_p)

    # delta rows are keyed by flat (B*nh) like lse: (B,S,nh) -> (B*nh,S,1)
    do4 = do.reshape(B, S, nh, hd)
    o4 = o.reshape(B, S, nh, hd)
    delta = jnp.sum(
        do4.astype(jnp.float32) * o4.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(B * nh, S, 1)

    qkv_p = jnp.pad(
        qkv.reshape(B, S, nh * three_hd), ((0, 0), (0, pad - S), (0, 0))
    )
    do_p = jnp.pad(do, ((0, 0), (0, pad - S), (0, 0)))
    lse_p = jnp.pad(
        lse, ((0, 0), (0, pad - S), (0, 0)), constant_values=-NEG_INF
    )
    delta_p = jnp.pad(delta, ((0, 0), (0, pad - S), (0, 0)))

    ins = [qkv_p, qkv_p, qkv_p, do_p, lse_p, delta_p]
    has_key_row = key_mask is not None
    if has_key_row:
        ins.append(_key_row(key_mask, sk_p))
    if dropout_rate > 0.0:
        ins.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))

    def _specs(q_of, k_of):
        # q_of/k_of: map grid point (b, a, c) -> q-block / k-block index
        specs = [
            pl.BlockSpec(
                (1, block_q, hd),
                lambda b, a, c: (b // nh, q_of(a, c), (b % nh) * 3),
            ),
            pl.BlockSpec(
                (1, block_k, hd),
                lambda b, a, c: (b // nh, k_of(a, c), (b % nh) * 3 + 1),
            ),
            pl.BlockSpec(
                (1, block_k, hd),
                lambda b, a, c: (b // nh, k_of(a, c), (b % nh) * 3 + 2),
            ),
            pl.BlockSpec(
                (1, block_q, hd),
                lambda b, a, c: (b // nh, q_of(a, c), b % nh),
            ),
            pl.BlockSpec(
                (1, block_q, 1), lambda b, a, c: (b, q_of(a, c), 0)
            ),
            pl.BlockSpec(
                (1, block_q, 1), lambda b, a, c: (b, q_of(a, c), 0)
            ),
        ]
        if has_key_row:
            specs.append(pl.BlockSpec(
                (1, 1, block_k), lambda b, a, c: (b // nh, 0, k_of(a, c))
            ))
        if dropout_rate > 0.0:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        return specs

    # dk/dv: grid (bh, kv, q) — q innermost
    dk, dv = pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal, scale, S, block_q, block_k,
            has_key_row, dropout_rate, False,
        ),
        grid=(B * nh, sk_p // block_k, sq_p // block_q),
        in_specs=_specs(q_of=lambda j, i: i, k_of=lambda j, i: j),
        out_specs=[
            pl.BlockSpec(
                (1, block_k, hd), lambda b, j, i: (b // nh, j, b % nh)
            ),
            pl.BlockSpec(
                (1, block_k, hd), lambda b, j, i: (b // nh, j, b % nh)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, sk_p, nh * hd), qkv.dtype),
            jax.ShapeDtypeStruct((B, sk_p, nh * hd), qkv.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
    )(*ins)

    # dq: grid (bh, q, kv) — kv innermost
    dq = pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal, scale, S, block_q, block_k,
            has_key_row, dropout_rate, False,
        ),
        grid=(B * nh, sq_p // block_q, sk_p // block_k),
        in_specs=_specs(q_of=lambda i, j: i, k_of=lambda i, j: j),
        out_specs=pl.BlockSpec(
            (1, block_q, hd), lambda b, i, j: (b // nh, i, b % nh)
        ),
        out_shape=jax.ShapeDtypeStruct((B, sq_p, nh * hd), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
    )(*ins)

    # the only relayout in the whole path: one concat into the qkv
    # cotangent (the projection's own (B, S, nh, 3·hd) layout)
    dqkv = jnp.concatenate(
        [
            dq[:, :S].reshape(B, S, nh, hd),
            dk[:, :S].reshape(B, S, nh, hd),
            dv[:, :S].reshape(B, S, nh, hd),
        ],
        axis=-1,
    )
    return dqkv


def _qkv_scale(qkv, scale):
    return scale if scale is not None else 1.0 / np.sqrt(qkv.shape[-1] // 3)


# The four entries below share one contract. ``qkv`` is (B, S, nh, 3*hd)
# — exactly the reshape of a fused QKV projection, q|k|v contiguous per
# head in the last dim — at a head width and count that
# `packed_heads_per_step` takes. ``key_mask`` is an optional (B, S) mask
# of KEYS (nonzero = attend): a padded batch's own row, added to every
# score row inside the kernels. It masks keys alone. A row whose own
# position is padded still attends the kept keys and holds their
# softmax-weighted values (finite; the mean of all values where a
# sequence keeps no key at all); nothing downstream of a padding mask
# reads such a row, and its cotangent is whatever the caller hands in
# (zero from a masked loss). The mask is data, not a parameter: its
# cotangent is zero.


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def flash_attention_qkv(
    qkv: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    key_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Zero-relayout self attention on a fused projection output.

    Returns the (B, S, nh*hd) context, laid out for the output
    projection. q/k/v tiles are read straight out of ``qkv`` by kernel
    index maps: no transpose, split, or concat materializes in forward
    (the multi-tile backward does one concat for the qkv cotangent, the
    one-tile backward none).
    """
    o, _ = _fwd_packed(
        qkv, causal, _qkv_scale(qkv, scale), block_q, block_k,
        key_mask=key_mask,
    )
    return o


def _faq_fwd(qkv, causal, scale, block_q, block_k, key_mask):
    o, lse = _fwd_packed(
        qkv, causal, _qkv_scale(qkv, scale), block_q, block_k,
        key_mask=key_mask,
    )
    return o, (qkv, o, lse, key_mask)


def _faq_bwd(causal, scale, block_q, block_k, res, do):
    qkv, o, lse, key_mask = res
    dqkv = _bwd_packed(
        causal, _qkv_scale(qkv, scale), block_q, block_k, (qkv, o, lse),
        do, key_mask=key_mask,
    )
    return (dqkv, None)


flash_attention_qkv.defvjp(_faq_fwd, _faq_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def flash_attention_qkv_dropout(
    qkv: jnp.ndarray,
    dropout_seed,
    dropout_rate: float,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    key_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """`flash_attention_qkv` with in-kernel attention dropout (see
    `flash_attention_dropout` for the seeding/regeneration scheme)."""
    o, _ = _fwd_packed(
        qkv, causal, _qkv_scale(qkv, scale), block_q, block_k,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        key_mask=key_mask,
    )
    return o


def _faqd_fwd(qkv, dropout_seed, dropout_rate, causal, scale,
              block_q, block_k, key_mask):
    o, lse = _fwd_packed(
        qkv, causal, _qkv_scale(qkv, scale), block_q, block_k,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        key_mask=key_mask,
    )
    return o, (qkv, o, lse, dropout_seed, key_mask)


def _faqd_bwd(dropout_rate, causal, scale, block_q, block_k, res, do):
    qkv, o, lse, seed, key_mask = res
    dqkv = _bwd_packed(
        causal, _qkv_scale(qkv, scale), block_q, block_k,
        (qkv, o, lse), do,
        dropout_rate=dropout_rate, dropout_seed=seed, key_mask=key_mask,
    )
    seed_ct = np.zeros((), jax.dtypes.float0)
    return (dqkv, seed_ct, None)


flash_attention_qkv_dropout.defvjp(_faqd_fwd, _faqd_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def flash_attention_qkv_bias(
    qkv: jnp.ndarray,
    qkv_bias: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    key_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """`flash_attention_qkv` with the QKV-projection BIAS fused in.

    ``qkv`` is the bias-free fused projection output (B, S, nh, 3*hd)
    (e.g. from `ColumnParallelLinear(skip_bias_add=True)`) and
    ``qkv_bias`` its (nh*3*hd,) bias. The add happens on tile load (the
    same bf16 add a matmul epilogue performs) and — the actual point —
    the backward emits fp32 bias-grad partials from VMEM, replacing the
    full-buffer XLA reduction over dqkv that cannot fuse with this
    kernel's opaque output. The reference fuses qkv biases into its
    attention kernels the same way
    (apex/contrib/csrc/multihead_attn/ *_bias variants)."""
    o, _ = _fwd_packed(
        qkv, causal, _qkv_scale(qkv, scale), block_q, block_k,
        qkv_bias=qkv_bias, key_mask=key_mask,
    )
    return o


def _faqb_fwd(qkv, qkv_bias, causal, scale, block_q, block_k, key_mask):
    o, lse = _fwd_packed(
        qkv, causal, _qkv_scale(qkv, scale), block_q, block_k,
        qkv_bias=qkv_bias, key_mask=key_mask,
    )
    return o, (qkv, qkv_bias, o, lse, key_mask)


def _faqb_bwd(causal, scale, block_q, block_k, res, do):
    qkv, qkv_bias, o, lse, key_mask = res
    dqkv, dbias = _bwd_packed(
        causal, _qkv_scale(qkv, scale), block_q, block_k,
        (qkv, o, lse), do, qkv_bias=qkv_bias, key_mask=key_mask,
    )
    return (
        dqkv, dbias.astype(qkv_bias.dtype).reshape(qkv_bias.shape), None,
    )


flash_attention_qkv_bias.defvjp(_faqb_fwd, _faqb_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_qkv_bias_dropout(
    qkv: jnp.ndarray,
    qkv_bias: jnp.ndarray,
    dropout_seed,
    dropout_rate: float,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    key_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """`flash_attention_qkv_bias` with in-kernel attention dropout."""
    o, _ = _fwd_packed(
        qkv, causal, _qkv_scale(qkv, scale), block_q, block_k,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        qkv_bias=qkv_bias, key_mask=key_mask,
    )
    return o


def _faqbd_fwd(qkv, qkv_bias, dropout_seed, dropout_rate, causal, scale,
               block_q, block_k, key_mask):
    o, lse = _fwd_packed(
        qkv, causal, _qkv_scale(qkv, scale), block_q, block_k,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        qkv_bias=qkv_bias, key_mask=key_mask,
    )
    return o, (qkv, qkv_bias, o, lse, dropout_seed, key_mask)


def _faqbd_bwd(dropout_rate, causal, scale, block_q, block_k, res, do):
    qkv, qkv_bias, o, lse, seed, key_mask = res
    dqkv, dbias = _bwd_packed(
        causal, _qkv_scale(qkv, scale), block_q, block_k,
        (qkv, o, lse), do,
        dropout_rate=dropout_rate, dropout_seed=seed, qkv_bias=qkv_bias,
        key_mask=key_mask,
    )
    seed_ct = np.zeros((), jax.dtypes.float0)
    return (
        dqkv,
        dbias.astype(qkv_bias.dtype).reshape(qkv_bias.shape),
        seed_ct,
        None,
    )


flash_attention_qkv_bias_dropout.defvjp(_faqbd_fwd, _faqbd_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    compute_dbias: bool = False,
):
    """`flash_attention` also returning the per-row log-sum-exp.

    The (o, lse) pair is the mergeable partial-attention form: two
    partials over disjoint key sets combine as

        lse = logaddexp(lse1, lse2)
        o   = o1 * exp(lse1 - lse) + o2 * exp(lse2 - lse)

    which is what ring/context-parallel attention reduces over
    (transformer/context_parallel.py). Differentiable in q/k/v with lse
    cotangents folded into the fused backward; like `flash_attention`,
    bias gradients are an explicit ``compute_dbias=True`` opt-in (the
    ring masks are constants).

    BEHAVIOR CHANGE (round 4): ``compute_dbias`` previously defaulted
    to True here. A caller differentiating a LEARNED bias must now
    pass ``compute_dbias=True`` or the bias cotangent is exact zero —
    silently, since the structure is unchanged. All in-repo callers
    pass constant masks (bias=None or padding masks).
    """
    return _fwd(
        q, k, v, bias, causal,
        scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]),
        block_q, block_k,
    )


def _fal_fwd(q, k, v, bias, causal, scale, block_q, block_k,
             compute_dbias):
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    o, lse = _fwd(q, k, v, bias, causal, s, block_q, block_k)
    return (o, lse), (q, k, v, bias, o, lse)


def _fal_bwd(causal, scale, block_q, block_k, compute_dbias, res, cot):
    do, dlse = cot
    s = scale if scale is not None else 1.0 / np.sqrt(res[0].shape[-1])
    return _bwd(causal, s, block_q, block_k, res, do, dlse=dlse,
                compute_dbias=compute_dbias)


flash_attention_with_lse.defvjp(_fal_fwd, _fal_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def flash_attention_dropout(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    dropout_seed,
    dropout_rate: float,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    compute_dbias: bool = False,
) -> jnp.ndarray:
    """`flash_attention` with in-kernel attention dropout.

    Torch semantics (softmax -> dropout -> @v): the normalizer uses the
    undropped probabilities and kept entries scale by 1/(1-rate). The
    keep mask is never materialized — all three kernels regenerate it
    from ``dropout_seed`` and the (batch, q-block, k-block) grid
    coordinates via the TPU PRNG (reference: the fused dropout of
    apex/contrib/csrc/multihead_attn and fmha kernels). TPU-only:
    `pltpu.prng_*` has no interpret-mode lowering — callers off-TPU
    must use their materialized fallback (ops._pallas.on_tpu()).
    ``dropout_seed`` is a traced int32 scalar, so per-step seeds do not
    recompile.
    """
    o, _ = _fwd(
        q, k, v, bias, causal,
        scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]),
        block_q, block_k,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
    )
    return o


def _fad_fwd(q, k, v, bias, dropout_seed, dropout_rate, causal, scale,
             block_q, block_k, compute_dbias):
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    o, lse = _fwd(
        q, k, v, bias, causal, s, block_q, block_k,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
    )
    return o, (q, k, v, bias, o, lse, dropout_seed)


def _fad_bwd(dropout_rate, causal, scale, block_q, block_k,
             compute_dbias, res, do):
    q, k, v, bias, o, lse, seed = res
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    dq, dk, dv, dbias = _bwd(
        causal, s, block_q, block_k, (q, k, v, bias, o, lse), do,
        dropout_rate=dropout_rate, dropout_seed=seed,
        compute_dbias=compute_dbias,
    )
    seed_ct = np.zeros((), jax.dtypes.float0)
    return (dq, dk, dv, dbias, seed_ct)


flash_attention_dropout.defvjp(_fad_fwd, _fad_bwd)
