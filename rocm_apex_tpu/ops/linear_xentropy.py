"""Chunked fused LM-head + cross-entropy: the ``(rows, vocab)`` logits
never materialize.

The tied LM head is the dominant non-attention cost of GPT training:
at bench scale (16x1024 tokens, 32k vocab) the bf16 logits tensor is
~1.07 GB and its gradient another ~1.07 GB, both round-tripping HBM
every step even though no consumer ever needs them at full size — the
loss is a per-row reduction and the gradients contract straight back
into ``dx`` and ``dW``. This module is the Liger-kernel design
(arXiv 2410.10989) expressed as a `lax.scan` over row chunks that XLA
schedules like a Pallas grid: flatten hidden states to
``(rows, hidden)``, iterate row chunks; per chunk compute
``logits_c = x_c @ W^T``, run the per-tile loss semantics of
`ops/xentropy._loss_block` (fp32 upcast, max/lse/target-gather, label
smoothing, ``padding_idx`` masking), and either

* save only the O(rows) ``lse`` statistics and recompute the chunk's
  softmax in the backward (`linear_cross_entropy_loss` — per-row
  losses, arbitrary cotangents), or
* form ``dlogits_c = p_c - onehot`` while the chunk is live and
  immediately contract it into ``dx_c = dlogits_c @ W`` and an
  accumulated ``dW += dlogits_c^T @ x_c``
  (`linear_cross_entropy_mean` — the train-step fast path, where the
  mean reduction makes the loss cotangent a scalar so the gradients
  can be finished inside the forward pass, Liger's FLCE trick: no
  recompute matmul, 6*N*H*V head FLOPs total, same as the
  materialized path).

Only chunk-sized ``(chunk, vocab)`` tiles ever exist; peak HBM for the
loss stage drops from 2 full logits-sized buffers to two chunk tiles
plus the fp32 ``dW`` accumulator. `vocab_parallel_linear_cross_entropy`
is the tp>1 variant: per-chunk partial max / sum-exp / target-gather
are psum'd over the tensor axis, the reduction structure of
`transformer/tensor_parallel/cross_entropy.py` applied chunk-wise.

When to prefer the materialized `ops.xentropy.
softmax_cross_entropy_loss_fused` instead: rows*vocab small enough
that the logits fit comfortably (the chunked scan then only adds loop
and ``dW``-accumulator overhead) — see docs/perf.md for the math.
"""

import functools

import jax
import jax.numpy as jnp

from rocm_apex_tpu.ops._pallas import SUBLANE
from rocm_apex_tpu.ops._pallas import pad_rows as _pad_rows
from rocm_apex_tpu.ops.xentropy import _loss_block

__all__ = [
    "linear_cross_entropy_loss",
    "linear_cross_entropy_mean",
    "vocab_parallel_linear_cross_entropy",
]

# Default chunk sizing: chunk*vocab ~ 2^27 elements keeps the two live
# low-precision chunk tiles (logits_c, dlogits_c) at ~256 MB each at
# bf16 while bounding the backward's dW-accumulator round trips at
# rows/chunk ~ 4 on the bench config (docs/perf.md quantifies the
# chunk-size tradeoff: smaller chunks shrink peak HBM linearly but pay
# one fp32 (vocab, hidden) accumulator read+write per chunk).
_DEFAULT_CHUNK_ELEMENTS = 1 << 27


def _chunk_rows(rows: int, vocab: int, chunk_size) -> int:
    if chunk_size is None:
        chunk_size = max(SUBLANE, _DEFAULT_CHUNK_ELEMENTS // max(1, vocab))
    chunk_size = max(SUBLANE, (chunk_size // SUBLANE) * SUBLANE)
    return min(chunk_size, max(SUBLANE, (rows + SUBLANE - 1) // SUBLANE * SUBLANE))


def _to_chunks(chunk, *arrays):
    """Pad rows to a multiple of ``chunk`` and reshape each (rows, c)
    array to (k, chunk, c) scan inputs. Padded rows are all-zero: zero
    hidden rows produce finite logits/losses and are sliced off (fwd)
    or carry a zero cotangent (bwd), so they never contribute."""
    out = []
    for a in arrays:
        ap = _pad_rows(a, chunk)
        out.append(ap.reshape(ap.shape[0] // chunk, chunk, *a.shape[1:]))
    return out


def _scan_chunks(body, init, xs):
    """`lax.scan` over chunk rows — except single-chunk calls (rows ≤
    chunk, the common test/eval scale), which inline the body: no loop
    tracing/compile cost and XLA sees a straight-line head."""
    if xs[0].shape[0] == 1:
        carry, out = body(init, tuple(a[0] for a in xs))
        return carry, jax.tree_util.tree_map(lambda o: o[None], out)
    return jax.lax.scan(body, init, xs)


def _chunk_logits(x_c, w):
    """One chunk of the head projection, fp32 for the loss math. The
    matmul accumulates in the compute dtype (the materialized `attend`
    path's `preferred_element_type`), the upcast fuses into the
    consuming reductions — no fp32 chunk is written back."""
    logits = jnp.einsum(
        "ch,vh->cv", x_c, w, preferred_element_type=x_c.dtype
    )
    return logits.astype(jnp.float32)


def _target_block(col, lbl, smoothing, vocab):
    """The smoothed one-hot target row block (`_loss_block`'s gradient
    counterpart): (1-eps) at the label column + eps/vocab everywhere."""
    return (
        jnp.where(col == lbl, 1.0 - smoothing, 0.0) + smoothing / vocab
    )


# ---------------------------------------------------------------------------
# serial, per-row losses (general cotangents; backward recomputes the
# chunk softmax from the saved lse)
# ---------------------------------------------------------------------------


def _fwd_impl(hidden2d, weight, labels, smoothing, chunk_size):
    rows, _ = hidden2d.shape
    w = weight.astype(hidden2d.dtype)
    chunk = _chunk_rows(rows, w.shape[0], chunk_size)
    xs, ls = _to_chunks(chunk, hidden2d, labels.reshape(-1, 1))

    def body(_, xl):
        x_c, l_c = xl
        loss, lse, _, _, _ = _loss_block(smoothing, _chunk_logits(x_c, w), l_c)
        return None, (loss[:, 0], lse[:, 0])

    _, (loss, lse) = _scan_chunks(body, None, (xs, ls))
    return loss.reshape(-1)[:rows], lse.reshape(-1)[:rows]


def _bwd_impl(hidden2d, weight, labels, lse, dloss, smoothing, chunk_size):
    rows, hdim = hidden2d.shape
    vocab = weight.shape[0]
    cdt = hidden2d.dtype
    w = weight.astype(cdt)
    chunk = _chunk_rows(rows, vocab, chunk_size)
    xs, ls, lses, dls = _to_chunks(
        chunk,
        hidden2d,
        labels.reshape(-1, 1),
        lse.reshape(-1, 1),
        dloss.astype(jnp.float32).reshape(-1, 1),
    )

    def body(dw, inp):
        x_c, l_c, lse_c, dl_c = inp
        logits = _chunk_logits(x_c, w)
        # softmax from the SAVED lse: no second max/sum pass
        p = jnp.exp(logits - lse_c)
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        dlog = (dl_c * (p - _target_block(col, l_c, smoothing, vocab))).astype(
            cdt
        )
        dx_c = jnp.einsum("cv,vh->ch", dlog, w, preferred_element_type=cdt)
        dw = dw + jnp.einsum(
            "cv,ch->vh", dlog, x_c, preferred_element_type=jnp.float32
        )
        return dw, dx_c

    dw0 = jnp.zeros((vocab, hdim), jnp.float32)
    dw, dxs = _scan_chunks(body, dw0, (xs, ls, lses, dls))
    dx = dxs.reshape(-1, hdim)[:rows]
    return dx.astype(hidden2d.dtype), dw.astype(weight.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def linear_cross_entropy_loss(
    hidden, weight, labels, smoothing=0.0, padding_idx=None, chunk_size=None
):
    """Per-row smoothed CE of the fused head ``hidden @ weight^T``.

    Args:
      hidden: ``(..., hidden)`` activations (any leading shape).
      weight: ``(vocab, hidden)`` projection table (the tied embedding).
      labels: integer ``(...)`` target ids.
      smoothing: label-smoothing epsilon (`ops.xentropy` semantics).
      padding_idx: rows whose label equals it get zero loss and zero
        gradient (``None`` disables, every label contributes).
      chunk_size: rows per chunk (default targets ~2^27 chunk elements).

    Returns fp32 per-row losses shaped like ``labels``. Differentiable
    in ``hidden`` and ``weight`` under arbitrary per-row cotangents;
    the backward recomputes each chunk's softmax from the saved lse
    (one extra head matmul — the price of never storing logits).
    """
    losses, _ = _fwd_impl(
        hidden.reshape(-1, hidden.shape[-1]),
        weight,
        labels.reshape(-1).astype(jnp.int32),
        smoothing,
        chunk_size,
    )
    losses = losses.reshape(labels.shape)
    if padding_idx is None:
        return losses
    return jnp.where(labels == padding_idx, 0.0, losses)


def _lxe_vjp_fwd(hidden, weight, labels, smoothing, padding_idx, chunk_size):
    lbl = labels.reshape(-1).astype(jnp.int32)
    losses, lse = _fwd_impl(
        hidden.reshape(-1, hidden.shape[-1]), weight, lbl, smoothing,
        chunk_size,
    )
    losses = losses.reshape(labels.shape)
    if padding_idx is not None:
        losses = jnp.where(labels == padding_idx, 0.0, losses)
    return losses, (hidden, weight, lbl, lse)


def _lxe_vjp_bwd(smoothing, padding_idx, chunk_size, res, dloss):
    hidden, weight, lbl, lse = res
    dl = dloss.reshape(-1)
    if padding_idx is not None:
        dl = jnp.where(lbl == padding_idx, 0.0, dl)
    dx, dw = _bwd_impl(
        hidden.reshape(-1, hidden.shape[-1]), weight, lbl, lse, dl,
        smoothing, chunk_size,
    )
    return dx.reshape(hidden.shape), dw, None


linear_cross_entropy_loss.defvjp(_lxe_vjp_fwd, _lxe_vjp_bwd)


# ---------------------------------------------------------------------------
# serial, mean-reduced (the train-step fast path: scalar cotangent, so
# dx/dW finish inside the forward pass — no recompute matmul)
# ---------------------------------------------------------------------------


def _row_weights(labels, loss_mask, padding_idx):
    """fp32 per-row loss weights reproducing `gpt_loss_fn`:
    ``sum(mask*loss)/max(sum(mask),1)`` with a mask, plain mean
    without; ``padding_idx`` rows are zeroed from the numerator only
    (they still count in the unmasked denominator, exactly like the
    zeroed per-row losses the materialized path feeds to
    `gpt_loss_fn`)."""
    if loss_mask is not None:
        m = jax.lax.stop_gradient(loss_mask).reshape(-1).astype(jnp.float32)
        rw = m / jnp.maximum(jnp.sum(m), 1.0)
    else:
        rw = jnp.full(labels.shape, 1.0 / labels.size, jnp.float32)
        rw = rw.reshape(-1)
    if padding_idx is not None:
        rw = jnp.where(labels.reshape(-1) == padding_idx, 0.0, rw)
    return rw


def _mean_fwd_impl(hidden2d, weight, labels, row_w, smoothing, chunk_size,
                   with_grads):
    rows, hdim = hidden2d.shape
    vocab = weight.shape[0]
    cdt = hidden2d.dtype
    w = weight.astype(cdt)
    chunk = _chunk_rows(rows, vocab, chunk_size)
    xs, ls, rws = _to_chunks(
        chunk, hidden2d, labels.reshape(-1, 1), row_w.reshape(-1, 1)
    )

    def body(carry, inp):
        x_c, l_c, rw_c = inp
        logits = _chunk_logits(x_c, w)
        loss, _, col, p, ssum = _loss_block(smoothing, logits, l_c)
        partial = jnp.sum(rw_c * loss)
        if not with_grads:
            return carry + partial, None
        acc, dw = carry
        # dlogits while the chunk is live: p/ssum is the softmax
        # (one exp pass serves loss and gradient, the _fwd_dg_kernel
        # trick), rw_c folds the mean reduction + mask + padding into
        # the per-row scale
        dlog = (
            rw_c * (p * (1.0 / ssum) - _target_block(col, l_c, smoothing, vocab))
        ).astype(cdt)
        dx_c = jnp.einsum("cv,vh->ch", dlog, w, preferred_element_type=cdt)
        dw = dw + jnp.einsum(
            "cv,ch->vh", dlog, x_c, preferred_element_type=jnp.float32
        )
        return (acc + partial, dw), dx_c

    if not with_grads:
        total, _ = _scan_chunks(body, jnp.float32(0.0), (xs, ls, rws))
        return total
    carry0 = (jnp.float32(0.0), jnp.zeros((vocab, hdim), jnp.float32))
    (total, dw), dxs = _scan_chunks(body, carry0, (xs, ls, rws))
    dx = dxs.reshape(-1, hdim)[:rows].astype(hidden2d.dtype)
    return total, dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def linear_cross_entropy_mean(
    hidden, weight, labels, loss_mask=None,
    smoothing=0.0, padding_idx=None, chunk_size=None,
):
    """Scalar masked-mean CE of the fused head — the train-step path.

    Equals ``gpt_loss_fn(linear_cross_entropy_loss(...), loss_mask)``
    but because the reduction is inside the op the loss cotangent is a
    SCALAR: the forward pass forms each chunk's ``dlogits`` while the
    chunk is live and contracts it straight into ``dx`` and the
    accumulated ``dW`` (backward is two scalar multiplies). Total head
    cost is 3 matmuls (fwd/dx/dW) — the materialized path's FLOPs with
    none of its logits-sized HBM traffic. ``loss_mask`` is treated as
    a constant (stop_gradient).
    """
    return _mean_fwd_impl(
        hidden.reshape(-1, hidden.shape[-1]),
        weight,
        labels.reshape(-1).astype(jnp.int32),
        _row_weights(labels, loss_mask, padding_idx),
        smoothing,
        chunk_size,
        with_grads=False,
    )


def _mean_vjp_fwd(hidden, weight, labels, loss_mask, smoothing, padding_idx,
                  chunk_size):
    lbl = labels.reshape(-1).astype(jnp.int32)
    total, dx, dw = _mean_fwd_impl(
        hidden.reshape(-1, hidden.shape[-1]), weight, lbl,
        _row_weights(labels, loss_mask, padding_idx), smoothing, chunk_size,
        with_grads=True,
    )
    # zero-size marker carries the weight dtype through the residuals
    # (the fp32-accumulated dW must come back in the primal's dtype)
    proto = jnp.zeros((0,), weight.dtype)
    return total, (dx.reshape(hidden.shape), dw, proto)


def _mean_vjp_bwd(smoothing, padding_idx, chunk_size, res, g):
    dx, dw, proto = res
    g32 = g.astype(jnp.float32)
    return (
        (g32 * dx.astype(jnp.float32)).astype(dx.dtype),
        (g32 * dw).astype(proto.dtype),
        None,
        None,
    )


linear_cross_entropy_mean.defvjp(_mean_vjp_fwd, _mean_vjp_bwd)


# ---------------------------------------------------------------------------
# vocab-parallel (tp > 1): the chunked head over a LOCAL vocab shard,
# per-chunk partial max / sum-exp / target-gather psum'd over the
# tensor axis (the reduction structure of
# transformer/tensor_parallel/cross_entropy.py applied chunk-wise)
# ---------------------------------------------------------------------------


def _vp_fwd_impl(hidden2d, weight, labels, axis_name, smoothing, chunk_size):
    rows, _ = hidden2d.shape
    w = weight.astype(hidden2d.dtype)
    v_local = w.shape[0]
    tp = jax.lax.axis_size(axis_name)
    vocab = v_local * tp
    start = jax.lax.axis_index(axis_name) * v_local
    chunk = _chunk_rows(rows, v_local, chunk_size)
    xs, ls = _to_chunks(chunk, hidden2d, labels.reshape(-1, 1))

    def body(_, xl):
        x_c, l_c = xl
        logits = _chunk_logits(x_c, w)  # (chunk, vocab/tp) fp32
        # 1. global max for stability (reference cross_entropy.py:30-35)
        m = jax.lax.pmax(jnp.max(logits, axis=1), axis_name)[:, None]
        sh = jnp.exp(logits - m)
        # 2. global sum-exp (reference :58-63)
        sum_exp = jax.lax.psum(jnp.sum(sh, axis=1), axis_name)[:, None]
        lse = m + jnp.log(sum_exp)
        # 3. this rank's slice of the target logit, masked outside the
        # local vocab range (reference :37-56); the iota-vs-shifted-
        # label compare is range mask and gather in one
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        xt = jax.lax.psum(
            jnp.sum(jnp.where(col == l_c - start, logits, 0.0), axis=1),
            axis_name,
        )[:, None]
        loss = lse - (1.0 - smoothing) * xt
        if smoothing > 0.0:
            sum_x = jax.lax.psum(jnp.sum(logits, axis=1), axis_name)[:, None]
            loss = loss - (smoothing / vocab) * sum_x
        return None, (loss[:, 0], lse[:, 0])

    _, (loss, lse) = _scan_chunks(body, None, (xs, ls))
    return loss.reshape(-1)[:rows], lse.reshape(-1)[:rows]


def _vp_bwd_impl(hidden2d, weight, labels, lse, dloss, axis_name, smoothing,
                 chunk_size):
    rows, hdim = hidden2d.shape
    cdt = hidden2d.dtype
    w = weight.astype(cdt)
    v_local = w.shape[0]
    vocab = v_local * jax.lax.axis_size(axis_name)
    start = jax.lax.axis_index(axis_name) * v_local
    chunk = _chunk_rows(rows, v_local, chunk_size)
    xs, ls, lses, dls = _to_chunks(
        chunk,
        hidden2d,
        labels.reshape(-1, 1),
        lse.reshape(-1, 1),
        dloss.astype(jnp.float32).reshape(-1, 1),
    )

    def body(dw, inp):
        x_c, l_c, lse_c, dl_c = inp
        logits = _chunk_logits(x_c, w)
        p = jnp.exp(logits - lse_c)  # global softmax, local columns
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        # local slice of the smoothed one-hot: the col compare is False
        # everywhere when the target lives on another rank
        tgt = (
            jnp.where(col == l_c - start, 1.0 - smoothing, 0.0)
            + smoothing / vocab
        )
        dlog = (dl_c * (p - tgt)).astype(cdt)
        # dx contracts over the GLOBAL vocab: psum the per-rank partials
        # (hidden is replicated across the axis, so this psum IS the
        # copy_to_tensor_model_parallel_region backward)
        dx_c = jax.lax.psum(
            jnp.einsum("cv,vh->ch", dlog, w, preferred_element_type=cdt),
            axis_name,
        )
        dw = dw + jnp.einsum(
            "cv,ch->vh", dlog, x_c, preferred_element_type=jnp.float32
        )
        return dw, dx_c

    dw0 = jnp.zeros((v_local, hdim), jnp.float32)
    dw, dxs = _scan_chunks(body, dw0, (xs, ls, lses, dls))
    dx = dxs.reshape(-1, hdim)[:rows]
    return dx.astype(hidden2d.dtype), dw.astype(weight.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def vocab_parallel_linear_cross_entropy(
    hidden, weight, labels, axis_name,
    smoothing=0.0, padding_idx=None, chunk_size=None,
):
    """`linear_cross_entropy_loss` over a vocab-sharded head.

    Args:
      hidden: ``(..., hidden)`` activations, REPLICATED across the
        tensor axis (every rank passes the same values).
      weight: ``(vocab/tp, hidden)`` local shard of the projection.
      labels: integer ``(...)`` GLOBAL token ids.
      axis_name: bound tensor-parallel mesh axis (shard_map).

    Returns replicated fp32 per-row losses. The gradient of ``hidden``
    is psum'd over the axis internally (do NOT additionally wrap the
    input in ``copy_to_tensor_model_parallel_region``); the gradient
    of ``weight`` is the local shard's.
    """
    losses, _ = _vp_fwd_impl(
        hidden.reshape(-1, hidden.shape[-1]),
        weight,
        labels.reshape(-1).astype(jnp.int32),
        axis_name,
        smoothing,
        chunk_size,
    )
    losses = losses.reshape(labels.shape)
    if padding_idx is None:
        return losses
    return jnp.where(labels == padding_idx, 0.0, losses)


def _vp_vjp_fwd(hidden, weight, labels, axis_name, smoothing, padding_idx,
                chunk_size):
    lbl = labels.reshape(-1).astype(jnp.int32)
    losses, lse = _vp_fwd_impl(
        hidden.reshape(-1, hidden.shape[-1]), weight, lbl, axis_name,
        smoothing, chunk_size,
    )
    losses = losses.reshape(labels.shape)
    if padding_idx is not None:
        losses = jnp.where(labels == padding_idx, 0.0, losses)
    return losses, (hidden, weight, lbl, lse)


def _vp_vjp_bwd(axis_name, smoothing, padding_idx, chunk_size, res, dloss):
    hidden, weight, lbl, lse = res
    dl = dloss.reshape(-1)
    if padding_idx is not None:
        dl = jnp.where(lbl == padding_idx, 0.0, dl)
    dx, dw = _vp_bwd_impl(
        hidden.reshape(-1, hidden.shape[-1]), weight, lbl, lse, dl,
        axis_name, smoothing, chunk_size,
    )
    return dx.reshape(hidden.shape), dw, None


vocab_parallel_linear_cross_entropy.defvjp(_vp_vjp_fwd, _vp_vjp_bwd)
