"""Latency-hiding collective matmuls for the tensor-parallel boundaries.

The Megatron-style TP layer pays a blocking collective at every
Column/RowParallel edge: `all_gather(x) @ W` first moves the whole
activation over ICI, then starts the MXU; `psum(x @ W)` finishes the
matmul before the first byte moves. XLA cannot fix this on its own —
operator fusion stops at dot boundaries (PAPERS.md: arXiv 2301.13062),
so the gathered operand and the pre-reduce product always materialize
between the collective and the dot. The fix is the decomposed
computation-collective schedule of arXiv 2305.06942: split the
collective into a `ppermute` ring of shard-sized (or finer, see
``chunk``) pieces and issue each hop's transfer next to a partial
matmul that does not depend on it, so the ICI transfer of piece i+1
rides under the MXU time of piece i.

Two ops, duals of each other (each is the other's backward):

* `all_gather_matmul(x, w, axis)` — ``all_gather(x, rows) @ w`` where
  ``x`` is the local rows-shard ``(..., rows_local, k)``: the resident
  shard multiplies into its output slot while the ring rotates the
  next shard in.
* `matmul_reduce_scatter(x, w, axis)` — ``psum_scatter(x @ w, rows)``
  where ``x`` holds full rows ``(..., rows, k_local)``: partial
  products accumulate into a rotating fp32 accumulator that lands on
  its destination rank after the last hop — the product is consumed
  piecewise and the full ``(..., rows, n)`` pre-reduce tensor never
  exists.

Both are `jax.custom_vjp`: the backward overlaps the transposed
collective the same way (d/dx of an all-gather-matmul IS a
matmul-reduce-scatter with ``wᵀ``, and vice versa; dW re-rotates the
saved operand instead of materializing the gather). Partial products
accumulate in fp32 regardless of input dtype (bf16 inputs hit the MXU,
sums stay fp32 until the final cast). Both degrade to the plain `lax`
collective + dot when the axis is unbound, ``axis_size == 1``, or
``chunk`` does not tile the shard — same numerics, no ring.

``comm_dtype="int8"`` (ops/quantized_collectives.py; EQuARX, arXiv
2506.17615) quantizes the ring hop payloads: the gather rings quantize
each rotating piece ONCE (per-row fp32 scales ride a sidecar ppermute)
and dequantize on arrival for the dot, so the int8-gather-matmul
equals ``dequant(int8(all_gather(x))) @ w`` slot-for-slot; the
reduce-scatter ring re-quantizes its rotating fp32 accumulator per hop
and adds the local partial product in full fp32. The backward rings
stay exact transposes of each other at the SAME comm dtype (dx of an
int8 gather-matmul is an int8 matmul-reduce-scatter with ``wᵀ``); the
degradation paths stay full-precision plain collectives. This is the
sequence-parallel entry/exit knob — opt-in, activation-quantization
noise is ~1% per hop payload row, acceptable for SP boundary
activations, not for logits.

The rows axis is ``-2`` (the flattened-token axis of a ``(rows, h)``
activation, or the sequence axis of ``(b, s, h)``); the contraction is
the last axis against ``w``'s first.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.ops.quantized_collectives import (
    check_comm_dtype,
    dequantize_int8,
    quantize_int8,
)

__all__ = ["all_gather_matmul", "matmul_reduce_scatter"]


def _bound_axis_size(axis_name) -> Optional[int]:
    """Static size of `axis_name`, or None when unbound (tp=1 / GSPMD
    usage outside shard_map)."""
    try:
        return axis_size(axis_name)
    except NameError:
        return None


def _mm(a, b):
    """fp32-accumulating matmul; inputs stay in their storage dtype so
    bf16 operands take the MXU fast path."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _out_dtype(x, w):
    return jnp.promote_types(x.dtype, w.dtype)


def _ring_chunks(rows: int, chunk: Optional[int]) -> Optional[int]:
    """Pieces per shard, or None when `chunk` does not tile `rows`
    (the caller then falls back to the plain collective)."""
    if chunk is None:
        return 1
    if chunk <= 0 or rows % chunk:
        return None
    return rows // chunk


# -- all_gather_matmul -------------------------------------------------


def _plain_ag_mm(x, w, axis_name):
    n = _bound_axis_size(axis_name)
    if n is not None and n > 1:
        x = jax.lax.all_gather(x, axis_name, axis=x.ndim - 2, tiled=True)
    return _mm(x, w).astype(_out_dtype(x, w))


def _rotating_pieces(x, m, chunk, ax, comm_dtype):
    """Split a gather-ring operand into its rotating payloads: raw
    slices for fp32, `(q, scale)` pairs — quantized ONCE — for int8."""
    pieces = []
    for j in range(m):
        piece = jax.lax.slice_in_dim(x, j * chunk, (j + 1) * chunk, axis=ax)
        pieces.append(
            quantize_int8(piece) if comm_dtype == "int8" else piece
        )
    return pieces


def _rotate_and_land(payload, axis_name, perm, rotate, comm_dtype, dtype):
    """One gather-ring hop: forward the payload (when ``rotate``) and
    return (next_payload_or_None, landed array in ``dtype``)."""
    if comm_dtype == "int8":
        q, s = payload
        nxt = None
        if rotate:
            nxt = (
                jax.lax.ppermute(q, axis_name, perm),
                jax.lax.ppermute(s, axis_name, perm),
            )
        return nxt, dequantize_int8(q, s, dtype)
    nxt = jax.lax.ppermute(payload, axis_name, perm) if rotate else None
    return nxt, payload


def _ring_ag_mm(x, w, axis_name, m, comm_dtype="fp32"):
    """Ring all-gather fused with the matmul: at hop i the resident
    shard (originally rank ``idx + i``'s) multiplies into its output
    slot, piece by piece, while each piece already permutes onward for
    hop i+1 — the transfer hides under the neighbouring dots."""
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    rows = x.shape[-2]
    chunk = rows // m
    ax = x.ndim - 2
    # receive from rank+1: hop i leaves rank (idx + i)'s shard resident
    perm = [(j, (j - 1) % n) for j in range(n)]
    out = jnp.zeros(
        x.shape[:-2] + (n * rows, w.shape[-1]), _out_dtype(x, w)
    )
    pieces = _rotating_pieces(x, m, chunk, ax, comm_dtype)
    for i in range(n):
        src = (idx + i) % n
        nxt = []
        for j, payload in enumerate(pieces):
            # issue the transfer BEFORE this piece's dot: XLA's
            # async collective-permute runs under the MXU work
            fwd, piece = _rotate_and_land(
                payload, axis_name, perm, i + 1 < n, comm_dtype, x.dtype
            )
            if fwd is not None:
                nxt.append(fwd)
            part = _mm(piece, w).astype(out.dtype)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, part, src * rows + j * chunk, axis=ax
            )
        if nxt:
            pieces = nxt
    return out


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def all_gather_matmul(x, w, axis_name, chunk=None, comm_dtype="fp32"):
    """``all_gather(x, axis=-2) @ w`` with the gather decomposed into a
    ppermute ring whose hops overlap the partial matmuls.

    Args:
      x: local rows-shard ``(..., rows_local, k)``.
      w: ``(k, n)`` — this rank's weight shard (column-parallel).
      axis_name: mesh axis to gather over.
      chunk: rows per ring piece (must tile ``rows_local``; None = one
        piece per shard). A non-tiling chunk falls back to the plain
        ``lax.all_gather`` + dot.
      comm_dtype: "fp32" (default) moves hop payloads untouched;
        "int8" quantizes each rotating piece once with per-row fp32
        scale sidecars (module docstring). Degradation paths stay
        full-precision.

    Returns ``(..., axis_size * rows_local, n)``. The gathered ``x``
    never materializes on the ring path.
    """
    check_comm_dtype(comm_dtype)
    n = _bound_axis_size(axis_name)
    if n is None or n == 1:
        return _mm(x, w).astype(_out_dtype(x, w))
    m = _ring_chunks(x.shape[-2], chunk)
    if m is None:
        return _plain_ag_mm(x, w, axis_name)
    return _ring_ag_mm(x, w, axis_name, m, comm_dtype)


def _ag_mm_fwd(x, w, axis_name, chunk, comm_dtype):
    return all_gather_matmul(x, w, axis_name, chunk, comm_dtype), (x, w)


def _ring_dw_from_gather(x, dy, axis_name, m, comm_dtype="fp32"):
    """dW = all_gather(x)ᵀ @ dy without materializing the gather: the
    saved local shard re-rotates and each hop contracts against its
    own slice of the cotangent."""
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    rows = x.shape[-2]
    chunk = rows // m
    ax = x.ndim - 2
    perm = [(j, (j - 1) % n) for j in range(n)]
    dw = jnp.zeros(x.shape[-1:] + dy.shape[-1:], jnp.float32)
    pieces = _rotating_pieces(x, m, chunk, ax, comm_dtype)
    for i in range(n):
        src = (idx + i) % n
        nxt = []
        for j, payload in enumerate(pieces):
            fwd, piece = _rotate_and_land(
                payload, axis_name, perm, i + 1 < n, comm_dtype, x.dtype
            )
            if fwd is not None:
                nxt.append(fwd)
            dy_piece = jax.lax.dynamic_slice_in_dim(
                dy, src * rows + j * chunk, chunk, axis=ax
            )
            dw = dw + jnp.einsum(
                "...rk,...rn->kn", piece, dy_piece,
                preferred_element_type=jnp.float32,
            )
        if nxt:
            pieces = nxt
    return dw


def _ag_mm_bwd(axis_name, chunk, comm_dtype, res, dy):
    x, w = res
    n = _bound_axis_size(axis_name)
    if n is None or n == 1:
        dx = _mm(dy, w.swapaxes(-1, -2)).astype(x.dtype)
        dw = jnp.einsum(
            "...rk,...rn->kn", x, dy, preferred_element_type=jnp.float32
        ).astype(w.dtype)
        return dx, dw
    m = _ring_chunks(x.shape[-2], chunk)
    if m is None:
        # plain-collective fallback: transposed collectives, no ring
        dx = jax.lax.psum_scatter(
            _mm(dy, w.swapaxes(-1, -2)), axis_name,
            scatter_dimension=dy.ndim - 2, tiled=True,
        ).astype(x.dtype)
        xg = jax.lax.all_gather(x, axis_name, axis=x.ndim - 2, tiled=True)
        dw = jnp.einsum(
            "...rk,...rn->kn", xg, dy, preferred_element_type=jnp.float32
        ).astype(w.dtype)
        return dx, dw
    # the transposed gather IS a matmul-reduce-scatter: same ring, same
    # overlap, same comm dtype, wᵀ as the operand
    dx = _ring_mm_rs(
        dy, w.swapaxes(-1, -2), axis_name, m, comm_dtype
    ).astype(x.dtype)
    dw = _ring_dw_from_gather(x, dy, axis_name, m, comm_dtype).astype(
        w.dtype
    )
    return dx, dw


all_gather_matmul.defvjp(_ag_mm_fwd, _ag_mm_bwd)


# -- matmul_reduce_scatter ---------------------------------------------


def _plain_mm_rs(x, w, axis_name):
    y = _mm(x, w)
    n = _bound_axis_size(axis_name)
    if n is not None and n > 1:
        y = jax.lax.psum_scatter(
            y, axis_name, scatter_dimension=y.ndim - 2, tiled=True
        )
    return y.astype(_out_dtype(x, w))


def _acc_hop(acc, axis_name, perm, comm_dtype):
    """One reduce-scatter-ring hop of the fp32 accumulator: int8 mode
    re-quantizes per hop (the value changes every hop), fp32 mode moves
    it untouched."""
    if comm_dtype == "int8":
        q, s = quantize_int8(acc)
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        return dequantize_int8(q, s)
    return jax.lax.ppermute(acc, axis_name, perm)


def _ring_mm_rs(x, w, axis_name, m, comm_dtype="fp32"):
    """Reduce-scatter fused with the matmul: a rotating fp32
    accumulator picks up each rank's partial product for one row block
    per hop and lands on the block's owner after the last hop. The
    full pre-reduce product never exists."""
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    rows_full = x.shape[-2]
    rows = rows_full // n
    chunk = rows // m
    ax = x.ndim - 2
    # accumulators advance to rank+1 each hop and must end at home
    perm = [(j, (j + 1) % n) for j in range(n)]
    acc = [None] * m
    for i in range(n):
        # the block this rank works on now reaches its owner in the
        # remaining n-1-i hops
        dst = (idx + n - 1 - i) % n
        for j in range(m):
            piece = jax.lax.dynamic_slice_in_dim(
                x, dst * rows + j * chunk, chunk, axis=ax
            )
            if acc[j] is not None:
                # rotate first, then add this rank's partial — the
                # permute of piece j hides under piece j+1's dot
                acc[j] = _acc_hop(acc[j], axis_name, perm, comm_dtype)
            part = _mm(piece, w)
            acc[j] = part if acc[j] is None else acc[j] + part
    return jnp.concatenate(acc, axis=ax).astype(_out_dtype(x, w))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def matmul_reduce_scatter(x, w, axis_name, chunk=None, comm_dtype="fp32"):
    """``psum_scatter(x @ w, axis=-2)`` with the reduction decomposed
    into a ppermute ring of accumulators overlapping the partial
    matmuls.

    Args:
      x: full-rows operand ``(..., rows, k_local)`` — this rank's
        contraction shard (row-parallel input).
      w: ``(k_local, n)`` — this rank's weight shard.
      axis_name: mesh axis to reduce-scatter over.
      chunk: rows per ring piece (must tile ``rows / axis_size``;
        None = one piece per destination block). A non-tiling chunk
        falls back to the plain dot + ``lax.psum_scatter``.
      comm_dtype: "fp32" (default) rotates the fp32 accumulator
        untouched; "int8" re-quantizes it per hop with per-row fp32
        scale sidecars (module docstring). Degradation paths stay
        full-precision.

    Returns the local row block ``(..., rows / axis_size, n)``, summed
    over the axis. Partial sums stay fp32 until the final cast.
    """
    check_comm_dtype(comm_dtype)
    n = _bound_axis_size(axis_name)
    if n is None or n == 1:
        return _mm(x, w).astype(_out_dtype(x, w))
    rows_full = x.shape[-2]
    if rows_full % n:
        raise ValueError(
            f"rows {rows_full} not divisible by axis size {n}"
        )
    m = _ring_chunks(rows_full // n, chunk)
    if m is None:
        return _plain_mm_rs(x, w, axis_name)
    return _ring_mm_rs(x, w, axis_name, m, comm_dtype)


def _mm_rs_fwd(x, w, axis_name, chunk, comm_dtype):
    return matmul_reduce_scatter(x, w, axis_name, chunk, comm_dtype), (x, w)


def _ring_dw_from_scatter(x, dy, axis_name, m, comm_dtype="fp32"):
    """dW = xᵀ @ all_gather(dy) without the gather: the local
    cotangent block rotates and contracts against the matching row
    slice of the saved full-rows operand."""
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    rows = dy.shape[-2]
    chunk = rows // m
    ax = dy.ndim - 2
    perm = [(j, (j - 1) % n) for j in range(n)]
    dw = jnp.zeros(x.shape[-1:] + dy.shape[-1:], jnp.float32)
    pieces = _rotating_pieces(dy, m, chunk, ax, comm_dtype)
    for i in range(n):
        src = (idx + i) % n
        nxt = []
        for j, payload in enumerate(pieces):
            fwd, piece = _rotate_and_land(
                payload, axis_name, perm, i + 1 < n, comm_dtype, dy.dtype
            )
            if fwd is not None:
                nxt.append(fwd)
            x_piece = jax.lax.dynamic_slice_in_dim(
                x, src * rows + j * chunk, chunk, axis=ax
            )
            dw = dw + jnp.einsum(
                "...rk,...rn->kn", x_piece, piece,
                preferred_element_type=jnp.float32,
            )
        if nxt:
            pieces = nxt
    return dw


def _mm_rs_bwd(axis_name, chunk, comm_dtype, res, dy):
    x, w = res
    n = _bound_axis_size(axis_name)
    if n is None or n == 1:
        dx = _mm(dy, w.swapaxes(-1, -2)).astype(x.dtype)
        dw = jnp.einsum(
            "...rk,...rn->kn", x, dy, preferred_element_type=jnp.float32
        ).astype(w.dtype)
        return dx, dw
    m = _ring_chunks(dy.shape[-2], chunk)
    if m is None:
        dyg = jax.lax.all_gather(
            dy, axis_name, axis=dy.ndim - 2, tiled=True
        )
        dx = _mm(dyg, w.swapaxes(-1, -2)).astype(x.dtype)
        dw = jnp.einsum(
            "...rk,...rn->kn", x, dyg, preferred_element_type=jnp.float32
        ).astype(w.dtype)
        return dx, dw
    # the transposed scatter IS an all-gather-matmul with wᵀ at the
    # same comm dtype
    dx = _ring_ag_mm(
        dy, w.swapaxes(-1, -2), axis_name, m, comm_dtype
    ).astype(x.dtype)
    dw = _ring_dw_from_scatter(x, dy, axis_name, m, comm_dtype).astype(
        w.dtype
    )
    return dx, dw


matmul_reduce_scatter.defvjp(_mm_rs_fwd, _mm_rs_bwd)
