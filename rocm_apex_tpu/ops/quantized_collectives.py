"""Quantized ring collectives: int8 ppermute hops with fp32 scale sidecars.

EQuARX (PAPERS.md: arXiv 2506.17615) shows an int8-quantized allreduce
built inside XLA loses negligible model quality while cutting wire
bytes ~4x. This module is that idea on the PR-3 ppermute-ring skeleton
(ops/collective_matmul.py): `ring_reduce_scatter` / `ring_all_gather` /
`ring_all_reduce` decompose the lax collective into axis_size-1
neighbour hops, and with ``comm_dtype="int8"`` every hop's payload is
symmetrically quantized to int8 with one fp32 scale per trailing-axis
row riding as a sidecar ppermute (two transfers per hop: the int8 body
and the tiny fp32 scale column — on a ``(rows, 1024)`` packed buffer
the sidecar is 0.4% of the fp32 payload).

Quantization contract (the properties the tests pin):

* **Deterministic round-to-nearest-even.** ``jnp.round`` is IEEE RTNE
  on every backend, and scale = amax/127 is a pure function of the
  payload — two replicas quantizing the same values produce bitwise
  identical ``(q, scale)`` pairs, and every replica dequantizing the
  same pair produces bitwise identical fp32. The all-gather therefore
  keeps params REPLICATED in the strict sense: each rank's own shard
  comes back as dequant(quant(shard)), the same array every other rank
  reconstructs.
* **fp32 hop accumulators.** The reduce-scatter quantizes only what
  moves: the rotating partial sum is re-quantized per hop (its value
  changes each hop), dequantized on arrival into fp32, and the local
  contribution is added in full fp32. The gather quantizes each shard
  ONCE and rotates the ``(q, scale)`` pair unchanged — re-quantizing a
  dequantized payload is idempotent (the row max dequantizes exactly
  back to the scale), so a single quantization error per element is
  the total error, it never compounds around the ring.
* **Graceful degradation.** Axis unbound or size 1 -> identity (what
  the lax collective computes over a 1-axis). A ``chunk`` that does
  not tile the shard -> the plain full-precision lax collective,
  bitwise identical to not using this module at all. Rows that do not
  tile the axis -> plain lax collective (reduce-scatter shares lax's
  divisibility requirement; `ring_all_reduce` falls back to
  ``lax.psum`` which has none).
* **Overflow transparency.** Non-finite inputs saturate (inf -> ±127
  at scale 1.0), so a quantized wire does NOT propagate inf/nan across
  ranks. Callers that need overflow detection must probe BEFORE the
  collective — exactly where contrib/optimizers/distributed.py runs
  its fused unscale+found_inf pass, and why that ordering is load-
  bearing for ``comm_dtype="int8"``.

The rings run under `jax.named_scope` ("qring_rs" / "qring_ag") so
monitor/audit.py can attribute the ppermute hop storm to its ring:
a quantized ring costs 2·m·(axis_size-1) ppermute equations (payload +
sidecar per hop, m chunks) where the lax collective costs one equation
— the audit's per-dtype byte split is what shows the int8 win.

Not differentiable-by-design: quantization has zero gradient almost
everywhere. The TP-boundary layers use ops/collective_matmul.py's
custom_vjp rings (which take the same ``comm_dtype`` knob); this
module serves the optimizer dataflow, which is never differentiated.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size

__all__ = [
    "COMM_DTYPES",
    "check_comm_dtype",
    "quantize_int8",
    "dequantize_int8",
    "ring_reduce_scatter",
    "ring_all_gather",
    "ring_all_reduce",
]

COMM_DTYPES = ("fp32", "int8")


def check_comm_dtype(comm_dtype: str) -> str:
    if comm_dtype not in COMM_DTYPES:
        raise ValueError(
            f"comm_dtype must be one of {COMM_DTYPES}, got {comm_dtype!r}"
        )
    return comm_dtype


def _bound_axis_size(axis_name) -> Optional[int]:
    """Static size of `axis_name`, or None when unbound."""
    try:
        return axis_size(axis_name)
    except NameError:
        return None


def _ring_chunks(rows: int, chunk: Optional[int]) -> Optional[int]:
    """Pieces per shard, or None when `chunk` does not tile `rows`."""
    if chunk is None:
        return 1
    if chunk <= 0 or rows % chunk:
        return None
    return rows // chunk


def quantize_int8(x):
    """Symmetric per-row int8 quantization of a hop payload.

    One fp32 scale per trailing-axis row: scale = amax(|row|)/127, q =
    RTNE(x/scale) clipped to ±127. All-zero (or non-finite-max) rows
    take scale 1.0 so dequantization is exact zeros there. Returns
    ``(q int8, scale fp32 with trailing dim 1)``.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(jnp.isfinite(amax) & (amax > 0.0), amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _hop(payload, axis_name, perm, quantized):
    """One ring hop of `payload` (fp32): quantize, move, dequantize."""
    if not quantized:
        return jax.lax.ppermute(payload, axis_name, perm)
    q, s = quantize_int8(payload)
    q = jax.lax.ppermute(q, axis_name, perm)
    s = jax.lax.ppermute(s, axis_name, perm)
    return dequantize_int8(q, s)


def ring_reduce_scatter(x, axis_name, *, dim=0, comm_dtype="int8",
                        chunk=None):
    """``psum_scatter(x, scatter_dimension=dim, tiled=True)`` as a
    ppermute ring with (optionally) int8-quantized hop payloads.

    Each rank feeds its full ``x``; the output is this rank's row block
    ``x.shape[dim] / axis_size``, summed over the axis. The rotating
    partial sum accumulates in fp32 and is (re)quantized only for the
    wire; rank r's block sums contributions in the fixed ring order
    r+1, r+2, ..., r — deterministic, so replicas agree bitwise on
    shared blocks and the fp32 ring is reproducible against an
    order-matched reference.

    Degradations (see module docstring): unbound/size-1 axis ->
    identity; non-tiling ``chunk`` or rows -> plain ``lax.psum_scatter``.
    """
    check_comm_dtype(comm_dtype)
    n = _bound_axis_size(axis_name)
    if n is None or n == 1:
        return x
    rows_full = x.shape[dim]
    m = _ring_chunks(rows_full // n, chunk) if rows_full % n == 0 else None
    if m is None:
        return jax.lax.psum_scatter(
            x, axis_name, scatter_dimension=dim, tiled=True
        )
    quantized = comm_dtype == "int8"
    idx = jax.lax.axis_index(axis_name)
    rows = rows_full // n
    piece_rows = rows // m
    # accumulators advance to rank+1 each hop and must end at home
    perm = [(j, (j + 1) % n) for j in range(n)]
    acc = [None] * m
    with jax.named_scope("qring_rs"):
        for i in range(n):
            # the block this rank touches now reaches its owner in the
            # remaining n-1-i hops
            dst = (idx + n - 1 - i) % n
            for j in range(m):
                piece = jax.lax.dynamic_slice_in_dim(
                    x, dst * rows + j * piece_rows, piece_rows, axis=dim
                ).astype(jnp.float32)
                if acc[j] is None:
                    acc[j] = piece
                else:
                    acc[j] = _hop(acc[j], axis_name, perm, quantized) + piece
    out = acc[0] if m == 1 else jnp.concatenate(acc, axis=dim)
    return out.astype(x.dtype)


def ring_all_gather(x, axis_name, *, dim=0, comm_dtype="int8", chunk=None):
    """``all_gather(x, axis=dim, tiled=True)`` as a ppermute ring with
    (optionally) int8-quantized hop payloads.

    With ``comm_dtype="int8"`` every shard — including the local one —
    is quantized ONCE and the ``(q, scale)`` pairs rotate unchanged;
    every rank dequantizes the same pairs, so the gathered array is
    bitwise identical on all ranks (the replicated-params invariant the
    ZeRO gather needs). The fp32 ring moves payloads untouched and is
    bitwise equal to ``lax.all_gather``.
    """
    check_comm_dtype(comm_dtype)
    n = _bound_axis_size(axis_name)
    if n is None or n == 1:
        return x
    m = _ring_chunks(x.shape[dim], chunk)
    if m is None:
        return jax.lax.all_gather(x, axis_name, axis=dim, tiled=True)
    quantized = comm_dtype == "int8"
    idx = jax.lax.axis_index(axis_name)
    rows = x.shape[dim]
    piece_rows = rows // m
    # receive from rank+1: hop i leaves rank (idx + i)'s shard resident
    perm = [(j, (j - 1) % n) for j in range(n)]
    out_shape = x.shape[:dim] + (n * rows,) + x.shape[dim + 1:]
    out = jnp.zeros(out_shape, x.dtype)
    with jax.named_scope("qring_ag"):
        pieces = []
        for j in range(m):
            piece = jax.lax.slice_in_dim(
                x, j * piece_rows, (j + 1) * piece_rows, axis=dim
            )
            pieces.append(quantize_int8(piece) if quantized else piece)
        for i in range(n):
            src = (idx + i) % n
            nxt = []
            for j, payload in enumerate(pieces):
                if quantized:
                    q, s = payload
                    if i + 1 < n:
                        nxt.append((
                            jax.lax.ppermute(q, axis_name, perm),
                            jax.lax.ppermute(s, axis_name, perm),
                        ))
                    landed = dequantize_int8(q, s, x.dtype)
                else:
                    if i + 1 < n:
                        nxt.append(
                            jax.lax.ppermute(payload, axis_name, perm)
                        )
                    landed = payload
                out = jax.lax.dynamic_update_slice_in_dim(
                    out, landed, src * rows + j * piece_rows, axis=dim
                )
            if nxt:
                pieces = nxt
    return out


def ring_all_reduce(x, axis_name, *, dim=0, comm_dtype="int8", chunk=None):
    """``psum(x)`` as ring reduce-scatter + ring all-gather (the
    classic two-phase ring allreduce). Falls back to ``lax.psum`` when
    the rows do not tile the axis."""
    check_comm_dtype(comm_dtype)
    n = _bound_axis_size(axis_name)
    if n is None or n == 1:
        return x
    if x.shape[dim] % n:
        return jax.lax.psum(x, axis_name)
    shard = ring_reduce_scatter(
        x, axis_name, dim=dim, comm_dtype=comm_dtype, chunk=chunk
    )
    return ring_all_gather(
        shard, axis_name, dim=dim, comm_dtype=comm_dtype, chunk=chunk
    )
