"""Fused multi-tensor ops over packed buffers: scale, axpby, L2 norm.

TPU-native equivalents of the amp_C multi-tensor kernels
(reference: csrc/multi_tensor_scale_kernel.cu:30-136 `ScaleFunctor`,
csrc/multi_tensor_axpby_kernel.cu, csrc/multi_tensor_l2norm_kernel.cu:29-370).
Each op is one Pallas call per dtype-group buffer; the reference's
device-side ``noop_flag`` overflow buffer becomes a per-grid-block flag
array OR-reduced on the outside — the whole thing stays inside jit, so
there is no D2H sync (the reference syncs at scaler.py:206-209).

Tree-level wrappers (`scale`, `axpby`, `l2norm`) pack/unpack around the
packed primitives; the optimizer layer calls the packed forms directly
to avoid re-packing.
"""

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocm_apex_tpu.ops._pallas import (
    DirectOutRef,
    DirectRef,
    kernel_dtype,
    on_tpu,
    pallas_call,
)
from rocm_apex_tpu.ops.packing import (
    WIDTH,
    PackedTree,
    group_segment_ids,
    pack_tree,
    respec,
    unpack_tree,
)

__all__ = [
    "scale_packed",
    "scale",
    "scale_sumsq_packed",
    "axpby_packed",
    "axpby",
    "l2norm_packed",
    "l2norm",
    "row_sumsq",
]

BLOCK_ROWS = 64  # 64x1024 fp32 = 256 KiB per buffer block in VMEM


def _grid(rows: int) -> int:
    assert rows % BLOCK_ROWS == 0, f"packed rows {rows} not {BLOCK_ROWS}-aligned"
    return rows // BLOCK_ROWS


def _vmem_spec():
    return pl.BlockSpec((BLOCK_ROWS, WIDTH), lambda i: (i, 0))


def _smem_scalar_spec():
    return pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)


# One non-finite flag per grid block. Mosaic blocks an output only in
# whole (8, 128) tiles — a (1, 1) SMEM block over a (grid, 1) array is
# refused as soon as grid > 1 — so each block broadcasts its flag over
# one int32 tile (4 KiB beside the 256 KiB block it read).
_FLAG_TILE = (8, 128)


def _flag_out_spec():
    return pl.BlockSpec(_FLAG_TILE, lambda i: (i, 0))


def _flag_out_shape(grid: int):
    return jax.ShapeDtypeStruct(
        (grid * _FLAG_TILE[0], _FLAG_TILE[1]), jnp.int32
    )


def _nonfinite_tile(x):
    bad = jnp.logical_not(jnp.isfinite(x).all()).astype(jnp.int32)
    return jnp.full(_FLAG_TILE, bad)



# ---------------------------------------------------------------------------
# scale: out = in * scale, with fused non-finite probe
# ---------------------------------------------------------------------------


def _scale_kernel(x_ref, s_ref, out_ref, flag_ref):
    x = x_ref[...].astype(jnp.float32) * s_ref[0, 0]
    flag_ref[...] = _nonfinite_tile(x)
    out_ref[...] = x.astype(out_ref.dtype)


def _scale_buffer(buf, s, out_dtype):
    rows = buf.shape[0]
    grid = _grid(rows)
    buf = buf.astype(kernel_dtype(buf.dtype))
    kd_out = kernel_dtype(out_dtype)
    if not on_tpu():
        # direct whole-buffer run of the same kernel body (the grid is
        # a row partition; see DirectRef) — skips the interpreter's
        # per-block slicing on the CPU harness
        o, f = DirectOutRef(kd_out), DirectOutRef(jnp.int32)
        _scale_kernel(DirectRef(buf), DirectRef(s), o, f)
        return o.value.astype(out_dtype), (f.value > 0).any()
    out, flags = pallas_call(
        _scale_kernel,
        grid=(grid,),
        in_specs=[_vmem_spec(), _smem_scalar_spec()],
        out_specs=[_vmem_spec(), _flag_out_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((rows, WIDTH), kd_out),
            _flag_out_shape(grid),
        ],
    )(buf, s)
    return out.astype(out_dtype), (flags > 0).any()


def scale_packed(
    packed: PackedTree, scale_val, out_dtype=None
) -> Tuple[PackedTree, jnp.ndarray]:
    """out = packed * scale; returns (out, found_inf).

    Semantics of `multi_tensor_scale` + noop_flag
    (reference: csrc/multi_tensor_scale_kernel.cu:30-136): the flag trips
    on any non-finite produced value and the caller decides whether to
    discard the result (a `lax.cond`/`where` instead of the reference's
    kernel-side early-out).
    """
    s = jnp.asarray(scale_val, jnp.float32).reshape(1, 1)
    outs, infs = [], []
    for buf, g in zip(packed.buffers, packed.spec.groups):
        od = jnp.dtype(out_dtype).name if out_dtype is not None else g.dtype
        out, inf = _scale_buffer(buf, s, od)
        outs.append(out)
        infs.append(inf)
    found_inf = jnp.stack(infs).any() if infs else jnp.asarray(False)
    return PackedTree(outs, respec(packed.spec, out_dtype)), found_inf


def scale(tree: Any, scale_val, out_dtype=None) -> Tuple[Any, jnp.ndarray]:
    """Tree-level `multi_tensor_scale`: returns (scaled_tree, found_inf)."""
    packed, found_inf = scale_packed(pack_tree(tree), scale_val, out_dtype)
    return unpack_tree(packed), found_inf


# ---------------------------------------------------------------------------
# scale + sumsq: out = in * scale, fused non-finite probe AND per-row sum of
# squares of the scaled values — the unscale/probe/grad-norm phase of the
# packed optimizer step in ONE read of each buffer.
# ---------------------------------------------------------------------------


def _scale_sumsq_kernel(x_ref, s_ref, out_ref, flag_ref, rsq_ref):
    x = x_ref[...].astype(jnp.float32) * s_ref[0, 0]
    flag_ref[...] = _nonfinite_tile(x)
    out_ref[...] = x.astype(out_ref.dtype)
    rsq_ref[...] = jnp.sum(x * x, axis=1, keepdims=True)


def _scale_sumsq_buffer(buf, s, out_dtype):
    rows = buf.shape[0]
    grid = _grid(rows)
    buf = buf.astype(kernel_dtype(buf.dtype))
    kd_out = kernel_dtype(out_dtype)
    if not on_tpu():
        o = DirectOutRef(kd_out)
        f = DirectOutRef(jnp.int32)
        r = DirectOutRef(jnp.float32)
        _scale_sumsq_kernel(DirectRef(buf), DirectRef(s), o, f, r)
        return o.value.astype(out_dtype), (f.value > 0).any(), r.value
    out, flags, rsq = pallas_call(
        _scale_sumsq_kernel,
        grid=(grid,),
        in_specs=[_vmem_spec(), _smem_scalar_spec()],
        out_specs=[
            _vmem_spec(),
            _flag_out_spec(),
            pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, WIDTH), kd_out),
            _flag_out_shape(grid),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
    )(buf, s)
    return out.astype(out_dtype), (flags > 0).any(), rsq


def scale_sumsq_packed(
    packed: PackedTree, scale_val, out_dtype=None
) -> Tuple[PackedTree, jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """out = packed * scale; returns (out, found_inf, per_group_row_sumsq).

    The scaler-unscale half-step of the packed optimizer
    (reference: multi_tensor_scale + multi_tensor_l2norm back to back,
    csrc/multi_tensor_scale_kernel.cu + csrc/multi_tensor_l2norm_kernel.cu)
    collapsed into a single pass: each dtype buffer is read once and
    yields the unscaled values, the non-finite flag, AND the (rows, 1)
    partial sums of squares the global-grad-norm clip consumes. The
    row-aligned layout keeps the row sums segmentable into per-tensor
    norms downstream (`l2norm_packed`).
    """
    s = jnp.asarray(scale_val, jnp.float32).reshape(1, 1)
    outs, infs, rsqs = [], [], []
    for buf, g in zip(packed.buffers, packed.spec.groups):
        od = jnp.dtype(out_dtype).name if out_dtype is not None else g.dtype
        out, inf, rsq = _scale_sumsq_buffer(buf, s, od)
        outs.append(out)
        infs.append(inf)
        rsqs.append(rsq)
    found_inf = jnp.stack(infs).any() if infs else jnp.asarray(False)
    return (
        PackedTree(outs, respec(packed.spec, out_dtype)),
        found_inf,
        tuple(rsqs),
    )


# ---------------------------------------------------------------------------
# axpby: out = a*x + b*y, fused non-finite probe
# ---------------------------------------------------------------------------


def _axpby_kernel(x_ref, y_ref, a_ref, b_ref, out_ref, flag_ref):
    out = (
        x_ref[...].astype(jnp.float32) * a_ref[0, 0]
        + y_ref[...].astype(jnp.float32) * b_ref[0, 0]
    )
    flag_ref[...] = _nonfinite_tile(out)
    out_ref[...] = out.astype(out_ref.dtype)


def axpby_packed(
    x: PackedTree, y: PackedTree, a, b, out_dtype=None
) -> Tuple[PackedTree, jnp.ndarray]:
    """out = a*x + b*y over packed buffers; returns (out, found_inf).

    The grad-accumulation merge kernel (reference:
    csrc/multi_tensor_axpby_kernel.cu, used by scaler.py:173-187).
    """
    if x.spec.groups != y.spec.groups:
        raise ValueError(
            "axpby_packed requires x and y packed under the same spec; "
            f"got {x.spec.groups} vs {y.spec.groups}"
        )
    a = jnp.asarray(a, jnp.float32).reshape(1, 1)
    b = jnp.asarray(b, jnp.float32).reshape(1, 1)
    outs, infs = [], []
    for xb, yb, g in zip(x.buffers, y.buffers, x.spec.groups):
        od = jnp.dtype(out_dtype).name if out_dtype is not None else g.dtype
        rows = xb.shape[0]
        grid = _grid(rows)
        xb = xb.astype(kernel_dtype(xb.dtype))
        yb = yb.astype(kernel_dtype(yb.dtype))
        kd_out = kernel_dtype(od)
        if not on_tpu():
            o, f = DirectOutRef(kd_out), DirectOutRef(jnp.int32)
            _axpby_kernel(
                DirectRef(xb), DirectRef(yb), DirectRef(a), DirectRef(b),
                o, f,
            )
            outs.append(o.value.astype(od))
            infs.append((f.value > 0).any())
            continue
        out, flags = pallas_call(
            _axpby_kernel,
            grid=(grid,),
            in_specs=[
                _vmem_spec(),
                _vmem_spec(),
                _smem_scalar_spec(),
                _smem_scalar_spec(),
            ],
            out_specs=[_vmem_spec(), _flag_out_spec()],
            out_shape=[
                jax.ShapeDtypeStruct((rows, WIDTH), kd_out),
                _flag_out_shape(grid),
            ],
        )(xb, yb, a, b)
        outs.append(out.astype(od))
        infs.append((flags > 0).any())
    found_inf = jnp.stack(infs).any() if infs else jnp.asarray(False)
    return PackedTree(outs, respec(x.spec, out_dtype)), found_inf


def axpby(x: Any, y: Any, a, b) -> Tuple[Any, jnp.ndarray]:
    """Tree-level axpby: returns (a*x + b*y, found_inf)."""
    px = pack_tree(x)
    py = pack_tree(y, px.spec)
    packed, found_inf = axpby_packed(px, py, a, b)
    return unpack_tree(packed), found_inf


# ---------------------------------------------------------------------------
# l2norm: global + optional per-tensor norms
# ---------------------------------------------------------------------------


def _rowsum_sq_kernel(x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.sum(x * x, axis=1, keepdims=True)


def row_sumsq(buf) -> jnp.ndarray:
    rows = buf.shape[0]
    grid = _grid(rows)
    buf = buf.astype(kernel_dtype(buf.dtype))
    if not on_tpu():
        o = DirectOutRef(jnp.float32)
        _rowsum_sq_kernel(DirectRef(buf), o)
        return o.value
    return pallas_call(
        _rowsum_sq_kernel,
        grid=(grid,),
        in_specs=[_vmem_spec()],
        out_specs=pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.float32),
    )(buf)


def l2norm_packed(
    packed: PackedTree, per_tensor: bool = False
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, ...]]]:
    """Global L2 norm (and per-tensor norms) of a packed pytree.

    Two-stage design like the reference (per-chunk partials then cleanup,
    csrc/multi_tensor_l2norm_kernel.cu:198-370): the Pallas stage reduces
    each 1024-wide row to a partial sum of squares; per-tensor norms fall
    out as a segmented row reduction thanks to the row-aligned layout
    (rows never straddle tensors, ops/packing.py).

    Returns (global_norm, per_group_tensor_norms or None); per-group
    results are arrays of per-tensor norms ordered like
    `spec.groups[k].leaf_specs`.
    """
    total = jnp.asarray(0.0, jnp.float32)
    per_group = []
    for buf, group in zip(packed.buffers, packed.spec.groups):
        row_sq = row_sumsq(buf)[:, 0]
        total = total + row_sq.sum()
        if per_tensor:
            seg = jnp.asarray(group_segment_ids(group))
            sums = jax.ops.segment_sum(
                row_sq, seg, num_segments=len(group.leaf_specs) + 1
            )[: len(group.leaf_specs)]
            per_group.append(jnp.sqrt(sums))
    return jnp.sqrt(total), tuple(per_group) if per_tensor else None


def l2norm(tree: Any, per_tensor: bool = False):
    """Tree-level L2 norm; per_tensor returns norms as a matching pytree."""
    packed = pack_tree(tree)
    global_norm, per_group = l2norm_packed(packed, per_tensor=per_tensor)
    if not per_tensor:
        return global_norm, None
    leaves = [None] * packed.spec.n_leaves
    for norms, group in zip(per_group, packed.spec.groups):
        for j, i in enumerate(group.leaf_indices):
            leaves[i] = norms[j]
    return global_norm, jax.tree_util.tree_unflatten(packed.spec.treedef, leaves)
