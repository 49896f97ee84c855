"""Grouped matrix product: rows laid out group by group, each group
multiplied by its own matrix.

The expert layer's core (`transformer/moe.py`): every (token, expert)
assignment that fell on an expert held here becomes one row, rows of one
expert lie together, and each expert's rows meet only that expert's
weights. Nothing is dropped and nothing is padded to a capacity: the
layout has room for every assignment (``layout_rows``), and a group is
padded only up to the next multiple of the row tile, so that a tile
belongs to ONE group and the kernel is a plain tiled matmul whose
right-hand block is picked by the tile's group (a scalar-prefetch index
map, as the paged attention kernels pick pages).

`group_layout` is a counting sort: one (assignments, groups) comparison
and a cumulative sum give every assignment its row. Tiles past the last
used one are not computed and not fetched (their index maps repeat the
last live tile), so a tick pays for the rows it has; rows nothing was
assigned to are never read back.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocm_apex_tpu.ops._pallas import pallas_call

__all__ = ["layout_rows", "group_layout", "grouped_matmul", "row_tile"]


def _round_up(x, m):
    return (x + m - 1) // m * m


def row_tile(assignments: int) -> int:
    """Rows of one tile. A decode tick has a few rows a group and is
    bound by reading the weights, so its tile is the smallest a bf16
    block may be (16 rows); a prefill chunk has hundreds a group and
    fills the MXU's 128."""
    return 128 if assignments >= 2048 else 16


def layout_rows(assignments: int, num_groups: int, block_m: int) -> int:
    """Rows the group-padded layout needs in the worst case: every
    assignment kept, every group padded by up to a tile."""
    return _round_up(assignments, block_m) + num_groups * block_m


def group_layout(group_ids, valid, num_groups: int, block_m: int):
    """Rows for ``group_ids`` ((A,) int32, in ``[0, num_groups)`` where
    ``valid``), in order of arrival inside each group.

    Returns ``dest`` ((A,): the assignment's row, ``rows`` = out of range
    where not valid), ``tile_group`` ((rows / block_m,): the group each
    tile belongs to), ``num_live`` ((1,): tiles in use) and ``sizes``
    ((num_groups,): assignments a group received)."""
    a = group_ids.shape[0]
    rows = layout_rows(a, num_groups, block_m)
    onehot = (
        (group_ids[:, None] == jnp.arange(num_groups)[None, :])
        & valid[:, None]
    ).astype(jnp.int32)
    sizes = jnp.sum(onehot, axis=0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    padded = (sizes + block_m - 1) // block_m * block_m
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(
        valid, starts[jnp.clip(group_ids, 0, num_groups - 1)] + rank, rows
    ).astype(jnp.int32)
    tile_rows = jnp.arange(rows // block_m, dtype=jnp.int32) * block_m
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_rows, side="right"), num_groups - 1
    ).astype(jnp.int32)
    num_live = (ends[-1:] // block_m).astype(jnp.int32)
    return dest, tile_group, num_live, sizes


def _kernel(tg_ref, nl_ref, x_ref, w_ref, o_ref):
    del tg_ref

    @pl.when(pl.program_id(1) < nl_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


def grouped_matmul(
    lhs, rhs, tile_group, num_live, *, block_m: int, block_n: int = 512,
    out_dtype=None,
):
    """``out[r] = lhs[r] @ rhs[tile_group[r // block_m]]`` for the rows
    of the first ``num_live`` tiles; later rows are left as they are
    (unwritten).

    ``lhs`` (rows, k) in the group-padded layout of `group_layout`,
    ``rhs`` (groups, k, n). The grid walks (n block, row tile) with the
    row tiles innermost, so consecutive tiles of one group reuse the
    weight block that is resident: a group's weights are read once per
    n block however many tiles it has. The whole of k is one block
    (4096 x 512 bf16 = 4 MiB, twice for the pipeline's two buffers)."""
    rows, k = lhs.shape
    groups, k2, n = rhs.shape
    if k != k2:
        raise ValueError(f"lhs k {k} != rhs k {k2}")
    if rows % block_m:
        raise ValueError(f"rows {rows} not a multiple of block_m {block_m}")
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"n {n} not a multiple of its block {bn}")
    out_dtype = out_dtype or lhs.dtype

    def tile(i, nl):
        return jnp.maximum(jnp.minimum(i, nl[0] - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // bn, rows // block_m),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, i, tg, nl: (tile(i, nl), 0)),
            pl.BlockSpec(
                (1, k, bn), lambda j, i, tg, nl: (tg[tile(i, nl)], 0, j)),
        ],
        out_specs=pl.BlockSpec(
            (block_m, bn), lambda j, i, tg, nl: (tile(i, nl), j)),
    )
    return pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024),
    )(tile_group, num_live, lhs, rhs)
