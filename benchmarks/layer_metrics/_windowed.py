"""What the readers of the window/global model's metrics share: the
operations and bytes its paged K/V decode kernel needs, worked out from
the tick's counters alone, so that they count the same work whatever
kernel does it. These are the benchmark's own counts; the program
reports only what it counted (`engine.tick`'s `kv_rows_read`,
`kv_rows_cached`, `decodes`, `window_pages_*`).

The trace keeps a `jax.named_scope` only in the instruction names of the
Mosaic kernels traced under it (`_hybrid.py`): `attn_global_decode` and
`attn_window_decode` are the decode grid's paged kernel
(`ops/flash_attention.py::flash_attention_decode_paged`) in a global and
in a window layer, `attn_global_chunk` and `attn_window_chunk` the packed
chunk's two kernels (the segment kernel over its own rows and the paged
read of its rows' cached prefixes). `attn_proj` and `attn_rope` hold no
kernel and read as nothing.
"""

from benchmarks.harness import program_trace, xplane
from benchmarks.layer_metrics import _hybrid

SCOPES = ("attn_global_decode", "attn_window_decode", "attn_global_chunk",
          "attn_window_chunk")


def attn_decode_counts(rows_read, queries, heads, kv_heads, head_dim,
                       itemsize=2):
    """(flops, bytes) of the grouped-head paged read for ``rows_read``
    cached positions (summed over live rows and layers) by ``queries``
    (live row, layer) pairs. Per position read: every query head's score
    over head_dim values and its weighted sum over as many, 2 operations
    a multiply-add; the position's K and V rows of every K/V head once.
    Per query: the heads' queries in and their outputs out."""
    flops = rows_read * 4.0 * heads * head_dim
    nbytes = (
        rows_read * 2 * kv_heads * head_dim * itemsize
        + queries * 2 * heads * head_dim * itemsize)
    return flops, nbytes


def group_layers(context):
    """(global layers, window layers) of the configuration."""
    kinds = context["family"].layer_types(context["config"])
    behind = sum(1 for k in kinds if k == "window")
    return len(kinds) - behind, behind


def group_rows(counts, n_global, n_window):
    """Positions the decode grids of ``counts``' ticks read in the global
    and in the window layers, from the two counters alone: every layer
    caches the same positions, so a layer's share of `kv_rows_cached` is
    what one global layer read, and what is left of `kv_rows_read` after
    the global layers' is the window layers'."""
    read = sum(int(c.get("kv_rows_read", 0)) for c in counts)
    cached = sum(int(c.get("kv_rows_cached", 0)) for c in counts)
    in_global = cached * n_global // (n_global + n_window)
    return in_global, read - in_global


def decode_roofline(context, group):
    """The share of its roofline of the decode kernel in the layers of
    ``group`` ("global" or "window") over the traced stretch, or None
    where the capture holds no such kernel or counter."""
    ops = _hybrid.scope_ops(context, f"attn_{group}_decode")
    counts = program_trace.tick_counts(context)
    if not ops or not counts or context.get("peaks") is None:
        return None
    if not any("kv_rows_read" in c for c in counts):
        return None
    n_global, n_window = group_layers(context)
    rows = group_rows(counts, n_global, n_window)[group == "window"]
    if not rows:
        return None
    s = context["family"].sizes(context["config"])
    layers = n_window if group == "window" else n_global
    queries = program_trace.total(counts, "decodes") * layers
    flops, nbytes = attn_decode_counts(
        rows, queries, s["heads"], s["kv_heads"], s["head_dim"])
    secs = xplane.total(xplane.busy_intervals(ops)) / 1e9
    least = _hybrid.least_seconds(flops, nbytes, context["peaks"])
    program_trace.say(
        f"  attn_{group}_decode_roofline: {rows} positions read by "
        f"{queries} (row, layer) queries in {len(counts)} ticks: "
        f"{flops / 1e9:.1f} GFLOP, {nbytes / 1e9:.2f} GB, least "
        f"{1e3 * least:.2f} ms of {1e3 * secs:.2f} ms")
    return 100.0 * least / secs
