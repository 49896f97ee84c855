"""The paged K/V decode kernel's share of its roofline in the GLOBAL
layers over the traced stretch: as `attn_window_decode_roofline`, for
the positions the global layers' decode grids attended over (their share
of `kv_rows_cached`: a global layer reads every cached position) and the
operations named `attn_global_decode`."""

from benchmarks.layer_metrics import _windowed


def read(context):
    return _windowed.decode_roofline(context, "global")
