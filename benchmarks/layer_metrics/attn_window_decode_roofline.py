"""The paged K/V decode kernel's share of its roofline in the WINDOW
layers over the traced stretch: the least time the chip's published
peaks allow for the positions those layers' decode grids attended over
AFTER the window's bound (`kv_rows_read` less the global layers' share
of `kv_rows_cached`) x 2,048 B plus the queries in and the outputs out,
and 4 x 28 x 128 operations a position (`_windowed.attn_decode_counts`),
over the time the operations named `attn_window_decode` took. 7 query
heads read one K/V head, 7 operations a byte: far under the chip's
ridge, so the bytes bind; a share over 100% would be a wrong count."""

from benchmarks.layer_metrics import _windowed


def read(context):
    return _windowed.decode_roofline(context, "window")
