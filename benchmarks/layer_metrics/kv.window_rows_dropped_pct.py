"""How much of the cache the window spared the decode kernels: 100 x (1 -
`kv_rows_read` / `kv_rows_cached`) over the traced ticks' decode grids
(both summed by the program over live slots and attention layers, after
and before the window's bound). 0 means the traffic never leaves a
window; with 6 window layers in 8 it cannot pass 75."""

from benchmarks.harness import program_trace


def read(context):
    counts = program_trace.tick_counts(context)
    if not counts:
        return None
    cached = program_trace.total(counts, "kv_rows_cached")
    if not cached:
        return None
    read_ = program_trace.total(counts, "kv_rows_read")
    program_trace.say(
        f"  kv.window_rows_dropped_pct: {read_} positions read of {cached} "
        f"cached in {len(counts)} ticks")
    return 100.0 * (1.0 - read_ / cached)
