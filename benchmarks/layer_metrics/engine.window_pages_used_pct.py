"""Share of the window group's reserved pages that hold positions, over
the traced ticks: 100 x mean of `window_pages_used` /
`window_pages_total` from the counters of each `engine.tick` span (read
at the tick's start), printing beside it the largest share of any tick
and the pages a tick gave back behind windows (`window_pages_freed`). At
100 a slot would stall for a window page."""

from benchmarks.harness import program_trace


def read(context):
    counts = program_trace.tick_counts(context)
    if not counts or not all(c.get("window_pages_total") for c in counts):
        return None
    shares = [
        int(c["window_pages_used"]) / int(c["window_pages_total"])
        for c in counts]
    freed = program_trace.total(counts, "window_pages_freed")
    program_trace.say(
        f"  engine.window_pages_used_pct: largest {100 * max(shares):.1f}% "
        f"of {counts[0]['window_pages_total']} pages; {freed} pages freed "
        f"behind windows in {len(counts)} ticks "
        f"({freed / len(counts):.3f} a tick)")
    return 100.0 * sum(shares) / len(shares)
