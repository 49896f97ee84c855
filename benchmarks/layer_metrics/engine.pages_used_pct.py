"""Share of the reserved K/V pages that hold tokens, over the traced
ticks: 100 x mean of `pages_used` / `pages_total` from the counters of
each `engine.tick` span (`pages_used` is read at the tick's start).
`decode_paged_roofline` takes the same count from the benchmark's own
read before each `step()`; both means are printed so that a disagreement
shows."""

from benchmarks.harness import program_trace


def read(context):
    counts = program_trace.tick_counts(context)
    if not counts or not all(c.get("pages_total") for c in counts):
        return None
    used = sum(int(c["pages_used"]) for c in counts) / len(counts)
    total = sum(int(c["pages_total"]) for c in counts) / len(counts)
    p = context.get("profiler", {})
    bench = [
        pages for s, e, pages in context.get("ticks", ())
        if s >= p.get("t_start", 0.0) and e <= p.get("t_stop", 0.0)
    ][:len(counts)]
    program_trace.say(
        f"  engine.pages_used_pct: mean pages_used {used:.3f} of {total:.0f} "
        f"over {len(counts)} ticks; the benchmark's own read before step(): "
        + (f"{sum(bench) / len(bench):.3f} over {len(bench)}" if bench
           else "none"))
    return 100.0 * used / total
