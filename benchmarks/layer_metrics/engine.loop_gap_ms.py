"""Median over the traced ticks of `gap_us`: from the previous `step()`'s
return to this one's entry, which the engine counts only where it had
work when the previous one returned. It is the serving loop's own time
between two ticks (here the benchmark's: due requests added, the tick's
record appended), a part of every bubble the engine cannot shorten.
`cum_gap_ms`, the same over the whole run since `reset_stats` (ramp
included), is printed beside it from the last traced tick."""

import statistics

from benchmarks.harness import program_trace, tick_account


def read(context):
    acc = tick_account.of(context)
    gaps = [tick_account.number(t.counts, "gap_us") for t in acc.ticks]
    if not gaps or any(g is None for g in gaps):
        return None
    last = tick_account.last_counts(acc)
    program_trace.say(
        f"  engine.loop_gap_ms: median {statistics.median(gaps) / 1e3:.4f} "
        f"ms over {len(gaps)} ticks (most {max(gaps) / 1e3:.3f}); since "
        f"reset cum_gap_ms {float(last['cum_gap_ms']):.1f} over "
        f"{int(last['cum_ticks_mixed']) + int(last['cum_ticks_decode'])} "
        "mixed and decode ticks")
    return statistics.median(gaps) / 1e3
