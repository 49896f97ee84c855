"""Median over the traced ticks of the engine's `engine.commit` phase:
everything after the fetch (prefix pages registered, tokens appended,
requests finished and evicted with their completion records, gauges, the
sensor plane's sample, the retrace sentinel's check). The ticks'
`finished` says which of them evicted a request: their commit is printed
beside the value."""

import statistics

from benchmarks.harness import program_trace


def read(context):
    value = program_trace.phase_median_ms(context, ("engine.commit",))
    evicting = [
        phases.get("engine.commit", 0) / 1e6
        for tick, phases in program_trace.traced_ticks(context)
        if int(tick.counts.get("finished", 0))]
    if evicting:
        program_trace.say(
            f"  engine.commit_ms: {len(evicting)} ticks finished a request; "
            f"their commit: median {statistics.median(evicting):.3f} ms")
    return value
