"""How uneven the router's load is: the most (token, expert) pairs one
held expert received in one layer (`moe_load_max`) over the mean a held
expert received (`moe_assignments` over layers x experts held), median
over the traced decode-only ticks (a mixed tick's counters sum two
applies of different sizes). 1 is an even spread; the grouped product's
time follows the fullest expert's tiles."""

import statistics

from benchmarks.harness import program_trace


def read(context):
    counts = program_trace.tick_counts(context)
    if not counts:
        return None
    s = context["family"].sizes(context["config"])
    held = s["held_hi"] - s["held_lo"]
    ratios = [
        int(c["moe_load_max"]) * s["layers"] * held / int(c["moe_assignments"])
        for c in counts
        if c.get("program") == "decode" and int(c.get("moe_assignments", 0))
    ]
    if not ratios:
        return None
    return statistics.median(ratios)
