"""Share of the decode grid's rows that emitted a token, over the traced
ticks: 100 x sum of `decodes` / sum of `slots`, from the counters of each
`engine.tick` span. A tick costs the same whatever its occupancy (the
programs have fixed shapes), so a fuller grid spreads that cost over more
tokens. The sum of `slots_busy` is printed beside it: the slot-ticks
leased to a request, decoding or not, so that an empty grid (no load) and
one held by prompts that are still prefilling can be told apart."""

from benchmarks.harness import program_trace


def read(context):
    counts = program_trace.tick_counts(context)
    if not counts:
        return None
    slots = program_trace.total(counts, "slots")
    if not slots:
        return None
    decodes = program_trace.total(counts, "decodes")
    # leased and not decoding: a prompt still prefilling, or stalled
    program_trace.say(
        f"  engine.decode_occupancy_pct: {decodes} decode rows over "
        f"{slots} slot-ticks in {len(counts)} ticks; "
        f"{program_trace.total(counts, 'slots_busy')} of them leased")
    return 100.0 * decodes / slots
