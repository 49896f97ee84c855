"""Share of the traced ticks that ran the mixed chunk+decode program
(`program == "mixed"` in the `engine.tick` counters): a mixed tick takes
about twice a decode-only tick, and every decoding request waits it out.
How full those ticks' chunks were (`chunk_tokens` over `budget`) is
printed beside it, and how much of that was prompt (`prefill_tokens`; the
rest is rows a speculative engine spends on verifying drafts)."""

from benchmarks.harness import program_trace


def read(context):
    counts = program_trace.tick_counts(context)
    if not counts:
        return None
    mixed = [c for c in counts if c.get("program") == "mixed"]
    budget = program_trace.total(mixed, "budget")
    used = program_trace.total(mixed, "chunk_tokens")
    program_trace.say(
        f"  engine.mixed_tick_pct: {len(mixed)} of {len(counts)} ticks "
        f"mixed; their chunks held {used} of {budget} budgeted tokens"
        + (f" ({100.0 * used / budget:.1f}%)" if budget else "")
        + f", {program_trace.total(mixed, 'prefill_tokens')} of them "
        "prompt tokens")
    return 100.0 * len(mixed) / len(counts)
