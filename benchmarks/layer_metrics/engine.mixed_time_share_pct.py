"""Share of the ticks' wall time since `reset_stats` that went to MIXED
ticks: `cum_ms_mixed` over `cum_ms_mixed` + `cum_ms_decode` of the last
traced tick, so over the whole run (ramp and window), where
`engine.mixed_tick_pct` counts the ticks of the traced seconds. A cell
whose share is high is bound by its prefill, and a faster mixed tick
there pulls prompts into the window whose outputs leave after it.

Printed beside it: both tick counts, and the prompt rows prefilled a
generated token (`cum_prefill_tokens` / `cum_generated`), which says how
far the run was from the trace's own ratio."""

from benchmarks.harness import program_trace, tick_account


def read(context):
    last = tick_account.last_counts(tick_account.of(context))
    mixed = tick_account.number(last, "cum_ms_mixed")
    if mixed is None:
        return None
    decode = float(last["cum_ms_decode"])
    rows, tokens = int(last["cum_prefill_tokens"]), int(last["cum_generated"])
    program_trace.say(
        f"  engine.mixed_time_share_pct: cum_ms_mixed {mixed:.1f} in "
        f"{int(last['cum_ticks_mixed'])} ticks, cum_ms_decode {decode:.1f} "
        f"in {int(last['cum_ticks_decode'])}; cum_prefill_tokens {rows} / "
        f"cum_generated {tokens}"
        + (f" = {rows / tokens:.2f} prompt rows a token" if tokens else ""))
    return 100.0 * mixed / (mixed + decode) if mixed + decode else 0.0
