"""Milliseconds that Python's cyclic collector paused the process, per
second of serving, over the WHOLE run since `reset_stats` (ramp, window
and what of the drain the capture's last tick has seen): `cum_gc_ms` of
the last traced tick over the seconds the engine spent in mixed and
decode ticks and in the gaps between them (`cum_ms_mixed` +
`cum_ms_decode` + `cum_gap_ms`). The capture holds a few seconds of a
run of minutes; the totals that ride on its last tick hold the rest.
0.0, not nothing, where no collection ran.

Printed beside it: `cum_gc_n`, `gc_max_ms` (the longest pause since
reset: one collection's, or the sum of those that fell between two
ticks), what the traced ticks themselves count (`gc_n`, `gc_us`: the
collections between one tick's reading and the next) and the `host.gc`
spans the traced stretch holds, by generation: the two agree where every
pause of the stretch fell between its first and last tick."""

from benchmarks.harness import program_trace, tick_account


def read(context):
    acc = tick_account.of(context)
    last = tick_account.last_counts(acc)
    paused = tick_account.number(last, "cum_gc_ms")
    if paused is None:
        return None
    seconds = sum(
        float(last[k]) for k in ("cum_ms_mixed", "cum_ms_decode",
                                 "cum_gap_ms")) / 1e3
    by_generation = {}
    for p in acc.pauses:
        by_generation.setdefault(p.counts.get("generation"), []).append(
            p.dur_ns / 1e6)
    program_trace.say(
        f"  host.gc_pause_ms_per_s: cum_gc_ms {paused:.3f} in cum_gc_n "
        f"{int(last['cum_gc_n'])} collections over {seconds:.2f} s of ticks "
        f"and gaps since reset; gc_max_ms {float(last['gc_max_ms']):.3f}; "
        f"the traced ticks count gc_n "
        f"{program_trace.total([t.counts for t in acc.ticks], 'gc_n')} and "
        f"gc_us {program_trace.total([t.counts for t in acc.ticks], 'gc_us')}"
        f"; the traced stretch holds {len(acc.pauses)} host.gc spans"
        + "".join(
            f"; generation {g}: {len(v)}, {sum(v):.3f} ms, longest "
            f"{max(v):.3f}" for g, v in sorted(by_generation.items(), key=str)))
    return paused / seconds if seconds else 0.0
