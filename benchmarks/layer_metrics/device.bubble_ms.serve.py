"""Median bubble between two consecutive executions of the engine's step
programs: the end of tick i-1's execution to the start of tick i's, on
the device's clock alone. The tick is synchronous, so this is the device
waiting for the host, and at saturation its share of the tick is the
share of tokens/s the host costs.

Printed beside it, from `harness/tick_account.py`: the clock's interval
(the offset between the device's clock and the host's, from causality);
each part of the bubble on that one clock (the fetch's tail, commit, the
rest of the tick after its fetch, the loop's gap, admit, pack, table
push, the rest of the next tick before its dispatch, the launch's lag)
as its median and as its seconds over the stretch, which sum to the
bubbles' total (to set beside `breakdown.idle_gaps`' `engine.step`); the
parts by the `program` of the tick that follows; where the capture holds
the runtime's own events, the tail and the lag each in two (the program's
end to the moment the runtime sees it and on to the fetch's return; the
dispatch's entry to the program's enqueueing and on to its start), and
where it does not, the tail and the lag as ONE part (the spans alone
leave the clock open by a millisecond and more, and the middle of that
would split them in equal halves: `tick_account.shown_parts`); and under
each part the time a `host.gc` pause took of it."""

import statistics

from benchmarks.harness import program_trace, tick_account
from benchmarks.harness.tick_account import shown_parts


def read(context):
    acc = tick_account.of(context)
    if not acc.bubbles:
        return None
    say = program_trace.say
    value = tick_account.ms([b.ns for b in acc.bubbles])
    total_s = sum(b.ns for b in acc.bubbles) / 1e9
    parts = [b.parts() for b in acc.bubbles]
    say(f"  device.bubble_ms.serve: median {value:.3f} ms over "
        f"{len(acc.bubbles)} bubbles (10th "
        f"{tick_account.ms([b.ns for b in acc.bubbles], 10):.3f}, 90th "
        f"{tick_account.ms([b.ns for b in acc.bubbles], 90):.3f}), "
        f"{total_s:.4f} s of the stretch; {tick_account.clock_line(acc)}")
    say("  device.bubble_ms.serve: parts, median ms (seconds over the "
        "stretch): " + ", ".join(
            f"{p} {tick_account.ms(ns):.3f} ({sum(ns) / 1e9:.4f})"
            for p, ns in shown_parts(acc, parts))
        + f"; the parts sum to {sum(sum(x.values()) for x in parts) / 1e9:.4f} s")
    by_program = {}
    for b, x in zip(acc.bubbles, parts):
        by_program.setdefault(b.after.program, []).append((b.ns, x))
    for program, rows in sorted(by_program.items(), key=str):
        say(f"  device.bubble_ms.serve: before a {program} tick ({len(rows)}):"
            f" median {tick_account.ms([ns for ns, _ in rows]):.3f} ms; "
            + ", ".join(
                f"{p} {tick_account.ms(ns):.3f}"
                for p, ns in shown_parts(acc, [x for _, x in rows])))
    fine = [b.fine() for b in acc.bubbles] if acc.split else []
    if any(fine):
        say("  device.bubble_ms.serve: by the runtime's own events, median "
            "ms: " + ", ".join(
                f"{k} {tick_account.ms([f[k] for f in fine if k in f]):.3f}"
                for k in ("end_to_seen", "seen_to_return",
                          "entry_to_enqueue", "enqueue_to_start")
                if any(k in f for f in fine)))
    paused = [tick_account.pause_share(acc, b) for b in acc.bubbles]
    if any(sum(x.values()) for x in paused):
        say("  device.bubble_ms.serve: of which host.gc pauses: " + ", ".join(
            f"{p} {sum(ns) / 1e6:.3f} ms"
            for p, ns in shown_parts(acc, paused) if sum(ns))
            + f" ({len(acc.pauses)} pauses in the stretch)")
    gaps = [
        tick_account.number(b.after.counts, "gap_us") for b in acc.bubbles]
    if all(g is not None for g in gaps):
        say(f"  device.bubble_ms.serve: loop_gap as the engine counts it "
            f"(gap_us): median {statistics.median(gaps) / 1e3:.3f} ms, "
            f"{sum(gaps) / 1e6:.4f} s")
    return value
