"""How far the slowest tick since `reset_stats` (wall plus the gap before
it: `slow_ms` of the last traced tick) lies above an ordinary tick of its
`program`: `slow_ms` less the traced stretch's median wall + gap of that
program's ticks (of all ticks where the stretch holds none of it). A host
stall of a tenth of a second or of seconds anywhere in the run, ramp
included, shows here though the capture holds only the window's last
seconds.

**The capture's own start is kept out.** The benchmark starts the
profiler between two ticks while the engine has work, so the some 45 ms
of `jax.profiler.start_trace` are the `gap` of the first traced tick, and
in a run that nothing else stalled they are the record (my chip runs, PR
37: granite 44.0 and 44.4 ms). A record whose `slow_tick` is the first
traced tick and whose `gap` lies further above the stretch's median than
any of its phases is the measurement, not the system: it is printed, and
the value is then the largest excess among the stretch's other ticks.
The run's longest stall of its own lies between that value and the
printed record, which hid whatever was shorter.

Printed beside it: which tick it was (`slow_tick`) and each of its
`slow_phases` (the engine's own clock: the gap and the tick's phases in
microseconds) beside that phase's median in the traced stretch, so the
phase at fault shows; and the stretch's own slowest tick after its first
with the collector's share of it (that tick's `gc_us`)."""

import statistics

from benchmarks.harness import program_trace, tick_account


def wall_and_gap_ms(tick):
    return tick.span.dur_ns / 1e6 + float(tick.counts["gap_us"]) / 1e3


def read(context):
    acc = tick_account.of(context)
    last = tick_account.last_counts(acc)
    slow_ms = tick_account.number(last, "slow_ms")
    if slow_ms is None:
        return None
    say = program_trace.say

    def like(program):
        return [t for t in acc.ticks if t.program == program] or acc.ticks

    def ordinary(program):
        return statistics.median(wall_and_gap_ms(t) for t in like(program))

    program = last["slow_program"]
    ticks = like(program)
    words = str(last["slow_phases"]).split()
    slow = dict(zip(words[::2], (int(w) for w in words[1::2])))
    medians = {"gap": statistics.median(
        float(t.counts["gap_us"]) for t in ticks)}
    for name in tick_account.PHASES:
        medians[name] = statistics.median(
            sum(k.dur_ns for k in t.kids if k.name == "engine." + name) / 1e3
            for t in ticks)
    medians["rest"] = statistics.median(
        (t.span.dur_ns - sum(k.dur_ns for k in t.kids)) / 1e3 for t in ticks)
    say(f"  host.longest_stall_ms: slow_ms {slow_ms:.3f} at slow_tick "
        f"{int(last['slow_tick'])} ({program}) against a median wall + gap "
        f"of {ordinary(program):.3f} ms over {len(ticks)} "
        f"{program if ticks[0].program == program else 'traced'} ticks; "
        "its phases in us (the stretch's median): " + ", ".join(
            f"{name} {us} ({medians.get(name, 0.0):.0f})"
            for name, us in slow.items()))
    value = slow_ms - ordinary(program)
    # the stretch's own slowest tick; its first tick's gap holds the
    # profiler's start whether or not that made the record
    worst = max(
        acc.ticks[1:], default=None,
        key=lambda t: wall_and_gap_ms(t) - ordinary(t.program))
    stretch = 0.0
    if worst is not None:
        stretch = wall_and_gap_ms(worst) - ordinary(worst.program)
        say(f"  host.longest_stall_ms: in the traced stretch after its "
            f"first tick the slowest is tick {worst.counts.get('tick')} "
            f"({worst.program}): wall + gap {wall_and_gap_ms(worst):.3f} ms, "
            f"{stretch:.3f} above the median, of which the collector "
            f"(gc_us) {float(worst.counts.get('gc_us', 0)) / 1e3:.3f}")
    first = acc.ticks[0].counts.get("tick")
    at_fault = max(
        slow, key=lambda name: slow[name] - medians.get(name, 0.0),
        default=None)
    if (at_fault == "gap" and first is not None
            and int(first) == int(last["slow_tick"])):
        say(f"  host.longest_stall_ms: the record is the first traced tick "
            f"and its gap ({slow['gap'] / 1e3:.3f} ms): the profiler's own "
            f"start, not a stall of the system; the stretch's {stretch:.3f} "
            f"is reported, and the run's longest stall lies between it and "
            f"{value:.3f}")
        return stretch
    return value
