"""Median over the traced ticks of what the tick's device program costs
the host beyond the program's own run time: from the return of
`engine.dispatch` to the return of `engine.fetch` (`jax.device_get` of
the tick's tokens) on the host's clock, less the duration of the
program's execution on the device's (its `XLA Modules` event). It is the
wait of the finished result for the host (the fetch's tail) plus the
launch's lag behind the dispatch's return, which is negative where the
device starts before the jitted call has returned.

Only durations enter, one from each clock. The bare tail, the end of
`engine.fetch` less the end of the tick's last operation, cannot be read:
the profiler aligns the device's clock with the host's differently in
every process, by up to 1.5 ms in PR 24's runs, which moves the tail one
way and the lag the other. A tick's execution is the one that holds the
middle of its `engine.fetch`: the host sits in the fetch for as long as
the program runs (41 ms and more), so a misalignment of a millisecond or
two cannot move the middle out of it. The executions' names are printed
beside the value, so that a tick matched to some other program shows.

With the four phase metrics this one sums to the tick less its program's
run time by construction, which is what `engine.tick_host_ms` reads from
outside: their agreement is an identity and no check of where the spans
lie (`tests/L0/test_engine_phases.py` checks that)."""

import bisect
import statistics

from benchmarks.harness import program_trace


def read(context):
    pt = program_trace.of(context)
    ticks = program_trace.traced_ticks(context)
    if not ticks or not pt.modules:
        return None
    starts = [start for _, start, _ in pt.modules]
    waits, programs, unmatched = [], {}, []
    for tick, _ in ticks:
        kids = pt.children(tick)
        fetch = [c for c in kids if c.name == "engine.fetch"]
        dispatch = [c for c in kids if c.name == "engine.dispatch"]
        if not fetch or not dispatch:
            continue  # the tick ran no program
        f, d = fetch[-1], dispatch[-1]
        middle = f.start_ns + f.dur_ns // 2
        i = bisect.bisect_right(starts, middle) - 1
        if i < 0 or middle >= starts[i] + pt.modules[i][2]:
            unmatched.append(
                f"tick {tick.counts.get('tick')} ({tick.counts.get('program')}"
                f", fetch {f.dur_ns / 1e6:.1f} ms)")
            continue
        name, _, run_ns = pt.modules[i]
        waits.append(((f.end_ns - d.end_ns) - run_ns) / 1e6)
        key = (tick.counts.get("program"), name.partition("(")[0])
        programs[key] = programs.get(key, 0) + 1
    if not waits:
        return None
    value = statistics.median(waits)
    program_trace.say(
        f"  engine.result_wait_ms: median {value:.3f} ms over {len(waits)} "
        f"ticks (least {min(waits):.3f}, most {max(waits):.3f}); executions "
        + ", ".join(f"{p}: {m} x{n}" for (p, m), n in sorted(
            programs.items(), key=str))
        + f"; {len(unmatched)} ticks with no execution around their fetch"
        + (": " + ", ".join(unmatched) if unmatched else ""))
    return value
