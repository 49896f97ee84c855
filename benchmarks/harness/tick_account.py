"""The tick's account: each traced `engine.tick` with its phases, its
execution on the device and the counts the engine keeps of itself, and
from them one clock for host and device, the bubble between two programs
split by phase, and the tick's time by program.

The engine's tick is synchronous: it packs, dispatches ONE program,
fetches its result and commits, and only then packs the next. So between
the end of one execution and the start of the next the device waits for
the host, and that bubble is the tick's host time. `xplane.idle_gaps`
puts all of it under the benchmark's one span, `engine.step`; here it is
put down to the program's own phases.

**The join.** A tick's execution is the `XLA Modules` event that lies
for more than half its length between the entry of the tick's
`engine.dispatch` and the return of its `engine.fetch`: the host sits
there for as long as the program runs, so a misalignment of a millisecond
or two cannot move a program of 15 ms and more out of it. (Where nothing
stalls the host this is the join `engine.result_wait_ms` makes, the
execution that holds the middle of the fetch; a pause of 100 ms inside
the fetch moves its middle past the program's end, and the stalled tick
is the one to keep.) A tick matched to none is counted and named.

**One clock from causality.** The profiler aligns the device's clock with
the host's only to about 1.5 ms, a third to a half of the host's share of
a tick. But a program cannot start before its `engine.dispatch` was
entered, and `engine.fetch` cannot return before the program ended. So
the offset d to ADD to a device time to get the host's satisfies, over
the ticks of the capture,

    max_i(dispatch_i.start - exec_i.start) <= d <= min_i(fetch_i.end - exec_i.end)

and the interval is as wide as the least launch lag plus the least fetch
tail over the stretch. Counted from `engine.dispatch`'s ENTRY the lag
holds the jitted call's own argument path, a millisecond that no tick
escapes, so these two bounds alone leave d open by 1.8-2.5 ms (my chip
runs, PR 37). The runtime's own events close it: the program cannot start
before the runtime put it on the device's queue (`LAUNCH`) nor the runtime's
wait for the device (`DONE`) return before it ended. A capture that holds
them (`load_marks`; a fixture keeps them among its host spans) bounds d by
them as well, to some 0.05 ms; one that does not, or whose events
contradict causality, is bounded by the two spans alone, and then the tail
and the lag are printed as ONE part (`shown_parts`): their sum is exact,
their split would be the interval's middle and no reading. The middle
is taken; an interval empty by more than `EMPTY_NS` (drift, or ticks
matched to the wrong executions) is read as no clock at all.

**The bubble and its parts.** Bubble of tick i = exec_i.start -
exec_(i-1).end, on the device's clock alone. On the aligned clock it is
tiled, in order, by: `fetch_tail` (from the end of execution i-1 to the
return of its `engine.fetch`), `commit` of tick i-1, `rest_after` (what
else of that tick lies after its fetch), `loop_gap` (from that tick's end
to the next one's start: the serving loop's own time, which the engine
counts from inside as `gap_us`), then `admit`, `pack`, `table_push` and
`rest_before` of tick i up to the entry of its `engine.dispatch`, and
`launch_lag` (from that entry to the start of execution i). The parts sum
to the bubble whatever d is; d only moves time between the tail and the
lag. A `host.gc` span (a pause of Python's collector) is shared out among
the parts it overlaps.

Everything is computed once a run and kept as `context["tick_account"]`.
Counts that the parent commit's engine does not keep (`gap_us`, the
`cum_*` totals, the `slow_*` record) read as None there and nothing is
raised.
"""

import bisect
import dataclasses
import statistics

from benchmarks.harness import program_trace, xplane
from benchmarks.harness.stats import percentile

EMPTY_NS = 50_000
# The TPU runtime's own host events that lie nearest a program's start
# and end (libtpu 0.0.34 under jax 0.9.0; every host event of a capture
# set against the executions by hand, PR 37): `DoEnqueueProgram`, on the
# runtime's queue thread, puts the program on the device's queue some
# 0.2 ms before it starts (`tpu::System::Execute`, the call that asks for
# it on the caller's thread, begins 0.25 ms earlier still), and
# `ReadSyncFlag`, on the thread that waits for the device, begins 0.05-0.1
# ms after it ends (the completion callback, `tpu::System::Execute=>Done`,
# 0.25 ms later). Neither can come on the wrong side of the program.
LAUNCH, DONE = "DoEnqueueProgram", "ReadSyncFlag"
PARTS = ("fetch_tail", "commit", "rest_after", "loop_gap", "admit", "pack",
         "table_push", "rest_before", "launch_lag")
PHASES = ("admit", "pack", "table_push", "dispatch", "fetch", "commit")


@dataclasses.dataclass
class Tick:
    span: object  # the `engine.tick` span
    kids: list  # its phase spans, in start order
    execution: tuple = None  # (program, start_ns, dur_ns) on the device
    launch: object = None  # the runtime's `LAUNCH` event after the dispatch's entry
    done: object = None  # its `DONE` event after that, before the fetch returns

    @property
    def counts(self):
        return self.span.counts

    @property
    def program(self):
        return self.span.counts.get("program")

    def last(self, name):
        """The tick's last phase span called `engine.<name>`, or None."""
        for kid in reversed(self.kids):
            if kid.name == "engine." + name:
                return kid
        return None


@dataclasses.dataclass
class Bubble:
    before: Tick
    after: Tick
    # [(part, start_ns, end_ns)] on the host's clock, tiling the bubble
    # from the aligned end of one execution to the aligned start of the next
    segments: list

    @property
    def ns(self):
        return self.after.execution[1] - (
            self.before.execution[1] + self.before.execution[2])

    def fine(self):
        """Where the runtime's events are there, the tail and the lag
        each in two: the program's end to the moment the runtime sees
        it and on to the fetch's return; the dispatch's entry to the
        program's enqueueing and on to its start."""
        out = {}
        tail, lag = self.segments[0], self.segments[-1]
        if self.before.done is not None:
            out["end_to_seen"] = self.before.done.start_ns - tail[1]
            out["seen_to_return"] = tail[2] - self.before.done.start_ns
        if self.after.launch is not None:
            out["entry_to_enqueue"] = self.after.launch.start_ns - lag[1]
            out["enqueue_to_start"] = lag[2] - self.after.launch.start_ns
        return out

    def parts(self):
        out = dict.fromkeys(PARTS, 0)
        for part, start, end in self.segments:
            out[part] += end - start
        return out


@dataclasses.dataclass
class Account:
    ticks: list  # [Tick], every traced tick in start order
    unmatched: list  # descriptions of the ticks that ran a program and joined none
    clock: tuple  # (lo_ns, hi_ns) of the offset's interval, or None
    bounds: str  # what bound the interval: the runtime's events or the spans
    # whether the runtime's events bound it: only then is the clock tight
    # enough to read `launch_lag` and `fetch_tail` apart (`shown_parts`)
    split: bool
    bubbles: list  # [Bubble]; empty where there is no clock
    pauses: list  # the `host.gc` spans of the traced stretch

    @property
    def offset_ns(self):
        return None if self.clock is None else sum(self.clock) // 2


# -- the join and the clock ------------------------------------------------------


def join(pt, t0_ns=None, t1_ns=None, marks=None):
    """([Tick], [description of each tick left without an execution]).
    ``marks``: the runtime's `LAUNCH` and `DONE` events (default: those
    among the capture's host spans)."""
    starts = [start for _, start, _ in pt.modules]
    if marks is None:
        marks = [s for s in pt.host if s.name in (LAUNCH, DONE)]
    mark_starts = [m.start_ns for m in marks]
    ticks, unmatched = [], []
    for span in pt.spans("engine.tick", t0_ns, t1_ns):
        kids = [
            k for k in pt.children(span)
            if k.name.partition(".")[2] in PHASES]
        tick = Tick(span, kids)
        ticks.append(tick)
        fetch = tick.last("fetch")
        if fetch is None or tick.last("dispatch") is None:
            continue  # the tick ran no program
        dispatch = tick.last("dispatch")
        first = max(0, bisect.bisect_right(starts, dispatch.start_ns) - 1)
        held = [
            m for m in pt.modules[first:bisect.bisect_left(
                starts, fetch.end_ns)]
            if 2 * (min(fetch.end_ns, m[1] + m[2])
                    - max(dispatch.start_ns, m[1])) > m[2]]
        if held:
            tick.execution = held[-1]
            inside = marks[bisect.bisect_left(mark_starts, dispatch.start_ns):
                           bisect.bisect_right(mark_starts, fetch.end_ns)]
            for m in inside:
                if m.name == LAUNCH:
                    tick.launch = m
                elif tick.launch is not None:
                    tick.done = m
        else:
            unmatched.append(
                f"tick {span.counts.get('tick')} ({tick.program}, fetch "
                f"{fetch.dur_ns / 1e6:.1f} ms)")
    return ticks, unmatched


def clock_interval(ticks, runtime=True):
    """(lo_ns, hi_ns) of the offset that takes a device time to the
    host's clock, from the joined ticks; None where none is joined.
    ``runtime=False`` leaves the runtime's events out."""
    lo = hi = None
    for t in ticks:
        if t.execution is None:
            continue
        _, start, dur = t.execution
        entered = t.last("dispatch").start_ns
        returned = t.last("fetch").end_ns
        if runtime and t.launch is not None:
            entered = t.launch.start_ns
        if runtime and t.done is not None:
            returned = t.done.start_ns
        lo = entered - start if lo is None else max(lo, entered - start)
        hi = (returned - (start + dur) if hi is None
              else min(hi, returned - (start + dur)))
    return None if lo is None else (lo, hi)


def bubble_between(before, after, offset_ns):
    """The bubble between two consecutive joined ticks, tiled by part on
    the host's clock with the device's times moved by ``offset_ns``."""
    _, start, dur = before.execution
    fetch, dispatch = before.last("fetch"), after.last("dispatch")
    segments = [("fetch_tail", start + dur + offset_ns, fetch.end_ns)]
    at = fetch.end_ns
    for kid in before.kids:
        if kid.start_ns >= at and kid.name == "engine.commit":
            segments.append(("rest_after", at, kid.start_ns))
            segments.append(("commit", kid.start_ns, kid.end_ns))
            at = kid.end_ns
    segments.append(("rest_after", at, before.span.end_ns))
    segments.append(("loop_gap", before.span.end_ns, after.span.start_ns))
    at = after.span.start_ns
    for kid in after.kids:
        part = kid.name.partition(".")[2]
        if kid.end_ns <= dispatch.start_ns and part in (
                "admit", "pack", "table_push"):
            segments.append(("rest_before", at, kid.start_ns))
            segments.append((part, kid.start_ns, kid.end_ns))
            at = kid.end_ns
    segments.append(("rest_before", at, dispatch.start_ns))
    segments.append(
        ("launch_lag", dispatch.start_ns, after.execution[1] + offset_ns))
    return Bubble(before, after, segments)


def load_marks(path):
    """The runtime's `LAUNCH` and `DONE` events out of an `.xplane.pb`
    (host events on the runtime's own threads, which
    `program_trace.load` does not keep), as `Span`s in start order."""
    from jax.profiler import ProfileData

    marks = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            marks += [
                program_trace.Span(
                    e.name, int(e.start_ns), int(e.duration_ns), {}, line.name)
                for e in line.events if e.name in (LAUNCH, DONE)]
    return sorted(marks, key=lambda s: s.start_ns)


def consecutive(a, b):
    """Two ticks with nothing of the engine's between them: the tick
    numbers follow on (a capture of the parent's engine carries them
    too), on one thread."""
    return (a.span.thread == b.span.thread
            and int(b.counts.get("tick", -1)) == int(a.counts.get("tick", -3)) + 1)


def build(pt, t0_ns=None, t1_ns=None, marks=None):
    ticks, unmatched = join(pt, t0_ns, t1_ns, marks)
    marked = sum(t.launch is not None and t.done is not None for t in ticks)
    # the bounds to try, the tightest first: the first whose interval is
    # not empty by more than `EMPTY_NS` is the clock
    candidates = [(False, "the dispatch's entry and the fetch's return")]
    if marked:
        candidates.insert(0, (
            True, f"the runtime's {LAUNCH} and {DONE} events of {marked} ticks"))
    clock, bounds, split = None, None, False
    for runtime, by in candidates:
        interval = clock_interval(ticks, runtime)
        if interval is None:
            break  # no tick joined to an execution
        if interval[1] - interval[0] >= -EMPTY_NS:
            clock, bounds, split = interval, by, runtime
            break
        program_trace.say(
            f"  tick_account: bounded by {by} the clock's interval is "
            f"empty by {(interval[0] - interval[1]) / 1e3:.1f} us (events "
            "that are not what they were taken for, drift, or ticks joined "
            "to the wrong executions): not read")
    bubbles = []
    if clock is not None:
        offset = sum(clock) // 2
        bubbles = [
            bubble_between(a, b, offset) for a, b in zip(ticks, ticks[1:])
            if a.execution is not None and b.execution is not None
            and consecutive(a, b)]
    return Account(
        ticks, unmatched, clock, bounds, split, bubbles,
        pt.spans("host.gc", t0_ns, t1_ns))


def of(context):
    """The run's `Account`, built on first use and said once."""
    if "tick_account" not in context:
        pt, marks = program_trace.of(context), None
        if "trace" in context and pt.host and not any(
                s.name == LAUNCH for s in pt.host):
            # a run's own capture (`run_cell` hands over its `trace`):
            # `program_trace.load` kept none of the runtime's events
            path = program_trace.find_newest()
            marks = load_marks(path) if path else None
        acc = build(pt, context["t0_ns"], context["t1_ns"], marks)
        context["tick_account"] = acc
        if acc.ticks:
            joined = sum(t.execution is not None for t in acc.ticks)
            program_trace.say(
                f"  tick_account: {len(acc.ticks)} ticks, {joined} joined "
                f"to an execution, {len(acc.unmatched)} with none around "
                "their fetch" + (
                    ": " + ", ".join(acc.unmatched[:5]) + (
                        " ..." if len(acc.unmatched) > 5 else "")
                    if acc.unmatched else "")
                + "; " + clock_line(acc) + f" (read under {versions()}; "
                f"{LAUNCH} and {DONE} are libtpu 0.0.34's names)")
    return context["tick_account"]


def versions():
    """"jax X, libtpu Y" of this process: `LAUNCH` and `DONE` are names
    of one runtime's internals, so the line that says what bound the
    clock says under which versions it was read."""
    from importlib import metadata

    def version(package):
        try:
            return metadata.version(package)
        except metadata.PackageNotFoundError:
            return "not installed"

    return f"jax {version('jax')}, libtpu {version('libtpu')}"


def clock_line(acc):
    if acc.clock is None:
        return "no clock"
    lo, hi = acc.clock
    return (f"device clock + d = host clock with d in [{lo / 1e3:.1f}, "
            f"{hi / 1e3:.1f}] us, width {(hi - lo) / 1e3:.1f} us, bounded "
            f"by {acc.bounds}; middle {acc.offset_ns / 1e3:.1f} us taken"
            + ("" if acc.split else
               ": too wide to read launch_lag and fetch_tail apart, they "
               "are given as one part"))


def shown_parts(acc, parts):
    """[(name, [ns of each bubble])] of the bubbles' parts (``parts``:
    one `Bubble.parts()` a bubble) as a reader prints them: all nine
    where the runtime's events bound the clock; else `fetch_tail` and
    `launch_lag` as their sum, which no clock moves, because the middle
    of an interval a millisecond wide makes the two equal by
    construction."""
    if acc.split:
        return [(p, [x[p] for x in parts]) for p in PARTS]
    both = [x["fetch_tail"] + x["launch_lag"] for x in parts]
    return [("fetch_tail+launch_lag", both)] + [
        (p, [x[p] for x in parts]) for p in PARTS[1:-1]]


# -- what the readers share ----------------------------------------------------------


def ms(ns_values, q=None):
    """Median (or the q-th percentile) of nanoseconds, in ms."""
    values = [v / 1e6 for v in ns_values]
    return statistics.median(values) if q is None else percentile(values, q)


def last_counts(acc):
    """The counts of the last traced tick: it carries the run's totals
    since `reset_stats`. None where the capture holds no tick."""
    return acc.ticks[-1].counts if acc.ticks else None


def number(counts, key):
    """One count as a float, or None where the engine keeps no such
    count (the parent commit's)."""
    return None if counts is None or key not in counts else float(counts[key])


def program_ms(context, program, what):
    """`tick.<program>_device_ms` and `tick.<program>_host_ms`: median
    over the joined ticks of that program of the execution's duration
    (``what == "device"``) or of the tick's wall less it (``"host"``)."""
    acc = of(context)
    ticks = [
        t for t in acc.ticks if t.program == program and t.execution is not None]
    if not ticks:
        if acc.ticks:
            program_trace.say(
                f"  tick.{program}_{what}_ms: no {program} tick joined to "
                f"an execution among {len(acc.ticks)} traced ticks")
        return None
    device = [t.execution[2] for t in ticks]
    values = device if what == "device" else [
        t.span.dur_ns - d for t, d in zip(ticks, device)]
    names = sorted({t.execution[0].partition("(")[0] for t in ticks})
    line = (f"  tick.{program}_{what}_ms: median {ms(values):.3f} ms over "
            f"{len(values)} ticks (10th {ms(values, 10):.3f}, 90th "
            f"{ms(values, 90):.3f}); executions {', '.join(names)}")
    if what == "device":
        passes = sorted({t.counts.get("model_passes") for t in ticks}, key=str)
        used = program_trace.total([t.counts for t in ticks], "chunk_tokens")
        budget = program_trace.total([t.counts for t in ticks], "budget")
        line += f"; model_passes {passes}" + (
            f"; chunk_tokens {used} of budget {budget} "
            f"({100.0 * used / budget:.1f}%)" if used and budget else "")
    else:
        line += f"; the tick's wall: median {ms([t.span.dur_ns for t in ticks]):.3f} ms"
    program_trace.say(line)
    return ms(values)


def pause_share(acc, bubble):
    """{part: ns of `host.gc` spans inside that part of the bubble}, 0
    where none fell."""
    out = dict.fromkeys(PARTS, 0)
    for part, start, end in bubble.segments:
        for p in acc.pauses:
            out[part] += max(0, min(end, p.end_ns) - max(start, p.start_ns))
    return out
