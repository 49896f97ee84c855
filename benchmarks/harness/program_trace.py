"""The program's own spans and counters, and the device operations named
after a scope, out of the run's profiler capture.

The program opens its spans with `rocm_apex_tpu.monitor.trace.phase`:
`jax.profiler.TraceAnnotation`s named `apex/<name>` whose counts ride as
annotation metadata. In a capture they are host events on the clock the
device planes share, beside the benchmark's `bench/` spans, and their
counts come back as the events' stats. `xplane.load` keeps neither
(only `bench/` names, no stats), and the context a metric reader is
handed holds neither the engine nor the trace's directory. So this file
finds the run's `.xplane.pb` itself: the newest under `TRACE_ROOT`,
which is `benchmarks/run.py`'s `OUT_DIR / "trace"` and which `run_cell`
empties for the cell before every traced run. It reads it once (a second
parse of the file `xplane.load` has read: the price of editing nothing),
says which file that was, takes it only if it holds a span inside the
run's traced stretch (another cell's older capture does not), and keeps
the reduced form as `context["program_trace"]`; a test puts a fixture
under that key and nothing is searched for.

The reduced form (`ProgramTrace`) holds the host events under `apex/`
and `bench/` with their stats and thread, and for the first device plane
every `XLA Ops` event as (instruction, start, duration) and every
`XLA Modules` event (one execution of a compiled program) as (name,
start, duration). It is saved and
loaded as gzipped JSON like `xplane.save_json`, so that a few ticks or
steps recorded on the chip stay beside the harness
(`fixtures/serve_phases.json.gz`, `fixtures/train_optimizer.json.gz`),
and `as_xplane` gives the same stretch as the `xplane.Trace` the older
readers take, so that both kinds of reader can be held to one recording.

Where an operation's scope is (looked at by hand, PR 24, TPU v5 lite,
jax 0.9.0; `ProfileData`, `event.stats` of the `XLA Ops` events of the
BERT step): NO stat holds it. An `XLA Ops` event has three stats,
`device_offset_ps`, `device_duration_ps` and `Time Scale Multiplier`, and
its name, the instruction's HLO text, carries no `op_name` metadata (a
Mosaic call's text ends in `frontend_attributes={kernel_metadata={}}`).
What a `jax.named_scope` leaves in the trace is the instruction NAME of
the Mosaic kernels traced under it (`%optimizer.299 = ... custom-call`),
as a flax module's scope does (`%self_attention.134`); a fusion keeps a
name made from its opcodes (`%multiply_reduce_fusion.124`) and cannot be
put down to a scope at all. So `scoped_ops` tells by the instruction's
name, and sees a scope's kernels and none of its fusions.
"""

import dataclasses
import glob
import gzip
import json
import os
import statistics

from benchmarks.harness import xplane
from benchmarks.harness.manifest import ROOT

# `rocm_apex_tpu.monitor.trace.PROGRAM_PREFIX`, spelt out: this file also
# reads captures of a program that has no such name (the parent commit)
PROGRAM_PREFIX = "apex/"
TRACE_ROOT = ROOT / ".bench_out" / "trace"


def say(*parts):
    print(*parts, flush=True)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str  # without the prefix for the program's, `bench/...` kept
    start_ns: int
    dur_ns: int
    counts: dict
    thread: str

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class ProgramTrace:
    host: list  # [Span], in start order: the program's and the benchmark's
    ops: list  # [(instruction, start_ns, dur_ns)], first device plane
    # [(program, start_ns, dur_ns)], in start order: each execution of a
    # compiled program on that plane
    modules: list = dataclasses.field(default_factory=list)
    _children: dict = dataclasses.field(
        default=None, repr=False, compare=False)

    # -- the program's spans ---------------------------------------------

    def spans(self, name, t0_ns=None, t1_ns=None):
        """The program's spans called ``name`` (no prefix) that lie
        wholly inside [t0, t1), in start order."""
        return [
            s for s in self.host
            if s.name == name
            and (t0_ns is None or s.start_ns >= t0_ns)
            and (t1_ns is None or s.end_ns <= t1_ns)
        ]

    def children(self, span):
        """The program's spans directly under ``span``: on its thread,
        inside its interval, and inside no other span that is."""
        if self._children is None:
            # one sweep per thread over the program's spans, outer
            # before inner: a span's parent is the innermost open span
            # that contains it
            self._children, threads = {}, {}
            for s in self.host:
                if not s.name.startswith(xplane.SPAN_PREFIX):
                    threads.setdefault(s.thread, []).append(s)
            for spans in threads.values():
                stack = []
                for s in sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns)):
                    while stack and stack[-1].end_ns < s.end_ns:
                        stack.pop()
                    if stack:
                        self._children.setdefault(
                            id(stack[-1]), []).append(s)
                    stack.append(s)
        return self._children.get(id(span), [])

    def self_ns(self, span):
        """The span's duration less what its children cover."""
        covered = xplane.merge_intervals(
            [(c.start_ns, c.end_ns) for c in self.children(span)])
        return span.dur_ns - xplane.total(covered)

    def ticks(self, t0_ns=None, t1_ns=None):
        """(the `engine.tick` span, {phase name: summed ns of the tick's
        children of that name}) for each tick wholly inside [t0, t1)."""
        out = []
        for tick in self.spans("engine.tick", t0_ns, t1_ns):
            phases = {}
            for c in self.children(tick):
                phases[c.name] = phases.get(c.name, 0) + c.dur_ns
            out.append((tick, phases))
        return out

    # -- the device's operations -----------------------------------------

    def scoped_ops(self, scope, t0_ns=None, t1_ns=None):
        """The operations that start inside [t0, t1) and whose
        instruction is named after ``scope`` (`%<scope>.<n>`): the
        Mosaic kernels traced under that `jax.named_scope` or module."""
        return [
            (name, s, d) for name, s, d in self.ops
            if name.lstrip("%").split(".")[0] == scope
            and (t0_ns is None or s >= t0_ns)
            and (t1_ns is None or s < t1_ns)
        ]

    # -- the same stretch for the older readers -----------------------------

    def as_xplane(self, plane=xplane.DEVICE_PREFIX + "0"):
        """An `xplane.Trace` of the same recording: the operations under
        their instruction names and the benchmark's spans."""
        host = {}
        for s in self.host:
            if s.name.startswith(xplane.SPAN_PREFIX):
                host.setdefault(s.thread, []).append(
                    (s.name, s.start_ns, s.dur_ns))
        planes = {xplane.HOST_PLANE: host}
        if self.ops:
            planes[plane] = {
                xplane.OPS_LINE: list(self.ops),
                xplane.MODULES_LINE: list(self.modules),
            }
        return xplane.Trace(planes)


# -- reading ------------------------------------------------------------------


def find_newest(root=None):
    """The newest `.xplane.pb` under ``root`` (`TRACE_ROOT`), or None."""
    files = glob.glob(os.path.join(
        str(TRACE_ROOT if root is None else root),
        "**", "plugins", "profile", "*", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path):
    """Read an `.xplane.pb` with `jax.profiler.ProfileData`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, ops, modules, first_device = [], [], [], None
    for plane in data.planes:
        if plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(PROGRAM_PREFIX):
                        counts = dict(e.stats)
                        name = name[len(PROGRAM_PREFIX):]
                    elif name.startswith(xplane.SPAN_PREFIX):
                        counts = {}
                    else:
                        continue
                    host.append(Span(
                        name, int(e.start_ns), int(e.duration_ns), counts,
                        line.name))
        elif plane.name.startswith(xplane.DEVICE_PREFIX) and (
                first_device is None or plane.name < first_device):
            first_device, ops, modules = plane.name, [], []
            for line in plane.lines:
                if line.name == xplane.OPS_LINE:
                    ops += [
                        (e.name.partition(" = ")[0], int(e.start_ns),
                         int(e.duration_ns))
                        for e in line.events
                    ]
                elif line.name == xplane.MODULES_LINE:
                    modules += [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    ]
    host.sort(key=lambda s: s.start_ns)
    modules.sort(key=lambda m: m[1])
    return ProgramTrace(host, ops, modules)


def of(context):
    """The run's `ProgramTrace`: `context["program_trace"]`, read from
    the newest capture on first use, and said which. Empty where there
    is no capture, or where the newest holds no span inside the run's
    traced stretch and so is some other run's."""
    if "program_trace" not in context:
        path = find_newest()
        pt = load(path) if path else ProgramTrace([], [])
        t0, t1 = context["t0_ns"], context["t1_ns"]
        if path is None:
            say(f"  program_trace: no capture under {TRACE_ROOT}")
        elif not any(s.start_ns >= t0 and s.end_ns <= t1 for s in pt.host):
            say(f"  program_trace: {path} holds no span inside the traced "
                "stretch, so it is not this run's: nothing read")
            pt = ProgramTrace([], [])
        else:
            say(f"  program_trace: read {path}: "
                f"{sum(not s.name.startswith(xplane.SPAN_PREFIX) for s in pt.host)}"
                f" program spans, {len(pt.ops)} operations, "
                f"{len(pt.modules)} program executions")
        context["program_trace"] = pt
    return context["program_trace"]


def clip(pt, t0_ns, t1_ns):
    """What lies wholly inside [t0, t1): how a fixture is cut to a few
    ticks or steps."""
    return ProgramTrace(
        [s for s in pt.host if s.start_ns >= t0_ns and s.end_ns <= t1_ns],
        [o for o in pt.ops if o[1] >= t0_ns and o[1] + o[2] <= t1_ns],
        [m for m in pt.modules if m[1] >= t0_ns and m[1] + m[2] <= t1_ns],
    )


def save_json(pt, path):
    with gzip.open(path, "wt") as f:
        json.dump({
            "host": [dataclasses.astuple(s) for s in pt.host],
            "ops": pt.ops,
            "modules": pt.modules,
        }, f)


def load_json(path):
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return ProgramTrace(
        [Span(*s) for s in data["host"]], [tuple(o) for o in data["ops"]],
        [tuple(m) for m in data.get("modules", [])])


# -- what the metric readers share ------------------------------------------------


def traced_ticks(context):
    """`ProgramTrace.ticks` over the traced stretch."""
    return of(context).ticks(context["t0_ns"], context["t1_ns"])


def phase_median_ms(context, names):
    """Median over the traced ticks of the time spent in the phases
    ``names`` (a tick without one counts 0 for it); None where the
    capture holds no tick of the engine's."""
    ticks = traced_ticks(context)
    if not ticks:
        return None
    per_tick = [
        sum(phases.get(n, 0) for n in names) / 1e6 for _, phases in ticks]
    value = statistics.median(per_tick)
    by_program = {}
    for (tick, _), ms in zip(ticks, per_tick):
        by_program.setdefault(tick.counts.get("program"), []).append(ms)
    say(f"  {' + '.join(names)}: median {value:.3f} ms over {len(ticks)} "
        f"ticks; by program " + ", ".join(
            f"{p} {statistics.median(v):.3f} ({len(v)})"
            for p, v in sorted(by_program.items(), key=str))
        + ("" if len(names) == 1 else "; each " + ", ".join(
            f"{statistics.median(ph.get(n, 0) for _, ph in ticks) / 1e6:.3f}"
            for n in names)))
    return value


def tick_counts(context):
    """The counters of each traced tick, or None where there is none."""
    ticks = traced_ticks(context)
    return [tick.counts for tick, _ in ticks] or None


def total(counts, key):
    """Sum of one counter over ticks; a tick without it counts 0."""
    return sum(int(c.get(key, 0)) for c in counts)


def queue_waits_ms(pt, t0_ns=None, t1_ns=None):
    """{request id: ms from its `engine.enqueue` to the start of the
    `engine.admit` inside [t0, t1) that leased it a slot}: the wait for a
    slot on the capture's one host clock, of the requests whose arrival
    the capture holds (`request_ids` are those `engine.enqueue` carries;
    a request preempted and leased again counts to its last lease)."""
    arrived = {
        int(s.counts["request_id"]): s.start_ns
        for s in pt.spans("engine.enqueue")}
    waits = {}
    for admit in pt.spans("engine.admit", t0_ns, t1_ns):
        for rid in str(admit.counts.get("request_ids", "")).split():
            if int(rid) in arrived:
                waits[int(rid)] = (admit.start_ns - arrived[int(rid)]) / 1e6
    return waits


# -- looking at a capture by hand, and cutting a fixture from it ---------------


def cut_ticks(pt, n):
    """``n`` consecutive `bench/engine.step` spans with all inside them,
    the earliest such stretch that holds a mixed tick."""
    outers = [
        s for s in pt.host if s.name == xplane.SPAN_PREFIX + "engine.step"]
    for i in range(len(outers) - n + 1):
        part = clip(pt, outers[i].start_ns, outers[i + n - 1].end_ns)
        if any(s.name == "engine.tick" and s.counts.get("program") == "mixed"
               for s in part.host):
            return part
    raise ValueError(f"no {n} consecutive ticks with a mixed one")


def cut_steps(pt, n):
    """The first ``n`` executions of the step program (every execution
    runs the same instructions, so the first one's name comes back once
    a step) with the host spans up to the ``n``-th `bench/step_dispatch`:
    the host dispatches ahead, so the two do not share a stretch."""
    first = [s for name, s, _ in pt.ops if name == pt.ops[0][0]]
    dispatches = [
        s for s in pt.host if s.name == xplane.SPAN_PREFIX + "step_dispatch"]
    return ProgramTrace(
        [s for s in pt.host if s.end_ns <= dispatches[n - 1].end_ns],
        [o for o in pt.ops if o[1] < first[n]],
        [m for m in pt.modules if m[1] < first[n]])


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="The program's spans in a capture, the stats of a few "
        "device operations, and a fixture cut from it.")
    ap.add_argument("path", nargs="?", help="an .xplane.pb (default: the "
                    "newest under benchmarks/run.py's trace directory)")
    ap.add_argument("--stats", default=None, help="print the whole name and "
                    "every stat of the first 3 XLA Ops events whose name "
                    "holds this text")
    ap.add_argument("--ticks", type=int, help="cut this many engine ticks, "
                    "one of them mixed")
    ap.add_argument("--steps", type=int, help="cut this many train steps")
    ap.add_argument("--save", help="write the cut as gzipped JSON here")
    args = ap.parse_args(argv)
    path = args.path or find_newest()
    say(f"capture {path}")
    if args.stats is not None:
        from jax.profiler import ProfileData

        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith(xplane.DEVICE_PREFIX):
                continue
            for line in plane.lines:
                shown = 0
                for e in line.events if line.name == xplane.OPS_LINE else ():
                    if args.stats in e.name and shown < 3:
                        shown += 1
                        say(f"{plane.name} {e.name}")
                        for k, v in e.stats:
                            say(f"    {k} = {v}")
            break
    pt = load(path)
    names = {}
    for s in pt.host:
        names[s.name] = names.get(s.name, 0) + 1
    say(f"host spans {names}")
    say(f"{len(pt.ops)} device operations")
    by_program = {}
    for tick, _ in pt.ticks():
        by_program.setdefault(tick.counts.get("program"), []).append(
            tick.dur_ns / 1e6)
    for program, ms in sorted(by_program.items(), key=str):
        say(f"{len(ms)} {program} ticks, median {statistics.median(ms):.3f} ms")
    if args.ticks:
        pt = cut_ticks(pt, args.ticks)
    elif args.steps:
        pt = cut_steps(pt, args.steps)
    for tick, phases in pt.ticks():
        say(f"tick {tick.counts} {tick.dur_ns / 1e6:.3f} ms: " + ", ".join(
            f"{n.split('.')[-1]} {d / 1e6:.3f}" for n, d in phases.items()))
    for rid, ms in sorted(queue_waits_ms(pt).items()):
        say(f"request {rid} waited {ms:.3f} ms for its slot")
    if args.save:
        save_json(pt, args.save)
        say(f"saved {len(pt.host)} host spans and {len(pt.ops)} operations "
            f"to {args.save}")


if __name__ == "__main__":
    main()
