"""From the profiler's `.xplane.pb` to busy and idle time, per-kernel time
and the idle gaps labelled by what the host was doing.

`load` turns the file into plain data (`Trace`): for every plane its
lines, for every line its events as (name, start_ns, duration_ns). The
reductions work on that, so they are tested on a trace recorded on the
chip and kept beside this file as JSON (`fixtures/`), and the same code
reads every later run.

What a TPU trace of this JAX looks like (looked at by hand, PR 23, TPU
v5 lite, jax 0.9.0): planes `/device:TPU:<n>` hold the lines `XLA
Modules` (one event per executed program, named `jit_<fn>(<hash>)`),
`XLA Ops` (one event per HLO operation or Mosaic kernel: this is what "an
operation ran on the device" means here), `Async XLA Ops` (copies in
flight, which overlap the ops and are not counted as busy) and, for a
program with a step marker, `Steps`. An op's name is its whole HLO text,
`%self_attention.134 = (bf16[...]) custom-call(...),
custom_call_target="tpu_custom_call", ...`: the instruction is named
after the flax module's scope, NOT after the Pallas kernel's function, so
a kernel is told by `tpu_custom_call`, its scope and its operand shapes
(`kernel_ops`). `/host:CPU` holds one line per host thread, and the
benchmark's `jax.profiler.TraceAnnotation` spans are events on the
`python3` line under the names given them (`bench/...`). All planes share
one clock, in nanoseconds from the start of the trace.
"""

import dataclasses
import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"


@dataclasses.dataclass
class Trace:
    # {plane name: {line name: [(event name, start_ns, duration_ns)]}}
    planes: dict

    def device_planes(self):
        return sorted(p for p in self.planes if p.startswith(DEVICE_PREFIX))

    def ops(self, plane):
        return self.planes[plane].get(OPS_LINE, [])

    def modules(self, plane):
        return self.planes[plane].get(MODULES_LINE, [])

    def host_spans(self):
        """The benchmark's own spans, (name without prefix, start, dur),
        in start order."""
        out = []
        for events in self.planes.get(HOST_PLANE, {}).values():
            out += [
                (n[len(SPAN_PREFIX):], s, d) for n, s, d in events
                if n.startswith(SPAN_PREFIX)
            ]
        return sorted(out, key=lambda e: e[1])


def find_xplane(trace_dir):
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """Read an `.xplane.pb` with `jax.profiler.ProfileData`. Device
    planes keep their `XLA Ops` and `XLA Modules` lines; the host plane
    keeps only the benchmark's spans (the rest is the runtime's own
    threads)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    ]
            planes[name] = lines
        elif name == HOST_PLANE:
            lines = {}
            for line in plane.lines:
                events = [
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                ]
                if events:
                    lines[line.name] = events
            planes[name] = lines
    return Trace(planes)


def load_traced_stretch(trace_dir):
    """(trace, t0_ns, t1_ns) of a run's profile: the stretch from the
    first to the last of the benchmark's own spans."""
    trace = load(find_xplane(trace_dir))
    spans = trace.host_spans()
    if not spans:
        raise RuntimeError("the trace holds none of the benchmark's spans")
    return trace, spans[0][1], max(s + d for _, s, d in spans)


def save_json(trace, path):
    with gzip.open(path, "wt") as f:
        json.dump(trace.planes, f)


def load_json(path):
    with gzip.open(path, "rt") as f:
        planes = json.load(f)
    return Trace({
        p: {l: [tuple(e) for e in ev] for l, ev in lines.items()}
        for p, lines in planes.items()
    })


def clip(trace, t0_ns, t1_ns):
    """The events that lie wholly inside [t0, t1): how a fixture is
    trimmed to a few ticks or steps."""
    return Trace({
        p: {
            l: [e for e in ev if e[1] >= t0_ns and e[1] + e[2] <= t1_ns]
            for l, ev in lines.items()
        }
        for p, lines in trace.planes.items()
    })


# -- reductions ---------------------------------------------------------


def merge_intervals(intervals):
    """Union of [start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_intervals(events, t0_ns=None, t1_ns=None):
    """Union of the events' intervals, cut to [t0, t1)."""
    ivs = []
    for _, s, d in events:
        e = s + d
        if t0_ns is not None:
            s = max(s, t0_ns)
        if t1_ns is not None:
            e = min(e, t1_ns)
        if e > s:
            ivs.append((s, e))
    return merge_intervals(ivs)


def total(intervals):
    return sum(e - s for s, e in intervals)


def overlap(intervals, s, e):
    """Length of [s, e) covered by sorted disjoint ``intervals``."""
    return sum(
        max(0, min(e, b) - max(s, a)) for a, b in intervals
        if b > s and a < e
    )


def window_of(trace):
    """[first op start, last op end) over the device planes."""
    starts, ends = [], []
    for p in trace.device_planes():
        for _, s, d in trace.ops(p):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_seconds(trace, t0_ns, t1_ns):
    """Seconds in [t0, t1) in which an operation ran on the device,
    averaged over the device planes."""
    planes = trace.device_planes()
    if not planes:
        raise ValueError("the trace holds no device plane")
    return sum(
        total(busy_intervals(trace.ops(p), t0_ns, t1_ns)) for p in planes
    ) / len(planes) / 1e9


_OPCODE = re.compile(r"[ )]([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"= \(?([a-z0-9]+\[[\d,]*\])")


def short_name(full):
    """`%self_attention.134 = (bf16[2048,16,128]{...}, ...) custom-call(...`
    -> `self_attention custom-call bf16[2048,16,128]`: the instruction's
    scope without its number, its opcode and its first result shape, so
    that the same operation in every layer and step sums under one name."""
    head, _, rest = full.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    if not rest:
        return base
    opcode = _OPCODE.search(" " + rest)
    shape = _SHAPE.search("= " + rest)
    return " ".join(
        x for x in (base, opcode.group(1) if opcode else "",
                    shape.group(1) if shape else "") if x)


def in_window(events, t0_ns=None, t1_ns=None):
    return [
        e for e in events
        if (t0_ns is None or e[1] >= t0_ns) and (t1_ns is None or e[1] < t1_ns)
    ]


def kernel_ops(trace, match, t0_ns=None, t1_ns=None):
    """The Mosaic kernels (`tpu_custom_call`) on the first device plane
    that start inside [t0, t1) and whose HLO text ``match`` accepts."""
    plane = trace.device_planes()[0]
    return [
        e for e in in_window(trace.ops(plane), t0_ns, t1_ns)
        if "tpu_custom_call" in e[0] and match(e[0])
    ]


def top_ops(trace, t0_ns, t1_ns, n=10):
    """The n operations that took most device time in [t0, t1) on the
    first device plane, summed under their short names."""
    per = {}
    for name, _, d in in_window(
            trace.ops(trace.device_planes()[0]), t0_ns, t1_ns):
        key = short_name(name)
        per[key] = per.get(key, 0.0) + d / 1e9
    return [
        [name, secs] for name, secs in
        sorted(per.items(), key=lambda kv: -kv[1])[:n]
    ]


def label_timeline(spans):
    """Nested (start, end, name) spans as a sorted disjoint list in which
    every stretch carries the innermost span that covers it."""
    out, stack, cursor = [], [], None

    def close(top):
        nonlocal cursor
        if top[1] > cursor:
            out.append((cursor, top[1], top[2]))
            cursor = top[1]

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack and s > cursor:
            out.append((cursor, s, stack[-1][2]))
        cursor = s
        stack.append((s, e, name))
    while stack:
        close(stack.pop())
    return out


def idle_gaps(trace, t0_ns, t1_ns, n=10):
    """The device's idle time in [t0, t1) on the first device plane,
    shared out among the benchmark's host spans: every stretch of a gap
    goes to the innermost span that covers it, or to "(no span)". The n
    largest sums as [name, seconds]."""
    plane = trace.device_planes()[0]
    busy = busy_intervals(trace.ops(plane), t0_ns, t1_ns)
    gaps, at = [], t0_ns
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = e
    if at < t1_ns:
        gaps.append((at, t1_ns))
    labels = label_timeline(
        [(s, s + d, name) for name, s, d in trace.host_spans()])
    sums, i = {}, 0
    for gs, ge in gaps:
        while i < len(labels) and labels[i][1] <= gs:
            i += 1
        j, covered = i, 0
        while j < len(labels) and labels[j][0] < ge:
            part = min(ge, labels[j][1]) - max(gs, labels[j][0])
            sums[labels[j][2]] = sums.get(labels[j][2], 0.0) + part / 1e9
            covered += part
            j += 1
        if ge - gs > covered:
            sums["(no span)"] = sums.get("(no span)", 0.0) + (ge - gs - covered) / 1e9
    return [
        [name, secs] for name, secs in
        sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    ]


def exposed_seconds(trace, is_collective, t0_ns, t1_ns):
    """Seconds in [t0, t1) on the first device plane in which a
    collective operation runs and no other operation does."""
    plane = trace.device_planes()[0]
    coll = busy_intervals(
        [e for e in trace.ops(plane) if is_collective(e[0])], t0_ns, t1_ns)
    comp = busy_intervals(
        [e for e in trace.ops(plane) if not is_collective(e[0])], t0_ns, t1_ns)
    return sum((e - s) - overlap(comp, s, e) for s, e in coll) / 1e9
