"""Helpers the per-layer metric readers share. A reader is one file
named after its metric with a `read(context)` that returns the number,
or None where it finds nothing to read. The context is what the cell's
kind of run hands over after a `--trace 1` run: the reduced trace with
the traced stretch [t0_ns, t1_ns), the benchmark's host spans, the
configuration with its family's module (sizes and counts), the mix, the
chip's peaks, and the kind's own counts."""

from benchmarks.harness import xplane


def traced_spans(context, name):
    """The benchmark's spans called ``name`` that lie wholly inside the
    traced stretch, as (start_ns, end_ns)."""
    t0, t1 = context["t0_ns"], context["t1_ns"]
    return [
        (s, s + d) for n, s, d in context["trace"].host_spans()
        if n == name and s >= t0 and s + d <= t1
    ]


def device_busy(context):
    """Sorted disjoint busy intervals of the first device plane inside
    the traced stretch."""
    trace = context["trace"]
    planes = trace.device_planes()
    if not planes:
        return None
    return xplane.busy_intervals(
        trace.ops(planes[0]), context["t0_ns"], context["t1_ns"])


def idle_pct(context):
    trace = context["trace"]
    if not trace.device_planes():
        return None
    t0, t1 = context["t0_ns"], context["t1_ns"]
    return 100.0 * (1.0 - xplane.busy_seconds(trace, t0, t1) * 1e9 / (t1 - t0))
