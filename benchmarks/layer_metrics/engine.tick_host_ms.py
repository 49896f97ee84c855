"""Median over the traced ticks of the wall time of `engine.step()` (the
benchmark's own span around the call) less the time the device was busy
inside that span: what the host adds to a tick."""

import statistics

from benchmarks.harness import xplane
from benchmarks.layer_metrics import _common


def read(context):
    ticks = _common.traced_spans(context, "engine.step")
    busy = _common.device_busy(context)
    if not ticks or busy is None:
        return None
    return statistics.median(
        ((e - s) - xplane.overlap(busy, s, e)) / 1e6 for s, e in ticks
    )
