"""Device-busy time per engine tick over the traced stretch."""

from benchmarks.harness import xplane
from benchmarks.layer_metrics import _common


def read(context):
    ticks = _common.traced_spans(context, "engine.step")
    busy = _common.device_busy(context)
    if not ticks or busy is None:
        return None
    return xplane.total(busy) / 1e6 / len(ticks)
