"""Device-busy time per optimizer step over the traced steps."""

from benchmarks.harness import xplane
from benchmarks.layer_metrics import _common


def read(context):
    steps = _common.traced_spans(context, "step_dispatch")
    busy = _common.device_busy(context)
    if not steps or busy is None:
        return None
    return xplane.total(busy) / 1e6 / len(steps)
