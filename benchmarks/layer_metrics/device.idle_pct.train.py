"""Share of the traced steps in which no operation ran on the device."""

from benchmarks.layer_metrics import _common


def read(context):
    return _common.idle_pct(context)
