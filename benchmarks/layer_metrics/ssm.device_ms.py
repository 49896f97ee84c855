"""Device time a traced tick of the operations named after the `ssm_*`
scopes: the decode grid's state update (`_hybrid.py` says what a scope's
name reaches and what it does not)."""

from benchmarks.layer_metrics import _hybrid


def read(context):
    return _hybrid.device_ms_per_tick(context, "ssm_")
