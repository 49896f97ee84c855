"""Device time a traced tick of the paged attention kernels of the
window/global model, the operations named after its four `attn_*`
scopes (`_windowed.py` says what each reaches), printed by scope."""

from benchmarks.harness import program_trace, xplane
from benchmarks.layer_metrics import _common, _hybrid, _windowed


def read(context):
    ticks = _common.traced_spans(context, "engine.step")
    by_scope = {
        scope: _hybrid.scope_ops(context, scope) for scope in _windowed.SCOPES}
    ops = [o for found in by_scope.values() for o in found or ()]
    if not ops or not ticks:
        return None
    for scope, found in by_scope.items():
        ms = xplane.total(xplane.busy_intervals(found or [])) / 1e6
        program_trace.say(
            f"  {scope}: {len(found or ())} operations, "
            f"{ms / len(ticks):.3f} ms a tick")
    return xplane.total(xplane.busy_intervals(ops)) / 1e6 / len(ticks)
