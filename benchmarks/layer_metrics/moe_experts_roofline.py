"""The grouped products' share of their roofline over the traced
stretch: the least time the chip's published peaks allow for the weights
of the experts touched plus the rows in and out
(`_hybrid.moe_experts_counts`, from the ticks' `moe_assignments` and
`moe_experts_touched`), over the time the operations named `moe_experts`
took. A decode tick is bound by reading the weights, a prefill chunk by
the products; a share over 100% would be a wrong count."""

from benchmarks.harness import program_trace, xplane
from benchmarks.layer_metrics import _hybrid


def read(context):
    ops = _hybrid.scope_ops(context, "moe_experts")
    counts = program_trace.tick_counts(context)
    if not ops or not counts or context.get("peaks") is None:
        return None
    pairs = program_trace.total(counts, "moe_assignments")
    touched = program_trace.total(counts, "moe_experts_touched")
    if not touched:
        return None
    s = context["family"].sizes(context["config"])
    flops, nbytes = _hybrid.moe_experts_counts(
        pairs, touched, s["hidden"], s["expert_width"])
    secs = xplane.total(xplane.busy_intervals(ops)) / 1e9
    least = _hybrid.least_seconds(flops, nbytes, context["peaks"])
    program_trace.say(
        f"  moe_experts_roofline: {pairs} pairs on {touched} (layer, expert) "
        f"weight sets in {len(counts)} ticks: {flops / 1e9:.1f} GFLOP, "
        f"{nbytes / 1e9:.2f} GB, least {1e3 * least:.2f} ms of "
        f"{1e3 * secs:.2f} ms")
    return 100.0 * least / secs
