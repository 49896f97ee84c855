"""The decode grid's state update's share of its roofline over the
traced stretch: the least time the chip's published peaks allow for
reading and writing the state of every live decode row in every Mamba
layer (`_hybrid.ssm_decode_counts`; the rows are the ticks' `decodes`,
which `state_slots_live` holds together with the slots the packed chunk
advanced), over the time the operations named `ssm_scan` took. It is
bound by memory: 5 operations per 8 bytes.

NOT covered: the packed chunk's scan. It runs as XLA's own operations,
whose names keep no scope in a TPU trace, so neither its time nor its
bytes are in this share (nor in `ssm.device_ms`), although the mixed
tick it belongs to is the one that sets both tails (PERF.md section
7)."""

from benchmarks.harness import program_trace, xplane
from benchmarks.layer_metrics import _hybrid


def read(context):
    ops = _hybrid.scope_ops(context, "ssm_scan")
    counts = program_trace.tick_counts(context)
    if not ops or not counts or context.get("peaks") is None:
        return None
    rows = program_trace.total(counts, "decodes")
    live = program_trace.total(counts, "state_slots_live")
    if not rows:
        return None
    s = context["family"].sizes(context["config"])
    layers = _hybrid.mamba_layers(context)
    flops, nbytes = _hybrid.ssm_decode_counts(
        rows * layers, s["m_n"], s["m_inner"])
    secs = xplane.total(xplane.busy_intervals(ops)) / 1e9
    least = _hybrid.least_seconds(flops, nbytes, context["peaks"])
    program_trace.say(
        f"  ssm_scan_roofline: {rows} decode rows x {layers} layers "
        f"(state_slots_live {live}: {live - rows} advanced by a chunk) in "
        f"{len(counts)} ticks: {nbytes / 1e9:.2f} GB, least "
        f"{1e3 * least:.2f} ms of {1e3 * secs:.2f} ms")
    return 100.0 * least / secs
