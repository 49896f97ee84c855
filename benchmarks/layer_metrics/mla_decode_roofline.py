"""The paged latent decode kernel's share of its roofline over the traced
stretch: the least time the chip's published peaks allow for the latent
rows the decode grid attended over (`latent_rows_read`, summed by the
program over live slots and attention blocks) plus the queries in and
the weighted latents out (`_latent.mla_decode_counts`), over the time the
operations named `mla_decode` took. 64 heads read one shared row, 121
operations a byte: under the chip's ridge of 240, so the bytes bind; a
share over 100% would be a wrong count."""

from benchmarks.harness import program_trace, xplane
from benchmarks.layer_metrics import _hybrid, _latent


def read(context):
    ops = _hybrid.scope_ops(context, "mla_decode")
    counts = program_trace.tick_counts(context)
    if not ops or not counts or context.get("peaks") is None:
        return None
    rows = program_trace.total(counts, "latent_rows_read")
    if not rows:
        return None
    s = context["family"].sizes(context["config"])
    queries = program_trace.total(counts, "decodes") * _latent.attention_blocks(
        context)
    flops, nbytes = _latent.mla_decode_counts(
        rows, queries, s["heads"], s["kv_rank"], s["rope"])
    secs = xplane.total(xplane.busy_intervals(ops)) / 1e9
    least = _hybrid.least_seconds(flops, nbytes, context["peaks"])
    program_trace.say(
        f"  mla_decode_roofline: {rows} latent rows read by {queries} "
        f"(row, block) queries in {len(counts)} ticks: {flops / 1e9:.1f} "
        f"GFLOP, {nbytes / 1e9:.2f} GB, least {1e3 * least:.2f} ms of "
        f"{1e3 * secs:.2f} ms")
    return 100.0 * least / secs
