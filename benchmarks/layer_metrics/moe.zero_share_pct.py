"""The share of (token, expert) pairs that fell on zero-compute experts
over the traced stretch: `moe_zero_assignments` over top-k x layers x the
rows routed (`decodes` + `chunk_tokens` of each tick). A third of the
router's outputs are zero experts, so an even router reads about 33; the
held experts' work a token falls as it rises."""

from benchmarks.harness import program_trace


def read(context):
    counts = program_trace.tick_counts(context)
    if not counts or not any("moe_zero_assignments" in c for c in counts):
        return None
    s = context["family"].sizes(context["config"])
    rows = program_trace.total(counts, "decodes") + program_trace.total(
        counts, "chunk_tokens")
    if not rows:
        return None
    zero = program_trace.total(counts, "moe_zero_assignments")
    program_trace.say(
        f"  moe.zero_share_pct: {zero} pairs on zero experts of "
        f"{s['top_k']} x {s['layers']} layers x {rows} rows in "
        f"{len(counts)} ticks")
    return 100.0 * zero / (s["top_k"] * s["layers"] * rows)
