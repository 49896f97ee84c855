"""What the readers of the hybrid model's metrics share: the device time
of the operations named after its scopes, and the operations and bytes
its two kernels need, worked out from the tick's counters. These are the
benchmark's own counts; the program reports only what it counted
(`engine.tick`'s `moe_assignments`, `moe_experts_touched`,
`moe_load_max`, `state_slots_live`, `decodes`).

The trace keeps a `jax.named_scope` only in the instruction names of the
Mosaic kernels traced under it (`harness/program_trace.py`), so a scope's
time is its kernels' time: `moe_experts` is the two grouped products of
`ops/grouped_matmul.py` (not the gather before them, the gated
activation between them or the weighted sum after them, which are
fusions), `ssm_scan` is the decode grid's state update of `ops/ssm.py`
(the packed chunk's scan is XLA's own operations: fusions and a
`ragged-dot` call that no name puts down to it). `moe_router`,
`moe_shared` and `ssm_conv` hold no kernel and read as nothing. The
counts below are therefore of what those kernels do, and no more.
"""

from benchmarks.harness import program_trace, xplane
from benchmarks.layer_metrics import _common


def scope_ops(context, prefix):
    """The operations of the traced stretch whose instruction is named
    after a scope that starts with ``prefix``, or None where the capture
    has none (a program without such scopes)."""
    t0, t1 = context["t0_ns"], context["t1_ns"]
    ops = [
        o for o in program_trace.of(context).ops
        if o[0].lstrip("%").split(".")[0].startswith(prefix)
        and t0 <= o[1] < t1
    ]
    return ops or None


def device_ms_per_tick(context, prefix):
    ops = scope_ops(context, prefix)
    ticks = _common.traced_spans(context, "engine.step")
    if not ops or not ticks:
        return None
    value = xplane.total(xplane.busy_intervals(ops)) / 1e6 / len(ticks)
    program_trace.say(
        f"  {prefix}*: {len(ops)} operations in {len(ticks)} ticks, "
        f"{value:.3f} ms a tick")
    return value


def least_seconds(flops, nbytes, peaks):
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def moe_experts_counts(assignments, experts_touched, hidden, width,
                       itemsize=2):
    """(flops, bytes) of the two grouped products for ``assignments``
    (token, expert) pairs spread over ``experts_touched`` (layer,
    expert) weight sets. Per pair: hidden -> 2 x width (gate and up) and
    width -> hidden, 2 operations a multiply-add. Bytes: each touched
    expert's three matrices once; per pair the row in, the gate and up
    halves out, the activated row in, the row out."""
    flops = assignments * 2.0 * (hidden * 2 * width + width * hidden)
    weights = experts_touched * 3.0 * hidden * width * itemsize
    rows = assignments * (hidden + 2 * width + width + hidden) * itemsize
    return flops, weights + rows


def ssm_decode_counts(slot_layers, state_dim, inner, state_itemsize=4):
    """(flops, bytes) of the decode grid's state update for
    ``slot_layers`` (live slot, layer) pairs: the state (state_dim x
    inner) read and written; per element a decay multiply, an outer
    product multiply, an add, and the multiply-add of the read-out.
    Bytes beside the state: the decay and dt*x rows (float32), B and C,
    y out."""
    elems = state_dim * inner
    flops = slot_layers * 5.0 * elems
    nbytes = slot_layers * (
        2.0 * elems * state_itemsize + 3 * inner * 4 + 2 * state_dim * 4)
    return flops, nbytes


def mamba_layers(context):
    fam = context["family"]
    return sum(1 for k in fam.layer_types(context["config"]) if k == "mamba")
