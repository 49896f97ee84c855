"""Device time per traced optimizer step of the optimizer's KERNELS: the
operations named after the scope `optimizer`
(`rocm_apex_tpu/optimizers/mixed.py` traces its whole update and the
master-to-model cast under `jax.named_scope`): the union of those
operations' intervals over the traced stretch, over the number of
`step_dispatch` spans in it.

The trace keeps a scope only in the instruction names of the Mosaic
kernels traced under it (`harness/program_trace.py` says what was looked
at), so this is LAMB's per-leaf kernel pair and not the whole update. It
leaves out the update's fusions, which no name or stat puts down to it:
the small leaves' tree math, the master-to-model cast, and the gradient
norm, which XLA fuses into the backward pass's weight-gradient matmuls as
a second output. An update that compiles to fusions alone (as
`MixedPrecisionAdam`'s may) reads as nothing here, and one that moved
leaves from kernels into fusions would read as a gain that is none."""

from benchmarks.harness import program_trace, xplane
from benchmarks.layer_metrics import _common

SCOPE = "optimizer"


def read(context):
    steps = _common.traced_spans(context, "step_dispatch")
    ops = program_trace.of(context).scoped_ops(
        SCOPE, context["t0_ns"], context["t1_ns"])
    if not steps or not ops:
        return None
    value = xplane.total(xplane.busy_intervals(ops)) / 1e6 / len(steps)
    program_trace.say(
        f"  optimizer.kernels_device_ms: {len(ops)} operations named after `{SCOPE}` "
        f"in {len(steps)} steps, {value:.3f} ms a step")
    return value
