"""The flash attention kernels' share of their roofline over the traced
training steps: forward plus backward, the least time the chip could
take for the operations and bytes the mathematics needs
(`counts.attention_train_counts`, per layer and step), over the time the
kernels' events took.

The trace names an operation after its flax scope, not after the kernel's
function (`_fwd_kernel`, `_bwd_dkv_kernel`, ...), so the kernels are the
Mosaic calls whose scope holds `self_attention`, forward or transposed."""

from benchmarks.harness import counts, xplane
from benchmarks.layer_metrics import _common


def is_attention_kernel(hlo):
    return "self_attention" in hlo.split(" = ")[0]


def read(context):
    trace, peaks = context["trace"], context.get("peaks")
    if not trace.device_planes() or peaks is None:
        return None
    t0, t1 = context["t0_ns"], context["t1_ns"]
    kernels = xplane.kernel_ops(trace, is_attention_kernel, t0, t1)
    steps = len(_common.traced_spans(context, "step_dispatch"))
    if not kernels or not steps:
        return None
    secs = sum(d for _, _, d in kernels) / 1e9
    family = context["family"]
    s = family.sizes(context["config"])
    t = context["mix"]["train"]
    flops, nbytes = counts.attention_train_counts(
        int(t["batch"]) // context["chips"], s["heads"], int(t["seq"]),
        s["hidden"] // s["heads"],
        causal=family.CAUSAL)
    flops, nbytes = flops * s["layers"] * steps, nbytes * s["layers"] * steps
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
