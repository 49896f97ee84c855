"""The paged decode attention kernel's share of its roofline over the
traced stretch: the least time the chip could take for the K/V bytes the
kernel has to read (it is bound by memory: 4 operations per 4 bytes),
over the time its events took.

Bytes: in every traced tick each layer's kernel reads the cached K and V
rows of the slots in flight, which is the pages in use at the tick's
start (`engine.pages_used`, read by the benchmark before each
`step()`), a page being `page_size` positions of `2 * hidden` values.

The trace names an operation after its flax scope, not after the kernel's
function (`_decode_paged_kernel`), so the kernel is told by what only it
has: a Mosaic call in `self_attention` whose first operand is the page
table, `s32[slots, pages_per_slot]`. That covers both of its uses in a
tick, the decode grid and the prefill chunk read against the cache.
"""

import re

from benchmarks.harness import counts, xplane
from benchmarks.layer_metrics import _common

_PAGE_TABLE_FIRST = re.compile(r"custom-call\(s32\[\d+,\d+\]")


def is_paged_kernel(hlo):
    return "self_attention" in hlo and bool(_PAGE_TABLE_FIRST.search(hlo))


def read(context):
    trace = context["trace"]
    if not trace.device_planes() or context.get("peaks") is None:
        return None
    t0, t1 = context["t0_ns"], context["t1_ns"]
    kernels = xplane.kernel_ops(trace, is_paged_kernel, t0, t1)
    if not kernels:
        return None
    secs = sum(d for _, _, d in kernels) / 1e9
    # the k-th traced tick is the k-th `engine.step` span the profiler saw
    n_traced = len(_common.traced_spans(context, "engine.step"))
    p = context["profiler"]
    traced = [
        pages for s, e, pages in context["ticks"]
        if s >= p["t_start"] and e <= p["t_stop"]
    ][:n_traced]
    s = context["family"].sizes(context["config"])
    page = int(context["mix"]["engine"]["page_size"])
    flops, nbytes = counts.decode_paged_counts(
        sum(traced) * page, s["layers"], s["hidden"])
    peaks = context["peaks"]
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
