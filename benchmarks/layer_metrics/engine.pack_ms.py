"""Median over the traced ticks of the engine's `engine.pack` phase (the
drafter, the slot loop that fills the chunk and length arrays, securing
pages, the decode grid, preemption) plus `engine.table_push` (the host's
page table copied to the device where a mapping changed)."""

from benchmarks.harness import program_trace


def read(context):
    return program_trace.phase_median_ms(
        context, ("engine.pack", "engine.table_push"))
