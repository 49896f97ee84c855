"""Median over the traced ticks of what the host spends handing the tick
to the device: the engine's `engine.rng` phase (the tick's PRNG key
split, eager dispatches of its own) plus `engine.dispatch` (the
`jnp.asarray` uploads of the tick's arrays and the jitted call until it
returns: flattening the parameter and K/V trees, enqueueing the
program). The device starts the tick's program somewhere inside it."""

from benchmarks.harness import program_trace


def read(context):
    return program_trace.phase_median_ms(
        context, ("engine.rng", "engine.dispatch"))
