"""Median over the traced ticks of the engine's `engine.admit` phase: the
watchdog, the shed and expired requests' results, and the leasing of free
slots to queued requests (`InferenceEngine.step`). A request's first
token waits for the tick that admits it, so this moves the time to first
token. With `engine.pack_ms`, `engine.dispatch_ms`, `engine.result_wait_ms`
and `engine.commit_ms` it splits `engine.tick_host_ms`, which times the
same tick from outside.

What admission did in those ticks is printed beside it, from the ticks'
`admitted` and `queue_depth` and from the request ids that
`engine.enqueue` and `engine.admit` share: how many requests were leased
a slot, the deepest queue left behind, and each one's wait for its slot
on the capture's own clock."""

import statistics

from benchmarks.harness import program_trace


def read(context):
    value = program_trace.phase_median_ms(context, ("engine.admit",))
    counts = program_trace.tick_counts(context)
    if counts:
        waits = sorted(program_trace.queue_waits_ms(
            program_trace.of(context), context["t0_ns"], context["t1_ns"]
        ).values())
        program_trace.say(
            f"  engine.admit_ms: {program_trace.total(counts, 'admitted')} "
            "requests leased a slot in "
            f"{sum(1 for c in counts if int(c.get('admitted', 0)))} of "
            f"{len(counts)} ticks; deepest queue left waiting "
            f"{max(int(c.get('queue_depth', 0)) for c in counts)}; waits "
            "from enqueue to lease: " + (
                f"median {statistics.median(waits):.3f} ms, most "
                f"{waits[-1]:.3f} ms of {len(waits)}" if waits else "none"))
    return value
