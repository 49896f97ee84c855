"""95th percentile, over the requests due in the window that were
served, of the time from when a request was due to when it was given a
slot: the generator's lateness plus the engine's own `queue_wait_ms`."""

from benchmarks.harness import stats


def read(context):
    waits = context.get("waits_ms")
    if not waits:
        return None
    return stats.percentile(waits, 95)
