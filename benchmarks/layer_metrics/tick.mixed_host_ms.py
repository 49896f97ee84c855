"""Median, over the traced ticks whose `program` is mixed, of the tick's
wall (its `engine.tick` span) less the duration of its execution on the
device: what the host adds to a mixed tick, from the program's own span
and the device's own event, durations only. The join is
`harness/tick_account.py`'s."""

from benchmarks.harness import tick_account


def read(context):
    return tick_account.program_ms(context, "mixed", "host")
