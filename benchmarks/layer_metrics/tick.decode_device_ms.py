"""Median duration of the executions (`XLA Modules` events) joined to the
traced ticks whose `program` is decode: what ONE decode tick costs the
device, where `step.device_ms.serve` and `moe.device_ms` divide by all
ticks and so follow the stretch's make-up of mixed and decode ticks. The
count, the 10th and 90th percentile, the ticks' `model_passes` and how
full their chunks were are printed beside it. The join is
`harness/tick_account.py`'s."""

from benchmarks.harness import tick_account


def read(context):
    return tick_account.program_ms(context, "decode", "device")
