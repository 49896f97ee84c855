"""The run's clock: `T0` is read when this module is first imported,
which `run.py` does before anything heavy, so `setup_s` counts the
imports, the device start-up, weights, compile or cache load, and
warm-up. `mark` prints where set-up time goes, one line per phase."""

import time

T0 = time.perf_counter()


def since_start():
    return time.perf_counter() - T0


def mark(label):
    print(f"  [set-up] {since_start():8.2f} s  {label}", flush=True)
