"""Named timers with device synchronization.

Reference: apex/transformer/pipeline_parallel/_timers.py:1-83
(`_Timer` with `torch.cuda.synchronize()` around start/stop, `Timers`
registry with `log`). JAX dispatch is asynchronous, so synchronization
here means waiting on a result: `stop` optionally takes an array and
fetches its value before reading the clock.
"""

import time
from typing import Optional

import jax
import numpy as np

__all__ = ["Timers"]


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self.elapsed_ = 0.0
        self.started_ = False
        self.start_time = 0.0

    def start(self):
        assert not self.started_, f"timer {self.name} already started"
        self.started_ = True
        self.start_time = time.perf_counter()

    def stop(self, sync_on=None):
        assert self.started_, f"timer {self.name} is not started"
        if sync_on is not None:
            np.asarray(jax.device_get(sync_on))  # true device sync
        self.elapsed_ += time.perf_counter() - self.start_time
        self.started_ = False

    def reset(self):
        self.elapsed_ = 0.0
        self.started_ = False

    def elapsed(self, reset: bool = True, sync_on=None) -> float:
        was_started = self.started_
        if was_started:
            self.stop(sync_on=sync_on)
        out = self.elapsed_
        if reset:
            self.reset()
        if was_started:
            self.start()
        return out


class Timers:
    """Registry (reference _timers.py Timers.__call__/log).

    Both sinks — `log` (stdout) and `write` (TensorBoard-style
    ``add_scalar``) — RESET the timers they report by default. The
    reference shipped an asymmetry (log reset=True, write reset=False)
    that double-counted every window in TensorBoard while stdout showed
    per-window numbers; one default means the two sinks can never
    disagree about what a value covers. Pass ``reset=False`` explicitly
    for cumulative reporting. ``sync_on`` on either sink gives a timer
    that is STILL RUNNING the true-device-sync stop treatment (a value
    fetch — `_Timer.stop`) before it is read."""

    def __init__(self):
        self.timers = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def log(
        self,
        names,
        normalizer: float = 1.0,
        reset: bool = True,
        printer=print,
        sync_on=None,
    ):
        assert normalizer > 0.0
        parts = ["time (ms)"]
        for name in names:
            if name in self.timers:
                ms = (
                    self.timers[name].elapsed(reset=reset, sync_on=sync_on)
                    * 1000.0
                    / normalizer
                )
                parts.append(f"{name}: {ms:.2f}")
        printer(" | ".join(parts))

    def write(
        self, names, writer, iteration, normalizer=1.0, reset=True,
        sync_on=None,
    ):
        """Tensorboard-style hook (reference _timers.py write), with
        `log`'s defaults and sync semantics (see class docstring)."""
        assert normalizer > 0.0
        for name in names:
            if name in self.timers:
                value = (
                    self.timers[name].elapsed(reset=reset, sync_on=sync_on)
                    / normalizer
                )
                writer.add_scalar(f"{name}-time", value, iteration)
