"""Kind `train`: the loop a user of the library runs. Each step makes a
fresh batch on the host from a seeded generator, uploads it, and
dispatches one optimizer step; a loss is fetched every few steps and at
the window's end, so the clock stops on a value that has arrived.

Set-up builds one object (the compiled step with its state), drives it
through its first steps by the window's own feed and call, keeps what
those steps showed (losses, the first gradient's norms, the parameters'
change), and hands the same object to the window. After the window the
state is freed and the plain reference follows the same first steps;
the two are compared leaf by leaf.
"""

import gc
import time

import jax
import numpy as np

from benchmarks.harness import clock
from benchmarks.harness.spans import CompileCounter, Spans

CHECK_STEPS = 3


class Runner:
    def __init__(self, cell, manifest, control=False, break_step=False):
        self.cell = cell
        self.config = cell["config"]
        self.mix = cell["mix"]
        self.family = manifest.family(self.config)
        self.control = control
        self.break_step = break_step
        self.compiles = CompileCounter()
        self.program = None

    def build(self, seed):
        self.seed = seed
        if self.program is None:
            self.program = self.family.train_setup(
                self.config, self.mix, break_step=self.break_step)
            clock.mark("step program built")
        self.program.start(seed)
        clock.mark("state on the device")
        self.batches = self.family.BatchMaker(self.config, self.mix, seed)
        prog = self.program
        self.first_batches, self.first_losses = [], []
        for step in range(CHECK_STEPS):
            batch = self.batches.make()
            self.first_batches.append(batch)
            prog.state, loss = prog.step(prog.state, prog.feed(batch))
            self.first_losses.append(float(loss))
            if step == 0:
                clock.mark("first step done (compile or cache load)")
                self.first_gradient = prog.first_gradient_norms()
        self.change = prog.change_norms()
        clock.mark("first steps and their norms done")

    def reseed(self, seed):
        """New state for the same compiled step."""
        self.program.free()
        gc.collect()
        self.build(seed)

    def measure(self, seed, seconds, trace_dir=None):
        prog, spans = self.program, Spans()
        t = self.mix["train"]
        fetch_every = int(t["fetch_loss_every"])
        trace_steps = int(t["trace_steps"]) if trace_dir else 0
        profiler = {"start_stall_s": 0.0, "stop_stall_s": 0.0}
        compiles_open = self.compiles.count
        steps, pending, last = 0, None, None
        t_open = time.perf_counter()
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            spans.tracing = True
            profiler["start_stall_s"] = time.perf_counter() - t_open
            t_open = time.perf_counter()
        while True:
            with spans.span("batch_make"):
                batch = self.batches.make()
            with spans.span("batch_upload"):
                dev = prog.feed(batch)
            with spans.span("step_dispatch"):
                prog.state, pending = prog.step(prog.state, dev)
            steps += 1
            if steps % fetch_every == 0 or steps == trace_steps:
                with spans.span("loss_fetch"):
                    last = float(pending)
            if spans.tracing and steps >= trace_steps:
                traced = (steps, time.perf_counter() - t_open)
                spans.tracing = False
                ts = time.perf_counter()
                jax.profiler.stop_trace()
                profiler["stop_stall_s"] = time.perf_counter() - ts
            if time.perf_counter() - t_open >= seconds:
                break
        with spans.span("loss_fetch"):
            last = float(pending)
        t_close = time.perf_counter()
        window_s = t_close - t_open - profiler["stop_stall_s"]
        tokens = steps * prog.tokens_per_step
        e2e = {
            "train_tokens_per_s_per_chip":
                tokens / window_s / self.cell["chips"],
        }
        info = {
            "steps": steps,
            "tokens_per_step": prog.tokens_per_step,
            "window_s": window_s,
            "step_ms_mean": 1e3 * window_s / steps,
            "last_loss": last,
            "first_losses": self.first_losses,
            "batch_make_ms_median": 1e3 * float(np.median(spans.durations("batch_make"))),
            "batch_upload_ms_median": 1e3 * float(np.median(spans.durations("batch_upload"))),
            "profiler": profiler,
        }
        context = {
            "spans": spans, "config": self.config, "family": self.family,
            "mix": self.mix, "chips": self.cell["chips"],
            "tokens_per_step": prog.tokens_per_step,
            "traced": traced if trace_dir else None,
        }
        return {
            "window_open": t_open,
            "attempted": steps,
            "failed": 0 if np.isfinite(last) else steps,
            "end_to_end": e2e,
            "info": info,
            "context": context,
            "compiles_in_window": self.compiles.count - compiles_open,
        }

    def free(self):
        if self.program is not None:
            self.program.free()
        gc.collect()

    def check(self):
        limits = self.mix["check"]["limits"]
        import jax.numpy as jnp

        ref = self.family.reference_train(
            self.config, self.mix, self.seed, self.first_batches,
            dtype=jnp.float32)
        got = {
            "losses": self.first_losses,
            "first_gradient_norms": self.first_gradient,
            "change_norms": self.change,
        }
        if self.control:
            # the control: the reference in the program's place, one
            # precision step down (bfloat16 throughout, no fp32 masters)
            got = self.family.reference_train(
                self.config, self.mix, self.seed, self.first_batches,
                dtype=jnp.bfloat16)
        values = compare(got, ref)
        comparisons = [
            {"name": k, "value": values[k], "limit": float(limits[k])}
            for k in limits
        ]
        correct = all(
            np.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in comparisons)
        detail = {
            "reference_losses": ref["losses"],
            "losses": got["losses"],
            "worst_gradient_leaf": values["_worst_gradient_leaf"],
            "worst_change_leaf": values["_worst_change_leaf"],
        }
        return correct, comparisons, detail


def worst_leaf_gap(got, ref):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for path, r in ref.items():
        gap = abs(float(got[path]) - r) / max(r, median)
        if not np.isfinite(gap):
            return float("inf"), path
        if gap > worst:
            worst, where = gap, path
    return worst, where


def compare(got, ref):
    loss_gap = max(
        abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"]))
    g, gw = worst_leaf_gap(got["first_gradient_norms"], ref["first_gradient_norms"])
    c, cw = worst_leaf_gap(got["change_norms"], ref["change_norms"])
    return {
        "loss_gap": float(loss_gap),
        "first_gradient_norm_gap": g,
        "change_norm_gap": c,
        "_worst_gradient_leaf": gw,
        "_worst_change_leaf": cw,
    }
