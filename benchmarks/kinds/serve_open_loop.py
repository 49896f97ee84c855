"""Kind `serve_open_loop`: requests arrive on a schedule, whatever the
server does, through `add_request` / `step()` of the family's engine.

One thread: it adds every request that is due, then runs one engine
tick, as a server built on this engine does (the tick is synchronous).
A request is timed from when it was DUE, so the wait a slow tick imposes
on later arrivals counts; how late the generator ran is reported.

Phases of one run: build and warm up (both step programs), ramp (the
same generator, before the window opens, to steady occupancy: set-up),
window (`--seconds`), drain (requests due in the window run to their
end; no new load), then the comparison with the reference after the
engine's memory is freed: the served tokens of a sample of requests, and
the keys and values one live slot held when the window closed.

The control (`control=True`) is the engine's own lower-precision path:
the same run with the K/V cache stored as int8.
"""

import gc
import time

import jax
import numpy as np

from benchmarks.harness import arrivals, clock, device, stats
from benchmarks.harness.spans import CompileCounter, Spans

ID_BASE = 1000  # request ids of the plan; warm-up requests stay below


class Runner:
    def __init__(self, cell, manifest, control=False):
        self.cell = cell
        self.config = cell["config"]
        self.mix = cell["mix"]
        self.family = manifest.family(self.config)
        self.control = control
        self.engine = None
        self.compiles = CompileCounter()

    # -- set-up ---------------------------------------------------------

    def build(self, seed):
        self.engine = self.family.serve_setup(
            self.config, self.mix, seed, control=bool(self.control))
        clock.mark("weights on the device, engine built")
        self._warm_up()

    def reseed(self, seed):
        """New weights for the same engine and compiled programs."""
        self.family.reseed(self.engine, self.config, seed)

    def _warm_up(self):
        """Compile (or load) the two programs a tick can run: the mixed
        chunk+decode step and the decode-only step. From `reset_stats`
        on the engine's sentinel raises on any further compile."""
        eng = self.engine
        vocab = self.family.sizes(self.config)["vocab"]
        rng = np.random.default_rng(0)
        budget = int(self.mix["engine"]["prefill_token_budget"])
        eng.add_request(rng.integers(0, vocab, size=budget + 8).tolist(), 6)
        eng.add_request(rng.integers(0, vocab, size=8).tolist(), 3)
        while eng.has_work():
            eng.step()
        if eng.mixed_trace_count != 1 or eng.decode_trace_count != 1:
            raise RuntimeError(
                f"warm-up did not reach both step programs: mixed "
                f"{eng.mixed_trace_count}, decode {eng.decode_trace_count}")
        # compiled here, run only at the window's close
        self.snapshot_program = self.family.kv_snapshot_program(eng)
        eng.reset_stats()

    # -- the run ----------------------------------------------------------

    def measure(self, seed, seconds, trace_dir=None):
        eng, mix = self.engine, self.mix
        # clean counters, and the engine's sentinel armed: from here to
        # the end of the drain any compile fails the next tick
        eng.reset_stats()
        s = self.family.sizes(self.config)
        plan = arrivals.build_plan(
            mix, seed, seconds, s["vocab"], int(mix["engine"]["capacity"]))
        n = len(plan.due)
        spans = Spans()
        trace_s = float(mix["trace_seconds"]) if trace_dir else 0.0
        t_add = np.zeros(n)
        results = {}
        ticks = []  # (start, end, pages in use at the tick's start)
        profiler = {"start_stall_s": 0.0, "stop_stall_s": 0.0}
        tracing = traced = False
        tokens_open = tokens_close = None
        t_open = t_close = None
        compiles_open = None
        snapshot = memory_peak = None
        t0 = time.perf_counter() + plan.ramp_s  # the window opens here
        nxt = 0
        drain_until = t0 + seconds + float(mix["drain_limit_s"])
        while True:
            now = time.perf_counter() - t0
            if tokens_open is None and now >= 0.0:
                tokens_open = eng.stats()["generated_tokens"]
                compiles_open = self.compiles.count
                t_open = time.perf_counter()
            # the traced stretch is the window's LAST `trace_seconds`:
            # stopping the profiler stalls this loop for seconds, and at
            # the close that delays only the drain, where nothing new is
            # due (inside the window it made requests wait for the
            # profiler)
            if trace_dir and not traced and now >= seconds - trace_s:
                t = time.perf_counter()
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                traced = tracing = spans.tracing = True
                profiler["start_stall_s"] = time.perf_counter() - t
                profiler["t_start"] = time.perf_counter()
            if tokens_close is None and now >= seconds:
                tokens_close = eng.stats()["generated_tokens"]
                compiles_close = self.compiles.count
                t_close = time.perf_counter()
                # the program's own peak: the snapshot below is the
                # benchmark's
                memory_peak = device.memory_peak_bytes()
                if tracing:
                    spans.tracing = tracing = False
                    profiler["t_stop"] = t_close
                    jax.profiler.stop_trace()
                    profiler["stop_stall_s"] = time.perf_counter() - t_close
            if tokens_close is not None and snapshot is None:
                # at the close, or at the first tick after it that finds
                # a slot decoding: a copy on the device, not waited for
                with spans.span("kv_snapshot"):
                    snapshot = self.family.kv_snapshot(
                        eng, self.snapshot_program)
            with spans.span("generator"):
                while nxt < n and plan.due[nxt] <= now:
                    with spans.span("add_request"):
                        t_add[nxt] = time.perf_counter()
                        eng.add_request(
                            plan.prompts[nxt], int(plan.max_new[nxt]),
                            request_id=ID_BASE + nxt)
                    nxt += 1
            if eng.has_work():
                pages = eng.pages_used
                t = time.perf_counter()
                with spans.span("engine.step"):
                    done = eng.step()
                ticks.append((t, time.perf_counter(), pages))
                for r in done:
                    results[r.request_id] = r
            elif nxt >= n:
                if tokens_close is not None:
                    break
                time.sleep(0.001)
            else:
                time.sleep(min(0.002, max(0.0, plan.due[nxt] - now)))
            if time.perf_counter() > drain_until:
                break
        if tracing:  # the drain's limit cut the run before the close
            jax.profiler.stop_trace()
            spans.tracing = False
        eng.retrace_sentinel.disarm()  # the reference may compile
        completions = {c["request_id"]: c for c in eng.completions}

        # -- per-request numbers, over the requests due in the window ---
        due_abs = t0 + plan.due
        in_window = np.flatnonzero(plan.in_window)
        ttft, tpot, waits, late, served = [], [], [], [], []
        missing = 0
        for i in in_window:
            c = completions.get(ID_BASE + int(i))
            r = results.get(ID_BASE + int(i))
            ok = (
                c is not None and r is not None
                and c["finish_reason"] == "length"
                and len(r.tokens) == int(plan.max_new[i])
            )
            if not ok:
                missing += 1
                continue
            lateness_ms = 1e3 * (t_add[i] - due_abs[i])
            late.append(lateness_ms)
            ttft.append(lateness_ms + c["ttft_ms"])
            waits.append(lateness_ms + c["queue_wait_ms"])
            if c["new_tokens"] > 1:
                tpot.append(c["tpot_ms"])
            served.append(int(i))
        window_s = t_close - t_open
        out_tokens = tokens_close - tokens_open
        e2e = {
            "ttft_p95_ms": stats.tail_with_missing(ttft, missing, 95),
            "tpot_p95_ms": stats.tail_with_missing(tpot, missing, 95),
            "serve_out_tokens_per_s": out_tokens / window_s,
        }
        st = eng.stats()
        info = {
            "requests_due_in_window": int(len(in_window)),
            "requests_served": len(served),
            "requests_missing": missing,
            "ttft_ms_median": stats.percentile(ttft, 50) if ttft else None,
            "tpot_ms_median": stats.percentile(tpot, 50) if tpot else None,
            "queue_wait_ms_median": stats.percentile(waits, 50) if waits else None,
            "generator_lateness_ms_median": stats.percentile(late, 50) if late else None,
            "generator_lateness_ms_max": max(late) if late else None,
            "window_s": window_s,
            "out_tokens_in_window": out_tokens,
            "ticks": len(ticks),
            "tick_ms_median": stats.percentile(
                [1e3 * (e - s) for s, e, _ in ticks], 50) if ticks else None,
            "pages_used_max": max((p for _, _, p in ticks), default=0),
            "pages_total": st["pages_total"],
            "preemptions": st["preemptions"],
            "page_stalls": st["page_stalls"],
            "queue_depth_at_close": st["queue_depth"],
            "drain_s": time.perf_counter() - t_close,
            "profiler": {k: v for k, v in profiler.items() if k.endswith("_s")},
        }
        self._last = {
            "plan": plan, "results": results, "served": served, "seed": seed,
            "snapshot": snapshot,
        }
        context = {
            "spans": spans, "ticks": ticks, "profiler": profiler,
            "config": self.config, "family": self.family, "mix": mix,
            "waits_ms": waits, "chips": self.cell["chips"],
        }
        return {
            "window_open": t_open,
            "attempted": int(len(in_window)),
            "failed": missing,
            "end_to_end": e2e,
            "info": info,
            "context": context,
            "compiles_in_window": compiles_close - compiles_open,
            "memory_peak_bytes": memory_peak,
        }

    # -- correctness ------------------------------------------------------

    def free(self):
        """Give the device back before the reference runs, so that the
        peak stays the program's."""
        eng = self.engine
        if eng is not None:
            for leaf in jax.tree_util.tree_leaves((eng.cache, eng.params)):
                leaf.delete()
            eng.cache = eng.params = None
        self.engine = None
        gc.collect()

    def check(self):
        """Two comparisons with the float32 reference. The served tokens
        of a seeded sample of the requests the window finished, the
        longest among them: how far each served token's reference logit
        lies below the reference's best. And the keys and values that
        one live slot held at the window's close, against the
        reference's over the same tokens, layer by layer."""
        last = self._last
        plan, results = last["plan"], last["results"]
        served = last["served"]
        limits = self.mix["check"]["limits"]
        values, detail = {}, {}
        if served:
            values, detail = self._token_gaps(plan, results, served, last["seed"])
        snap = last["snapshot"]
        r = results.get(snap["request_id"]) if snap else None
        if r is not None:
            k_gaps, v_gaps = self.family.reference_kv_gaps(
                self.config, last["seed"], list(r.prompt) + list(r.tokens),
                snap)
            values["kv_gap_first_layer"] = max(k_gaps[0], v_gaps[0])
            values["kv_gap_worst_layer"] = max(k_gaps + v_gaps)
            detail["kv_rows_checked"] = snap["rows"]
            detail["k_gap_by_layer"] = [round(g, 5) for g in k_gaps]
            detail["v_gap_by_layer"] = [round(g, 5) for g in v_gaps]
        last["snapshot"] = None  # the copied rows go back to the device
        comparisons = [
            {"name": k, "value": values.get(k), "limit": float(limits[k])}
            for k in limits
        ]
        # a number that could not be read is not a number within its limit
        correct = all(
            c["value"] is not None and c["value"] <= c["limit"]
            for c in comparisons)
        return correct, comparisons, detail

    def _token_gaps(self, plan, results, served, seed):
        rng = np.random.default_rng(int(seed))
        k = min(int(self.mix["check"]["sample_requests"]), len(served))
        longest = max(
            served, key=lambda i: len(plan.prompts[i]) + int(plan.max_new[i]))
        rest = [i for i in served if i != longest]
        sample = [longest] + [
            int(i) for i in rng.choice(rest, size=min(k - 1, len(rest)),
                                       replace=False)
        ]
        seqs = []
        for i in sample:
            r = results[ID_BASE + i]
            if list(r.prompt) != list(plan.prompts[i]):
                raise RuntimeError("a result came back under another prompt")
            seqs.append((r.prompt, r.tokens))
        # blocks of sequences of like length: the float32 attention
        # scores of a block have to fit beside one layer's weights
        order = sorted(range(len(seqs)), key=lambda j: len(seqs[j][0]) + len(seqs[j][1]))
        gaps, margins = [None] * len(seqs), [None] * len(seqs)
        block = int(self.mix["check"].get("block", 4))
        for a in range(0, len(order), block):
            idx = order[a: a + block]
            for j, (g, m) in zip(idx, self.family.reference_gaps(
                    self.config, seed, [seqs[j] for j in idx])):
                gaps[j], margins[j] = g, m
        gaps, margin = np.concatenate(gaps), np.concatenate(margins)
        values = {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}
        detail = {
            "checked_requests": len(seqs),
            "checked_tokens": int(gaps.size),
            "longest_checked": max(len(p) + len(t) for p, t in seqs),
            "tokens_off_reference_argmax": int((gaps > 0).sum()),
            "gap_quantiles_50_90_99": [
                float(np.quantile(gaps, q)) for q in (0.5, 0.9, 0.99)],
            "reference_margin_quantiles_1_10_50": [
                float(np.quantile(margin, q)) for q in (0.01, 0.1, 0.5)],
            "tokens_with_gap_over_0.05_0.1_0.2": [
                int((gaps > t).sum()) for t in (0.05, 0.1, 0.2)],
        }
        return values, detail
