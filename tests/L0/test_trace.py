"""Span tracer, per-request serving timelines, and the numerics
flight recorder (ISSUE-6).

The acceptance bars under test:

* the Chrome trace-event export is valid JSON with named per-request
  tracks whose span boundaries REPRODUCE the TTFT/queue-wait numbers
  the engine's ``stats()`` and per-request completion records report
  (one shared ``perf_counter`` clock — three reports, zero ways to
  disagree);
* with tracing disabled (the default) the engine's compiled programs
  and trace counters are untouched — the NULL tracer records nothing
  and ``span()`` allocates nothing;
* the flight recorder's in-graph group probes follow the Metrics psum
  convention, add ZERO equations when not requested (jaxpr-asserted
  via the auditor), and an injected NaN produces a dump naming the
  offending param group in agreement with the amp scaler's skip-path
  counters.

Wall-time note (ROADMAP): the engine tests reuse test_inference's
EXACT shape tuple (fp32_cfg model, slots=2, capacity=24, budget=4,
init seq 8 / seed 1) so every compiled program is a compile-cache hit;
everything else here is host-side or make_jaxpr-only (zero compiles).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from rocm_apex_tpu.amp import LossScaler
from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
from rocm_apex_tpu.monitor import (
    NULL_TRACER,
    FlightRecorder,
    JsonlWriter,
    Metrics,
    MetricRegistry,
    RetraceError,
    RetraceSentinel,
    Tracer,
    audit,
    group_nonfinite,
    merge_traces,
    mint_trace_id,
    trace_lifelines,
)
from rocm_apex_tpu.monitor.trace import _NULL_SPAN, export_merged_trace


def _mesh(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} simulated devices")
    return Mesh(np.array(devs[:n]), ("tensor",))


# ---------------------------------------------------------------------------
# Tracer (host-only, no jax programs)
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_add_span_instant_round_trip(self):
        t = Tracer()
        with t.span("live", track="a", tokens=3):
            pass
        t.add_span("retro", 1.0, 1.5, track="b", n=7)
        t.instant("mark", ts=2.0, track="b")
        evs = t.events()
        meta = {
            e["tid"]: e["args"]["name"]
            for e in evs
            if e.get("ph") == "M" and e["name"] == "thread_name"
        }
        assert set(meta.values()) == {"a", "b"}
        spans = {e["name"]: e for e in evs if e["ph"] == "X"}
        assert spans["live"]["args"] == {"tokens": 3}
        assert spans["live"]["dur"] >= 0.0
        assert spans["retro"]["dur"] == pytest.approx(0.5e6)
        assert meta[spans["retro"]["tid"]] == "b"
        (inst,) = [e for e in evs if e["ph"] == "i"]
        assert inst["name"] == "mark"
        # same track name -> same tid
        assert inst["tid"] == spans["retro"]["tid"]

    def test_ring_buffer_drops_oldest(self):
        t = Tracer(capacity=3)
        for i in range(5):
            t.add_span(f"s{i}", 0.0, 1.0)
        names = [e["name"] for e in t.events() if e["ph"] == "X"]
        assert names == ["s2", "s3", "s4"]

    def test_export_is_valid_chrome_json(self, tmp_path):
        t = Tracer()
        with t.span("step", step=1):
            pass
        path = tmp_path / "trace.json"
        n = t.export_chrome_trace(str(path))
        data = json.loads(path.read_text())
        assert len(data["traceEvents"]) == n
        phases = {e["ph"] for e in data["traceEvents"]}
        assert "X" in phases and "M" in phases
        for e in data["traceEvents"]:
            if e["ph"] == "X":
                assert e["ts"] >= 0.0 and e["dur"] >= 0.0

    def test_disabled_tracer_is_free_and_silent(self):
        t = Tracer(enabled=False)
        # the no-op context manager is one SHARED instance: the
        # disabled hot path never allocates
        assert t.span("a") is _NULL_SPAN
        assert t.span("b", track="x", tokens=1) is _NULL_SPAN
        assert t.step_span(3) is _NULL_SPAN
        with t.span("a"):
            pass
        t.add_span("a", 0.0, 1.0)
        t.instant("b")
        assert t.events() == []
        assert NULL_TRACER.enabled is False and NULL_TRACER.events() == []

    def test_step_span_records_step_number(self):
        t = Tracer(annotate_device=False)
        with t.step_span(7):
            pass
        (ev,) = [e for e in t.events() if e["ph"] == "X"]
        assert ev["name"] == "train_step" and ev["args"] == {"step": 7}


# ---------------------------------------------------------------------------
# per-request serving timelines (test_inference's exact engine shapes)
# ---------------------------------------------------------------------------


def fp32_cfg(**kw):
    kw.setdefault("vocab_size", 96)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("max_position_embeddings", 32)
    kw.setdefault("hidden_dropout", 0.0)
    kw.setdefault("attention_dropout", 0.0)
    kw.setdefault("tensor_parallel_size", 1)
    kw.setdefault("params_dtype", jnp.float32)
    kw.setdefault("dtype", jnp.float32)
    return GPTConfig(**kw)


def make_model(cfg, seq=8, seed=1):
    model = GPTModel(cfg)
    toks = jnp.zeros((1, seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), toks)
    return model, params


def greedy_engine(model, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("capacity", 24)
    kw.setdefault("prefill_token_budget", 4)
    kw.setdefault("sampling", SamplingParams(temperature=0.0))
    return InferenceEngine(model, params, **kw)


PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]


class TestServingTimelines:
    def _run_traced(self):
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        tracer = Tracer()
        eng = greedy_engine(model, params, tracer=tracer)
        results = eng.generate(PROMPTS, max_new_tokens=3)
        return eng, tracer, results

    def test_completion_records_reproduce_stats_percentiles(
        self, tmp_path
    ):
        """The bench.py serve --trace contract: the jsonl completion
        records' TTFT/queue-wait distributions reproduce the already-
        reported stats() percentiles (same clock, same values)."""
        eng, _, results = self._run_traced()
        # export through the same JsonlWriter path the bench uses
        path = tmp_path / "requests.jsonl"
        with open(path, "w") as f:
            w = JsonlWriter(stream=f)
            for rec in eng.completions:
                w.emit(rec)
        rows = [json.loads(l) for l in path.read_text().splitlines()]
        assert len(rows) == len(results) == len(PROMPTS)
        s = eng.stats()
        for q, key in ((50, "ttft_ms_p50"), (95, "ttft_ms_p95")):
            got = float(np.percentile([r["ttft_ms"] for r in rows], q))
            assert got == pytest.approx(s[key], rel=1e-6), key
        for q, key in (
            (50, "queue_wait_ms_p50"), (95, "queue_wait_ms_p95"),
        ):
            got = float(
                np.percentile([r["queue_wait_ms"] for r in rows], q)
            )
            assert got == pytest.approx(s[key], rel=1e-6, abs=1e-9), key
        by_id = {r["request_id"]: r for r in rows}
        for res in results:
            rec = by_id[res.request_id]
            assert rec["new_tokens"] == len(res.tokens)
            assert rec["prompt_tokens"] == len(res.prompt)
            assert rec["finish_reason"] == res.finish_reason
            assert rec["ttft_ms"] >= rec["queue_wait_ms"] >= 0.0
            assert rec["e2e_ms"] >= rec["ttft_ms"]
            assert rec["tpot_ms"] >= 0.0
            # budget=4 SHARED across slots: at least ceil(prompt/4)
            # ticks carried this prompt, at most one per token
            assert (
                -(-rec["prompt_tokens"] // 4)
                <= rec["chunks"]
                <= rec["prompt_tokens"]
            )

    def test_trace_span_boundaries_reproduce_ttft(self):
        """Per-request tracks: queue_wait starts at enqueue, decode
        starts at the first token — their gap IS the reported TTFT."""
        eng, tracer, _ = self._run_traced()
        evs = tracer.events()
        tracks = {
            e["args"]["name"]: e["tid"]
            for e in evs
            if e.get("ph") == "M" and e["name"] == "thread_name"
        }
        assert "engine" in tracks  # the mixed/decode tick track
        by_id = {r["request_id"]: r for r in eng.completions}
        for rid, rec in by_id.items():
            tid = tracks[f"req{rid}"]
            mine = [
                e for e in evs
                if e.get("tid") == tid and e["ph"] in ("X", "i")
            ]
            names = [e["name"] for e in mine]
            assert names[0] == "enqueue" and names[-1] == "finish"
            spans = {}
            for e in mine:
                if e["ph"] == "X":
                    spans.setdefault(e["name"], []).append(e)
            # chunk spans carry the packed token counts as args and
            # account for the whole prompt
            chunk_tokens = [
                s["args"]["tokens"] for s in spans["prefill_chunk"]
            ]
            assert sum(chunk_tokens) == rec["prompt_tokens"]
            assert len(chunk_tokens) == rec["chunks"]
            assert all(0 < c <= 4 for c in chunk_tokens)
            (qw,) = spans["queue_wait"]
            (dec,) = spans["decode"]
            # boundaries -> latencies (ts is µs): enqueue -> lease is
            # the queue wait, enqueue -> decode start is the TTFT
            assert qw["dur"] / 1e3 == pytest.approx(
                rec["queue_wait_ms"], abs=1e-3
            )
            assert (dec["ts"] - qw["ts"]) / 1e3 == pytest.approx(
                rec["ttft_ms"], abs=1e-3
            )

    def test_disabled_path_records_nothing_and_keeps_one_trace(self):
        """The default engine rides the shared NULL tracer: no events,
        and the one-mixed-trace contract (pinned independently by
        test_inference) is visibly intact on the same run."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params)
        assert eng.tracer is NULL_TRACER
        results = eng.generate(PROMPTS, max_new_tokens=3)
        assert eng.tracer.events() == []
        assert eng.mixed_trace_count == 1
        assert eng.decode_trace_count <= 1
        # completion records are unconditional host bookkeeping
        assert len(eng.completions) == len(results)
        # ...and reset with the rest of the telemetry
        eng.reset_stats()
        assert eng.completions == []

    def test_whole_prompt_path_timeline(self):
        """The legacy A/B path traces too: one 'prefill' span (the
        padded compiled call) instead of chunk spans."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        tracer = Tracer()
        eng = greedy_engine(
            model, params, prefill_token_budget=None,
            max_prompt_len=24, tracer=tracer,
        )
        eng.generate([[1, 2, 3]], max_new_tokens=2)
        names = [
            e["name"] for e in tracer.events() if e["ph"] in ("X", "i")
        ]
        assert "prefill" in names and "queue_wait" in names
        assert "prefill_chunk" not in names
        (rec,) = eng.completions
        assert rec["chunks"] == 1


# ---------------------------------------------------------------------------
# fleet-causal tracing: merge_traces / trace_lifelines (host-only)
# ---------------------------------------------------------------------------


class TestMergeTraces:
    def _fleet(self):
        """Hand-built three-tracer fleet: a request admitted on the
        router, prefilled on replica 0, migrated, finished on replica
        1 — the hop shape the real router/engine pair emits."""
        import time

        router, rep0, rep1 = Tracer(), Tracer(), Tracer()
        tid = mint_trace_id()
        t = time.perf_counter()
        router.instant("admit", ts=t, track="req0",
                       request_id=0, trace_id=tid)
        router.instant("dispatch", ts=t + 0.001, track="req0",
                       request_id=0, trace_id=tid)
        rep0.instant("resume", ts=t + 0.002, track="req0",
                     request_id=0, trace_id=tid)
        rep0.add_span("prefill_chunk", t + 0.002, t + 0.004,
                      track="req0", tokens=4, trace_id=tid)
        router.instant("migrate", ts=t + 0.005, track="req0",
                       request_id=0, trace_id=tid)
        rep1.instant("resume", ts=t + 0.006, track="req0",
                     request_id=0, trace_id=tid)
        rep1.instant("finish", ts=t + 0.009, track="req0",
                     request_id=0, trace_id=tid)
        return [router, rep0, rep1], tid

    def test_pids_labels_and_renormalized_clock(self):
        tracers, _ = self._fleet()
        body = merge_traces(tracers, labels=["router", "r0", "r1"])
        procs = {
            e["pid"]: e["args"]["name"]
            for e in body["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert procs == {1: "router", 2: "r0", 3: "r1"}
        assert body["otherData"]["processes"] == {
            "1": "router", "2": "r0", "3": "r1",
        }
        data = [
            e for e in body["traceEvents"] if e["ph"] in ("X", "i")
        ]
        # one common clock zero: every event lands at a nonnegative
        # offset, and cross-process ordering is preserved (the router
        # admit precedes the replica-1 finish)
        assert all(e["ts"] >= 0.0 for e in data)
        by_name = {(e["pid"], e["name"]): e["ts"] for e in data}
        assert by_name[(1, "admit")] < by_name[(3, "finish")]
        assert by_name[(2, "resume")] < by_name[(3, "resume")]

    def test_lifelines_exactly_one_finish_across_pids(self):
        tracers, tid = self._fleet()
        lines = trace_lifelines(merge_traces(tracers))
        assert set(lines) == {tid}
        line = lines[tid]
        assert line["pids"] == [1, 2, 3]
        assert line["finishes"] == 1
        assert "admit" in line["names"] and "migrate" in line["names"]
        assert line["events"] == 7

    def test_validation(self):
        with pytest.raises(ValueError, match="at least one"):
            merge_traces([])
        with pytest.raises(ValueError, match="labels"):
            merge_traces([Tracer(), Tracer()], labels=["only-one"])

    def test_export_is_valid_json(self, tmp_path):
        tracers, tid = self._fleet()
        path = tmp_path / "fleet.json"
        n = export_merged_trace(str(path), tracers)
        body = json.loads(path.read_text())
        assert len(body["traceEvents"]) == n
        assert trace_lifelines(body)[tid]["finishes"] == 1

    def test_mint_trace_id_unique_and_prefixed(self):
        ids = {mint_trace_id() for _ in range(100)}
        assert len(ids) == 100
        assert all(i.startswith("t") for i in ids)
        assert mint_trace_id(prefix="q").startswith("q")


# ---------------------------------------------------------------------------
# runtime retrace sentinel (one tiny fresh jit per compile probe)
# ---------------------------------------------------------------------------


def _fresh_compile(offset):
    """Force one real compilation event: a brand-new lambda is a new
    jit cache entry, so jax traces (and compiles) it from scratch."""
    jax.jit(lambda x: x + offset).lower(
        jnp.ones((3,), jnp.float32)
    ).compile()


class TestRetraceSentinel:
    def test_counts_then_trips_after_arm(self):
        reg = MetricRegistry()
        s = RetraceSentinel(reg)
        try:
            _fresh_compile(1.0)
            assert s.counts.get("trace", 0) >= 1
            assert s.tripped == 0 and s.check() == 0  # not armed yet
            s.arm()
            _fresh_compile(2.0)
            assert s.tripped >= 1
            assert s.check() == s.tripped  # count policy: no raise
            # both registry families moved with the dict counters
            snap = reg.snapshot()
            total = sum(
                x["value"]
                for x in snap["xla_compiles_total"]["series"]
            )
            post = sum(
                x["value"]
                for x in snap["xla_compiles_post_warmup_total"]["series"]
            )
            assert total >= post >= 1
        finally:
            s.close()

    def test_raise_policy_fails_the_next_check(self):
        s = RetraceSentinel(policy="raise")
        try:
            s.arm()
            _fresh_compile(3.0)
            with pytest.raises(RetraceError, match="after warmup"):
                s.check()
            s.disarm()
        finally:
            s.close()

    def test_closed_sentinel_stops_counting(self):
        s = RetraceSentinel()
        s.arm()
        s.close()
        before = s.tripped
        _fresh_compile(4.0)
        assert s.tripped == before

    def test_tracer_instant_on_trip(self):
        tr = Tracer()
        s = RetraceSentinel(tracer=tr)
        try:
            s.arm()
            _fresh_compile(5.0)
        finally:
            s.close()
        hits = [
            e for e in tr.events()
            if e["ph"] == "i" and e["name"] == "retrace"
        ]
        assert hits and hits[0]["args"]["phase"] in ("trace", "compile")

    def test_validation_and_status(self):
        with pytest.raises(ValueError, match="policy"):
            RetraceSentinel(policy="explode")
        with pytest.raises(ValueError, match="trip phases"):
            RetraceSentinel(trip_phases=("warp",))
        s = RetraceSentinel()
        try:
            st = s.status()
            assert st["policy"] == "count" and st["armed"] is False
            assert st["tripped"] == 0
        finally:
            s.close()


# ---------------------------------------------------------------------------
# numerics flight recorder
# ---------------------------------------------------------------------------


class TestGroupNonfinite:
    def test_flags_fire_per_group(self):
        g = {
            "ok": {"w": jnp.ones((3,))},
            "bad_nan": {"w": jnp.array([1.0, jnp.nan])},
            "bad_inf": {"w": jnp.array([jnp.inf, 1.0])},
        }
        flags = {k: float(v) for k, v in group_nonfinite(g).items()}
        assert flags == {
            "nonfinite/ok": 0.0,
            "nonfinite/bad_nan": 1.0,
            "nonfinite/bad_inf": 1.0,
        }

    def test_shard_map_psum_convention(self):
        """A NaN on ONE shard must flag the group on EVERY rank (the
        probe psums before the finiteness test — the Metrics rule)."""
        mesh = _mesh(4)
        x = jnp.ones((8,)).at[5].set(jnp.nan)

        def f(xs):
            flags = group_nonfinite(
                {"g": {"w": xs}, "h": {"w": jnp.ones_like(xs)}},
                axis_name="tensor",
            )
            # rank-1 so out_specs can concatenate one entry per rank
            return (
                flags["nonfinite/g"][None],
                flags["nonfinite/h"][None],
            )

        g_flag, h_flag = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P("tensor"),),
            out_specs=(P("tensor"), P("tensor")), check_vma=False,
        ))(x)
        # every rank reports the global verdict
        assert np.asarray(g_flag).tolist() == [1.0] * 4
        assert np.asarray(h_flag).tolist() == [0.0] * 4

    def test_off_path_adds_zero_equations(self):
        """The recorder-off acceptance bar, jaxpr-asserted: a step that
        does not call group_nonfinite carries exactly the baseline
        program — same collective counts, same dot count, same
        intermediate shapes. The flags-on step adds exactly one psum
        per group and nothing else."""
        mesh = _mesh(2)

        def baseline(x):
            # hand-written reference step: no recorder import anywhere
            grads = {"a": {"w": x * 2.0}, "b": {"w": x + 1.0}}
            m = Metrics.empty().record(
                "loss", jnp.sum(grads["a"]["w"]), axis_name="tensor"
            )
            return m

        def step(with_flags):
            def f(x):
                grads = {"a": {"w": x * 2.0}, "b": {"w": x + 1.0}}
                m = Metrics.empty().record(
                    "loss", jnp.sum(grads["a"]["w"]), axis_name="tensor"
                )
                if with_flags:
                    m = m.merge(Metrics(group_nonfinite(
                        grads, axis_name="tensor"
                    )))
                return m
            return f

        x = jnp.ones((4,), jnp.float32)

        def shmap(f):
            return shard_map(
                f, mesh=mesh, in_specs=(P("tensor"),), out_specs=P(),
                check_vma=False,
            )

        ref = audit(shmap(baseline), x)
        off = audit(shmap(step(False)), x)
        on = audit(shmap(step(True)), x)
        assert off.counts == ref.counts
        assert off.dot_count == ref.dot_count
        assert off.shapes == ref.shapes
        assert on.count("psum") == ref.count("psum") + 2  # one/group
        assert on.dot_count == ref.dot_count


class TestFlightRecorder:
    def test_ring_window_and_no_dump_on_clean_run(self):
        rec = FlightRecorder(last_k=3)
        for it in range(5):
            assert rec.record(it, {"loss": 1.0 + it}) is None
        assert rec.dumps == []
        assert [s["step"] for s in rec._ring] == [2, 3, 4]

    def test_validation(self):
        with pytest.raises(ValueError, match="last_k"):
            FlightRecorder(last_k=0)

    def test_injected_nan_dumps_offending_group_and_agrees_with_scaler(
        self, tmp_path
    ):
        """The ISSUE-6 anomaly bar: a NaN forced into ONE param group's
        grads mid-run produces a dump naming that step and that group,
        and the amp scaler's skip-path counters tell the same story
        (one overflow, scale halved at the dumped step)."""
        scaler = LossScaler(loss_scale="dynamic")
        params = {
            "embedding": {"w": jnp.ones((4,), jnp.float32)},
            "head": {"w": jnp.ones((3,), jnp.float32)},
        }

        @jax.jit
        def step(sstate, x, inject):
            def loss_fn(p):
                terms = jax.tree_util.tree_map(
                    lambda w: jnp.sum((w * x[: w.shape[0]]) ** 2), p
                )
                leaves = jax.tree_util.tree_leaves(terms)
                return scaler.scale(sstate, sum(leaves))

            grads = jax.grad(loss_fn)(params)
            # the injection: poison ONE group's grads on demand
            grads["head"] = jax.tree_util.tree_map(
                lambda g: g + jnp.where(inject, jnp.nan, 0.0),
                grads["head"],
            )
            unscaled, found_inf = scaler.unscale(sstate, grads)
            sstate2, _ = scaler.update(sstate, found_inf)
            metrics = (
                Metrics.empty()
                .merge(Metrics(group_nonfinite(unscaled)))
                .merge(Metrics(scaler.telemetry(sstate2, found_inf)))
            )
            return sstate2, metrics

        dump_path = tmp_path / "nan_dump.jsonl"
        recorder = FlightRecorder(last_k=4, path=str(dump_path))
        sstate = scaler.init()
        x = jnp.arange(1.0, 5.0)
        bundles = []
        for it in range(6):
            sstate, metrics = step(sstate, x, jnp.asarray(it == 3))
            bundle = recorder.record(it, metrics)
            if bundle is not None:
                bundles.append(bundle)

        (bundle,) = bundles  # exactly the injected step dumped
        assert bundle["step"] == 3
        assert "head" in bundle["offending"]
        assert "embedding" not in bundle["offending"]
        assert "found_inf" in bundle["offending"]
        # scaler agreement: the snapshot rode the POST-update state —
        # one overflow counted, window reset, scale halved from the
        # init 2**16; and the live state says the same afterwards
        snap = bundle["snapshot"]
        assert snap["overflows"] == 1.0
        assert snap["unskipped"] == 0.0
        assert bundle["loss_scale"] == 2.0**15
        assert float(sstate.overflows) == 1.0
        assert float(sstate.loss_scale) == 2.0**15
        # the history window covers the steps leading into the blow-up
        assert [s["step"] for s in bundle["history"]] == [0, 1, 2, 3]
        assert all(
            s["nonfinite/head"] == 0.0 for s in bundle["history"][:-1]
        )
        # the jsonl artifact parses back to the same bundle
        (row,) = [
            json.loads(l) for l in dump_path.read_text().splitlines()
        ]
        assert row["step"] == 3 and row["offending"] == bundle["offending"]

    def test_max_dumps_caps_disk(self, tmp_path):
        path = tmp_path / "d.jsonl"
        rec = FlightRecorder(last_k=2, path=str(path), max_dumps=2)
        for it in range(5):
            rec.record(it, {"loss": float("nan")})
        assert len(rec.dumps) == 2
        assert len(path.read_text().splitlines()) == 2
