"""The program's spans, counters and operation scopes out of a capture
(`harness/program_trace.py`) and the nine per-layer metrics read from
them: on hand-made spans where the answer is known, on two stretches
recorded on the chip in PR 24 and kept beside the harness, on PR 23's
recordings (which hold no program span: every reader finds nothing and
says so), and end to end through a rehearsed run of the serving cell."""

import os
import pathlib

import pytest

from benchmarks import run as bench
from benchmarks.harness import program_trace, xplane
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.program_trace import ProgramTrace, Span

FIXTURES = pathlib.Path(xplane.__file__).parent / "fixtures"
SERVE = "gpt1p3b-serve-chat"
TRAIN = "bert345m-train-s512"

# What each reader gives on the stretches recorded on the chip (my chip
# runs, PR 24: `python -m benchmarks.harness.program_trace --ticks 3
# --save ...` after a `--trace 1` run of cell 1 in the second round's
# call 1, `--steps 2` after one of cell 2 in the first round's call 2;
# those whole runs read 0.009, 0.102, 2.818, 1.079, 0.067 ms, 36.3, 9.0,
# 19.9 % and 18.784 ms).
RECORDED = {
    "engine.admit_ms": 0.007349,
    "engine.pack_ms": 0.342749,
    "engine.dispatch_ms": 3.034178,
    "engine.result_wait_ms": 1.094654,
    "engine.commit_ms": 0.04788,
    "engine.decode_occupancy_pct": 18.75,
    "engine.mixed_tick_pct": 100.0 / 3,
    "engine.pages_used_pct": 10.0,
    "optimizer.kernels_device_ms": 18.7851005,
}
PHASES = ["engine.admit_ms", "engine.pack_ms", "engine.dispatch_ms",
          "engine.result_wait_ms", "engine.commit_ms"]


def reader(name):
    return Manifest(bench.ROOT).layer_metric(name)


def context_of(pt, trace=None, **more):
    """What `run_cell` hands a reader, from a recording: the traced
    stretch runs from the first to the last of the benchmark's spans (in
    a training run the last is the `loss_fetch` that waits for the last
    step, which a recording cut to two steps does not hold: there the
    stretch ends with the operations)."""
    trace = trace or pt.as_xplane()
    spans = trace.host_spans()
    ends = [s + d for _, s, d in spans] + [s + d for _, s, d in pt.ops]
    return dict(dict(
        trace=trace, program_trace=pt, t0_ns=spans[0][1], t1_ns=max(ends)),
        **more)


def recorded_context(name):
    cell = SERVE if name.startswith("engine.") else TRAIN
    file = "serve_phases" if cell == SERVE else "train_optimizer"
    return context_of(program_trace.load_json(FIXTURES / f"{file}.json.gz"))


def old_context(file):
    """One of PR 23's recordings as a reader meets the parent commit's
    capture: the benchmark's spans and the operations, no program span
    and no scope."""
    trace = xplane.load_json(FIXTURES / file)
    pt = ProgramTrace(
        [Span(xplane.SPAN_PREFIX + n, s, d, {}, "python3")
         for n, s, d in trace.host_spans()],
        [(n.partition(" = ")[0], s, d)
         for n, s, d in trace.ops(trace.device_planes()[0])])
    return context_of(pt, trace)


def span(name, start, dur, thread="main", **counts):
    return Span(name, start, dur, counts, thread)


def toy():
    host = [
        span("bench/engine.step", 0, 1000),
        span("engine.tick", 10, 900, tick=7, program="mixed", decodes=3,
             slots=4, slots_busy=4, chunk_tokens=8, prefill_tokens=8,
             budget=16, pages_used=5, pages_total=10, admitted=2,
             queue_depth=1, finished=1),
        span("engine.enqueue", 2, 3, request_id=1001, prompt_tokens=8),
        span("engine.enqueue", 6, 2, request_id=1002, prompt_tokens=5),
        span("engine.admit", 20, 30, request_ids="1001 1002"),
        span("engine.pack", 60, 100),
        span("engine.dispatch", 200, 300),
        span("engine.dispatch", 520, 80),  # a retried device step
        span("engine.fetch", 600, 250),
        span("engine.commit", 860, 40),
        span("engine.tick", 400, 50, thread="other", tick=0,
             program="none", slots=4),
        span("bench/engine.step", 1000, 500),
        span("engine.tick", 1010, 480, tick=8, program="decode", decodes=4,
             slots=4, chunk_tokens=0, budget=16, pages_used=6,
             pages_total=10),
        span("engine.fetch", 1100, 300),
    ]
    ops = [
        ("%optimizer.3", 450, 100),
        ("%fusion.4", 560, 240),
        ("%optimizer.9", 1150, 200),
        ("%optimizer_state.2", 1350, 10),
    ]
    modules = [("jit__mixed(1)", 560, 240), ("jit__decode(2)", 1150, 210)]
    return ProgramTrace(host, ops, modules)


# -- hand-made spans -----------------------------------------------------------


def test_children_and_self_time():
    pt = toy()
    tick = pt.spans("engine.tick")[0]
    kids = pt.children(tick)
    assert [k.name for k in kids] == [
        "engine.admit", "engine.pack", "engine.dispatch",
        "engine.dispatch", "engine.fetch", "engine.commit"]
    # the other thread's tick lies inside in time and is no child
    assert all(k.thread == "main" for k in kids)
    assert pt.self_ns(tick) == 900 - (30 + 100 + 300 + 80 + 250 + 40)
    assert pt.self_ns(kids[0]) == 30
    assert pt.children(kids[0]) == []


def test_ticks_carry_counters_and_summed_phases():
    ticks = toy().ticks(0, 1500)
    main = [(t, p) for t, p in ticks if t.thread == "main"]
    assert [t.counts["tick"] for t, _ in main] == [7, 8]
    assert main[0][1]["engine.dispatch"] == 380
    assert main[1][1] == {"engine.fetch": 300}
    # wholly inside [t0, t1) only
    assert [t.counts["tick"] for t, _ in toy().ticks(0, 1200)] == [7, 0]


def test_a_scope_is_told_by_the_instruction_name():
    under = toy().scoped_ops("optimizer", 0, 2000)
    assert [o[0] for o in under] == ["%optimizer.3", "%optimizer.9"]
    assert toy().scoped_ops("optimizer", 500, 2000) == [
        ("%optimizer.9", 1150, 200)]
    steps = ProgramTrace(
        toy().host + [span("bench/step_dispatch", 0, 10),
                      span("bench/step_dispatch", 20, 10)], toy().ops)
    assert reader("optimizer.kernels_device_ms").read(
        context_of(steps, t1_ns=2000)) == pytest.approx(300 / 2 / 1e6)


def test_round_trip_clip_and_the_older_readers_view(tmp_path):
    pt = toy()
    path = tmp_path / "pt.json.gz"
    program_trace.save_json(pt, path)
    back = program_trace.load_json(path)
    assert back.host == pt.host and back.ops == pt.ops
    assert back.modules == pt.modules
    cut = program_trace.clip(pt, 1000, 1500)
    assert cut.modules == [("jit__decode(2)", 1150, 210)]
    assert [s.name for s in cut.host] == [
        "bench/engine.step", "engine.tick", "engine.fetch"]
    assert [o[0] for o in cut.ops] == ["%optimizer.9", "%optimizer_state.2"]
    trace = pt.as_xplane()
    assert [n for n, _, _ in trace.host_spans()] == [
        "engine.step", "engine.step"]
    assert xplane.busy_seconds(trace, 0, 2000) == pytest.approx(
        (100 + 240 + 200 + 10) * 1e-9)
    part = program_trace.cut_ticks(pt, 1)
    assert [s.counts.get("tick") for s in part.host if s.thread == "main"][:2] == [
        None, 7]


def test_result_wait_is_host_stretch_less_the_programs_run(capsys):
    """From the last dispatch's return to the fetch's return, less the
    duration of the program execution around the fetch's middle:
    durations only, so a shift of the device's clock against the host's
    changes nothing."""
    wait = reader("engine.result_wait_ms")
    # tick 7: (850 - 600) - 240; tick 8 ran no dispatch
    assert wait.read(context_of(toy())) == pytest.approx(10 / 1e6)
    assert "mixed: jit__mixed x1; 0 ticks with no execution" in (
        capsys.readouterr().out)
    shifted = ProgramTrace(
        toy().host, toy().ops, [(n, s - 30, d) for n, s, d in toy().modules])
    assert wait.read(context_of(shifted)) == pytest.approx(10 / 1e6)
    # no execution around the fetch: the tick is left out and said so
    far = ProgramTrace(toy().host, toy().ops, [("jit__mixed(1)", 0, 100)])
    assert wait.read(context_of(far)) is None
    assert wait.read(context_of(ProgramTrace(toy().host, toy().ops))) is None


def test_queue_wait_joins_enqueue_and_admit_by_request_id():
    assert program_trace.queue_waits_ms(toy()) == {
        1001: pytest.approx(18 / 1e6), 1002: pytest.approx(14 / 1e6)}
    # a lease outside the stretch asked for is not counted
    assert program_trace.queue_waits_ms(toy(), 500, 1500) == {}


def test_counters_on_hand_made_ticks(capsys):
    ctx = context_of(toy(), ticks=[(0.0, 1.0, 5), (1.0, 2.0, 7)],
                     profiler={"t_start": 0.0, "t_stop": 3.0})
    assert reader("engine.decode_occupancy_pct").read(ctx) == pytest.approx(
        100.0 * 7 / 12)
    assert reader("engine.mixed_tick_pct").read(ctx) == pytest.approx(
        100.0 / 3)
    # the tick of the other thread has no pages: nothing to read
    assert reader("engine.pages_used_pct").read(ctx) is None
    ctx = context_of(
        ProgramTrace([s for s in toy().host if s.thread == "main"], []),
        ticks=[(0.0, 1.0, 5), (1.0, 2.0, 7)],
        profiler={"t_start": 0.0, "t_stop": 3.0})
    assert reader("engine.pages_used_pct").read(ctx) == pytest.approx(55.0)
    assert reader("engine.admit_ms").read(ctx) == pytest.approx(30 / 2 / 1e6)
    assert reader("engine.commit_ms").read(ctx) == pytest.approx(40 / 2 / 1e6)
    out = capsys.readouterr().out
    # the counters no metric's value reads are printed beside one
    assert "8 of 16 budgeted tokens (50.0%), 8 of them prompt" in out
    assert "12 slot-ticks in 3 ticks; 4 of them leased" in out
    assert ("2 requests leased a slot in 1 of 2 ticks; deepest queue left "
            "waiting 1; waits from enqueue to lease: median 0.000 ms") in out
    assert "1 ticks finished a request; their commit: median 0.000 ms" in out
    assert "mean pages_used 5.500 of 10" in out and "6.000 over 2" in out
    assert reader("engine.dispatch_ms").read(ctx) == pytest.approx(
        380 / 2 / 1e6)


def test_the_finder_picks_the_newest_capture(tmp_path):
    assert program_trace.find_newest(tmp_path) is None
    paths = []
    for i, cell in enumerate(["a", "b", "a"]):
        d = tmp_path / cell / "plugins" / "profile" / f"2026_0{i}"
        d.mkdir(parents=True)
        paths.append(d / "vm.xplane.pb")
        paths[-1].write_bytes(b"")
        os.utime(paths[-1], (1000 + i, 1000 + i))
    assert program_trace.find_newest(tmp_path) == str(paths[-1])
    os.utime(paths[1], (5000, 5000))
    assert program_trace.find_newest(tmp_path) == str(paths[1])


# -- the recordings ---------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_reader_on_the_chip_recording(name):
    assert reader(name).read(recorded_context(name)) == pytest.approx(
        RECORDED[name], rel=1e-6)


def test_the_phases_and_the_outside_reading_are_one_identity():
    """On the recorded ticks the five phase medians sum to the host time
    `engine.tick_host_ms` reads from outside, within 15%. That is an
    identity and no check of where the spans lie: the five sum to the
    tick less its program's run time by construction, and the outside
    reading is `step()` less the device's busy time inside it. It holds
    the two readings to one recording (a reader that dropped a phase or
    took the wrong execution would break it);
    `tests/L0/test_engine_phases.py` checks the spans' placement."""
    ctx = recorded_context("engine.admit_ms")
    outside = reader("engine.tick_host_ms").read(ctx)
    inside = sum(reader(n).read(ctx) for n in PHASES)
    assert inside == pytest.approx(outside, rel=0.15)


def test_the_recorded_ticks_hold_a_mixed_one_and_the_old_metrics_read():
    ctx = recorded_context("engine.admit_ms")
    programs = [t.counts["program"] for t, _ in program_trace.traced_ticks(ctx)]
    assert len(programs) == 3 and "mixed" in programs
    assert reader("step.device_ms.serve").read(ctx) > 0
    ctx = recorded_context("optimizer.kernels_device_ms")
    assert len(ctx["trace"].host_spans()) > 0
    assert reader("step.device_ms.train").read(ctx) > reader(
        "optimizer.kernels_device_ms").read(ctx) > 0


@pytest.mark.parametrize("name", sorted(RECORDED))
@pytest.mark.parametrize("file", ["serve_ticks.json.gz", "train_steps.json.gz"])
def test_reader_finds_nothing_in_a_capture_without_program_spans(name, file):
    """As on the parent commit, whose program opens no `apex/` span and
    has no `optimizer` scope: None, and nothing raised."""
    assert reader(name).read(old_context(file)) is None


def test_no_capture_at_all_reads_as_empty(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(program_trace, "TRACE_ROOT", tmp_path)
    ctx = {"t0_ns": 0, "t1_ns": 10, "trace": xplane.Trace({})}
    assert program_trace.of(ctx).host == []
    assert reader("engine.admit_ms").read(ctx) is None
    assert "no capture under" in capsys.readouterr().out


def test_a_capture_of_another_run_is_refused(tmp_path, monkeypatch, capsys):
    """The newest capture is this run's only if it holds a span inside
    the run's traced stretch; which file was read is said either way."""
    path = tmp_path / "cell" / "plugins" / "profile" / "x" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    monkeypatch.setattr(program_trace, "TRACE_ROOT", tmp_path)
    monkeypatch.setattr(program_trace, "load", lambda p: toy())
    ctx = {"t0_ns": 5000, "t1_ns": 9000}
    assert program_trace.of(ctx).host == []
    assert f"{path} holds no span inside the traced stretch" in (
        capsys.readouterr().out)
    ctx = {"t0_ns": 0, "t1_ns": 1500}
    assert program_trace.of(ctx).host == toy().host
    assert f"read {path}: 12 program spans, 4 operations, 2 program" in (
        capsys.readouterr().out)


# -- the manifest, and a run ---------------------------------------------------


def test_manifest_is_sound_with_the_nine_entries():
    m = Manifest(bench.ROOT)
    assert m.problems() == []
    serve, train = m.cell(SERVE)["per_layer"], m.cell(TRAIN)["per_layer"]
    assert set(RECORDED) - {"optimizer.kernels_device_ms"} <= set(serve)
    assert "optimizer.kernels_device_ms" in train and "optimizer.kernels_device_ms" not in serve
    assert len(serve) == 5 + 8 and len(train) == 4 + 1
    assert {m.per_layer[n]["source"] for n in PHASES} == {"program_span"}


def test_rehearsed_serving_run_prints_the_host_only_metrics(capsys):
    """Cell 1 at toy size on the CPU under `--trace 1`: the default
    engine, built by the family with no tracer, emits the spans, and the
    readers that need no device plane print what they read."""
    rc = bench.main([
        "--workload", SERVE, "--seed", str(2**31 + 24), "--seconds", "1.5",
        "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0, out
    for label in ("engine.admit: median", "engine.pack + engine.table_push",
                  "engine.rng + engine.dispatch", "engine.commit: median",
                  "engine.decode_occupancy_pct:", "engine.mixed_tick_pct:",
                  "engine.pages_used_pct: mean pages_used"):
        assert label in out, label
    # the engine's count and the benchmark's own read agree
    line = next(l for l in out.splitlines() if "engine.pages_used_pct:" in l)
    words = line.replace(":", " ").split()
    assert words[words.index("pages_used") + 1] == words[
        words.index("step()") + 1]
    # the counters that enter no metric's value are read out beside one
    assert "requests leased a slot in" in out and "of them leased" in out
    # no device plane on a CPU: the result's wait has nothing to read
    assert "engine.result_wait_ms" not in out
