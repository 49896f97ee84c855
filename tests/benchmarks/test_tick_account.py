"""The tick's account out of a capture (`harness/tick_account.py`) and the
nine per-layer metrics read from it (ISSUE 37): the clock's interval and
the bubble's parts on hand-made captures where the answer is known, each
reader on a stretch of a saturated cell recorded on the chip with the
finished engine (`fixtures/serve_bubbles.json.gz`), on captures that hold
no tick or none of the new counts (None, nothing raised), and the
manifest's new entries.

Every context here carries its capture under `program_trace`; nothing
reads or patches `program_trace.TRACE_ROOT`.
"""

import pathlib

import pytest

from benchmarks import run as bench
from benchmarks.harness import program_trace, tick_account, xplane
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.program_trace import ProgramTrace, Span

FIXTURES = pathlib.Path(xplane.__file__).parent / "fixtures"
GRANITE = "granite4hs-serve-chat"
LONGCAT = "longcat-serve-agent-sat"
SMALLTHINKER = "smallthinker-serve-longmix-sat"
SATURATED = [GRANITE, LONGCAT, SMALLTHINKER]

# the new entries and the cells each is listed for (ISSUE 37's table: a
# traced stretch of the smallthinker cell may hold no decode tick)
NEW = {
    "tick.mixed_device_ms": SATURATED,
    "tick.decode_device_ms": [GRANITE, LONGCAT],
    "tick.mixed_host_ms": SATURATED,
    "tick.decode_host_ms": [GRANITE, LONGCAT],
    "device.bubble_ms.serve": SATURATED,
    "engine.loop_gap_ms": SATURATED,
    "host.gc_pause_ms_per_s": SATURATED,
    "host.longest_stall_ms": SATURATED,
    "engine.mixed_time_share_pct": SATURATED,
}
# readers that need a count of this PR's engine; the others read spans
# and executions, which the parent commit's capture holds too
FROM_COUNTS = ["engine.loop_gap_ms", "host.gc_pause_ms_per_s",
               "host.longest_stall_ms", "engine.mixed_time_share_pct"]
# every older entry's list, as the parent commit's manifest has it: the
# open form of what `test_longcat_cell.py` and `test_smallthinker_cell.py`
# pinned (held as a lower bound: a later PR that appends to a list or adds
# an entry does not have to edit this)
GPT, BERT = ["gpt1p3b-serve-chat"], ["bert345m-train-s512"]
OLDER = {
    "engine.tick_host_ms": GPT, "engine.queue_wait_p95_ms": GPT,
    "step.device_ms.serve": GPT, "decode_paged_roofline": GPT,
    "device.idle_pct.serve": GPT, "train_step.mfu_pct": BERT,
    "step.device_ms.train": BERT, "attn_train_roofline": BERT,
    "device.idle_pct.train": BERT, "engine.admit_ms": GPT,
    "engine.pack_ms": GPT, "engine.dispatch_ms": GPT,
    "engine.result_wait_ms": GPT, "engine.commit_ms": GPT,
    "engine.decode_occupancy_pct": GPT, "engine.mixed_tick_pct": GPT,
    "engine.pages_used_pct": GPT, "optimizer.kernels_device_ms": BERT,
    "moe.device_ms": [GRANITE, LONGCAT], "ssm.device_ms": [GRANITE],
    "moe_experts_roofline": [GRANITE, LONGCAT],
    "ssm_scan_roofline": [GRANITE],
    "moe.load_max_over_mean": [GRANITE, LONGCAT],
    "mla.device_ms": [LONGCAT], "mla_decode_roofline": [LONGCAT],
    "moe.zero_share_pct": [LONGCAT],
    "attn_paged.device_ms": [SMALLTHINKER],
    "attn_window_decode_roofline": [SMALLTHINKER],
    "attn_global_decode_roofline": [SMALLTHINKER],
    "kv.window_rows_dropped_pct": [SMALLTHINKER],
    "engine.window_pages_used_pct": [SMALLTHINKER],
}


def reader(name):
    return Manifest(bench.ROOT).layer_metric(name)


def context_of(pt):
    ends = [s.end_ns for s in pt.host] + [s + d for _, s, d in pt.modules]
    return {"program_trace": pt, "t0_ns": 0, "t1_ns": max(ends, default=1)}


# -- a hand-made capture: six ticks, every time known ------------------------------

OFFSET = 700_000  # host clock - device clock, ns
SLOW = "gap 30 admit 20 pack 700 table_push 100 dispatch 1200 fetch 92000 commit 150 rest 50"


def synthetic(n=6, offset=OFFSET, counts=True, pause=None, marks=False):
    """``n`` ticks of a synchronous engine, mixed every third; the
    device's clock runs ``offset`` behind the host's. Launch lags are 90,
    100, 110 us and fetch tails 60, 80 us in turn, so the offset's
    interval is [offset - 90, offset + 60] us. ``pause`` = (tick index,
    ns): a `host.gc` span of that length in the loop's gap before it.
    ``marks``: the runtime enqueues each program 30 us before it starts
    and sees it done 25 us after it ends, on threads of their own."""
    host, modules = [], []
    t, cum = 1_000_000, {"mixed": 0.0, "decode": 0.0, "gap": 0.0}
    ticks = {"mixed": 0, "decode": 0}
    for i in range(n):
        program = "mixed" if i % 3 == 0 else "decode"
        run = 30_000_000 if program == "mixed" else 17_000_000
        gap = 40_000 if i else 0
        if pause and pause[0] == i:
            host.append(Span("host.gc", t + 10_000, pause[1],
                             {"generation": 2}, "main"))
            gap += pause[1]
        t += gap
        tick0 = t
        admit = (tick0 + 10_000, 20_000)
        pack = (tick0 + 40_000, 600_000 + 10_000 * i)
        push = (pack[0] + pack[1] + 5_000, 100_000)
        dispatch = (push[0] + push[1] + 15_000, 1_200_000)
        exec0 = dispatch[0] + 90_000 + 10_000 * (i % 3)  # on the host's clock
        tail = 60_000 + 20_000 * (i % 2)
        fetch0 = dispatch[0] + dispatch[1] + 3_000
        fetch = (fetch0, exec0 + run + tail - fetch0)
        commit = (fetch[0] + fetch[1] + 8_000, 150_000)
        tick1 = commit[0] + commit[1] + 12_000
        ticks[program] += 1
        cum[program] += (tick1 - tick0) / 1e6
        cum["gap"] += gap / 1e6
        c = dict(tick=100 + i, program=program, model_passes=1, budget=512,
                 chunk_tokens=400 if program == "mixed" else 0)
        if counts:
            paused = pause[1] if pause and pause[0] <= i else 0
            here = pause[1] if pause and pause[0] == i else 0
            c.update(
                gap_us=gap // 1000, gc_us=here // 1000, gc_n=int(bool(here)),
                cum_ticks_mixed=ticks["mixed"], cum_ticks_decode=ticks["decode"],
                cum_ms_mixed=cum["mixed"], cum_ms_decode=cum["decode"],
                cum_gap_ms=cum["gap"], cum_gc_ms=paused / 1e6,
                cum_gc_n=int(bool(paused)), gc_max_ms=paused / 1e6,
                cum_prefill_tokens=4000, cum_generated=500, slow_ms=95.0,
                slow_tick=7, slow_program="decode", slow_phases=SLOW)
        host += [
            Span("bench/engine.step", tick0 - 5_000, tick1 - tick0 + 8_000,
                 {}, "main"),
            Span("engine.tick", tick0, tick1 - tick0, c, "main"),
            Span("engine.admit", *admit, {}, "main"),
            Span("engine.pack", *pack, {}, "main"),
            Span("engine.table_push", *push, {}, "main"),
            Span("engine.dispatch", *dispatch, {}, "main"),
            Span("engine.fetch", *fetch, {}, "main"),
            Span("engine.commit", *commit, {}, "main")]
        modules.append((f"jit__{program}(1)", exec0 - offset, run))
        if marks:
            host += [
                Span(tick_account.LAUNCH, exec0 - 30_000, 95_000, {}, "runtime"),
                Span(tick_account.DONE, exec0 + run + 25_000, 90_000, {},
                     "callbacks")]
        t = tick1
    host.sort(key=lambda s: (s.start_ns, -s.dur_ns))
    return ProgramTrace(host, [], modules)


def test_every_tick_is_joined_to_its_execution():
    ticks, unmatched = tick_account.join(synthetic())
    assert unmatched == [] and len(ticks) == 6
    assert [t.execution[0] for t in ticks] == [
        "jit__mixed(1)", "jit__decode(1)", "jit__decode(1)"] * 2
    assert [k.name for k in ticks[0].kids] == [
        "engine." + n for n in tick_account.PHASES]
    # an execution that does not hold the fetch's middle is no match
    pt = synthetic()
    pt.modules[2] = ("jit__decode(1)", pt.modules[2][1] + 10**10, 10)
    ticks, unmatched = tick_account.join(pt)
    assert ticks[2].execution is None
    assert unmatched == ["tick 102 (decode, fetch 16.0 ms)"]


@pytest.mark.parametrize("offset", [OFFSET, -1_400_000, 0])
def test_the_clocks_interval_holds_the_known_offset(offset):
    acc = tick_account.build(synthetic(offset=offset))
    lo, hi = acc.clock
    assert (lo, hi) == (offset - 90_000, offset + 60_000)
    assert lo <= offset <= hi and acc.offset_ns == offset - 15_000
    assert "width 150.0 us, bounded by the dispatch's entry and the fetch's" in (
        tick_account.clock_line(acc))


def test_the_runtimes_events_close_the_interval(capsys):
    acc = tick_account.build(synthetic(marks=True))
    assert acc.clock == (OFFSET - 30_000, OFFSET + 25_000)
    assert all(t.launch is not None and t.done is not None for t in acc.ticks)
    assert "DoEnqueueProgram and ReadSyncFlag events of 6 ticks" in (
        tick_account.clock_line(acc))
    fine = acc.bubbles[1].fine()  # between ticks 1 and 2, d 2.5 us off
    assert fine == {
        "end_to_seen": 25_000 + 2_500, "seen_to_return": 55_000,
        "entry_to_enqueue": 80_000, "enqueue_to_start": 30_000 - 2_500}
    assert sum(fine.values()) == (
        acc.bubbles[1].parts()["fetch_tail"]
        + acc.bubbles[1].parts()["launch_lag"])
    reader("device.bubble_ms.serve").read(
        dict(context_of(synthetic(marks=True)), tick_account=acc))
    assert "by the runtime's own events, median ms: end_to_seen 0.028" in (
        capsys.readouterr().out)
    assert acc.split
    # events that contradict causality (the program seen done BEFORE its
    # end on any clock its enqueueing allows) are not read, and said: the
    # spans alone bound the clock, and the tail and the lag are one part
    pt = synthetic(marks=True)
    pt.host = [
        Span(s.name, s.start_ns - 400_000, s.dur_ns, s.counts, s.thread)
        if s.name == tick_account.DONE else s for s in pt.host]
    acc = tick_account.build(pt)
    assert acc.clock == (OFFSET - 90_000, OFFSET + 60_000) and not acc.split
    assert "events of 6 ticks the clock's interval is empty by 345.0 us" in (
        capsys.readouterr().out)
    assert "bounded by the dispatch's entry" in tick_account.clock_line(acc)


def test_without_the_runtimes_events_tail_and_lag_are_one_part(capsys):
    """The spans alone leave the clock open by the least lag plus the
    least tail; its middle would make the two equal by construction, so
    they are printed as their sum, which no clock moves."""
    acc = tick_account.build(synthetic())
    assert not acc.split
    parts = [b.parts() for b in acc.bubbles]
    shown = dict(tick_account.shown_parts(acc, parts))
    assert list(shown) == ["fetch_tail+launch_lag", *tick_account.PARTS[1:-1]]
    assert shown["fetch_tail+launch_lag"] == [
        x["fetch_tail"] + x["launch_lag"] for x in parts]
    assert sum(map(sum, shown.values())) == sum(b.ns for b in acc.bubbles)
    line = tick_account.clock_line(acc)
    assert line.endswith("they are given as one part")
    reader("device.bubble_ms.serve").read(context_of(synthetic()))
    out = capsys.readouterr().out
    # a fallback nobody asked for is noticed: the account's first line
    # says under which versions it looked for the runtime's events
    assert "(read under jax " in out and ", libtpu " in out
    # a tail of 60 or 80 us and then a lag of 90, 100 or 110: the five
    # bubbles hold 160, 190, 150, 180 and 170 us of them
    assert "fetch_tail+launch_lag 0.170 (0.0008), commit 0.150" in out
    assert " launch_lag 0." not in out and "by the runtime's own" not in out
    # with them the nine parts are read apart
    marked = tick_account.build(synthetic(marks=True))
    assert [p for p, _ in tick_account.shown_parts(
        marked, [b.parts() for b in marked.bubbles])] == list(tick_account.PARTS)
    assert "one part" not in tick_account.clock_line(marked)


def test_an_empty_interval_is_read_as_no_clock(capsys):
    """One execution shifted against the others (drift, or a tick joined
    to the wrong program) empties the interval: by under 50 us the middle
    is still taken, by more nothing is read."""
    def shifted(by):
        pt = synthetic()
        name, start, dur = pt.modules[3]
        pt.modules[3] = (name, start + by, dur)
        return tick_account.build(pt)

    near = shifted(-190_000)  # the interval is empty by 40 us
    assert near.clock[1] - near.clock[0] == -40_000 and len(near.bubbles) == 5
    far = shifted(-400_000)
    assert far.clock is None and far.bubbles == []
    assert "empty by 250.0 us" in capsys.readouterr().out
    ctx = context_of(synthetic())
    ctx["tick_account"] = far
    assert reader("device.bubble_ms.serve").read(ctx) is None
    # the metrics that need no clock still read
    assert reader("tick.decode_device_ms").read(ctx) == pytest.approx(17.0)


@pytest.mark.parametrize("d", [OFFSET, OFFSET - 90_000, OFFSET + 3_000_000])
def test_the_parts_sum_to_the_bubble_whatever_the_offset(d):
    ticks, _ = tick_account.join(synthetic())
    for a, b in zip(ticks, ticks[1:]):
        bubble = tick_account.bubble_between(a, b, d)
        parts = bubble.parts()
        assert set(parts) == set(tick_account.PARTS)
        assert sum(parts.values()) == bubble.ns
        # d only moves time between the tail and the lag
        true = tick_account.bubble_between(a, b, OFFSET).parts()
        assert parts["fetch_tail"] - true["fetch_tail"] == OFFSET - d
        assert parts["launch_lag"] - true["launch_lag"] == d - OFFSET
        for part in tick_account.PARTS[1:-1]:
            assert parts[part] == true[part], part


def test_the_parts_are_the_times_the_capture_was_made_from():
    ticks, _ = tick_account.join(synthetic())
    parts = tick_account.bubble_between(ticks[1], ticks[2], OFFSET).parts()
    assert parts == {
        "fetch_tail": 80_000, "commit": 150_000, "rest_after": 8_000 + 12_000,
        "loop_gap": 40_000, "admit": 20_000, "pack": 620_000,
        "table_push": 100_000, "rest_before": 10_000 + 10_000 + 5_000 + 15_000,
        "launch_lag": 110_000}


def test_ticks_that_do_not_follow_on_make_no_bubble():
    pt = synthetic()
    gone = next(s for s in pt.host
                if s.name == "engine.tick" and s.counts["tick"] == 102)
    cut = ProgramTrace(
        [s for s in pt.host
         if not gone.start_ns <= s.start_ns < gone.end_ns], [], pt.modules)
    acc = tick_account.build(cut)
    assert len(acc.ticks) == 5 and len(acc.bubbles) == 3


def test_a_pause_of_the_collector_shows_under_the_part_it_fell_in(capsys):
    ctx = context_of(synthetic(pause=(4, 160_000_000)))
    value = reader("device.bubble_ms.serve").read(ctx)
    out = capsys.readouterr().out
    assert "of which host.gc pauses: loop_gap 160.000 ms (1 pauses" in out
    assert value == pytest.approx(1.18, abs=0.011)  # the median hardly moves
    assert reader("host.gc_pause_ms_per_s").read(ctx) == pytest.approx(
        160.0 / ((2 * 31.105 + 4 * 18.13 + 160.2) / 1e3), rel=2e-3)
    out = capsys.readouterr().out
    assert "cum_gc_n 1" in out and "gc_max_ms 160.000" in out
    assert "generation 2: 1, 160.000 ms, longest 160.000" in out
    # the loop's gap as the engine counts it, beside the capture's own
    assert reader("engine.loop_gap_ms").read(ctx) == pytest.approx(0.04)


def test_the_readers_on_the_hand_made_capture(capsys):
    ctx = context_of(synthetic())
    assert reader("tick.mixed_device_ms").read(ctx) == pytest.approx(30.0)
    assert reader("tick.decode_device_ms").read(ctx) == pytest.approx(17.0)
    out = capsys.readouterr().out
    assert "over 2 ticks" in out and "model_passes [1]" in out
    assert "chunk_tokens 800 of budget 1024 (78.1%)" in out
    assert "6 ticks, 6 joined to an execution, 0 with none" in out
    # the wall less the run: what lies before the launch and after the end
    assert reader("tick.mixed_host_ms").read(ctx) == pytest.approx(1.105)
    assert reader("tick.decode_host_ms").read(ctx) == pytest.approx(1.125)
    assert reader("device.bubble_ms.serve").read(ctx) == pytest.approx(1.18)
    out = capsys.readouterr().out
    assert "d in [610.0, 760.0] us, width 150.0 us" in out
    assert "pack 0.630 (0.0032)" in out and "the parts sum to 0.0059 s" in out
    assert "before a mixed tick (1)" in out and "before a decode tick (4)" in out
    assert "(gap_us): median 0.040 ms" in out
    assert reader("engine.loop_gap_ms").read(ctx) == pytest.approx(0.04)
    assert reader("host.gc_pause_ms_per_s").read(ctx) == 0.0  # not None
    # the slowest tick was a decode tick: 95 ms against 18.125 + 0.04
    assert reader("host.longest_stall_ms").read(ctx) == pytest.approx(76.835)
    out = capsys.readouterr().out
    assert "slow_tick 7 (decode)" in out and "fetch 92000 (15972)" in out
    assert reader("engine.mixed_time_share_pct").read(ctx) == pytest.approx(
        100 * 62.21 / (62.21 + 72.52), abs=0.01)
    assert "= 8.00 prompt rows a token" in capsys.readouterr().out


def test_the_profilers_own_start_is_kept_out_of_the_longest_stall(capsys):
    """The benchmark starts the capture between two ticks, so its start is
    the first traced tick's gap. Where that is the record since reset the
    reader says so and gives the stretch's own slowest tick after the
    first: here a collection of 60 ms before tick 104."""
    pt = synthetic(pause=(4, 60_000_000))
    for s in pt.host:
        if s.name == "engine.tick":
            s.counts.update(
                slow_ms=75.105, slow_tick=100, slow_program="mixed",
                slow_phases="gap 44000 admit 20 pack 600 table_push 100 "
                "dispatch 1200 fetch 29000 commit 150 rest 35")
    value = reader("host.longest_stall_ms").read(context_of(pt))
    out = capsys.readouterr().out
    assert "slow_tick 100 (mixed)" in out
    assert "the record is the first traced tick and its gap (44.000 ms)" in out
    assert "the slowest is tick 104 (decode)" in out
    assert "of which the collector (gc_us) 60.000" in out
    # tick 104: wall 18.13 + gap 60.04 against the decode ticks' median
    # wall + gap, 18.185 (the stalled tick is one of the four)
    assert value == pytest.approx(59.985, abs=1e-6)
    # the same record at another tick, or in another phase, is the system's
    for change in (dict(slow_tick=103), dict(
            slow_phases="gap 40 admit 20 pack 600 table_push 100 dispatch "
            "1200 fetch 72960 commit 150 rest 35")):
        for s in pt.host:
            if s.name == "engine.tick":
                s.counts.update(change)
        assert reader("host.longest_stall_ms").read(
            context_of(pt)) == pytest.approx(75.105 - 31.125, abs=0.011)
        assert "the record is the first" not in capsys.readouterr().out


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_in_a_capture_without_ticks(name):
    empty = ProgramTrace([], [], [])
    assert reader(name).read(context_of(empty)) is None
    # the benchmark's own spans and the operations alone, as PR 23 recorded
    old = xplane.load_json(FIXTURES / "serve_ticks.json.gz")
    pt = ProgramTrace(
        [Span(xplane.SPAN_PREFIX + n, s, d, {}, "python3")
         for n, s, d in old.host_spans()], [])
    assert reader(name).read(context_of(pt)) is None


@pytest.mark.parametrize("name", FROM_COUNTS)
def test_reader_finds_nothing_where_the_engine_keeps_no_such_count(name):
    """As on the parent commit, whose ticks carry none of the account:
    None and nothing raised. The spans and executions are there, so the
    other five read."""
    ctx = context_of(synthetic(counts=False))
    assert reader(name).read(ctx) is None
    assert reader("device.bubble_ms.serve").read(ctx) == pytest.approx(1.18)
    assert reader("tick.mixed_host_ms").read(ctx) == pytest.approx(1.105)


# -- the stretch recorded on the chip ----------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    return program_trace.load_json(FIXTURES / "serve_bubbles.json.gz")


def test_the_recorded_stretch_holds_both_programs_and_the_account(recorded):
    acc = tick_account.build(recorded)
    programs = [t.program for t in acc.ticks]
    assert len(programs) >= 12
    assert programs.count("mixed") >= 2 and programs.count("decode") >= 2
    numbers = [int(t.counts["tick"]) for t in acc.ticks]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    assert acc.unmatched == [] and len(acc.bubbles) == len(acc.ticks) - 1
    lo, hi = acc.clock
    assert 0 <= hi - lo < 300_000  # 227 us over fourteen ticks, 36 over 163
    assert "DoEnqueueProgram and ReadSyncFlag events of 14 ticks" in (
        tick_account.clock_line(acc))
    # without the runtime's events the same ticks leave d open by 2 ms
    spans_only = tick_account.clock_interval(acc.ticks, runtime=False)
    assert spans_only[0] < lo and hi < spans_only[1]
    assert spans_only[1] - spans_only[0] > 1_500_000
    for b in acc.bubbles:
        parts = b.parts()
        assert sum(parts.values()) == b.ns
        assert all(v >= 0 for v in parts.values()), parts
    last = acc.ticks[-1].counts
    assert int(last["cum_ticks_mixed"]) + int(last["cum_ticks_decode"]) > 1000
    # the loop's gap from inside (`gap_us`) and from the capture agree
    for b in acc.bubbles:
        assert abs(b.parts()["loop_gap"] / 1e3 - int(b.after.counts["gap_us"])) < 15


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_chip_recording(name, recorded):
    assert reader(name).read(context_of(recorded)) == pytest.approx(
        RECORDED[name], rel=1e-6)


# What each reader gives on that stretch (my chip run, PR 37: fourteen
# ticks from the start of the traced stretch of a `--trace 1` run of
# `granite4hs-serve-chat` with the finished engine, seed 3700000301, cut
# with `program_trace.clip` and `save_json` as `program_trace.py --ticks
# --save` does, keeping the runtime's `DoEnqueueProgram` and
# `ReadSyncFlag` events among the host spans and no device operation;
# that whole run's 163 traced ticks read 64.034, 15.640, 3.981, 2.453,
# 2.546, 0.024, 0.100, 90.17 and 40.365).
RECORDED = {
    "tick.mixed_device_ms": 63.8013015,
    "tick.decode_device_ms": 15.172448,
    "tick.mixed_host_ms": 4.4949585,
    "tick.decode_host_ms": 2.4823405,
    "device.bubble_ms.serve": 2.506224,
    "engine.loop_gap_ms": 0.027,
    "host.gc_pause_ms_per_s": 0.10312765766163938,
    "host.longest_stall_ms": 67.11155,
    "engine.mixed_time_share_pct": 40.85823374072949,
}


# -- the manifest -------------------------------------------------------------------


def test_the_new_entries_are_sound_and_listed_for_their_cells():
    """Open on purpose: a later PR may list any of these for another
    cell, add entries and reorder them without editing this test."""
    m = Manifest(bench.ROOT)
    assert m.problems() == []
    assert set(NEW) <= set(m.per_layer)
    for name, cells in NEW.items():
        entry = m.per_layer[name]
        assert set(cells) <= set(entry["workloads"]), name
        assert entry["moves"] == "serve_out_tokens_per_s"
        assert (bench.ROOT / "benchmarks" / "layer_metrics"
                / f"{name}.py").is_file()
    assert {m.per_layer[n]["layer"] for n in NEW} == {
        "model step", "serving engine", "device"}
    assert m.per_layer["device.bubble_ms.serve"]["source"] == "device_trace"
    assert {m.per_layer[n]["source"] for n in FROM_COUNTS} == {
        "program_counter"}
    for cell in SATURATED:
        assert set(NEW) - {"tick.decode_device_ms", "tick.decode_host_ms"} <= (
            set(m.cell(cell)["per_layer"]))


def test_every_older_entry_still_lists_what_the_parents_manifest_has():
    """A superset test: what the parent commit's manifest listed is still
    listed (an entry taken away or a cell dropped from a list fails), and
    a list may grow."""
    m = Manifest(bench.ROOT)
    for name, cells in OLDER.items():
        assert set(cells) <= set(m.per_layer[name]["workloads"]), name
