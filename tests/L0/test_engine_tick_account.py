"""The tick's account of itself (ISSUE 37): the loop's gap, ticks and
their wall time by program, the collector's pauses and the slowest tick,
as counts on ``engine.tick`` and in `stats()`; the collector's hook in
`monitor/trace.py`.

Read from an enabled `Tracer`'s ring (the args of a ring span are the
annotation's metadata: tests/L0/test_engine_phases.py) and, for the
``host.gc`` span, from a real `jax.profiler` capture.

Wall-time note (ROADMAP): the engine is test_engine_phases' (and so
test_paging's) shape tuple: its programs are compile-cache hits.
"""

import gc
import time

import jax
import pytest
from test_engine_phases import MAX_NEW, PROMPTS, make_engine, read_capture

from rocm_apex_tpu.inference.engine import TICK_PHASES
from rocm_apex_tpu.monitor import Tracer
from rocm_apex_tpu.monitor import trace as trace_mod

# every count the account puts on ``engine.tick`` beside the tick's own
# (docs/observability.md names each one's reader)
ACCOUNT = {
    "gap_us", "gc_us", "gc_n",
    "cum_ticks_mixed", "cum_ticks_decode", "cum_ms_mixed", "cum_ms_decode",
    "cum_gap_ms", "cum_gc_ms", "cum_gc_n", "gc_max_ms",
    "cum_prefill_tokens", "cum_generated",
    "slow_ms", "slow_tick", "slow_program", "slow_phases",
}
# what a chunked GPT engine's tick carried before (PERF.md section 3)
TICK_COUNTS = {
    "tick", "program", "model_passes", "decodes", "slots", "chunk_tokens",
    "budget", "pages_used", "pages_total", "slots_busy", "prefill_tokens",
    "admitted", "queue_depth", "finished",
}
IN_STATS = sorted(
    ACCOUNT - {"gap_us", "gc_us", "gc_n", "slow_program", "slow_phases",
               "cum_prefill_tokens", "cum_generated"})


def tick_args(tracer):
    return [
        e["args"] for e in tracer.events()
        if e["ph"] == "X" and e["name"] == "engine.tick"]


def step_all(eng):
    while eng.has_work():
        eng.step()


def warm_engine(tracer=None):
    """An engine that has served PROMPTS (both programs exist), its
    stats reset and its tracer's ring empty."""
    eng = make_engine(tracer=tracer)
    for p in PROMPTS:
        eng.add_request(p, MAX_NEW)
    step_all(eng)
    eng.reset_stats()
    if tracer is not None:
        tracer.clear()
    return eng


@pytest.fixture(scope="module")
def served():
    """A warmed-up engine serves PROMPTS after `reset_stats`; the ticks'
    args, and `stats()` and the record as read after the last tick."""
    tracer = Tracer()
    eng = warm_engine(tracer)
    for p in PROMPTS:
        eng.add_request(p, MAX_NEW)
    step_all(eng)
    return {"ticks": tick_args(tracer), "stats": eng.stats(),
            "record": eng.slowest_tick()}


def test_the_tick_carries_exactly_its_counts_and_the_account(served):
    for t in served["ticks"]:
        assert set(t) == TICK_COUNTS | ACCOUNT


def test_gap_is_zero_after_a_reset_and_after_an_idle_engine(served):
    ticks = served["ticks"]
    assert ticks[0]["gap_us"] == 0  # the first tick after reset_stats
    # the serving loop above came straight back while there was work
    assert all(t["gap_us"] > 0 for t in ticks[1:])
    tracer = Tracer()
    eng = warm_engine(tracer)
    eng.add_request(PROMPTS[1], 1)
    step_all(eng)
    assert not eng.has_work()  # the last tick left nothing behind
    tracer.clear()
    time.sleep(0.02)  # waiting for a request is not the loop's time
    eng.add_request(PROMPTS[0], MAX_NEW)
    eng.step()
    assert eng.has_work()
    time.sleep(0.02)  # ... and this is
    eng.step()
    step_all(eng)
    first, second = tick_args(tracer)[:2]
    assert first["gap_us"] == 0
    assert 20_000 <= second["gap_us"] < 2_000_000
    assert second["cum_gap_ms"] - first["cum_gap_ms"] == pytest.approx(
        second["gap_us"] / 1e3, abs=2e-3)


def test_cumulative_counts_are_monotone_and_split_by_program(served):
    ticks = served["ticks"]
    assert {t["program"] for t in ticks} == {"mixed", "decode"}
    mixed = decode = 0
    before = None
    for t in ticks:
        mixed += t["program"] == "mixed"
        decode += t["program"] == "decode"
        assert (t["cum_ticks_mixed"], t["cum_ticks_decode"]) == (mixed, decode)
        if before is not None:
            for key in ACCOUNT - {"gap_us", "gc_us", "gc_n", "slow_tick",
                                  "slow_program", "slow_phases"}:
                assert t[key] >= before[key], key
            grew = "cum_ms_" + t["program"]
            other = "cum_ms_" + ("decode" if t["program"] == "mixed" else "mixed")
            assert t[grew] > before[grew] and t[other] == before[other]
        before = t
    # the ticks' wall and the gaps between them fill the serving loop
    assert ticks[-1]["cum_gap_ms"] == pytest.approx(
        sum(t["gap_us"] for t in ticks) / 1e3, abs=1e-3 * len(ticks))


def test_the_last_tick_carries_what_stats_returns(served):
    last, stats = served["ticks"][-1], served["stats"]
    for key in IN_STATS:
        assert stats[key] == last[key], key
    assert last["cum_generated"] == stats["generated_tokens"] == (
        len(PROMPTS) * MAX_NEW)
    assert last["cum_prefill_tokens"] == stats["prompt_tokens"] == sum(
        len(p) for p in PROMPTS)
    assert last["cum_ticks_mixed"] == stats["mixed_steps"]
    record = served["record"]
    assert record == {k: last[k] for k in record}
    assert set(record) == {"slow_ms", "slow_tick", "slow_program",
                           "slow_phases"}
    # `stats()` stays name -> number: `MetricsLogger.log_step` floats it
    assert all(isinstance(float(v), float) for v in stats.values())


def test_reset_stats_zeroes_the_account():
    eng = make_engine()
    for p in PROMPTS[:2]:
        eng.add_request(p, 2)
    step_all(eng)
    assert eng.stats()["cum_ms_mixed"] > 0 and eng.stats()["slow_ms"] > 0
    eng.reset_stats()
    stats = eng.stats()
    assert [stats[k] for k in IN_STATS if k != "slow_tick"] == [0] * (
        len(IN_STATS) - 1)
    assert eng.slowest_tick() == {
        "slow_ms": 0.0, "slow_tick": -1, "slow_program": "none",
        "slow_phases": ""}


def test_a_forced_collection_shows_in_the_next_tick():
    tracer = Tracer()
    eng = warm_engine(tracer)
    eng.add_request(PROMPTS[2], MAX_NEW)
    eng.step()
    before = trace_mod.gc_pauses()
    gc.collect()
    after = trace_mod.gc_pauses()
    eng.step()
    step_all(eng)
    assert after[0] == before[0] + 1 and after[1] > before[1]
    paused_ms = 1e3 * (after[1] - before[1])
    ticks = tick_args(tracer)
    hit = ticks[1]
    assert hit["gc_n"] >= 1
    assert hit["gc_us"] >= int(1e3 * paused_ms) > 0
    assert hit["gap_us"] >= int(1e3 * paused_ms)  # it fell in the loop's gap
    assert hit["cum_gc_n"] == ticks[0]["cum_gc_n"] + hit["gc_n"]
    assert hit["gc_max_ms"] >= min(paused_ms, 1e3 * after[2]) * 0.999
    assert ticks[-1]["gc_max_ms"] >= hit["gc_max_ms"]
    assert ticks[-1]["cum_gc_ms"] >= paused_ms * 0.999
    # the collection made this tick the slowest, and its gap says where
    assert ticks[-1]["slow_tick"] == hit["tick"]
    words = ticks[-1]["slow_phases"].split()
    assert words[0] == "gap" and int(words[1]) == hit["gap_us"]


def test_the_hook_is_installed_once_for_two_engines(tmp_path):
    """Two engines (and a third ask) leave ONE callback: a collection
    under a capture is one ``host.gc`` span with its generation, not
    two."""
    a, b = make_engine(), make_engine()
    trace_mod.install_gc_hook()
    assert gc.callbacks.count(trace_mod._on_gc) == 1
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    was = gc.isenabled()
    gc.disable()  # only the collections this test asks for
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            before = trace_mod.gc_pauses()
            gc.collect()
            gc.collect(0)
            after = trace_mod.gc_pauses()
        finally:
            jax.profiler.stop_trace()
    finally:
        if was:
            gc.enable()
    assert after[0] == before[0] + 2
    assert after[2] >= (after[1] - before[1]) / 2 > 0
    spans = [s for s in read_capture(str(tmp_path)) if s["name"] == "host.gc"]
    assert [s["counts"]["generation"] for s in spans] == [2, 0]
    assert all(set(s["counts"]) == {"generation"} for s in spans)
    assert all(s["end"] > s["start"] for s in spans)
    del a, b


@pytest.mark.parametrize("phase", ["pack", "fetch", "commit"])
def test_a_tick_made_slow_becomes_the_record_and_names_its_phase(
        phase, monkeypatch):
    eng = warm_engine()
    for p in PROMPTS[:2]:
        eng.add_request(p, MAX_NEW)
    eng.step()
    eng.step()
    slow_at = eng.tick_count
    target = {"pack": "_guard_capacity", "fetch": "_fetch",
              "commit": "_close_tick"}[phase]
    plain = getattr(eng, target)

    def slowed(*args, **kw):
        if eng.tick_count == slow_at:
            time.sleep(0.25)
        return plain(*args, **kw)

    monkeypatch.setattr(eng, target, slowed)
    step_all(eng)
    record = eng.slowest_tick()
    assert record["slow_tick"] == slow_at
    assert 250.0 <= record["slow_ms"] < 2500.0
    assert record["slow_program"] in ("mixed", "decode")
    words = record["slow_phases"].split()
    assert "," not in record["slow_phases"]  # `phase`'s rule for a string
    assert words[0::2] == ["gap", *TICK_PHASES]
    us = dict(zip(words[0::2], (int(w) for w in words[1::2])))
    assert us[phase] >= 250_000
    assert sum(v for k, v in us.items() if k != phase) < 250_000
    # the phases and the gap ARE the record, to the rounding of each
    assert sum(us.values()) == pytest.approx(1e3 * record["slow_ms"], abs=9)
    assert eng.stats()["slow_ms"] == record["slow_ms"]


def test_varz_shows_the_account_to_an_operator():
    import json
    import urllib.request

    from rocm_apex_tpu.monitor import MetricRegistry, start_exporter

    eng = warm_engine()
    eng.add_request(PROMPTS[2], MAX_NEW)
    step_all(eng)
    with start_exporter(MetricRegistry(), engine=eng) as server:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/varz", timeout=10) as r:
            shown = json.loads(r.read())["engine"]
    stats = eng.stats()
    for key in IN_STATS:
        assert shown[key] == stats[key], key
    # the account and the record alone: a scrape does not run `stats()`
    # (percentiles over every request, a walk over every mapped page)
    # beside the stepping thread
    assert set(shown) == {*IN_STATS, "slow_program", "slow_phases"}
    assert shown["slow_program"] in ("mixed", "decode")
    assert shown["slow_phases"].split()[0::2] == ["gap", *TICK_PHASES]


def test_the_counts_are_handed_over_only_while_somebody_keeps_them(
        tmp_path, monkeypatch):
    """With no capture live and no tracer the tick asks its span
    (`is_enabled`) and builds no keywords for it; the account itself
    runs all the same (`stats()`, ``/varz``). Under a capture, or with a
    tracer whose ring records the span, every tick carries them."""
    assert not trace_mod.phase("x").is_enabled()
    assert not Tracer(enabled=False).phase("x").is_enabled()
    assert Tracer().phase("x").is_enabled()
    handed = []
    plain = jax.profiler.TraceAnnotation.set_metadata

    def spy(self, **counts):
        handed.append(set(counts))
        return plain(self, **counts)

    monkeypatch.setattr(
        jax.profiler.TraceAnnotation, "set_metadata", spy, raising=False)
    eng = warm_engine()  # NULL_TRACER
    eng.add_request(PROMPTS[2], MAX_NEW)
    step_all(eng)
    ticks = eng.stats()["cum_ticks_mixed"] + eng.stats()["cum_ticks_decode"]
    assert ticks >= 2 and eng.stats()["slow_ms"] > 0
    assert not [c for c in handed if "program" in c]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert trace_mod.phase("x").is_enabled()
        eng.add_request(PROMPTS[2], MAX_NEW)
        step_all(eng)
    finally:
        jax.profiler.stop_trace()
    carried = [c for c in handed if "program" in c]
    assert len(carried) == ticks and all(ACCOUNT <= c for c in carried)


def test_the_account_leaves_the_programs_alone():
    """No retrace, no further compile: the account is host arithmetic
    (the programs' equation counts are pinned in test_windowed_parts)."""
    from rocm_apex_tpu.monitor import RetraceSentinel

    eng = warm_engine()
    sentinel = RetraceSentinel(policy="raise")
    sentinel.arm()
    try:
        for p in PROMPTS:
            eng.add_request(p, MAX_NEW)
        step_all(eng)
        assert sentinel.check() == 0
    finally:
        sentinel.close()
    assert eng.mixed_trace_count == 1 and eng.decode_trace_count == 1
