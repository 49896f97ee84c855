"""The engine's tick and the optimizer's update seen from inside
(ISSUE 24): phase spans and tick counters in the profiler's own trace.

One toy engine serves a few requests under a real `jax.profiler`
capture, read back with `ProfileData`; every test reads that one
recording. The spans are `monitor.trace.phase` annotations
(``apex/<name>``, counts as metadata): they are there exactly when a
capture is, and no constructor argument switches them.

Wall-time note (ROADMAP): the engine is test_paging's shape tuple
(fp32_cfg model, slots=2, capacity=24, budget=4, 4-row pages), so its
programs are compile-cache hits.
"""

import glob
import os
import statistics

import jax
import jax.numpy as jnp
import pytest
from _helpers import PAGE
from _serving import served_toy

from rocm_apex_tpu import profiler
from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
from rocm_apex_tpu.monitor import NULL_TRACER, Tracer
from rocm_apex_tpu.monitor.trace import _NULL_SPAN, PROGRAM_PREFIX, phase
from rocm_apex_tpu.optimizers.mixed import (
    OPTIMIZER_SCOPE,
    MixedPrecisionAdam,
    MixedPrecisionLamb,
)

PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], list(range(10, 19)), [20, 21]]
MAX_NEW = 4
CHILDREN = (
    "engine.admit", "engine.pack", "engine.table_push",
    "engine.dispatch", "engine.fetch", "engine.commit",
)


def make_engine(**kw):
    cfg = GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        tensor_parallel_size=1, params_dtype=jnp.float32,
        dtype=jnp.float32,
    )
    model = GPTModel(cfg)
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))
    kw = dict(dict(prefill_token_budget=4, paged=True, page_size=PAGE), **kw)
    return InferenceEngine(
        model, params, num_slots=2, capacity=24,
        sampling=SamplingParams(temperature=0.0), **kw)


def serve(eng, first_id):
    """All of PROMPTS through add_request/step(); the results by id and
    `pages_used` as read before each tick."""
    for i, p in enumerate(PROMPTS):
        eng.add_request(p, MAX_NEW, request_id=first_id + i)
    results, pages_before = {}, []
    while eng.has_work():
        pages_before.append(eng.pages_used)
        for r in eng.step():
            results[r.request_id] = r
    return results, pages_before


def read_capture(trace_dir):
    """The host events under ``apex/`` as dicts (name without the
    prefix, start_ns, end_ns, counts), in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    out.append({
                        "name": e.name[len(PROGRAM_PREFIX):],
                        "start": e.start_ns,
                        "end": e.start_ns + e.duration_ns,
                        "counts": dict(e.stats),
                    })
    return sorted(out, key=lambda s: (s["start"], -s["end"]))


@pytest.fixture(scope="module")
def recording(tmp_path_factory):
    """A warmed-up engine with an enabled Tracer serves PROMPTS under a
    capture; the same engine built without a tracer served them before
    with no capture live."""
    plain = make_engine()
    assert plain.tracer is NULL_TRACER
    plain_results, _ = serve(plain, 100)

    tracer = Tracer()
    eng = make_engine(tracer=tracer)
    serve(eng, 0)  # warm-up: both programs exist from here on
    eng.reset_stats()
    tracer.clear()
    tick0 = eng.tick_count
    trace_dir = str(tmp_path_factory.mktemp("capture"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        results, pages_before = serve(eng, 100)
    finally:
        jax.profiler.stop_trace()
    spans = read_capture(trace_dir)
    ticks = [s for s in spans if s["name"] == "engine.tick"]
    for t in ticks:
        t["children"] = [
            s for s in spans
            if s["name"] in CHILDREN
            and s["start"] >= t["start"] and s["end"] <= t["end"]
        ]
    return {
        "eng": eng, "tracer": tracer, "results": results,
        "plain_results": plain_results, "pages_before": pages_before,
        "spans": spans, "ticks": ticks, "n_ticks": eng.tick_count - tick0,
    }


class TestTickSpans:
    def test_one_tick_span_per_step(self, recording):
        ticks = recording["ticks"]
        assert len(ticks) == recording["n_ticks"] > 0
        numbers = [t["counts"]["tick"] for t in ticks]
        assert numbers == list(range(numbers[0], numbers[0] + len(ticks)))
        # nothing of the old names is left in the capture (a collection
        # that fell into it is a ``host.gc`` span: ISSUE 37)
        assert {s["name"] for s in recording["spans"]} <= {
            "engine.tick", "engine.enqueue", "host.gc", *CHILDREN}

    def test_children_tile_the_tick_in_order(self, recording):
        order = {n: i for i, n in enumerate(CHILDREN)}
        uncovered = []
        for t in recording["ticks"]:
            kids = t["children"]
            names = [k["name"] for k in kids]
            assert names[0] == "engine.admit"
            assert names[-1] == "engine.commit"
            assert [order[n] for n in names] == sorted(
                order[n] for n in names)
            assert len(set(names)) == len(names)
            ran = t["counts"]["program"] != "none"
            for name in ("engine.dispatch", "engine.fetch"):
                assert (name in names) == ran
            for a, b in zip(kids, kids[1:]):
                assert a["end"] <= b["start"]  # no overlap
            covered = sum(k["end"] - k["start"] for k in kids)
            uncovered.append(
                1.0 - covered / (t["end"] - t["start"]))
        # the phases cover the tick but for the statements between them
        assert statistics.median(uncovered) < 0.1

    def test_counters_account_for_every_token(self, recording):
        ticks, eng = recording["ticks"], recording["eng"]
        counts = [t["counts"] for t in ticks]
        first_tokens = len(PROMPTS)
        assert (
            sum(c["decodes"] for c in counts) + first_tokens
            == eng.stats()["generated_tokens"]
            == len(PROMPTS) * MAX_NEW
        )
        assert sum(c["prefill_tokens"] for c in counts) == sum(
            len(p) for p in PROMPTS)
        for c in counts:
            assert c["chunk_tokens"] == c["prefill_tokens"] <= c["budget"] == 4
            assert 0 <= c["decodes"] <= c["slots_busy"] <= c["slots"] == 2
        assert sum(c["admitted"] for c in counts) == len(PROMPTS)
        assert sum(c["finished"] for c in counts) == len(PROMPTS)
        assert counts[0]["queue_depth"] == len(PROMPTS) - 2

    def test_pages_used_is_the_read_before_the_tick(self, recording):
        counts = [t["counts"] for t in recording["ticks"]]
        assert [c["pages_used"] for c in counts] == recording["pages_before"]
        assert max(recording["pages_before"]) > 0
        total = recording["eng"].stats()["pages_total"]
        assert all(c["pages_total"] == total for c in counts)

    def test_program_names_the_one_trace_each(self, recording):
        eng = recording["eng"]
        programs = [t["counts"]["program"] for t in recording["ticks"]]
        assert set(programs) == {"mixed", "decode"}
        assert eng.mixed_trace_count == 1 and eng.decode_trace_count == 1
        st = eng.stats()
        assert programs.count("mixed") == st["mixed_steps"]
        for t in recording["ticks"]:
            c = t["counts"]
            assert (c["program"] == "mixed") == (c["chunk_tokens"] > 0)
            # the GPT engine's mixed tick applies the model to the chunk
            # and then to the grid (a served model of declared layers
            # reads 1 there: test_step_programs.py)
            assert not eng.programs.one_pass
            assert c["model_passes"] == (2 if c["program"] == "mixed" else 1)

    def test_enqueue_and_admit_carry_the_request_ids(self, recording):
        enq = [s for s in recording["spans"] if s["name"] == "engine.enqueue"]
        assert [s["counts"]["request_id"] for s in enq] == [
            100 + i for i in range(len(PROMPTS))]
        assert [s["counts"]["prompt_tokens"] for s in enq] == [
            len(p) for p in PROMPTS]
        leased = []
        for t in recording["ticks"]:
            # the ids ride on the admit span only in a tick that leases
            admit = t["children"][0]["counts"]
            assert set(admit) <= {"request_ids"}
            ids = [int(x) for x in str(admit.get("request_ids", "")).split()]
            assert len(ids) == t["counts"]["admitted"]
            leased += ids
        assert sorted(leased) == [100 + i for i in range(len(PROMPTS))]
        # every arrival lies before the tick that leased it a slot
        assert max(s["end"] for s in enq) <= recording["ticks"][0]["start"]


def hand_off_engine(case, tracer):
    if case == "hybrid-counters":
        model, params, _ = served_toy("hybrid")
        return InferenceEngine(
            model, params, num_slots=3, capacity=64, paged=True,
            page_size=PAGE, prefill_token_budget=16, tracer=tracer,
            sampling=SamplingParams(temperature=0.0))
    if case == "adapter-pool":
        from rocm_apex_tpu.inference import AdapterPool

        pool = AdapterPool(2, 32, max_resident=4, max_rank=4)
        return make_engine(
            tracer=tracer, paged=False, page_size=None, adapter_pool=pool)
    if case == "contiguous":
        return make_engine(tracer=tracer, paged=False, page_size=None)
    return make_engine(tracer=tracer)


class TestHandOff:
    """ISSUE 29: a tick that runs a program makes ONE call into the
    runtime on the way in (the jitted step program, host arrays as they
    are, the key split inside it) and one `device_get` on the way
    out."""

    @pytest.mark.parametrize(
        "case", ["paged", "contiguous", "hybrid-counters", "adapter-pool"])
    def test_one_call_in_one_fetch_out(self, case, monkeypatch):
        tracer = Tracer()
        eng = hand_off_engine(case, tracer)
        serve(eng, 0)  # warm-up: both programs exist from here on
        tracer.clear()

        inside, eager, fetched = [], [], []
        run_program, fetch = eng._run_program, eng._fetch

        def watched_run(*args, **kw):
            inside.append(True)
            try:
                return run_program(*args, **kw)
            finally:
                inside.pop()

        def watched_fetch(values):
            fetched.append(values)
            return fetch(values)

        def counting(name, fn):
            def wrapper(*args, **kw):
                if inside:
                    eager.append(name)
                return fn(*args, **kw)
            return wrapper

        monkeypatch.setattr(eng, "_run_program", watched_run)
        monkeypatch.setattr(eng, "_fetch", watched_fetch)
        monkeypatch.setattr(
            jax.random, "split", counting("split", jax.random.split))
        monkeypatch.setattr(jnp, "asarray", counting("asarray", jnp.asarray))
        monkeypatch.setattr(
            jax, "device_put", counting("device_put", jax.device_put))
        results, _ = serve(eng, 100)
        monkeypatch.undo()

        assert len(results) == len(PROMPTS)
        assert eager == []
        # one fetch a tick: the program's own outputs (tokens, flags)
        # and the counters of a cache that keeps them
        counted = case == "hybrid-counters"
        for values, counters in fetched:
            assert len(values) in (2, 4)
            assert (counters is not None) == counted
            assert all(
                isinstance(v, jax.Array)
                for v in jax.tree_util.tree_leaves((values, counters)))
        assert eng.mixed_trace_count == 1 and eng.decode_trace_count == 1

        ring = sorted(
            (e for e in tracer.events()
             if e["ph"] == "X" and e["name"].startswith("engine.")),
            key=lambda e: (e["ts"], -e["dur"]))
        ticks = [e for e in ring if e["name"] == "engine.tick"]
        assert {t["args"]["program"] for t in ticks} >= {"mixed", "decode"}
        assert len([t for t in ticks if t["args"]["program"] != "none"]) == (
            len(fetched))
        if case == "hybrid-counters":
            # the cache's counters came back in the same fetch
            assert sum(t["args"]["moe_assignments"] for t in ticks) > 0
        uncovered = []
        for t in ticks:
            kids = [
                e for e in ring if e["name"] in CHILDREN
                and e["ts"] >= t["ts"]
                and e["ts"] + e["dur"] <= t["ts"] + t["dur"]]
            names = [k["name"] for k in kids]
            ran = t["args"]["program"] != "none"
            assert (
                names.count("engine.dispatch") == names.count("engine.fetch")
                == int(ran))
            for a, b in zip(kids, kids[1:]):
                assert a["ts"] + a["dur"] <= b["ts"]
            uncovered.append(1.0 - sum(k["dur"] for k in kids) / t["dur"])
        # the five phases still tile the tick
        assert statistics.median(uncovered) < 0.1


class TestWholePromptMode:
    def test_legacy_engine_counts_its_own_ticks(self):
        """The whole-prompt A/B baseline admits inside its step and has
        no chunk: its ticks are ``whole``, and the counts still account
        for every token (read from an enabled Tracer's ring: the args of
        a ring span are the annotation's metadata)."""
        tracer = Tracer()
        cfg_eng = make_engine(
            tracer=tracer, paged=False, page_size=None,
            prefill_token_budget=None, max_prompt_len=24)
        results, _ = serve(cfg_eng, 0)
        assert len(results) == len(PROMPTS)
        ticks = [
            e["args"] for e in tracer.events()
            if e["ph"] == "X" and e["name"] == "engine.tick"]
        assert {c["program"] for c in ticks} == {"whole"}
        assert sum(c["admitted"] for c in ticks) == len(PROMPTS)
        assert sum(c["finished"] for c in ticks) == len(PROMPTS)
        assert sum(c["prefill_tokens"] for c in ticks) == sum(
            len(p) for p in PROMPTS)
        assert (
            sum(c["decodes"] for c in ticks) + len(PROMPTS)
            == cfg_eng.stats()["generated_tokens"])
        assert all(c["pages_total"] == 0 == c["chunk_tokens"] for c in ticks)
        # a whole-prompt prefill an admission, and the grid
        assert all(
            c["model_passes"] == c["admitted"] + (c["decodes"] > 0)
            for c in ticks)
        phases = {
            e["name"] for e in tracer.events()
            if e["ph"] == "X" and e["name"].startswith("engine.")}
        assert phases == {
            "engine.tick", "engine.admit", "engine.dispatch",
            "engine.fetch", "engine.commit"}


class TestNoCapture:
    def test_served_tokens_do_not_depend_on_a_capture(self, recording):
        """The engine with no tracer and no capture live, and the traced
        engine under a capture, serve the same tokens."""
        got, plain = recording["results"], recording["plain_results"]
        assert sorted(got) == sorted(plain)
        for rid, r in got.items():
            assert r.tokens == plain[rid].tokens
            assert r.finish_reason == "length"

    def test_no_capture_compiles_and_records_nothing(self):
        from rocm_apex_tpu.monitor import RetraceSentinel

        eng = make_engine()
        serve(eng, 0)
        sentinel = RetraceSentinel(policy="raise")
        sentinel.arm()
        try:
            assert not jax.profiler.TraceAnnotation.is_enabled()
            results, _ = serve(eng, 100)
            assert sentinel.check() == 0
        finally:
            sentinel.close()
        assert len(results) == len(PROMPTS)
        assert eng.mixed_trace_count == 1 and eng.decode_trace_count == 1
        assert eng.tracer.events() == []


class TestOnePrimitive:
    def test_tracer_ring_holds_the_same_phases(self, recording):
        """An enabled Tracer records, on its engine track, the phases the
        capture holds: same names, same order, same boundaries (the ring
        reads its clock just inside the annotation)."""
        evs = recording["tracer"].events()
        engine_tid = next(
            e["tid"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
            and e["args"]["name"] == "engine")
        ring = [
            e for e in evs if e["ph"] == "X" and e["tid"] == engine_tid]
        assert {e["name"] for e in ring} <= {"engine.tick", *CHILDREN}
        capture = [
            s for s in recording["spans"]
            if s["name"] not in ("engine.enqueue", "host.gc")]
        # the ring records a span when it closes, the capture sorts by
        # start: compare both in start order
        ring.sort(key=lambda e: (e["ts"], -e["dur"]))
        assert [e["name"] for e in ring] == [s["name"] for s in capture]
        # one offset between the two clocks (us against ns)
        offset = statistics.median(
            s["start"] / 1e3 - e["ts"] for e, s in zip(ring, capture))
        for e, s in zip(ring, capture):
            assert abs(s["start"] / 1e3 - e["ts"] - offset) < 500.0
            assert 0 <= (s["end"] - s["start"]) / 1e3 - e["dur"] < 500.0
        tick = next(e for e in ring if e["name"] == "engine.tick")
        assert {"tick", "program", "decodes", "pages_used"} <= set(
            tick["args"])

    def test_disabled_tracer_span_is_still_free(self):
        t = Tracer(enabled=False)
        assert t.span("a", tokens=1) is _NULL_SPAN
        assert t.step_span(3) is _NULL_SPAN
        # the engine's phases do not go through span(): a disabled
        # tracer still hands out the annotation
        ann = t.phase("engine.tick", track="engine", tick=0)
        assert isinstance(ann, jax.profiler.TraceAnnotation)
        with ann as a:
            a.set_metadata(program="none")
        assert t.events() == []

    def test_every_span_maker_goes_through_phase(self, tmp_path):
        """`Tracer.span`, `Tracer.step_span` and `profiler.annotate` give
        ``apex/`` annotations with their arguments as metadata: no JSON
        in a name."""
        t = Tracer()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with t.span("prefill", track="a", tokens=3):
                pass
            with t.step_span(7):
                pass
            with profiler.annotate("matmul", m=128, dtype="bf16"):
                pass
            with phase("bare") as p:
                p.set_metadata(late=1)
        finally:
            jax.profiler.stop_trace()
        got = {s["name"]: s["counts"] for s in read_capture(str(tmp_path))}
        assert got["prefill"] == {"tokens": 3}
        assert got["train_step"]["step_num"] == 7
        assert got["matmul"] == {"m": 128, "dtype": "bf16"}
        assert got["bare"] == {"late": 1}
        assert [e["name"] for e in t.events() if e["ph"] == "X"] == [
            "prefill", "train_step"]


class TestOptimizerScope:
    @pytest.mark.parametrize("make", [
        lambda: MixedPrecisionLamb(1e-3, store_model=False),
        lambda: MixedPrecisionLamb(1e-3),
        lambda: MixedPrecisionAdam(1e-3),
    ], ids=["lamb-cast-on-demand", "lamb", "adam"])
    def test_update_is_under_the_scope_and_forward_is_not(self, make):
        """The lowered step carries `optimizer` in the op_name of the
        update's operations (moments, norms, the master-to-model cast)
        and of none of the forward's or backward's."""
        opt = make()
        state = opt.init({
            "w": jnp.ones((256, 256), jnp.float32),
            "b": jnp.zeros((256,), jnp.float32),
        })

        def loss_fn(p, x):
            with jax.named_scope("forward"):
                return jnp.sum(jnp.tanh(x @ p["w"] + p["b"]) ** 2)

        def train_step(state, x):
            loss, grads = jax.value_and_grad(loss_fn)(
                opt.model_params(state), x)
            state, _ = opt.step_and_probe(state, grads)
            return state, loss

        text = jax.jit(train_step).lower(
            state, jnp.ones((8, 256), jnp.bfloat16)
        ).as_text(debug_info=True)
        names = [
            line.split('loc("', 1)[1].split('"', 1)[0]
            for line in text.splitlines()
            if line.startswith("#loc") and 'loc("jit(train_step)' in line
        ]
        under = [n for n in names if f"/{OPTIMIZER_SCOPE}/" in n]
        forward = [n for n in names if "forward" in n]
        assert under and forward
        assert not set(under) & set(forward)
        for op in ("sqrt", "convert_element_type"):
            assert any(n.endswith("/" + op) for n in under), op
        for op in ("dot_general", "tanh"):
            assert any(n.endswith("/" + op) for n in forward), op
            assert not any(n.endswith("/" + op) for n in under), op
