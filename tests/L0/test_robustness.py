"""Request-lifecycle robustness (ISSUE 12): deadlines, cancellation,
fault isolation, graceful drain, and the seeded chaos harness.

The contract under test is the ISSUE-12 acceptance bar: under a seeded
`FaultPlan` (page-allocation failures, device-step exceptions, NaN/Inf
logits poisoning, host-fetch failures) every NON-faulted request's
tokens are bitwise identical to a fault-free run, every teardown path
(cancel, deadline, quarantine, requeue, drain) leaves the PR-7 page
allocator invariants intact with zero leaked pages, every submitted
request yields exactly one result (completed + shed + quarantined +
cancelled + expired == submitted, never a silent drop), and the mixed
step still traces exactly ONCE — the poison/flag plumbing adds
``x + 0.0`` to fault-free logits and nothing else.

Every engine here shares test_inference's shape tuple (slots=2,
capacity=24, budget=4, the fp32_cfg model; page_size=4 for the paged
layouts) so the persistent compile cache pays each program once. The
fault-free
references are TWO module-scoped runs (contiguous + paged) at
``MAX_REF`` tokens: greedy decoding is a deterministic per-slot stream,
so every shorter or truncated run in this file compares against a
bitwise PREFIX of the same reference — one engine instead of one per
test (engine construction re-traces its jitted programs, the dominant
cost at this model size). Greedy sampling (temperature=0) also makes
the comparisons schedule-independent: a cancel or retry changes WHICH
tick serves a slot's tokens, never the tokens themselves.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_apex_tpu.inference import (
    FINISH_REASONS,
    Fault,
    FaultInjected,
    FaultPlan,
    InferenceEngine,
    NO_FAULTS,
    SamplingParams,
)
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel


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


@pytest.fixture(scope="module")
def model_and_params():
    cfg = fp32_cfg()
    model = GPTModel(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), toks)
    return model, params


#: compiled-step donors, one per trace geometry seen in this module:
#: chaos/watchdog/queue kwargs are host-side and don't affect the
#: traced graphs, so every same-geometry engine adopts the first one's
#: programs (`step_source=`) instead of re-tracing — the module warms
#: up once per layout. Incompatible geometries are refused by the
#: engine and fall through to a fresh build that seeds a new donor.
_STEP_DONORS: list = []


def greedy_engine(model, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("capacity", 24)
    kw.setdefault("prefill_token_budget", 4)
    kw.setdefault("sampling", SamplingParams(temperature=0.0))
    for donor in _STEP_DONORS:
        try:
            return InferenceEngine(
                model, params, step_source=donor, **kw
            )
        except ValueError:
            continue
    eng = InferenceEngine(model, params, **kw)
    _STEP_DONORS.append(eng)
    return eng


def run_to_done(eng, max_ticks=400):
    """Step until idle; results keyed by request id. Bounded so a
    broken engine fails the test instead of hanging the suite."""
    out = {}
    ticks = 0
    while eng.has_work():
        for r in eng.step():
            out[r.request_id] = r
        ticks += 1
        assert ticks < max_ticks, "engine failed to drain"
    return out


def ref_tokens(model, params, prompts, max_new, **kw):
    """Fault-free greedy reference: request id -> token list (ids are
    assigned in prompt order, same as the runs under test)."""
    eng = greedy_engine(model, params, **kw)
    return {
        r.request_id: r.tokens
        for r in eng.generate(prompts, max_new)
    }


PROMPTS = [
    [1, 2, 3, 1, 2],
    [7, 8, 9, 7, 8, 9, 7, 8, 9],
    [4, 5, 6, 4],
    [2, 4, 6, 8, 2, 4],
]
#: reference stream length — every test's max_new is <= this, so its
#: fault-free expectation is ref[rid][:max_new] (greedy prefix
#: property; prompt 9 + 12 generated fits capacity 24)
MAX_REF = 12
MAX_NEW = 5  # the chaos-parity run length


@pytest.fixture(scope="module")
def contig_ref(model_and_params):
    model, params = model_and_params
    return ref_tokens(model, params, PROMPTS, MAX_REF)


@pytest.fixture(scope="module")
def paged_ref(model_and_params):
    model, params = model_and_params
    return ref_tokens(
        model, params, PROMPTS, MAX_REF, paged=True, page_size=4
    )


# ---------------------------------------------------------------------------
# FaultPlan scheduling (pure host logic — no device work)
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            Fault(site="gpu_on_fire", tick=0)

    def test_schedule_required(self):
        with pytest.raises(ValueError, match="no schedule"):
            Fault(site="device_step")

    def test_validation_bounds(self):
        with pytest.raises(ValueError, match="1-based"):
            Fault(site="logits", nth=0)
        with pytest.raises(ValueError, match="every"):
            Fault(site="logits", every=0)
        with pytest.raises(ValueError, match="p must be"):
            Fault(site="logits", p=1.5)

    def test_nth_every_and_times(self):
        plan = FaultPlan([
            Fault(site="page_alloc", nth=2),
            Fault(site="page_alloc", every=3, times=2),
        ])
        hits = [
            plan.fire("page_alloc") is not None for _ in range(12)
        ]
        # nth=2 fires once on call 2; every=3 fires on calls 3 and 6
        # then exhausts its times=2 cap (calls 9, 12 stay quiet)
        assert hits == [
            False, True, True, False, False, True,
            False, False, False, False, False, False,
        ]
        assert plan.calls("page_alloc") == 12
        assert plan.fires["page_alloc"] == 3
        assert plan.fires["device_step"] == 0

    def test_tick_schedule_ignores_call_count(self):
        plan = FaultPlan([Fault(site="device_step", tick=3)])
        assert plan.fire("device_step", tick=0) is None
        assert plan.fire("device_step", tick=3) is not None
        # times=1 default: a revisit of the tick does not re-fire
        assert plan.fire("device_step", tick=3) is None

    def test_seeded_probabilistic_replays(self):
        plan = FaultPlan(
            [Fault(site="host_fetch", p=0.5, times=None)], seed=7
        )
        first = [
            plan.fire("host_fetch") is not None for _ in range(64)
        ]
        plan.reset()
        again = [
            plan.fire("host_fetch") is not None for _ in range(64)
        ]
        assert first == again
        assert any(first) and not all(first)
        other = FaultPlan(
            [Fault(site="host_fetch", p=0.5, times=None)], seed=8
        )
        assert first != [
            other.fire("host_fetch") is not None for _ in range(64)
        ]

    def test_null_plan_disabled(self):
        assert NO_FAULTS.enabled is False
        assert FaultPlan([Fault(site="logits", tick=0)]).enabled
        # robustness reasons are part of the public finish vocabulary
        for reason in ("deadline", "cancelled", "error", "queue_full"):
            assert reason in FINISH_REASONS


# ---------------------------------------------------------------------------
# Deadlines and cancellation
# ---------------------------------------------------------------------------


class TestDeadlinesAndCancel:
    def test_queue_ttl_expires_before_admission(
        self, model_and_params, contig_ref
    ):
        model, params = model_and_params
        eng = greedy_engine(model, params)
        for p in PROMPTS[:2]:
            eng.add_request(p, 8)
        eng.step()  # both slots leased
        late = eng.add_request(PROMPTS[2], 8, queue_ttl=1e-3)
        time.sleep(5e-3)
        done = run_to_done(eng)
        assert done[late].finish_reason == "deadline"
        assert done[late].tokens == []
        # the in-flight pair never saw the expiry
        assert done[0].tokens == contig_ref[0][:8]
        assert done[1].tokens == contig_ref[1][:8]
        assert eng.stats()["deadline_exceeded"] == 1.0
        rec = [
            c for c in eng.completions if c["request_id"] == late
        ][0]
        assert rec["finish_reason"] == "deadline"
        assert rec["new_tokens"] == 0

    def test_e2e_deadline_expires_in_flight(
        self, model_and_params, contig_ref
    ):
        model, params = model_and_params
        eng = greedy_engine(model, params)
        rid = eng.add_request(PROMPTS[0], MAX_REF, timeout=30.0)
        done = {}
        # decode a few tokens, then rewind the deadline so the next
        # tick-boundary sweep expires the request IN FLIGHT — timing-
        # deterministic (a real wall-clock timeout races the first
        # tick's compile on a cold cache)
        while not (
            eng._slots[0] is not None
            and len(eng._slots[0].generated) >= 3
        ):
            for r in eng.step():
                done[r.request_id] = r
        eng._slots[0].req.deadline = time.perf_counter() - 1.0
        done.update(run_to_done(eng))
        res = done[rid]
        assert res.finish_reason == "deadline"
        # partial work is delivered, and it is a bitwise prefix of the
        # fault-free stream (the deadline changes when we stop, never
        # what was computed)
        assert 3 <= len(res.tokens) < MAX_REF
        assert res.tokens == contig_ref[0][: len(res.tokens)]
        assert eng.stats()["deadline_exceeded"] == 1.0
        assert eng.num_active == 0

    def test_cancel_in_queue(self, model_and_params):
        model, params = model_and_params
        eng = greedy_engine(model, params)
        for p in PROMPTS[:2]:
            eng.add_request(p, 6)
        eng.step()
        rid = eng.add_request(PROMPTS[2], 6)
        res = eng.cancel(rid)
        assert res is not None and res.finish_reason == "cancelled"
        assert res.tokens == [] and eng.num_queued == 0
        assert eng.cancel(rid) is None  # already finished
        assert eng.cancel(999) is None  # unknown id
        done = run_to_done(eng)
        assert set(done) == {0, 1}
        assert eng.stats()["cancelled"] == 1.0

    def test_cancel_during_chunked_prefill_paged(
        self, model_and_params, paged_ref
    ):
        """Cancel mid-prefill on the paged engine: pages release with
        the allocator invariants intact and the surviving request is
        bitwise untouched."""
        model, params = model_and_params
        eng = greedy_engine(model, params, paged=True, page_size=4)
        baseline = eng._allocator.snapshot()
        victim = eng.add_request(PROMPTS[1], 6)  # 9 toks: 3 ticks
        eng.add_request(PROMPTS[0], 6)
        eng.step()
        st = eng._slots[0]
        assert st is not None and st.prefilling  # mid-prefill, really
        res = eng.cancel(victim)
        assert res.finish_reason == "cancelled" and res.tokens == []
        eng._allocator.assert_consistent()
        done = run_to_done(eng)
        # the keeper serves PROMPTS[0]: its stream matches the
        # reference run's request 0 regardless of its id here
        assert done[1].tokens == paged_ref[0][:6]
        eng._allocator.assert_consistent()
        assert eng._allocator.snapshot() == baseline  # zero leaks

    def test_cancel_during_decode(self, model_and_params, contig_ref):
        model, params = model_and_params
        eng = greedy_engine(model, params)
        a = eng.add_request(PROMPTS[0], MAX_REF)
        b = eng.add_request(PROMPTS[1], MAX_REF)
        done = {}
        # run until the long request has decoded a few tokens
        while not (
            eng._slots[1] is not None
            and len(eng._slots[1].generated) >= 3
        ):
            for r in eng.step():
                done[r.request_id] = r
        res = eng.cancel(b)
        assert res.finish_reason == "cancelled"
        assert 3 <= len(res.tokens) < MAX_REF
        assert res.tokens == contig_ref[1][: len(res.tokens)]
        done.update(run_to_done(eng))
        assert done[a].tokens == contig_ref[0]
        # exactly one result per submitted request
        assert len(eng.completions) == 2


# ---------------------------------------------------------------------------
# Fault isolation: NaN quarantine, step retry, requeue-on-exhaustion
# ---------------------------------------------------------------------------


class TestFaultIsolation:
    def test_nan_quarantines_only_that_slot(
        self, model_and_params, contig_ref
    ):
        model, params = model_and_params
        plan = FaultPlan(
            [Fault(site="logits", tick=4, payload={"slot": 1})]
        )
        eng = greedy_engine(model, params, faults=plan)
        for p in PROMPTS[:2]:
            eng.add_request(p, 8)
        done = run_to_done(eng)
        assert done[1].finish_reason == "error"
        assert len(done[1].tokens) < 8
        # the victim's pre-fault tokens are a bitwise prefix; the
        # poisoned token itself is never delivered
        assert done[1].tokens == contig_ref[1][: len(done[1].tokens)]
        # the co-scheduled slot is bitwise identical to fault-free —
        # its logits saw +0.0, nothing else
        assert done[0].finish_reason == "length"
        assert done[0].tokens == contig_ref[0][:8]
        st = eng.stats()
        assert st["quarantined"] == 1.0
        assert eng.mixed_trace_count == 1  # no trace under any plan

    def test_inf_payload_and_flight_recorder(
        self, model_and_params, tmp_path
    ):
        from rocm_apex_tpu.monitor.recorder import FlightRecorder

        model, params = model_and_params
        dump = str(tmp_path / "postmortem.jsonl")
        fr = FlightRecorder(last_k=8, path=dump)
        plan = FaultPlan([Fault(
            site="logits", tick=3,
            payload={"slot": 0, "value": float("inf")},
        )])
        eng = greedy_engine(
            model, params, faults=plan, flight_recorder=fr
        )
        done = {
            r.request_id: r
            for r in eng.generate(PROMPTS[:2], 8)
        }
        assert done[0].finish_reason == "error"
        assert done[1].finish_reason == "length"
        # the quarantine dumped a nonfinite/slot0 bundle
        assert len(fr.dumps) == 1
        assert "nonfinite/slot0" in str(fr.dumps[0])
        assert (tmp_path / "postmortem.jsonl").exists()

    def test_step_retry_recovers_bitwise(
        self, model_and_params, contig_ref
    ):
        """Transient device-step AND host-fetch failures (separate
        ticks) retry against the pre-step cache and the SAME rng
        split: the output stream is bitwise identical to a run with
        no fault at all."""
        model, params = model_and_params
        plan = FaultPlan([
            Fault(site="device_step", tick=1),
            Fault(site="host_fetch", tick=3),
        ])
        eng = greedy_engine(
            model, params, faults=plan, max_step_retries=2
        )
        done = {
            r.request_id: r
            for r in eng.generate(PROMPTS[:2], 6)
        }
        assert done[0].tokens == contig_ref[0][:6]
        assert done[1].tokens == contig_ref[1][:6]
        st = eng.stats()
        assert st["step_retries"] == 2.0
        assert plan.fires["device_step"] == 1
        assert plan.fires["host_fetch"] == 1
        assert eng.mixed_trace_count == 1

    def test_retry_exhaustion_requeues_then_recovers(
        self, model_and_params, paged_ref
    ):
        """Retries exhausted: the failure propagates but every
        in-flight request is back in the queue with its pages
        released; the next successful ticks recompute to a bitwise-
        identical stream."""
        model, params = model_and_params
        plan = FaultPlan([Fault(site="device_step", tick=2)])
        eng = greedy_engine(
            model, params, paged=True, page_size=4,
            faults=plan, max_step_retries=0,
        )
        baseline = eng._allocator.snapshot()
        for p in PROMPTS[:2]:
            eng.add_request(p, 6)
        done = {}
        raised = 0
        while eng.has_work():
            try:
                for r in eng.step():
                    done[r.request_id] = r
            except FaultInjected:
                raised += 1
                # consistent engine at the catch site: slots free,
                # pages released, requests queued for recompute
                assert eng.num_active == 0
                assert eng.num_queued == 2
                eng._allocator.assert_consistent()
        assert raised == 1
        assert done[0].tokens == paged_ref[0][:6]
        assert done[1].tokens == paged_ref[1][:6]
        st = eng.stats()
        assert st["preemptions"] >= 2.0
        eng._allocator.assert_consistent()
        assert eng._allocator.snapshot() == baseline

    def test_page_alloc_fault_defers_not_corrupts(
        self, model_and_params, paged_ref
    ):
        """An injected allocator failure takes the ordinary
        backpressure path: tokens are deferred a tick, never lost,
        never wrong."""
        model, params = model_and_params
        plan = FaultPlan(
            [Fault(site="page_alloc", every=1, times=3)]
        )
        eng = greedy_engine(
            model, params, paged=True, page_size=4, faults=plan
        )
        done = {
            r.request_id: r
            for r in eng.generate(PROMPTS[:2], 6)
        }
        assert done[0].tokens == paged_ref[0][:6]
        assert done[1].tokens == paged_ref[1][:6]
        st = eng.stats()
        assert st["page_stalls"] >= 1.0
        assert plan.fires["page_alloc"] == 3
        eng._allocator.assert_consistent()


# ---------------------------------------------------------------------------
# Graceful degradation: shed, drain, watchdog, bounded generate
# ---------------------------------------------------------------------------


class TestGracefulDegradation:
    def test_bounded_queue_sheds_newest_never_silently(
        self, model_and_params
    ):
        model, params = model_and_params
        eng = greedy_engine(model, params, max_queue=1)
        kept = eng.add_request(PROMPTS[0], 4)
        shed = eng.add_request(PROMPTS[1], 4)  # queue full: shed
        done = run_to_done(eng)
        assert done[shed].finish_reason == "queue_full"
        assert done[shed].tokens == []
        assert done[kept].finish_reason == "length"
        st = eng.stats()
        assert st["shed"] == 1.0
        # accounting identity: one completion record per submission
        assert len(eng.completions) == 2
        reasons = sorted(
            c["finish_reason"] for c in eng.completions
        )
        assert reasons == ["length", "queue_full"]

    def test_drain_finishes_everything_and_closes_admission(
        self, model_and_params, contig_ref
    ):
        model, params = model_and_params
        eng = greedy_engine(model, params)
        for p in PROMPTS[:3]:
            eng.add_request(p, 5)
        eng.step()
        assert not eng.draining
        out = {r.request_id: r for r in eng.drain()}
        assert eng.draining and not eng.has_work()
        # everything accepted before the drain completed normally
        for rid in range(3):
            assert out[rid].tokens == contig_ref[rid][:5]
        with pytest.raises(RuntimeError, match="draining"):
            eng.add_request(PROMPTS[0], 2)

    def test_drain_shed_queue_cancels_only_queued(
        self, model_and_params
    ):
        model, params = model_and_params
        eng = greedy_engine(model, params, paged=True, page_size=4)
        baseline = eng._allocator.snapshot()
        for p in PROMPTS[:3]:
            eng.add_request(p, 5)
        eng.step()  # 2 slots leased, 1 queued
        out = {
            r.request_id: r for r in eng.drain(shed_queue=True)
        }
        # the queued request was cancelled up front; the in-flight
        # pair ran to completion — the SIGTERM fast path
        assert out[2].finish_reason == "cancelled"
        assert out[0].finish_reason == "length"
        assert out[1].finish_reason == "length"
        assert eng.stats()["cancelled"] == 1.0
        eng._allocator.assert_consistent()
        assert eng._allocator.snapshot() == baseline

    def test_watchdog_dumps_and_raises(self, model_and_params, tmp_path):
        model, params = model_and_params
        dump = str(tmp_path / "watchdog.json")
        eng = greedy_engine(
            model, params,
            watchdog_timeout=0.01, watchdog_dump_path=dump,
        )
        eng.add_request(PROMPTS[0], 4)
        # simulate a wedged device: no token progress for > timeout
        eng._last_progress -= 10.0
        with pytest.raises(RuntimeError, match="serving watchdog"):
            eng.step()
        assert eng.stats()["watchdog_fires"] == 1.0
        with open(dump) as f:
            bundle = json.load(f)
        assert bundle["event"] == "watchdog"
        assert bundle["stalled_seconds"] > 0.01
        assert "queue_depth=1" in bundle["diagnosis"]

    def test_generate_stall_bound_is_diagnostic(self, model_and_params):
        """`generate()` no longer spins forever on a wedged engine: a
        bounded run of zero-progress ticks raises naming the stuck
        work instead of hanging the caller."""
        model, params = model_and_params
        eng = greedy_engine(model, params)
        eng._GENERATE_STALL_TICKS = 5  # instance override for speed
        eng._step_chunked = lambda: ([], {})  # wedge: ticks do nothing
        with pytest.raises(RuntimeError, match="generate"):
            eng.generate([PROMPTS[0]], 4)


# ---------------------------------------------------------------------------
# The acceptance bar: seeded chaos parity across cache layouts
# ---------------------------------------------------------------------------


class TestChaosParity:
    @pytest.mark.parametrize("layout,refname", [
        pytest.param({}, "contig", id="contig"),
        pytest.param(
            {"paged": True, "page_size": 4}, "paged", id="paged-bf16"
        ),
        pytest.param(
            {"paged": True, "page_size": 4, "kv_dtype": jnp.int8},
            None, id="paged-int8",
        ),
    ])
    def test_chaos_run_matches_fault_free(
        self, model_and_params, contig_ref, paged_ref, layout, refname
    ):
        """One seeded plan — an allocator failure, a device-step
        retry, a NaN-poisoned slot — plus a mid-prefill cancel, on
        every cache layout: the surviving requests are bitwise
        identical to the fault-free run, the accounting identity
        holds, the trace count stays 1, and a drained paged engine
        returns every page to the pool."""
        model, params = model_and_params
        if refname == "contig":
            ref = contig_ref
        elif refname == "paged":
            ref = paged_ref
        else:  # int8 pages quantize: its reference is its own layout
            ref = ref_tokens(model, params, PROMPTS, MAX_REF, **layout)
        plan = FaultPlan([
            # consulted on paged layouts only; 0 fires on contiguous
            Fault(site="page_alloc", nth=3),
            Fault(site="device_step", tick=2),
            Fault(site="logits", tick=4, payload={"slot": 1}),
        ], seed=12)
        eng = greedy_engine(
            model, params, faults=plan, max_step_retries=2, **layout
        )
        if eng.paged:
            baseline = eng._allocator.snapshot()
        for p in PROMPTS:
            eng.add_request(p, MAX_NEW)
        done = {}
        for _ in range(2):
            for r in eng.step():
                done[r.request_id] = r
        # request 1 (9-token prompt, budget 4) is still prefilling
        assert eng._slots[1] is not None and eng._slots[1].prefilling
        res = eng.cancel(1)
        assert res.finish_reason == "cancelled" and res.tokens == []
        done.update(
            {r.request_id: r for r in eng.drain()}
        )
        st = eng.stats()
        # the chaos schedule landed: one retry recovered, one slot
        # quarantined, one cancel — and nothing else was touched
        assert st["step_retries"] >= 1.0
        assert st["cancelled"] == 1.0
        assert st["quarantined"] == 1.0
        errored = [
            rid for rid, r in done.items()
            if r.finish_reason == "error"
        ]
        assert len(errored) == 1
        victim = errored[0]
        assert done[victim].tokens == ref[victim][
            : len(done[victim].tokens)
        ]
        for rid in range(len(PROMPTS)):
            if rid == 1 or rid == victim:
                continue
            assert done[rid].finish_reason == "length"
            assert done[rid].tokens == ref[rid][:MAX_NEW], (
                f"request {rid} diverged under chaos"
            )
        # accounting identity: every submission, exactly one record
        assert len(eng.completions) == len(PROMPTS)
        reasons = [c["finish_reason"] for c in eng.completions]
        assert reasons.count("cancelled") == 1
        assert reasons.count("error") == 1
        assert eng.mixed_trace_count == 1
        if eng.paged:
            assert plan.fires["page_alloc"] == 1
            assert st["page_stalls"] >= 1.0
            eng._allocator.assert_consistent()
            assert eng._allocator.snapshot() == baseline, (
                "pages leaked across the chaos run"
            )
