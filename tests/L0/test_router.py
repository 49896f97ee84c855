"""Multi-replica serving fabric (ISSUE 15): the `ReplicaRouter`.

The contract under test is the ISSUE-15 acceptance bar: N replicas
behind one surface must be INVISIBLE in tokens — placement, failover,
and rolling drain never change greedy outputs. Under a seeded
``replica_kill`` every in-flight request is resubmitted as prompt +
tokens emitted so far and recomputed through the destination's chunked
prefill, so the recovered stream is bitwise-identical to an
undisturbed single-replica run and no token is emitted twice; every
submitted request yields exactly one result (the fleet accounting
identity); the killed replica's slots and pages provably free; the
merged fleet registry reproduces the combined per-replica completion
streams bucket-for-bucket.

Every engine here shares test_inference/test_robustness's shape tuple
(slots=2, capacity=24, budget=4, the fp32_cfg model; page_size=4 for
the paged layouts) so the persistent compile cache pays each program
once. The
fault-free references are module-scoped single-engine runs at
``MAX_REF`` tokens: greedy decoding is a deterministic per-slot
stream, so every shorter run compares against a bitwise PREFIX of the
same reference, and a kill/drain/migration changes WHICH replica
serves a token, never the token itself.
"""

import http.client
import json

import jax
import jax.numpy as jnp
import pytest

from rocm_apex_tpu.inference import (
    Fault,
    FaultPlan,
    InferenceEngine,
    ReplicaRouter,
    SamplingParams,
)
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
from rocm_apex_tpu.monitor import start_exporter
from rocm_apex_tpu.monitor.telemetry import MetricRegistry
from rocm_apex_tpu.monitor.trace import Tracer, trace_lifelines


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


#: identical engine configs keep greedy outputs replica-independent
EKW = dict(
    num_slots=2, capacity=24, prefill_token_budget=4,
    sampling=SamplingParams(temperature=0.0),
)


def build_router(model, params, donor=None, *, replicas=2,
                 engine_kwargs=None, **kw):
    """Build a 2-replica fleet. With `donor` (a warmed module-scoped
    engine of the same geometry) the replicas adopt its compiled steps
    — the suite pays the fused-step warm-up once per layout, not once
    per test. One test (`test_single_vs_multi_parity`) deliberately
    builds WITHOUT a donor to cover the router's internal
    construction + step-sharing path."""
    ekw = dict(EKW)
    ekw.update(engine_kwargs or {})
    if donor is None:
        return ReplicaRouter(
            model, params, replicas=replicas, engine_kwargs=ekw, **kw
        )
    engines = [
        InferenceEngine(model, params, step_source=donor, **ekw)
        for _ in range(replicas)
    ]
    return ReplicaRouter(engines=engines, **kw)


def run_to_done(router, max_ticks=400):
    """Step the fleet until idle; results keyed by request id.
    Bounded so a broken router fails the test instead of hanging."""
    out = {}
    ticks = 0
    while router.has_work():
        for r in router.step():
            assert r.request_id not in out, "double delivery"
            out[r.request_id] = r
        ticks += 1
        assert ticks < max_ticks, "fleet failed to drain"
    return out


PROMPTS = [
    [1, 2, 3, 1, 2],
    [7, 8, 9, 7, 8, 9, 7, 8, 9],
    [4, 5, 6, 4],
    [2, 4, 6, 8, 2, 4],
]
MAX_REF = 12
MAX_NEW = 5


def _ref_env(model, params, **kw):
    """(warmed reference engine, its greedy reference tokens) — the
    engine doubles as the layout's compiled-step donor."""
    ekw = dict(EKW)
    ekw.update(kw)
    eng = InferenceEngine(model, params, **ekw)
    ref = {
        r.request_id: r.tokens
        for r in eng.generate(PROMPTS, MAX_REF)
    }
    return eng, ref


@pytest.fixture(scope="module")
def contig_env(model_and_params):
    model, params = model_and_params
    return _ref_env(model, params)


@pytest.fixture(scope="module")
def paged_env(model_and_params):
    model, params = model_and_params
    return _ref_env(model, params, paged=True, page_size=4)


@pytest.fixture(scope="module")
def contig_ref(contig_env):
    return contig_env[1]


@pytest.fixture(scope="module")
def paged_ref(paged_env):
    return paged_env[1]


@pytest.fixture(scope="module")
def contig_donor(contig_env):
    return contig_env[0]


@pytest.fixture(scope="module")
def paged_donor(paged_env):
    return paged_env[0]


def assert_parity(results, ref, max_new):
    """Positional token parity against the single-engine reference
    (greedy prefix property: any max_new <= MAX_REF is a prefix)."""
    for i, r in enumerate(results):
        assert r.tokens == ref[i][:max_new], (
            f"request {i}: fleet tokens {r.tokens} != "
            f"single-replica reference {ref[i][:max_new]}"
        )


# ---------------------------------------------------------------------------
# placement parity + fleet accounting
# ---------------------------------------------------------------------------


def test_single_vs_multi_parity(model_and_params, contig_ref):
    # one router exercises the whole happy path: placement parity,
    # merged telemetry, and the fleet exporter surface
    model, params = model_and_params
    router = build_router(model, params)
    results = router.generate(PROMPTS, MAX_NEW)
    assert_parity(results, contig_ref, MAX_NEW)
    s = router.stats()
    assert s["submitted"] == s["completed"] == len(PROMPTS)
    assert s["migrations"] == s["replica_quarantines"] == 0
    # host-only fabric: each replica still traced its mixed step once
    for i in range(router.num_replicas):
        assert router.replica(i).mixed_trace_count == 1
        assert router.replica(i).num_active == 0

    # --- merged telemetry reproduces the per-replica streams ---
    merged = router.merged_registry()
    # counts add exactly: one ttft observation per completion,
    # whichever replica served it
    per_rep = [
        router.replica(i).registry.get("serve_ttft_ms").count()
        for i in range(router.num_replicas)
    ]
    assert all(n > 0 for n in per_rep)  # both replicas served
    fleet_hist = merged.get("serve_ttft_ms")
    assert fleet_hist.count() == sum(per_rep) == len(PROMPTS)
    # bucket-wise merge is exact and associative: a hand-built merge
    # reproduces the same snapshot, so scraped percentiles are the
    # combined-stream percentiles
    manual = MetricRegistry()
    manual.merge_from(router.registry)
    for i in range(router.num_replicas):
        manual.merge_from(router.replica(i).registry)
    assert (
        merged.snapshot()["serve_ttft_ms"]
        == manual.snapshot()["serve_ttft_ms"]
    )
    for p in (50.0, 95.0):
        assert fleet_hist.percentile(p) == pytest.approx(
            manual.get("serve_ttft_ms").percentile(p)
        )

    # --- the fleet exporter: zero-arg provider re-merges per scrape,
    # /healthz answers 503 only when NO replica is healthy ---
    srv = start_exporter(router=router, port=0)
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", srv.port, timeout=10
        )
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200
        assert b"serve_ttft_ms_count" in body  # fleet stream
        assert b"router_events_total" in body  # router stream
        conn.request("GET", "/healthz")
        hz = conn.getresponse()
        rep = json.loads(hz.read())
        assert hz.status == 200 and rep["healthy"]
        assert rep["healthy_replicas"] == 2
        conn.request("GET", "/varz")
        vz = json.loads(conn.getresponse().read())
        assert len(vz["replica_detail"]) == 2
        # one drained replica: degraded but alive -> still 200
        router.drain_replica(0)
        conn.request("GET", "/healthz")
        hz = conn.getresponse()
        assert hz.status == 200
        assert json.loads(hz.read())["healthy_replicas"] == 1
        # zero replicas in rotation is the outage: 503
        router.drain_replica(1)
        conn.request("GET", "/healthz")
        hz = conn.getresponse()
        rep = json.loads(hz.read())
        assert hz.status == 503 and not rep["healthy"]
        conn.close()
    finally:
        srv.close()


def test_fleet_accounting_identity(model_and_params, contig_env):
    # bounded global admission: shed-newest queue_full results flow
    # through step() like the engine's, and the identity closes —
    # every submitted request accounted exactly once
    model, params = model_and_params
    donor, contig_ref = contig_env
    router = build_router(model, params, donor, max_queue=2)
    results = router.generate(PROMPTS, MAX_NEW)
    assert len(results) == len(PROMPTS)
    served, shed = results[:2], results[2:]
    assert_parity(served, contig_ref, MAX_NEW)
    for r in shed:
        assert r.finish_reason == "queue_full" and r.tokens == []
    s = router.stats()
    assert s["submitted"] == s["completed"] == 4.0
    assert s["shed"] == 2.0
    assert s["finished_queue_full"] == 2.0
    # admission closes at drain, idempotently
    router.drain()
    router.drain()
    with pytest.raises(RuntimeError, match="draining"):
        router.add_request(PROMPTS[0], 2)


# ---------------------------------------------------------------------------
# failover: kill mid-decode, recover token-identically
# ---------------------------------------------------------------------------


def test_kill_mid_decode_recovery_parity(model_and_params, contig_env):
    model, params = model_and_params
    donor, contig_ref = contig_env
    plan = FaultPlan(
        [Fault(site="replica_kill", tick=4, payload={"replica": 0})],
        seed=0,
    )
    router = build_router(
        model, params, donor, faults=plan, rejoin_after=4
    )
    for p in PROMPTS:
        router.add_request(p, MAX_NEW)
    done = run_to_done(router)
    assert plan.fires.get("replica_kill") == 1
    assert router.fault_log == [("replica_kill", 4, 0)]
    # tick 4 is mid-decode for this workload: the kill migrated live
    # requests, and their recomputed continuations are bitwise equal
    results = [done[i] for i in sorted(done)]
    assert len(results) == len(PROMPTS)
    assert_parity(results, contig_ref, MAX_NEW)
    s = router.stats()
    assert s["replica_kills"] == 1.0
    assert s["replica_quarantines"] == 1.0
    assert s["migrations"] >= 1.0
    assert s["submitted"] == s["completed"] == len(PROMPTS)
    # the carcass is evacuated: no slot leases survive the kill
    assert router.replica(0).num_active == 0
    assert router.replica(0).num_queued == 0
    # recovery never re-traces: the survivor reuses its compiled step
    for i in range(router.num_replicas):
        assert router.replica(i).mixed_trace_count == 1
    # the quarantined replica probes back into rotation on idle ticks
    for _ in range(router.rejoin_after + 2):
        if router.replica_state(0) == "up":
            break
        router.step()
    assert router.replica_state(0) == "up"
    assert router.stats()["replica_rejoins"] == 1.0


def test_kill_paged_no_page_leak(model_and_params, paged_env):
    # same failover on the paged layout: the killed replica's pages
    # are freed by the evacuation and the allocator invariants hold
    model, params = model_and_params
    donor, paged_ref = paged_env
    plan = FaultPlan(
        [Fault(site="replica_kill", tick=4, payload={"replica": 0})],
        seed=0,
    )
    router = build_router(
        model, params, donor, faults=plan,
        engine_kwargs=dict(paged=True, page_size=4),
    )
    for p in PROMPTS:
        router.add_request(p, MAX_NEW)
    done = run_to_done(router)
    assert plan.fires.get("replica_kill") == 1
    assert_parity([done[i] for i in sorted(done)], paged_ref, MAX_NEW)
    for i in range(router.num_replicas):
        rep = router.replica(i)
        assert rep.pages_used == 0, f"replica {i} leaked pages"
        rep._allocator.assert_consistent()


def test_kill_mid_decode_trace_continuity(model_and_params, contig_env):
    """ISSUE-19 fleet-causal acceptance on the failover path: a
    request killed mid-decode keeps its admission-minted trace_id
    across the resubmission, so the merged fleet trace renders it as
    ONE lifeline spanning BOTH replica processes with exactly one
    finish — and the kill instant names the recovered ids."""
    model, params = model_and_params
    donor, contig_ref = contig_env
    plan = FaultPlan(
        [Fault(site="replica_kill", tick=4, payload={"replica": 0})],
        seed=0,
    )
    router = build_router(
        model, params, donor, faults=plan, tracer=Tracer()
    )
    for i in range(router.num_replicas):
        router.replica(i).tracer = Tracer()  # one process id each
    for p in PROMPTS:
        router.add_request(p, MAX_NEW)
    done = run_to_done(router)
    assert plan.fires.get("replica_kill") == 1
    assert_parity([done[i] for i in sorted(done)], contig_ref, MAX_NEW)
    body = router.merged_trace()
    # default labels: the router first, then replica<i>:<class>
    assert body["otherData"]["processes"]["1"] == "router"
    assert body["otherData"]["processes"]["2"] == "replica0:mixed"
    lines = trace_lifelines(body)
    assert len(lines) == len(PROMPTS)
    for tid, line in lines.items():
        assert line["finishes"] == 1, (tid, line)
        assert 1 in line["pids"], (tid, line)  # admitted on the router
    # the kill migrated at least one in-flight request: its lifeline
    # spans the victim AND the survivor processes
    migrated = [
        line for line in lines.values()
        if len([p for p in line["pids"] if p > 1]) > 1
    ]
    assert migrated, lines
    assert any(2 in m["pids"] and 3 in m["pids"] for m in migrated)
    # the router's kill instant names what it recovered (the trace_id
    # join keys ride the fleet event, not just the per-request tracks)
    kills = [
        e for e in body["traceEvents"]
        if e.get("ph") == "i" and e["name"] == "kill_replica"
    ]
    assert len(kills) == 1
    recovered = kills[0]["args"]["trace_ids"]
    assert recovered and all(t in lines for t in recovered)
    # every lifeline shows the admit -> dispatch -> ... -> finish arc
    for line in lines.values():
        assert "admit" in line["names"]
        assert "dispatch" in line["names"]


def test_fault_plan_replay(model_and_params, contig_donor):
    # the chaos witness: reset() + a fresh fleet replays the exact
    # (site, tick, replica) sequence — a red run reproduces from its
    # command line
    model, params = model_and_params
    faults = [
        Fault(site="replica_kill", tick=3, payload={"replica": 1}),
        Fault(site="replica_stall", tick=1,
              payload={"replica": 0, "ticks": 2}),
        Fault(site="replica_slow", tick=2,
              payload={"replica": 0, "seconds": 0.0}),
    ]
    plan = FaultPlan(faults, seed=7)
    router_a = build_router(model, params, contig_donor, faults=plan)
    for p in PROMPTS[:2]:
        router_a.add_request(p, 3)
    done_a = run_to_done(router_a)
    log_a = list(router_a.fault_log)
    assert len(log_a) >= 3
    plan.reset()
    router_b = build_router(model, params, contig_donor, faults=plan)
    for p in PROMPTS[:2]:
        router_b.add_request(p, 3)
    done_b = run_to_done(router_b)
    assert router_b.fault_log == log_a
    # and chaos stays invisible in tokens, both runs
    toks_a = {i: done_a[i].tokens for i in done_a}
    toks_b = {i: done_b[i].tokens for i in done_b}
    assert toks_a == toks_b


# ---------------------------------------------------------------------------
# prefix affinity
# ---------------------------------------------------------------------------


def test_prefix_affinity_accounting(model_and_params, paged_donor):
    # requests sharing a stored prefix chase its pages: the router
    # places them on the replica already holding the chain, so CoW
    # sharing keeps working across the fleet
    model, params = model_and_params
    router = build_router(
        model, params, paged_donor,
        engine_kwargs=dict(
            paged=True, page_size=4, prefix_sharing=True
        ),
    )
    base = [3, 1, 4, 1, 5, 9, 2, 6]  # two full pages
    router.generate([base + [50]], 3)  # materializes + stores prefix
    owner = [
        i for i in range(router.num_replicas)
        if router.replica(i).prefix_match_tokens(base + [60]) > 0
    ]
    assert len(owner) == 1  # exactly one replica holds the chain
    results = router.generate([base + [60], base + [61]], 3)
    assert len(results) == 2
    s = router.stats()
    assert s["affinity_hits"] >= 2.0, s
    assert router.replica(owner[0]).stats()["prefix_hits"] >= 2.0
    for i in range(router.num_replicas):
        rep = router.replica(i)
        rep._allocator.assert_consistent()


# ---------------------------------------------------------------------------
# rolling drain / rejoin
# ---------------------------------------------------------------------------


def test_rolling_drain_liveness(model_and_params, contig_env):
    # restart-without-downtime: drain a replica mid-run, the fleet
    # keeps serving (tokens unmoved), the replica rejoins and serves
    # again
    model, params = model_and_params
    donor, contig_ref = contig_env
    router = build_router(model, params, donor)
    ids = [router.add_request(p, MAX_NEW) for p in PROMPTS]
    done = {}
    for _ in range(3):
        for r in router.step():
            done[r.request_id] = r
    router.drain_replica(0)
    assert router.replica_state(0) == "drained"
    assert router.replica(0).num_active == 0
    done.update(run_to_done(router))
    assert_parity([done[i] for i in ids], contig_ref, MAX_NEW)
    assert router.stats()["completed"] == len(PROMPTS)
    router.rejoin_replica(0)
    assert router.replica_state(0) == "up"
    assert router.healthy_replicas == 2
    # the rejoined replica serves new traffic, tokens unmoved
    again = router.generate(PROMPTS[:2], 3)
    assert_parity(again, contig_ref, 3)


# ---------------------------------------------------------------------------
# engine lifecycle: idempotent drain, clean reopen
# ---------------------------------------------------------------------------


def test_engine_drain_idempotent_and_reopen(model_and_params, contig_env):
    model, params = model_and_params
    donor, contig_ref = contig_env
    eng = InferenceEngine(model, params, step_source=donor, **EKW)
    rid = eng.add_request(PROMPTS[0], 3)
    # reopen() refuses dirty state: admission must stay closed until
    # the engine is PROVABLY clean
    with pytest.raises(RuntimeError, match="queued"):
        eng.reopen()
    done = {r.request_id: r for r in eng.drain()}
    assert done[rid].tokens == contig_ref[0][:3]
    assert eng.drain() == []  # idempotent: second drain is a no-op
    assert eng.draining
    eng.reopen()
    assert not eng.draining
    # a reopened engine serves again, bitwise the same, no re-trace
    res = eng.generate(PROMPTS[:2], 3)
    assert [r.tokens for r in res] == [
        contig_ref[0][:3], contig_ref[1][:3]
    ]
    assert eng.mixed_trace_count == 1

    # --- the migration format, round-tripped on the same engine:
    # prompt + tokens emitted so far, resumed through the chunked
    # prefill path, continues bitwise ---
    for p in PROMPTS[:2]:
        eng.add_request(p, MAX_NEW)
    for _ in range(4):
        eng.step()
    recs = eng.evacuate()
    assert len(recs) == 2
    assert eng.num_active == 0 and eng.num_queued == 0
    assert eng.stats()["evacuated"] == 2.0
    for rec in recs:
        eng.resume_request(
            rec["prompt"], rec["max_new_tokens"],
            rec["request_id"], generated=rec["generated"],
            enqueued_at=rec["enqueued_at"], deadline=rec["deadline"],
            queue_deadline=rec["queue_deadline"],
            first_token_at=rec["first_token_at"],
            chunks=rec["chunks"],
        )
    out = {}
    while eng.has_work():
        for r in eng.step():
            out[r.request_id] = r
    assert_parity([out[r["request_id"]] for r in recs],
                  contig_ref, MAX_NEW)
    assert eng.mixed_trace_count == 1  # still the one fused trace


