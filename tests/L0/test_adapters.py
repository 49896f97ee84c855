"""Multi-LoRA serving tier: segmented deltas, the paged adapter pool,
and per-tenant SLO admission (ISSUE 18).

The contract under test: per-slot low-rank deltas gathered out of one
rank-padded packed pool must ride the SAME fused mixed chunk+decode
program at any adapter mix (`mixed_trace_count` stays 1 across swaps,
park/reclaim, and preemption), adapter-0 traffic must be bitwise
identical to a pool-less engine (zero extra FLOPs proven on the
`lax.cond` skip branch), the pool must stay leak-free (refs back to
the base's single self-ref) after every teardown path, residency
pressure must backpressure at admission without deadlock, per-tenant
labeled metric families must degrade to the ``other`` overflow tenant
at the cardinality cap instead of raising on the hot path, and the
tier scheduler (tier-ordered admission, tier-aware shed, opt-in tier
preemption) must never change a surviving request's tokens.

Engines here reuse test_inference.py's shape tuple (slots=2,
capacity=24, budget=4, the fp32_cfg model) so the persistent compile
cache pays the lora programs once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_apex_tpu.inference import (
    BASE_ADAPTER_ID,
    AdapterPool,
    InferenceEngine,
    ReplicaRouter,
    SamplingParams,
)
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
from rocm_apex_tpu.ops.lora import (
    apply_lora,
    pad_rank,
    segmented_lora_delta,
)


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


CFG = fp32_cfg()


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTModel(CFG)
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )
    return model, params


def make_pool(max_resident=4, max_rank=4):
    return AdapterPool(
        CFG.num_layers, CFG.hidden_size,
        max_resident=max_resident, max_rank=max_rank,
    )


def register(pool, name, rank=2, scale=0.6, tier=0, seed=1):
    """Register a random adapter. scale=0.6 on the 32-wide model is
    big enough to visibly flip greedy argmax — the delta-took-effect
    canary several tests rely on."""
    rng = np.random.RandomState(seed)
    ws = [
        {
            "qkv": (scale * rng.randn(CFG.hidden_size, rank),
                    scale * rng.randn(rank, 3 * CFG.hidden_size)),
            "dense": (scale * rng.randn(CFG.hidden_size, rank),
                      scale * rng.randn(rank, CFG.hidden_size)),
        }
        for _ in range(CFG.num_layers)
    ]
    return pool.register(name, ws, rank=rank, tier=tier)


def make_engine(model_and_params, pool=None, **kw):
    model, params = model_and_params
    kw.setdefault("sampling", SamplingParams(temperature=0.0))
    kw.setdefault("seed", 0)
    return InferenceEngine(
        model, params, num_slots=2, capacity=24,
        prefill_token_budget=4, adapter_pool=pool, **kw
    )


def drain(eng, sink=None):
    out = {}
    while eng.has_work():
        for r in eng.step():
            out[r.request_id] = r
    if sink is not None:
        sink.update(out)
    return out


PROMPTS = [[3, 5, 7, 9], [11, 13], [2, 4, 6, 8, 10], [5, 5, 5]]


# ---------------------------------------------------------------------------
# ops/lora.py: the segmented gather->bmm pass
# ---------------------------------------------------------------------------


class TestSegmentedDelta:
    def test_matches_dense_reference(self):
        rng = np.random.RandomState(0)
        t, h, o, P, r = 6, 8, 12, 3, 2
        x = rng.randn(t, h).astype(np.float32)
        A = rng.randn(P, h, r).astype(np.float32)
        B = rng.randn(P, r, o).astype(np.float32)
        ids = np.array([0, 1, 2, 1, 0, 2], np.int32)
        got = np.asarray(segmented_lora_delta(
            jnp.asarray(x), jnp.asarray(A), jnp.asarray(B),
            jnp.asarray(ids),
        ))
        want = np.stack([
            x[i] @ A[ids[i]] @ B[ids[i]] for i in range(t)
        ])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_base_slot_zeros_contribute_nothing(self):
        rng = np.random.RandomState(1)
        A = rng.randn(3, 8, 2).astype(np.float32)
        B = rng.randn(3, 2, 8).astype(np.float32)
        A[0] = 0.0
        B[0] = 0.0
        x = rng.randn(4, 8).astype(np.float32)
        ids = jnp.array([0, 2, 0, 1], jnp.int32)
        d = np.asarray(segmented_lora_delta(
            jnp.asarray(x), jnp.asarray(A), jnp.asarray(B), ids
        ))
        assert np.all(d[0] == 0.0) and np.all(d[2] == 0.0)
        assert np.any(d[1] != 0.0) and np.any(d[3] != 0.0)

    def test_apply_lora_adds_delta_when_active(self):
        rng = np.random.RandomState(2)
        b, s, h, o = 1, 4, 8, 8
        y = jnp.asarray(rng.randn(b, s, o).astype(np.float32))
        x = jnp.asarray(rng.randn(b, s, h).astype(np.float32))
        A = jnp.asarray(rng.randn(2, h, 2).astype(np.float32))
        B = jnp.asarray(rng.randn(2, 2, o).astype(np.float32))
        ids = jnp.array([1, 0, 1, 1], jnp.int32)
        got = apply_lora(y, x, (A, B), ids, jnp.any(ids != 0))
        want = y + segmented_lora_delta(
            x.reshape(s, h), A, B, ids
        ).reshape(b, s, o)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6
        )
        # inactive: y passes through untouched (bitwise)
        off = apply_lora(y, x, (A, B), ids, jnp.asarray(False))
        assert np.array_equal(np.asarray(off), np.asarray(y))

    def test_skip_branch_is_provably_free(self):
        """The pure-base proof: the `lax.cond` false branch contains
        ZERO equations — not merely cheap ones — so a pure-base tick
        pays no adapter FLOPs at all."""
        A = jnp.zeros((3, 8, 2), jnp.float32)
        B = jnp.zeros((3, 2, 8), jnp.float32)
        ids = jnp.zeros((4,), jnp.int32)

        def f(y, x, active):
            return apply_lora(y, x, (A, B), ids, active)

        jaxpr = jax.make_jaxpr(f)(
            jnp.ones((1, 4, 8)), jnp.ones((1, 4, 8)),
            jnp.asarray(False),
        )
        conds = [
            e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"
        ]
        assert len(conds) == 1
        branch_eqns = sorted(
            len(b.jaxpr.eqns) for b in conds[0].params["branches"]
        )
        assert branch_eqns[0] == 0, (
            f"skip branch must be the identity, has "
            f"{branch_eqns[0]} equations"
        )
        assert branch_eqns[1] > 0  # the on branch does real work

    def test_pad_rank_exact_and_scaled(self):
        rng = np.random.RandomState(3)
        a = rng.randn(8, 3).astype(np.float32)
        b = rng.randn(3, 5).astype(np.float32)
        a_p, b_p = pad_rank(a, b, 6, alpha=6.0)
        assert a_p.shape == (8, 6) and b_p.shape == (6, 5)
        assert np.all(a_p[:, 3:] == 0.0) and np.all(b_p[3:, :] == 0.0)
        # zero-padding is exact; alpha/r folds into B once
        np.testing.assert_allclose(
            a_p @ b_p, (a @ b) * 2.0, rtol=1e-5
        )
        # default alpha = r: scale exactly 1
        a_1, b_1 = pad_rank(a, b, 3)
        np.testing.assert_allclose(a_1 @ b_1, a @ b, rtol=1e-6)
        with pytest.raises(ValueError, match="exceeds the pool"):
            pad_rank(a, b, 2)
        with pytest.raises(ValueError, match="matching"):
            pad_rank(a, rng.randn(4, 5), 6)


# ---------------------------------------------------------------------------
# AdapterPool: registry + paged residency
# ---------------------------------------------------------------------------


class TestAdapterPool:
    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="max_resident"):
            make_pool(max_resident=1)
        with pytest.raises(ValueError, match="max_rank"):
            make_pool(max_rank=0)
        with pytest.raises(ValueError, match="geometry"):
            AdapterPool(0, 32)

    def test_register_validation_and_ids(self):
        pool = make_pool()
        a1 = register(pool, "t1", seed=1)
        a2 = register(pool, "t2", seed=2)
        assert (a1, a2) == (1, 2)
        assert pool.num_registered == 2
        assert pool.lookup("t2") == a2 and pool.lookup("nope") is None
        assert pool.tenant_of(a1) == "t1"
        assert pool.tenant_of(BASE_ADAPTER_ID) == "base"
        assert pool.rank_of(a1) == 2 and pool.rank_of(0) == 0
        assert pool.known(0) and pool.known(a1) and not pool.known(99)
        with pytest.raises(ValueError, match="already registered"):
            register(pool, "t1")
        with pytest.raises(ValueError, match="bad tenant"):
            register(pool, "base")
        with pytest.raises(ValueError, match="per-layer"):
            pool.register("t3", [], rank=2)
        with pytest.raises(ValueError, match="A shape"):
            pool.register(
                "t3",
                [{"qkv": (np.zeros((5, 2)), np.zeros((2, 96)))}
                 for _ in range(CFG.num_layers)],
                rank=2,
            )

    def test_acquire_release_park_reclaim_revive(self):
        pool = make_pool(max_resident=3)  # base + 2 adapter slots
        a1, a2, a3 = (
            register(pool, f"t{i}", seed=i) for i in (1, 2, 3)
        )
        # base is free and permanent
        assert pool.acquire(BASE_ADAPTER_ID) == 0
        pool.release(BASE_ADAPTER_ID)
        s1 = pool.acquire(a1)
        s2 = pool.acquire(a2)
        assert {s1, s2} == {1, 2}
        assert pool.snapshot()["uploads"] == 2
        # every slot pinned: backpressure, not an exception
        assert pool.acquire(a3) is None
        # park a1 (bytes stay), revive it for free
        pool.release(a1)
        assert pool.resident(a1) and pool.refs(a1) == 0
        assert pool.acquire(a1) == s1
        snap = pool.snapshot()
        assert snap["revivals"] == 1 and snap["uploads"] == 2
        # park a1 again; a3's alloc now reclaims the LRU parked slot
        pool.release(a1)
        s3 = pool.acquire(a3)
        assert s3 == s1 and not pool.resident(a1)
        snap = pool.snapshot()
        assert snap["evictions"] == 1 and snap["uploads"] == 3
        pool.release(a2)
        pool.release(a3)
        pool.assert_consistent()
        assert pool.snapshot()["refs"] == 1  # base self-ref only
        with pytest.raises(KeyError, match="unknown"):
            pool.acquire(99)
        with pytest.raises(RuntimeError, match="non-resident"):
            pool.release(a1)

    def test_buffer_setter_validation(self):
        pool = make_pool()
        with pytest.raises(ValueError, match="keys"):
            pool.buffers = {"qkv": pool.buffers["qkv"]}

    def test_uploaded_slot_holds_padded_factors(self):
        pool = make_pool(max_rank=4)
        a1 = register(pool, "t1", rank=2, seed=5)
        slot = pool.acquire(a1)
        A = np.asarray(pool.buffers["qkv"][0])  # (L, P, h, r)
        assert np.any(A[:, slot, :, :2] != 0.0)
        assert np.all(A[:, slot, :, 2:] == 0.0)  # rank padding
        assert np.all(np.asarray(pool.buffers["qkv"][0])[:, 0] == 0.0)


# ---------------------------------------------------------------------------
# engine integration: one trace, parity, churn, accounting
# ---------------------------------------------------------------------------


class TestEngineLora:
    def test_adapter0_bitwise_parity_and_one_trace(
        self, model_and_params
    ):
        base = make_engine(model_and_params)
        for p in PROMPTS:
            base.add_request(p, 5)
        out_b = drain(base)

        pool = make_pool()
        register(pool, "t1", seed=1)
        eng = make_engine(model_and_params, pool)
        for p in PROMPTS:
            eng.add_request(p, 5)  # all adapter 0
        out_l = drain(eng)
        assert {
            k: r.tokens for k, r in out_b.items()
        } == {k: r.tokens for k, r in out_l.items()}
        assert eng.mixed_trace_count == 1

    @pytest.mark.slow
    def test_mixed_batch_base_rides_unchanged(self, model_and_params):
        base = make_engine(model_and_params)
        ids_b = [base.add_request(p, 5) for p in PROMPTS[:3]]
        out_b = drain(base)

        pool = make_pool()
        a1 = register(pool, "t1", seed=1)
        a2 = register(pool, "t2", seed=2)
        eng = make_engine(model_and_params, pool)
        ids_l = [
            eng.add_request(PROMPTS[0], 5, adapter_id=a1),
            eng.add_request(PROMPTS[1], 5, adapter_id=a2),
            eng.add_request(PROMPTS[2], 5),
        ]
        out_l = drain(eng)
        assert eng.mixed_trace_count == 1
        # the base request in the mixed batch: bitwise identical
        assert out_l[ids_l[2]].tokens == out_b[ids_b[2]].tokens
        # the adapters actually did something
        assert out_l[ids_l[0]].tokens != out_b[ids_b[0]].tokens
        # tenants attributed on the completion records
        recs = {c["request_id"]: c for c in eng.completions}
        assert recs[ids_l[0]]["tenant"] == "t1"
        assert recs[ids_l[2]]["tenant"] == "base"

    def test_park_reclaim_churn_never_retraces_or_leaks(
        self, model_and_params
    ):
        pool = make_pool(max_resident=3)  # 2 adapter slots
        aids = [
            register(pool, f"t{i}", seed=i) for i in (1, 2, 3, 4)
        ]
        eng = make_engine(model_and_params, pool)
        for aid in aids + [aids[0], aids[2]]:
            eng.add_request([1, 2, 3], 3, adapter_id=aid)
            drain(eng)
        snap = pool.snapshot()
        assert snap["evictions"] > 0 and snap["revivals"] >= 0
        assert eng.mixed_trace_count == 1
        pool.assert_consistent()
        assert snap["refs"] == 1

    def test_tenant_accounting_identity_and_stats(
        self, model_and_params
    ):
        pool = make_pool()
        a1 = register(pool, "t1", seed=1)
        a2 = register(pool, "t2", seed=2)
        eng = make_engine(model_and_params, pool)
        for p, a in zip(PROMPTS, [0, a1, a2, a1]):
            eng.add_request(p, 3, adapter_id=a)
        drain(eng)
        ts = eng.tenant_stats()
        assert set(ts) == {"base", "t1", "t2"}
        assert ts["t1"]["completed"] == 2
        assert sum(s["completed"] for s in ts.values()) == len(
            eng.completions
        )
        assert sum(
            s["generated_tokens"] for s in ts.values()
        ) == sum(c["new_tokens"] for c in eng.completions)
        st = eng.stats()
        for k in ("adapters_registered", "adapters_resident",
                  "adapter_uploads", "adapter_evictions",
                  "adapter_revivals", "adapter_stalls",
                  "tier_preemptions", "tier_sheds"):
            assert k in st, k
        assert st["adapters_registered"] == 2.0
        eng.reset_stats()
        assert eng.tenant_stats() == {}

    def test_add_request_validation(self, model_and_params):
        eng = make_engine(model_and_params)
        with pytest.raises(ValueError, match="adapter_pool"):
            eng.add_request([1, 2], 2, adapter_id=1)
        pool = make_pool()
        register(pool, "t1")
        eng2 = make_engine(model_and_params, pool)
        with pytest.raises(KeyError, match="unknown adapter_id"):
            eng2.add_request([1, 2], 2, adapter_id=42)

    def test_adopt_steps_refuses_pool_mismatch(
        self, model_and_params
    ):
        pool = make_pool()
        register(pool, "t1")
        src = make_engine(model_and_params)
        with pytest.raises(ValueError, match="adapter_pool presence"):
            make_engine(model_and_params, pool, step_source=src)
        src_l = make_engine(model_and_params, pool)
        other = make_pool(max_rank=8)  # different packed geometry
        with pytest.raises(ValueError, match="adapter pool geometry"):
            make_engine(model_and_params, other, step_source=src_l)
        # matching geometry adopts: programs shared, traces shared
        twin_pool = make_pool()
        register(twin_pool, "t1")
        twin = make_engine(model_and_params, twin_pool,
                           step_source=src_l)
        assert twin.programs is src_l.programs


# ---------------------------------------------------------------------------
# residency backpressure + tier scheduling
# ---------------------------------------------------------------------------


class TestAdmission:
    @pytest.mark.slow
    def test_residency_backpressure_resolves(self, model_and_params):
        pool = make_pool(max_resident=2)  # ONE adapter slot
        b1 = register(pool, "x1", seed=21)
        b2 = register(pool, "x2", seed=22)
        eng = make_engine(model_and_params, pool)
        r1 = eng.add_request([1, 2], 6, adapter_id=b1)
        r2 = eng.add_request([3, 4], 6, adapter_id=b2)
        done = {}
        ticks = 0
        while eng.has_work():
            for r in eng.step():
                done[r.request_id] = r
            ticks += 1
            assert ticks < 200, "residency backpressure deadlocked"
        assert set(done) == {r1, r2}
        assert all(
            r.finish_reason == "length" for r in done.values()
        )
        assert eng.stats()["adapter_stalls"] > 0
        pool.assert_consistent()
        assert pool.snapshot()["refs"] == 1

    @pytest.mark.slow
    def test_tier_aware_queue_shed(self, model_and_params):
        pool = make_pool()
        lo = register(pool, "free", tier=0, seed=31)
        hi = register(pool, "paid", tier=2, seed=32)
        eng = make_engine(model_and_params, pool, max_queue=2)
        busy = [eng.add_request([9] * 6, 8) for _ in range(2)]
        eng.step()  # busy fills both slots
        q1 = eng.add_request([1, 2], 3, adapter_id=lo)
        q2 = eng.add_request([3, 4], 3, adapter_id=lo)
        # queue full; the high-tier arrival sheds the NEWEST request
        # of the LOWEST tier, not itself
        q3 = eng.add_request([5, 6], 3, adapter_id=hi)
        res = drain(eng)
        assert res[q2].finish_reason == "queue_full"
        assert res[q3].finish_reason == "length"
        assert res[q1].finish_reason == "length"
        assert eng.stats()["tier_sheds"] == 1.0
        assert all(res[b].finish_reason == "length" for b in busy)
        pool.assert_consistent()
        assert pool.snapshot()["refs"] == 1

    @pytest.mark.slow
    def test_tier_preemption_token_identical(self, model_and_params):
        pool = make_pool()
        lo = register(pool, "lo", tier=0, seed=41)
        hi = register(pool, "hi", tier=3, seed=42)
        eng = make_engine(model_and_params, pool,
                          tier_preemption=True)
        busy = [
            eng.add_request([7] * 4, 8, adapter_id=lo)
            for _ in range(3)
        ]
        for _ in range(2):
            eng.step()
        vip = eng.add_request([8, 8], 3, adapter_id=hi)
        res = drain(eng)
        assert eng.stats()["tier_preemptions"] >= 1.0
        assert len(res[vip].tokens) == 3
        # preempted low-tier requests still finish IN FULL with the
        # tokens a calm run produces
        assert all(len(res[b].tokens) == 8 for b in busy)
        calm_pool = make_pool()
        lo_c = register(calm_pool, "lo", tier=0, seed=41)
        calm = make_engine(model_and_params, calm_pool)
        calm_ids = [
            calm.add_request([7] * 4, 8, adapter_id=lo_c)
            for _ in range(3)
        ]
        res_c = drain(calm)
        for b, c in zip(busy, calm_ids):
            assert res[b].tokens == res_c[c].tokens
        pool.assert_consistent()
        assert pool.snapshot()["refs"] == 1
        assert eng.mixed_trace_count == 1


# ---------------------------------------------------------------------------
# per-tenant telemetry: labeled families under the cardinality cap
# ---------------------------------------------------------------------------


class TestTenantTelemetry:
    @pytest.mark.slow
    def test_overflow_tenant_never_raises_on_hot_path(
        self, model_and_params
    ):
        from rocm_apex_tpu.monitor.telemetry import MetricRegistry

        reg = MetricRegistry(max_label_sets=8)
        pool = make_pool(max_resident=8)
        aids = [
            register(pool, f"t{i}", seed=10 + i) for i in range(5)
        ]
        eng = make_engine(model_and_params, pool, registry=reg)
        for i, aid in enumerate([0] + aids):
            eng.add_request([1 + i, 2, 3], 3, adapter_id=aid)
        drain(eng)
        # the cap bit some tenants; they fold into "other" instead of
        # raising CardinalityError mid-serve
        assert eng._tenant_overflowed
        assert "other" in eng._tenant_label_ok
        # the unlabeled aggregate still counts every request
        assert eng._h_ttft.count() == 6
        # host accounting keeps TRUE tenant names regardless
        assert set(eng.tenant_stats()) == {"base"} | {
            f"t{i}" for i in range(5)
        }
        # reset keeps the overflow series alive for the next window
        eng.reset_stats()
        eng.add_request([1, 2], 2, adapter_id=aids[0])
        drain(eng)
        assert len(eng.completions) == 1

    def test_tenant_slo_board_isolation(self):
        from rocm_apex_tpu.monitor import (
            BurnRule, MetricRegistry, TenantSLOBoard,
        )

        reg = MetricRegistry()
        hist = reg.histogram(
            "serve_ttft_ms", "ttft", labelnames=("tenant",)
        )
        board = TenantSLOBoard(
            hist, objective=0.9, threshold_ms=100.0,
            windows=(BurnRule(4.0, 2.0, 2.0),),
        )
        board.ensure("calm")
        board.ensure("burst")
        board.tick(now=0.0)
        for i in range(12):
            hist.observe(5.0, tenant="calm")
            # the burster blows the threshold every time
            hist.observe(500.0, tenant="burst")
            board.tick(now=float(i + 1))
            board.alerts(now=float(i + 1))
        assert board.monitors["burst"].events, "burst never fired"
        assert not board.monitors["calm"].events, (
            "the burst bled into the calm tenant's monitor"
        )
        alerts = board.alerts(now=13.0)
        assert all(a["tenant"] == "burst" for a in alerts)
        status = board.status(now=13.0)
        assert set(status) == {"calm", "burst"}

    def test_slo_labels_restricted_to_latency(self):
        from rocm_apex_tpu.monitor import SLO, MetricRegistry

        reg = MetricRegistry()
        good = reg.counter("good_total", "g")
        total = reg.counter("all_total", "t")
        with pytest.raises(ValueError, match="latency"):
            SLO("ratio", 0.99, good=good, total=total,
                labels={"tenant": "x"})

    @pytest.mark.slow
    def test_board_sync_maps_engine_tenants(self, model_and_params):
        from rocm_apex_tpu.monitor import TenantSLOBoard

        pool = make_pool()
        a1 = register(pool, "t1", seed=1)
        eng = make_engine(model_and_params, pool)
        eng.add_request([1, 2], 2, adapter_id=a1)
        eng.add_request([3, 4], 2)
        drain(eng)
        board = TenantSLOBoard(eng._h_ttft)
        board.sync(eng)
        assert set(board.monitors) == {"base", "t1"}


# ---------------------------------------------------------------------------
# router: adapter-affinity placement
# ---------------------------------------------------------------------------


class TestRouterAdapterAffinity:
    @pytest.mark.slow
    def test_affinity_and_validation(self, model_and_params):
        def mk():
            pool = make_pool()
            aid = register(pool, "t1", seed=1)
            return make_engine(model_and_params, pool), aid

        e0, aid = mk()
        e1, _ = mk()
        router = ReplicaRouter(engines=[e0, e1])
        out = {}
        router.add_request([1, 2, 3], 3, adapter_id=aid)
        while router.has_work():
            for r in router.step():
                out[r.request_id] = r
        # follow-up requests stick to the replica holding the adapter
        for _ in range(3):
            router.add_request([4, 5], 3, adapter_id=aid)
        while router.has_work():
            for r in router.step():
                out[r.request_id] = r
        st = router.stats()
        assert st["adapter_affinity_hits"] >= 3.0
        assert all(
            r.finish_reason == "length" for r in out.values()
        )
        with pytest.raises(KeyError, match="not registered"):
            router.add_request([1], 2, adapter_id=77)
        bare = ReplicaRouter(
            engines=[make_engine(model_and_params),
                     make_engine(model_and_params)]
        )
        with pytest.raises(ValueError, match="AdapterPool"):
            bare.add_request([1], 2, adapter_id=1)
