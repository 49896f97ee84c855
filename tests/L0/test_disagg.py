"""Disaggregated serving tier: tp>1 mixed trace, page shipping, classes.

The ISSUE-17 acceptance bar as executable checks:

  * the tp=2 fused mixed step emits greedy tokens IDENTICAL to tp=1
    from the same tp=1 checkpoint (`shard_tp1_params`), still as ONE
    compiled trace per tick, with per-chip KV bytes exactly halved;
  * page-shipping migration (`evacuate(ship_pages=True)` ->
    `resume_request(pages=...)`) is token-identical to the token-replay
    path, leak-free on BOTH allocators, and falls back to replay —
    still token-identical — when the chaos plan drops the payload at
    the `page_ship` site;
  * a `replica_classes=["prefill", "decode"]` fleet produces the same
    greedy tokens as an identical-replica fleet while actually handing
    prompts off as shipped pages (handoffs, page_migrations and the
    decode replica's `page_ships` all advance) and publishing
    per-class TTFT/TPOT histograms;
  * `SharedPrefixRegistry` indexes chain keys published by the
    engines' `PrefixStore` hooks and `best()` returns per-replica
    matched-token depths;
  * `PagedKVCache.create(validate_tpu_layout=True)` rejects non
    sublane-multiple page sizes per pool dtype (8/fp32, 16/bf16,
    32/int8) and stays off on the CPU backend;
  * `flash_attention_decode_paged`'s dead-step re-point: table entries
    past a slot's live prefix are never fetched (the index map clamps
    onto the last live page), at full heads AND at per-shard head
    counts — the per-chip kernel instance the tp>1 cache sharding
    creates.

Engine tests reuse the test_inference shape tuple (fp32_cfg model,
slots=2, capacity=24, budget=4, page_size=4) so the persistent compile
cache pays each paged program once.
The tp=2 programs are a new geometry and compile cold once per cache
generation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_apex_tpu.inference import (
    Fault,
    FaultPlan,
    InferenceEngine,
    PagedKVCache,
    PrefixStore,
    ReplicaRouter,
    SamplingParams,
    SharedPrefixRegistry,
    shard_tp1_params,
)
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
from rocm_apex_tpu.ops.flash_attention import flash_attention_decode_paged
from rocm_apex_tpu.transformer import parallel_state


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
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )
    return model, params


#: the test_inference/test_paging shape tuple, paged
EKW = dict(
    num_slots=2, capacity=24, prefill_token_budget=4,
    paged=True, page_size=4,
    sampling=SamplingParams(temperature=0.0), seed=0,
)

PROMPTS = [
    [5, 6, 7, 8, 9, 10, 11],
    [3, 1, 4, 1, 5, 9, 2, 6, 5, 3],
    [12, 13],
]
MAX_NEW = 8

#: compiled-step donors, one per trace geometry seen in this module
_STEP_DONORS: list = []


def make_engine(model, params, **kw):
    ekw = dict(EKW)
    ekw.update(kw)
    for donor in _STEP_DONORS:
        try:
            return InferenceEngine(model, params, step_source=donor, **ekw)
        except ValueError:
            continue
    eng = InferenceEngine(model, params, **ekw)
    _STEP_DONORS.append(eng)
    return eng


def drain(engine, out=None, max_ticks=200):
    out = {} if out is None else out
    for _ in range(max_ticks):
        for r in engine.step():
            out[r.request_id] = (list(r.tokens), r.finish_reason)
        if engine.num_active == 0 and engine.num_queued == 0:
            return out
    raise AssertionError("engine failed to drain")


def run_all(engine, prompts=PROMPTS):
    for p in prompts:
        engine.add_request(list(p), max_new_tokens=MAX_NEW)
    return drain(engine)


def tp2_setup(model_and_params):
    """tp=2 mesh + model + params sliced from the tp=1 checkpoint."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 simulated devices")
    mesh = parallel_state.initialize_model_parallel(
        2, 1, devices=devs[:2]
    )
    _, params1 = model_and_params
    model2 = GPTModel(fp32_cfg(tensor_parallel_size=2))
    params2 = shard_tp1_params(model2, params1, mesh)
    return model2, params2


# ---------------------------------------------------------------------------
# rung 1: tp>1 mixed trace
# ---------------------------------------------------------------------------


class TestMixedTP:
    def test_tp2_matches_tp1_greedy(self, model_and_params):
        """tp=2 serve: token-identical to tp=1, ONE mixed trace,
        per-chip KV bytes exactly halved — the rung-1 acceptance."""
        model1, params1 = model_and_params
        eng1 = make_engine(model1, params1)
        out1 = run_all(eng1)
        assert eng1.mixed_trace_count == 1

        model2, params2 = tp2_setup(model_and_params)
        eng2 = InferenceEngine(model2, params2, **EKW)
        out2 = run_all(eng2)
        assert eng2.mixed_trace_count == 1
        assert out1 == out2

        kv1 = eng1.per_chip_kv_bytes()
        kv2 = eng2.per_chip_kv_bytes()
        assert kv2 * 2 == kv1, (kv1, kv2)


# ---------------------------------------------------------------------------
# rung 2: page-shipping migration
# ---------------------------------------------------------------------------


def migrate(model, params, ship, faults=None):
    """Run until every slot has generated >= 2 tokens, evacuate,
    resume into a fresh engine, and drain. Returns (tokens, stats)."""
    src = make_engine(model, params)
    for p in PROMPTS[:2]:
        src.add_request(list(p), max_new_tokens=MAX_NEW)
    out = {}
    for _ in range(40):
        for r in src.step():
            out[r.request_id] = (list(r.tokens), r.finish_reason)
        live = [s for s in src._slots if s is not None]
        if live and all(len(s.generated) >= 2 for s in live):
            break
    recs = src.evacuate(ship_pages=ship)
    # the source released every leased page, shipped or not
    src._allocator.assert_consistent()
    assert src._allocator.pages_used == 0
    if ship:
        assert any("pages" in r for r in recs), recs
    kw = {} if faults is None else {"faults": faults}
    dst = make_engine(model, params, **kw)
    for rec in recs:
        dst.resume_request(
            rec["prompt"], rec["max_new_tokens"], rec["request_id"],
            generated=rec["generated"],
            enqueued_at=rec["enqueued_at"], deadline=rec["deadline"],
            queue_deadline=rec["queue_deadline"],
            first_token_at=rec["first_token_at"], chunks=rec["chunks"],
            pages=rec.get("pages"),
        )
    drain(dst, out)
    dst._allocator.assert_consistent()
    return out, dst.stats()


class TestPageShipping:
    def test_ship_token_identity(self, model_and_params):
        """Shipped-page resume emits EXACTLY the replay path's tokens,
        and the import path actually ran (no silent fallback)."""
        model, params = model_and_params
        base = run_all(make_engine(model, params), PROMPTS[:2])
        replay, rst = migrate(model, params, ship=False)
        ship, sst = migrate(model, params, ship=True)
        assert sst["page_ships"] >= 1, sst
        assert sst["page_ship_fallbacks"] == 0, sst
        assert rst["page_ships"] == 0, rst
        assert base == replay
        assert base == ship

    def test_ship_chaos_fallback(self, model_and_params):
        """Chaos drops EVERY payload at the `page_ship` site: the
        destination falls back to token replay, still token-identical,
        with both allocators leak-free (asserted inside migrate)."""
        model, params = model_and_params
        base = run_all(make_engine(model, params), PROMPTS[:2])
        plan = FaultPlan(
            faults=[Fault(site="page_ship", every=1, times=None)]
        )
        chaos, cst = migrate(model, params, ship=True, faults=plan)
        assert cst["page_ships"] == 0, cst
        assert cst["page_ship_fallbacks"] >= 1, cst
        assert base == chaos

    @pytest.mark.slow
    def test_ship_tp2(self, model_and_params):
        """Page shipping is tp-agnostic: full-head payloads land in a
        head-sharded destination with the same greedy tokens."""
        model2, params2 = tp2_setup(model_and_params)
        base = run_all(InferenceEngine(model2, params2, **EKW),
                       PROMPTS[:2])
        global _STEP_DONORS
        saved = _STEP_DONORS
        _STEP_DONORS = []  # tp2 engines must not adopt tp1 programs
        try:
            ship, sst = migrate(model2, params2, ship=True)
        finally:
            _STEP_DONORS = saved
        assert sst["page_ships"] >= 1, sst
        assert sst["page_ship_fallbacks"] == 0, sst
        assert base == ship


# ---------------------------------------------------------------------------
# rung 3: prefill/decode replica classes
# ---------------------------------------------------------------------------

FLEET_PROMPTS = [
    [5, 6, 7, 8, 9, 10, 11],
    [3, 1, 4, 1, 5, 9, 2, 6, 5, 3],
    [5, 6, 7, 8, 9, 10, 12],  # shares a page-4 prefix with #0
    [12, 13],
]


class TestReplicaClasses:
    def test_disagg_fleet_parity(self, model_and_params):
        """A prefill/decode fleet matches an identical fleet token for
        token while actually migrating work: handoffs fire, payloads
        ship as pages, and the decode replica imports them."""
        model, params = model_and_params
        base = ReplicaRouter(
            model, params, replicas=2, engine_kwargs=dict(EKW)
        )
        r_base = base.generate(FLEET_PROMPTS, max_new_tokens=MAX_NEW)

        dis = ReplicaRouter(
            model, params, replicas=2, engine_kwargs=dict(EKW),
            replica_classes=["prefill", "decode"],
        )
        r_dis = dis.generate(FLEET_PROMPTS, max_new_tokens=MAX_NEW)
        for r0, r1 in zip(r_base, r_dis):
            assert r0.tokens == r1.tokens, (r0, r1)
            assert r0.finish_reason == r1.finish_reason
        st = dis.stats()
        assert st["handoffs"] >= 1, st
        assert st["page_migrations"] >= 1, st
        # the decode-class replica (index 1) imported shipped pages
        assert dis.replica(1).stats()["page_ships"] >= 1
        for i in range(2):
            dis.replica(i)._allocator.assert_consistent()
        # per-class latency families reached the merged registry
        merged = dis.merged_registry()
        text = merged.exposition()
        assert "router_ttft_ms" in text
        assert "router_tpot_ms" in text
        assert 'replica_class="decode"' in text

    def test_handoff_trace_continuity(self, model_and_params):
        """ISSUE-19 fleet-causal acceptance on the handoff path: a
        prompt prefilled on the prefill replica and decoded on the
        decode replica is ONE trace_id lifeline spanning both replica
        processes, finished exactly once, with the router's handoff
        instant carrying the same join key."""
        from rocm_apex_tpu.monitor.trace import Tracer, trace_lifelines

        model, params = model_and_params
        dis = ReplicaRouter(
            model, params, replicas=2, engine_kwargs=dict(EKW),
            replica_classes=["prefill", "decode"],
            tracer=Tracer(),
        )
        for i in range(2):
            dis.replica(i).tracer = Tracer()
        dis.generate(FLEET_PROMPTS, max_new_tokens=MAX_NEW)
        st = dis.stats()
        assert st["handoffs"] >= 1, st
        body = dis.merged_trace()
        assert body["otherData"]["processes"]["2"] == "replica0:prefill"
        assert body["otherData"]["processes"]["3"] == "replica1:decode"
        lines = trace_lifelines(body)
        assert len(lines) == len(FLEET_PROMPTS)
        assert all(l["finishes"] == 1 for l in lines.values()), lines
        handoff_ids = {
            e["args"]["trace_id"] for e in body["traceEvents"]
            if e.get("ph") == "i" and e["name"] == "handoff"
        }
        assert len(handoff_ids) >= 1
        for tid in handoff_ids:
            # prefilled on pid 2, decoded (and finished) on pid 3
            assert lines[tid]["pids"] == [1, 2, 3], lines[tid]
            assert "finish" in lines[tid]["names"]

    def test_class_validation(self, model_and_params):
        model, params = model_and_params
        with pytest.raises(ValueError, match="decode"):
            # prefill without a decode target is a dead end
            ReplicaRouter(
                model, params, replicas=2, engine_kwargs=dict(EKW),
                replica_classes=["prefill", "prefill"],
            )
        with pytest.raises(ValueError):
            ReplicaRouter(
                model, params, replicas=2, engine_kwargs=dict(EKW),
                replica_classes=["mixed"],  # wrong length
            )

    @pytest.mark.slow
    def test_disagg_acceptance_heavy(self, model_and_params):
        """Heavy acceptance: a 3-class fleet (prefill, decode, mixed)
        under a larger prompt mix stays token-identical to a uniform
        fleet and leak-free end to end."""
        model, params = model_and_params
        prompts = [
            [(7 * i + 3 * j) % 96 for j in range(3 + (i % 9))]
            for i in range(12)
        ]
        base = ReplicaRouter(
            model, params, replicas=3, engine_kwargs=dict(EKW)
        )
        r_base = base.generate(prompts, max_new_tokens=MAX_NEW)
        dis = ReplicaRouter(
            model, params, replicas=3, engine_kwargs=dict(EKW),
            replica_classes=["prefill", "decode", "mixed"],
        )
        r_dis = dis.generate(prompts, max_new_tokens=MAX_NEW)
        for r0, r1 in zip(r_base, r_dis):
            assert r0.tokens == r1.tokens, (r0, r1)
        st = dis.stats()
        assert st["handoffs"] >= 1, st
        for i in range(3):
            dis.replica(i)._allocator.assert_consistent()
            assert dis.replica(i)._allocator.pages_used == 0


# ---------------------------------------------------------------------------
# shared prefix registry
# ---------------------------------------------------------------------------


class TestSharedPrefixRegistry:
    def test_publish_unpublish_best(self):
        reg = SharedPrefixRegistry(page_size=4)
        k1 = (None, (1, 2, 3, 4))
        k2 = (k1, (5, 6, 7, 8))
        reg.publish(0, k1)
        reg.publish(1, k1)
        reg.publish(1, k2)
        assert len(reg) == 2
        assert reg.holders(k1) == {0, 1}
        # replica 1 holds the deeper chain; the walk stops where each
        # replica's coverage ends
        best = reg.best([1, 2, 3, 4, 5, 6, 7, 8, 9])
        assert best == {0: 4, 1: 8}
        # never claims the WHOLE prompt (last token must stay live)
        assert reg.best([1, 2, 3, 4]) == {}
        reg.unpublish(1, k2)
        reg.unpublish(1, k1)
        assert reg.best([1, 2, 3, 4, 5, 6, 7, 8, 9]) == {0: 4}
        reg.unpublish(0, k1)
        assert len(reg) == 0
        assert reg.best([1, 2, 3, 4, 5]) == {}

    def test_store_hooks_feed_registry(self):
        """PrefixStore pub/sub: registrations flow into the registry,
        orphan-cascade unregistration flows back out."""
        store = PrefixStore(page_size=4)
        reg = SharedPrefixRegistry(page_size=4)
        store.on_register = lambda key, page: reg.publish(7, key)
        store.on_unregister = lambda key, page: reg.unpublish(7, key)
        k1 = store.register(None, [1, 2, 3, 4], page=10)
        k2 = store.register(k1, [5, 6, 7, 8], page=11)
        assert len(reg) == 2
        assert reg.best([1, 2, 3, 4, 5, 6, 7, 8, 9]) == {7: 8}
        # duplicate chain: first registration wins, no double publish
        store.register(None, [1, 2, 3, 4], page=12)
        assert reg.holders(k1) == {7}
        # unregistering the ROOT cascades through the child
        store.unregister_page(10)
        assert len(reg) == 0
        assert k2 not in reg._holders


# ---------------------------------------------------------------------------
# satellite: sublane-multiple page_size validation
# ---------------------------------------------------------------------------


class TestSublaneValidation:
    ARGS = dict(num_layers=1, num_slots=2, capacity=32,
                num_heads=2, head_dim=8)

    @pytest.mark.parametrize(
        "dtype,quantized,bad,good",
        [
            (jnp.float32, False, 4, 8),
            (jnp.bfloat16, False, 8, 16),
            (jnp.bfloat16, True, 16, 32),  # int8 pools
        ],
    )
    def test_sublane_multiple_enforced(self, dtype, quantized, bad,
                                       good):
        with pytest.raises(ValueError, match="sublane"):
            PagedKVCache.create(
                page_size=bad, dtype=dtype, quantized=quantized,
                validate_tpu_layout=True, **self.ARGS
            )
        cache = PagedKVCache.create(
            page_size=good, dtype=dtype, quantized=quantized,
            validate_tpu_layout=True, **self.ARGS
        )
        assert cache.page_size == good

    def test_auto_off_on_cpu(self):
        """The check only self-arms on the TPU backend: CPU tests keep
        their tiny page_size=4 fp32 pools."""
        cache = PagedKVCache.create(page_size=4, dtype=jnp.float32,
                                    **self.ARGS)
        assert cache.page_size == 4


# ---------------------------------------------------------------------------
# satellite: dead-step DMA re-point under head sharding
# ---------------------------------------------------------------------------


def _paged_reference(q, k_pool, v_pool, table, lengths):
    """numpy softmax attention over each slot's live prefix rows."""
    bh, t, d = q.shape
    num_pages, nh, ps, _ = k_pool.shape
    out = np.zeros_like(np.asarray(q))
    scale = 1.0 / np.sqrt(d)
    for b in range(bh):
        slot, head = b // nh, b % nh
        n = int(lengths[slot])
        if n == 0:
            continue
        pages = [int(p) for p in table[slot, : -(-n // ps)]]
        k = np.concatenate(
            [np.asarray(k_pool[p, head]) for p in pages]
        )[:n]
        v = np.concatenate(
            [np.asarray(v_pool[p, head]) for p in pages]
        )[:n]
        s = np.asarray(q[b]) @ k.T * scale
        s = s - s.max(axis=-1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(axis=-1, keepdims=True)
        out[b] = p @ v
    return out


class TestDeadStepRepoint:
    """Grid steps past a slot's live prefix must neither fetch nor
    contribute: the kernel's index map clamps them onto the last live
    page (a repeated block index is not refetched — no DMA) and the
    compute guard masks them. Pinned by pointing every DEAD table
    entry at a garbage page and demanding bit-identical output."""

    NUM_PAGES, NH, PS, D, SLOTS = 8, 4, 8, 16, 2

    def _build(self):
        rng = np.random.default_rng(0)
        num_pages, nh, ps, d = self.NUM_PAGES, self.NH, self.PS, self.D
        k_pool = rng.standard_normal(
            (num_pages, nh, ps, d), dtype=np.float32
        )
        v_pool = rng.standard_normal(
            (num_pages, nh, ps, d), dtype=np.float32
        )
        # a poisoned page: huge values that would blow up the softmax
        # if any dead step ever fetched it
        k_pool[5] = 1e4
        v_pool[5] = -1e4
        q = rng.standard_normal(
            (self.SLOTS * nh, 1, d), dtype=np.float32
        )
        lengths = np.array([10, 5], np.int32)  # 2 live pages, 1
        sent = num_pages
        table = np.array(
            [[0, 1, sent], [2, sent, sent]], np.int32
        )
        return q, k_pool, v_pool, table, lengths

    def test_dead_entries_never_fetched_full_heads(self):
        q, k_pool, v_pool, table, lengths = self._build()
        clean = flash_attention_decode_paged(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), jnp.asarray(lengths),
        )
        poisoned = np.where(table == self.NUM_PAGES, 5, table)
        dirty = flash_attention_decode_paged(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(poisoned), jnp.asarray(lengths),
        )
        assert np.array_equal(np.asarray(clean), np.asarray(dirty))
        ref = _paged_reference(q, k_pool, v_pool, table, lengths)
        np.testing.assert_allclose(
            np.asarray(clean), ref, rtol=2e-5, atol=2e-5
        )

    def test_dead_entries_never_fetched_per_shard_heads(self):
        """The tp>1 cache shards pools over heads: each chip's kernel
        instance sees nh/tp heads. Run the kernel per 2-head shard,
        with poisoned dead entries, and demand the concatenation match
        the full-head result exactly."""
        q, k_pool, v_pool, table, lengths = self._build()
        full = np.asarray(flash_attention_decode_paged(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), jnp.asarray(lengths),
        )).reshape(self.SLOTS, self.NH, 1, self.D)
        poisoned = np.where(table == self.NUM_PAGES, 5, table)
        q4 = q.reshape(self.SLOTS, self.NH, 1, self.D)
        for lo in (0, 2):  # the two tp=2 shards
            shard = np.asarray(flash_attention_decode_paged(
                jnp.asarray(
                    q4[:, lo:lo + 2].reshape(-1, 1, self.D)
                ),
                jnp.asarray(k_pool[:, lo:lo + 2]),
                jnp.asarray(v_pool[:, lo:lo + 2]),
                jnp.asarray(poisoned), jnp.asarray(lengths),
            )).reshape(self.SLOTS, 2, 1, self.D)
            assert np.array_equal(shard, full[:, lo:lo + 2])
