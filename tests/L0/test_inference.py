"""Inference tier: KV cache correctness, sampling, continuous batching.

The contract under test is the ISSUE-1 acceptance bar plus the ISSUE-5
chunked-prefill bar: prefill+decode through the preallocated cache must
reproduce the full-sequence forward logits at fp32 tolerance on CPU,
sampling must replay under a fixed seed, slot eviction/reuse must not
pollute a successor request, the engine's compiled programs must trace
exactly once while serving mixed-length traffic with mid-stream admits
and evictions, and the token-budget chunked scheduler must be greedy-
token-identical to the whole-prompt path while (a) serving prompts
longer than any whole-prompt pad width, (b) decoding every tick while
a long prefill streams, and (c) never materializing a full-prompt-width
activation in the mixed step (`monitor.audit.assert_no_intermediate`).

Every engine in this file shares ONE shape tuple (slots=2, capacity=24,
budget=4, the fp32_cfg model) so the persistent compile cache pays each
program once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_apex_tpu.inference import (
    InferenceEngine,
    KVCache,
    SamplingParams,
    greedy,
    sample,
    top_k_logits,
    top_p_logits,
)
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel


def fp32_cfg(**kw):
    """Tiny fp32 GPT: CPU-exact numerics so cache-vs-full comparisons
    test the CACHE PLUMBING, not bf16 rounding."""
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


# ---------------------------------------------------------------------------
# KV cache pytree
# ---------------------------------------------------------------------------


class TestKVCache:
    def test_create_shapes_and_default_dtype(self):
        cfg = fp32_cfg()
        cache = KVCache.for_model(cfg, num_slots=3, capacity=16)
        assert cache.num_layers == cfg.num_layers
        assert cache.num_slots == 3
        assert cache.capacity == 16
        hd = cfg.head_dim
        assert cache.k[0].shape == (3, 16, cfg.num_attention_heads, hd)
        # dtype follows the model's compute dtype (bf16 under O4/O5)
        assert cache.k[0].dtype == cfg.dtype
        bf = KVCache.for_model(
            fp32_cfg(dtype=jnp.bfloat16), num_slots=1, capacity=8
        )
        assert bf.k[0].dtype == jnp.bfloat16

    def test_write_at_per_slot_offsets(self):
        cache = KVCache.create(1, 2, 8, 1, 4, dtype=jnp.float32)
        cache = cache.replace(lengths=jnp.array([0, 3], jnp.int32))
        new = jnp.ones((2, 2, 1, 4), jnp.float32)
        cache = cache.write(0, new, new * 2.0)
        k = np.asarray(cache.k[0])
        # slot 0 wrote rows [0, 2), slot 1 wrote rows [3, 5)
        assert np.all(k[0, 0:2] == 1.0) and np.all(k[0, 2:] == 0.0)
        assert np.all(k[1, 3:5] == 1.0)
        assert np.all(k[1, :3] == 0.0) and np.all(k[1, 5:] == 0.0)
        # write does not advance; advance does, with masking + clamp
        assert np.array_equal(np.asarray(cache.lengths), [0, 3])
        adv = cache.advance(2, active=jnp.array([True, False]))
        assert np.array_equal(np.asarray(adv.lengths), [2, 3])
        assert np.asarray(cache.advance(100).lengths).max() == 8

    def test_slot_view_write_back_roundtrip(self):
        cache = KVCache.create(2, 3, 4, 2, 4, dtype=jnp.float32)
        cache = cache.replace(lengths=jnp.array([1, 2, 3], jnp.int32))
        sub = cache.slot_view(1)
        assert sub.num_slots == 1
        assert int(sub.lengths[0]) == 2
        sub = sub.replace(
            k=tuple(b + 5.0 for b in sub.k),
            v=tuple(b + 7.0 for b in sub.v),
            lengths=jnp.array([4], jnp.int32),
        )
        back = cache.write_back(1, sub)
        assert np.array_equal(np.asarray(back.lengths), [1, 4, 3])
        assert np.all(np.asarray(back.k[0][1]) == 5.0)
        assert np.all(np.asarray(back.k[0][0]) == 0.0)  # untouched

    def test_reset_slot(self):
        cache = KVCache.create(1, 2, 4, 1, 4)
        cache = cache.replace(lengths=jnp.array([3, 2], jnp.int32))
        cache = cache.reset_slot(0)
        assert np.array_equal(np.asarray(cache.lengths), [0, 2])

    def test_write_at_scatters_chunk_and_drops_pads(self):
        """The chunked-prefill write: one packed chunk lands at per-
        token (slot, position) destinations in one scatter; padding
        tokens carry slot id == num_slots and must not touch any row."""
        cache = KVCache.create(1, 2, 8, 1, 4, dtype=jnp.float32)
        slots = jnp.array([0, 0, 1, 2], jnp.int32)  # last is padding
        pos = jnp.array([2, 3, 5, 0], jnp.int32)
        new = jnp.arange(1, 5, dtype=jnp.float32)[
            :, None, None
        ] * jnp.ones((4, 1, 4), jnp.float32)
        cache = cache.write_at(0, slots, pos, new, new * 10.0)
        k = np.asarray(cache.k[0])
        v = np.asarray(cache.v[0])
        assert np.all(k[0, 2] == 1.0) and np.all(k[0, 3] == 2.0)
        assert np.all(k[1, 5] == 3.0) and np.all(v[1, 5] == 30.0)
        # pad token (slot 2 of 2) dropped; everything else untouched
        written = np.zeros((2, 8), bool)
        written[0, 2] = written[0, 3] = written[1, 5] = True
        assert np.all(k[~written] == 0.0)
        # lengths are NOT advanced (the engine commits cursors)
        assert np.array_equal(np.asarray(cache.lengths), [0, 0])


# ---------------------------------------------------------------------------
# prefill + decode == full forward
# ---------------------------------------------------------------------------


class TestCacheCorrectness:
    @pytest.mark.parametrize("impl", ["flash", "jnp"])
    def test_prefill_then_decode_matches_full_forward(self, impl):
        cfg = fp32_cfg(attention_impl=impl)
        model, params = make_model(cfg)
        # 4 un-jitted decode traces after the prefill: enough to cross
        # the prefill boundary and advance the cache repeatedly; the
        # T=12 original spent ~half the file's wall time re-tracing
        # the interpret-mode flash decode per step
        T, Lp = 9, 5
        toks = jax.random.randint(jax.random.PRNGKey(3), (1, T), 0, 96)
        full = np.asarray(model.apply(params, toks))

        cache = KVCache.for_model(cfg, num_slots=1, capacity=T)
        pre, cache = model.apply(params, toks[:, :Lp], cache=cache)
        np.testing.assert_allclose(
            np.asarray(pre), full[:, :Lp], rtol=1e-5, atol=1e-5
        )
        assert int(cache.lengths[0]) == Lp
        for i in range(Lp, T):
            step, cache = model.apply(params, toks[:, i : i + 1], cache=cache)
            np.testing.assert_allclose(
                np.asarray(step[:, 0]), full[:, i], rtol=1e-5, atol=1e-5
            )
        assert int(cache.lengths[0]) == T

    def test_decode_under_jit_with_batched_slots(self):
        """The engine's shape: every slot decodes in one program at its
        own length; per-slot logits must match each slot's own
        full-sequence forward."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        B, T = 3, 10
        toks = jax.random.randint(jax.random.PRNGKey(4), (B, T), 0, 96)
        lens = [4, 7, 2]  # mixed live prefixes
        full = np.asarray(model.apply(params, toks))

        cache = KVCache.for_model(cfg, num_slots=B, capacity=T)
        # per-slot prefill of different lengths through slot views
        for s in range(B):
            sub = cache.slot_view(s)
            _, sub = model.apply(params, toks[s : s + 1, : lens[s]], cache=sub)
            cache = cache.write_back(s, sub)

        @jax.jit
        def decode(params, cache, step_toks):
            return model.apply(params, step_toks, cache=cache)

        step_toks = jnp.stack(
            [toks[s, lens[s]] for s in range(B)]
        ).reshape(B, 1)
        logits, cache = decode(params, cache, step_toks)
        for s in range(B):
            np.testing.assert_allclose(
                np.asarray(logits[s, 0]), full[s, lens[s]],
                rtol=1e-5, atol=1e-5,
            )

    def test_cache_rejects_padding_mask_and_training_mode(self):
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        from rocm_apex_tpu.models.gpt import ParallelAttention

        attn = ParallelAttention(cfg, attn_mask_type="padding")
        x = jnp.zeros((1, 4, cfg.hidden_size), jnp.float32)
        cache = KVCache.for_model(cfg, 1, 8)
        with pytest.raises(ValueError, match="causal"):
            attn.init(
                jax.random.PRNGKey(0), x,
                cache=(cache.k[0], cache.v[0], cache.lengths),
            )
        with pytest.raises(ValueError, match="labels"):
            model.apply(
                params, jnp.zeros((1, 4), jnp.int32),
                labels=jnp.zeros((1, 4), jnp.int32), cache=cache,
            )


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


class TestSampling:
    def _logits(self, shape=(4, 32), seed=0):
        return jax.random.normal(jax.random.PRNGKey(seed), shape) * 3.0

    def test_fixed_seed_replays(self):
        logits = self._logits()
        rng = jax.random.PRNGKey(7)
        a = sample(rng, logits, temperature=0.8, top_k=8, top_p=0.9)
        b = sample(rng, logits, temperature=0.8, top_k=8, top_p=0.9)
        assert np.array_equal(np.asarray(a), np.asarray(b))
        c = sample(jax.random.PRNGKey(8), logits, temperature=0.8)
        d = sample(jax.random.PRNGKey(7), logits, temperature=0.8)
        # different seed must be able to differ (not a constant fn)
        assert not np.array_equal(np.asarray(c), np.asarray(d))

    def test_temperature_zero_is_greedy(self):
        logits = self._logits()
        got = sample(jax.random.PRNGKey(0), logits, temperature=0.0)
        assert np.array_equal(np.asarray(got), np.asarray(greedy(logits)))

    def test_top_k_restricts_support(self):
        logits = self._logits((2, 64))
        masked = top_k_logits(logits, 5)
        # per-row: exactly that row's top-5 logits survive the filter
        for row in range(2):
            alive = np.flatnonzero(np.asarray(masked[row]) > -1e29)
            row_top = np.asarray(jax.lax.top_k(logits[row], 5)[1])
            assert set(alive.tolist()) == set(row_top.tolist())
        # and sampled tokens always land inside the top-5 support
        for seed in range(10):
            tok = np.asarray(
                sample(jax.random.PRNGKey(seed), logits, top_k=5)
            )
            for row in range(2):
                row_top = set(
                    np.asarray(jax.lax.top_k(logits[row], 5)[1]).tolist()
                )
                assert int(tok[row]) in row_top

    def test_top_p_keeps_minimal_nucleus(self):
        # peaked distribution: one token holds >0.9 of the mass, so
        # top_p=0.5 must keep exactly that token
        logits = jnp.array([[10.0, 1.0, 0.5, 0.0]])
        masked = np.asarray(top_p_logits(logits, 0.5))
        assert masked[0, 0] == 10.0
        assert np.all(masked[0, 1:] < -1e29)
        # p=1.0 keeps everything
        full = np.asarray(top_p_logits(logits, 1.0))
        np.testing.assert_array_equal(full, np.asarray(logits))

    def test_filter_validation(self):
        logits = self._logits()
        with pytest.raises(ValueError, match="top_k"):
            top_k_logits(logits, 0)
        with pytest.raises(ValueError, match="top_p"):
            top_p_logits(logits, 0.0)


# ---------------------------------------------------------------------------
# continuous batching engine
# ---------------------------------------------------------------------------


def greedy_engine(model, params, **kw):
    """Chunked-prefill greedy engine — ONE shape tuple for the whole
    file (slots=2, capacity=24, budget=4) so every test hits the same
    compiled mixed/decode programs."""
    kw.setdefault("num_slots", 2)
    kw.setdefault("capacity", 24)
    kw.setdefault("prefill_token_budget", 4)
    kw.setdefault("sampling", SamplingParams(temperature=0.0))
    return InferenceEngine(model, params, **kw)


def whole_engine(model, params, **kw):
    """The legacy whole-prompt A/B baseline (pad width 24)."""
    kw.setdefault("prefill_token_budget", None)
    kw.setdefault("max_prompt_len", 24)
    return greedy_engine(model, params, **kw)


class TestEngine:
    def test_slot_reuse_does_not_pollute(self):
        """4 mixed-length requests through 2 slots: the late requests
        are prefilled into EVICTED slots over a longer predecessor's
        stale cache; greedy outputs must equal solo runs bit-for-bit
        (any leaked stale key would shift the argmax)."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10]]
        eng = greedy_engine(model, params)
        # 4 new tokens: the first wave still finishes and evicts before
        # the late requests prefill into the stale slots (the contract
        # under test); 6 only added decode steps to every solo replay
        batched = eng.generate(prompts, max_new_tokens=4)
        assert [r.request_id for r in batched] == [0, 1, 2, 3]
        assert all(r.finish_reason == "length" for r in batched)
        assert all(len(r.tokens) == 4 for r in batched)
        for i, p in enumerate(prompts):
            solo = greedy_engine(model, params).generate(
                [p], max_new_tokens=4
            )[0]
            assert solo.tokens == batched[i].tokens, f"request {i} polluted"

    def test_mixed_step_compiles_exactly_once(self):
        """Mixed prompt lengths, a mid-stream admit, and evictions must
        all reuse ONE compiled mixed chunk+decode program (and at most
        one decode-only fast-path program) — the fixed-shape contract:
        the prompt mix never retraces."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params)
        eng.add_request([1, 2, 3, 4, 5], max_new_tokens=4)
        eng.add_request([6], max_new_tokens=2)
        done = []
        for _ in range(3):
            done += eng.step()
        # mid-stream admit while the first request is still decoding
        eng.add_request([7, 8], max_new_tokens=3)
        while eng.has_work():
            done += eng.step()
        assert len(done) == 3
        assert eng.mixed_trace_count == 1
        assert eng.decode_trace_count <= 1
        assert eng.prefill_trace_count == 0  # whole-prompt path unused

    def test_whole_prompt_engine_compiles_exactly_once(self):
        """The legacy A/B path keeps its own invariant: one compiled
        prefill, one compiled decode."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = whole_engine(model, params)
        eng.add_request([1, 2, 3, 4, 5], max_new_tokens=3)
        eng.add_request([6], max_new_tokens=2)
        while eng.has_work():
            eng.step()
        assert eng.prefill_trace_count == 1
        assert eng.decode_trace_count == 1
        assert eng.mixed_trace_count == 0

    def test_eos_finishes_request(self):
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        # discover the greedy continuation, then rig eos to the first
        # token that has no earlier occurrence (so the eos stop fires
        # at a known position)
        ref = greedy_engine(model, params).generate(
            [[1, 2, 3]], max_new_tokens=8
        )[0]
        k = next(
            i for i, t in enumerate(ref.tokens)
            if t not in ref.tokens[:i]
        )
        eng = greedy_engine(model, params, eos_id=ref.tokens[k])
        got = eng.generate([[1, 2, 3]], max_new_tokens=8)[0]
        assert got.finish_reason == "eos"
        assert got.tokens == ref.tokens[: k + 1]

    def test_capacity_forces_eviction(self):
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params, capacity=8)
        r = eng.generate([[1, 2, 3, 4, 5, 6]], max_new_tokens=20)[0]
        # 6 prompt tokens + generated tokens may occupy at most 8 cache
        # rows; the engine must stop BEFORE any clamped write
        assert r.finish_reason == "capacity"
        assert len(r.prompt) + len(r.tokens) - 1 <= 8

    def test_capacity_guard_raises_host_side_not_clamps(self):
        """The ISSUE-7 clamp fix. (a) The legitimate edge — a prompt
        that exactly fills capacity — completes with ONE token and
        finish_reason='capacity': its fused first-token decode is
        SUPPRESSED (completion_idx=-1), where the old path issued a
        device write at `capacity` that dynamic_update_slice silently
        clamped onto the last live row. (b) A live slot positioned at
        capacity entering decode (an invariant violation) raises a
        host-side error naming the slot, instead of wedging the
        length at the clamp forever."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params)
        r = eng.generate(
            [list(range(eng.capacity))], max_new_tokens=5
        )[0]
        assert r.finish_reason == "capacity"
        assert len(r.tokens) == 1
        eng2 = greedy_engine(model, params)
        eng2.add_request([1, 2, 3], max_new_tokens=20)
        eng2.step()
        eng2._slots[0].pos = eng2.capacity  # white-box corruption
        with pytest.raises(RuntimeError, match="slot 0"):
            eng2.step()

    def test_request_validation(self):
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params)
        with pytest.raises(ValueError, match="non-empty"):
            eng.add_request([], 4)
        # the chunked engine has NO prompt-length ceiling below the
        # physical cache: only a prompt that cannot fit capacity rows
        # is rejected (the old max_prompt_len admit error is gone)
        eng.add_request(list(range(eng.capacity)), 4)
        with pytest.raises(ValueError, match="capacity"):
            eng.add_request(list(range(eng.capacity + 1)), 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.add_request([1], 0)
        # legacy whole-prompt path: the pad width is a real bound
        weng = whole_engine(model, params, max_prompt_len=8)
        with pytest.raises(ValueError, match="pad width"):
            weng.add_request(list(range(9)), 4)
        with pytest.raises(ValueError, match="prefill_token_budget"):
            greedy_engine(model, params, prefill_token_budget=0)
        # tp>1 construction demands the parallel_state mesh (and the
        # paged/chunked serving mode) up front
        with pytest.raises(ValueError, match="tp>1"):
            InferenceEngine(
                GPTModel(fp32_cfg(tensor_parallel_size=2)), params
            )

    def test_seeded_engine_replays_sampled_stream(self):
        cfg = fp32_cfg()
        model, params = make_model(cfg)

        def run():
            eng = greedy_engine(
                model, params,
                sampling=SamplingParams(temperature=0.9, top_k=12),
                seed=42,
            )
            return [
                r.tokens for r in eng.generate(
                    [[1, 2], [3, 4, 5]], max_new_tokens=5
                )
            ]

        assert run() == run()


# ---------------------------------------------------------------------------
# chunked-prefill token-budget scheduler
# ---------------------------------------------------------------------------


class TestChunkedPrefill:
    def test_greedy_parity_with_whole_prompt_engine(self):
        """The chunked scheduler must be TOKEN-IDENTICAL to the
        whole-prompt baseline under greedy sampling: chunk sizes that
        do (8 = 2*4) and do not (3, 5, 18) divide the budget, plus a
        prompt LONGER than any whole-prompt pad width the old engine
        ever allowed in this file (18 > 8) — it streams through in
        budget-sized pieces and completes."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        prompts = [
            [1, 2, 3],
            [4, 5, 6, 7, 8],
            list(range(10, 18)),
            list(range(30, 48)),  # 18 tokens: 4+4+4+4+2 chunks
        ]
        chunked = greedy_engine(model, params).generate(
            prompts, max_new_tokens=4
        )
        whole = whole_engine(model, params).generate(
            prompts, max_new_tokens=4
        )
        for c, w in zip(chunked, whole):
            assert c.tokens == w.tokens, c.request_id
            assert c.finish_reason == "length"
            assert len(c.tokens) == 4

    def test_prefill_chunk_caps_per_request_share(self):
        """`prefill_chunk` (the per-request fairness knob inside the
        budget) must not change the tokens, only the schedule."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        prompts = [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10]]
        base = greedy_engine(model, params).generate(
            prompts, max_new_tokens=3
        )
        capped = greedy_engine(model, params, prefill_chunk=2).generate(
            prompts, max_new_tokens=3
        )
        assert [r.tokens for r in base] == [r.tokens for r in capped]

    def test_budget_goes_to_the_oldest_lease_not_the_lowest_slot(self):
        """Two prompts that together exceed the budget: the one leased
        FIRST is fed first, whichever slot it sits in. A long prompt in
        slot 1 used to wait behind every newcomer in slot 0; by lease
        it keeps the budget until it is done, and the tokens are the
        same either way."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params)
        eng.add_request([1, 2, 3], max_new_tokens=2)  # slot 0, ends fast
        eng.add_request(list(range(5, 19)), max_new_tokens=3)  # slot 1
        eng.step()  # [1,2,3] whole + 1 row of the long prompt; 2 tokens
        assert eng._slots[0] is None and eng._slots[1].cursor == 1
        eng.add_request(list(range(20, 30)), max_new_tokens=3)
        eng.step()  # the newcomer takes slot 0 and waits: 4 rows to slot 1
        assert eng._slots[1].cursor == 5 and eng._slots[0].cursor == 0
        eng.step(), eng.step()
        assert eng._slots[1].cursor == 13 and eng._slots[0].cursor == 0
        eng.step()  # the old prompt's last row, then the newcomer's first 3
        assert not eng._slots[1].prefilling and eng._slots[0].cursor == 3
        done = {}
        while eng.has_work():
            for r in eng.step():
                done[tuple(r.prompt)] = r.tokens
        alone = greedy_engine(model, params).generate(
            [list(range(5, 19)), list(range(20, 30))], max_new_tokens=3
        )
        for r in alone:
            assert done[tuple(r.prompt)] == r.tokens

    def test_decode_liveness_while_long_prefill_streams(self):
        """Head-of-line blocking is gone: while an 16-token prompt
        streams through the 4-token budget (4 ticks), the already-
        decoding slot must emit exactly one token EVERY tick."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params)
        eng.add_request([1, 2, 3], max_new_tokens=20)
        # tick 1 prefills [1,2,3] fully; the sampled first token is
        # fed straight into the fused decode -> TWO tokens in one tick
        # (the whole-prompt admit-tick cadence, without the pad)
        eng.step()
        assert len(eng._slots[0].generated) == 2
        eng.add_request(list(range(5, 21)), max_new_tokens=4)  # 16 toks
        for tick in range(4):  # the long prefill occupies 4 full ticks
            before = len(eng._slots[0].generated)
            eng.step()
            assert len(eng._slots[0].generated) == before + 1, (
                f"decode starved at streaming tick {tick}"
            )
        # the long request finished prefill on the 4th streaming tick
        # and already holds first+second tokens; no decode tick ever
        # waited on it
        assert len(eng._slots[1].generated) == 2

    def test_mixed_step_has_no_full_width_prefill_activation(self):
        """The executable ISSUE-5 acceptance bar: audit the traced
        mixed step and prove no padded full-prompt-width activation —
        (1, L, hidden) / (slots, L, hidden) / (1, L, vocab) for the
        18-token prompt of the parity test or the 24-row pad width —
        exists anywhere in the program. The legacy whole-prompt
        prefill, audited the same way, DOES carry its pad-width
        activation (the waste the scheduler removes)."""
        from rocm_apex_tpu.monitor import assert_no_intermediate, audit

        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params)
        B, S = eng.prefill_token_budget, eng.num_slots
        i32 = jnp.int32
        rng = jax.random.PRNGKey(0)
        args = (
            eng.params, eng.cache,
            jnp.zeros((B,), i32), jnp.full((B,), S, i32),
            jnp.zeros((B,), i32), jnp.zeros((S,), i32),
            jnp.zeros((S,), i32), jnp.full((S,), -1, i32),
            jnp.zeros((S,), i32), jnp.zeros((S,), bool),
            jnp.zeros((B,), jnp.float32), jnp.zeros((S,), jnp.float32),
            rng,
        )
        h, v = cfg.hidden_size, cfg.vocab_size
        report = assert_no_intermediate(
            eng.programs.mixed_fn, (1, 18, h), *args
        )
        for shape in [
            (S, 18, h), (1, 18, v), (1, 24, h), (S, 24, h), (1, 24, v),
        ]:
            assert not report.has_intermediate(shape), shape
        # contrast: the whole-prompt prefill materializes its pad width
        weng = whole_engine(model, params)
        wreport = audit(
            weng.programs.prefill_fn, weng.params, weng.cache,
            jnp.zeros((1, 24), i32), 0, 18, rng,
        )
        assert wreport.has_intermediate((1, 24, h))

    def test_stats_expose_queue_wait_and_ttft_percentiles(self):
        """Per-request tails (the numbers that surface head-of-line
        blocking) ride `stats()` alongside the PR-1 counters."""
        cfg = fp32_cfg()
        model, params = make_model(cfg)
        eng = greedy_engine(model, params)
        s0 = eng.stats()
        assert s0["ttft_ms_p95"] == 0.0 and s0["queue_wait_ms_p50"] == 0.0
        eng.generate(
            [[1, 2, 3], [4, 5], [6, 7, 8, 9]], max_new_tokens=3
        )
        s = eng.stats()
        assert s["admitted"] == 3.0 and s["mixed_steps"] >= 1.0
        assert s["ttft_ms_p95"] >= s["ttft_ms_p50"] > 0.0
        assert s["queue_wait_ms_p95"] >= s["queue_wait_ms_p50"] >= 0.0
        # TTFT includes the queue wait by construction
        assert s["ttft_ms_p50"] >= s["queue_wait_ms_p50"]
