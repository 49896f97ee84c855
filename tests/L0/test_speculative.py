"""Speculative-decoding tier: drafter, parity, rollback, one-trace.

The ISSUE-9 acceptance bar as executable checks: the n-gram
self-drafter proposes real continuations (and the −1 left-pad / stale
history region can never false-match); greedy speculative output is
token-identical to the non-speculative baseline at k ∈ {2, 4} on all
three cache layouts (contiguous, paged bf16/fp32, paged int8) while
``mixed_trace_count`` stays 1; a drafter that is always wrong still
yields exact baseline tokens (rollback = "don't commit", so rejected
rows can never pollute the cache — including shared prefix pages);
every drafted token is accounted as accepted or rolled back; the spec
mixed step materializes no full-pad-width activation; and the paged
allocator preempts-and-requeues under pool deadlock instead of
wedging, with greedy output unchanged.

Engines reuse test_inference's model config (fp32_cfg, slots=2,
capacity=24); speculative engines share ONE budget (6 = slots × (k+1)
at k=2) so the persistent compile cache pays each spec program once,
and baselines use the budget-4 tuple the rest of the suite already
compiled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _helpers import ON_CHIP, PAGE as PS, PAGE_I8 as PS_I8

from rocm_apex_tpu.inference import (
    InferenceEngine,
    NGramDrafter,
    SamplingParams,
)
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel

# Page geometry by platform: see _helpers.
# (pool pages, new tokens) at which two 8-token prompts deadlock
# mid-decode: 5 pages of 4 rows leave each 14-row request short of its
# 4th page; with 8-row pages, 4 pages leave each 18-row request short of
# its 3rd
PREEMPT_POOL, PREEMPT_NEW = (4, 10) if ON_CHIP else (5, 6)


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
    return cfg, model, params


#: compiled-step donors, one per trace geometry (layout/budget/spec_k)
#: seen in this module: same-geometry engines adopt the first one's
#: programs (`step_source=`) instead of re-tracing; incompatible
#: geometries are refused by the engine and seed a new donor.
_STEP_DONORS: list = []


def base_engine(model, params, **kw):
    """Non-speculative baseline on the suite-wide budget-4 tuple."""
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


def spec_engine(model, params, k=2, **kw):
    """Speculative engine: ONE budget (6) for every k and layout in
    this file — the spec programs' shapes depend on the budget, not
    k, so both k=2 and k=4 hit the same compiled mixed/commit pair."""
    kw.setdefault("prefill_token_budget", 6)
    kw.setdefault("spec_k", k)
    return base_engine(model, params, **kw)


# periodic tails: the self-drafter's high-acceptance regime, so the
# accept path (not just the bonus token) is genuinely exercised
PROMPTS = [[1, 2, 3, 1, 2, 3, 1, 2], [7, 8, 9, 7, 8, 9, 7]]
MAX_NEW = 8

LAYOUTS = [
    pytest.param({}, id="contig"),
    pytest.param({"paged": True, "page_size": PS}, id="paged"),
    pytest.param(
        {"paged": True, "page_size": PS_I8, "kv_dtype": jnp.int8},
        id="paged-int8",
    ),
]

# one baseline run per layout, shared by the parity AND rollback
# tests (the baseline engine is the expensive half of each A/B)
_BASELINES = {}


def baseline_tokens(model, params, layout):
    key = tuple(sorted((k, str(v)) for k, v in layout.items()))
    if key not in _BASELINES:
        _BASELINES[key] = [
            r.tokens
            for r in base_engine(model, params, **layout).generate(
                PROMPTS, max_new_tokens=MAX_NEW
            )
        ]
    return _BASELINES[key]


# ---------------------------------------------------------------------------
# n-gram drafter
# ---------------------------------------------------------------------------


class TestNGramDrafter:
    def _hist(self, tokens, window=16):
        h = np.full((1, window), -1, np.int32)
        h[0, window - len(tokens):] = tokens
        return h, np.array([len(tokens)], np.int32)

    def test_suffix_match_proposes_following_tokens(self):
        d = NGramDrafter(3, window=16)
        hist, n = self._hist([5, 6, 7, 8, 5, 6, 7])
        drafts, counts = d(hist, n)
        # the suffix 3-gram (5,6,7) occurred at the start; the tokens
        # that FOLLOWED it are the proposal
        assert int(counts[0]) == 3
        assert drafts[0].tolist() == [8, 5, 6]

    def test_no_repeat_means_no_proposal(self):
        d = NGramDrafter(3, window=16)
        hist, n = self._hist([1, 2, 3, 4, 5, 6, 7])
        drafts, counts = d(hist, n)
        assert int(counts[0]) == 0

    def test_pad_and_stale_regions_cannot_match(self):
        """The −1 left pad (and any stale bytes beyond ``lengths``)
        must never anchor a match: a 2-token history whose bigram DOES
        appear verbatim in the dead region proposes nothing."""
        d = NGramDrafter(3, window=16)
        hist = np.full((1, 16), -1, np.int32)
        hist[0, 9:11] = [4, 5]   # dead: beyond the live length
        hist[0, 14:16] = [4, 5]  # live suffix
        drafts, counts = d(hist, np.array([2], np.int32))
        assert int(counts[0]) == 0

    def test_validates_construction(self):
        with pytest.raises(ValueError, match="k must be"):
            NGramDrafter(0)
        with pytest.raises(ValueError, match="window"):
            NGramDrafter(8, window=4)


# ---------------------------------------------------------------------------
# exact parity + the one-trace contract
# ---------------------------------------------------------------------------


class TestSpeculativeParity:
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_greedy_parity_one_trace_and_accounting(
        self, layout, model_and_params
    ):
        """THE acceptance bar: greedy speculative output is
        token-identical to baseline at k=2 and k=4 on every cache
        layout, the spec engine compiles exactly one mixed program
        (and zero decode-only programs — spec mode never takes the
        stale-length fast path), and every drafted token is accounted
        as accepted or rolled back."""
        cfg, model, params = model_and_params
        base = baseline_tokens(model, params, layout)
        for k in (2, 4):
            eng = spec_engine(model, params, k=k, **layout)
            res = eng.generate(PROMPTS, max_new_tokens=MAX_NEW)
            for r, b in zip(res, base):
                assert r.tokens == b, f"k={k} diverged"
            assert eng.mixed_trace_count == 1
            assert eng.decode_trace_count == 0
            s = eng.stats()
            assert s["tokens_drafted"] > 0
            # accept/rollback accounting: drafted = accepted + rejected,
            # and a span with any rejected token counts one rollback
            rejected = s["tokens_drafted"] - s["tokens_accepted"]
            assert rejected >= 0
            assert (s["rollbacks"] > 0) == (rejected > 0)
            assert s["acceptance_rate"] == pytest.approx(
                s["tokens_accepted"] / s["tokens_drafted"]
            )

    def test_spec_stats_flush_as_last_value(self):
        """The engine's speculative counters are monotonic: the
        MetricsLogger must flush them as last value, never a window
        mean (satellite a)."""
        from rocm_apex_tpu.monitor import MetricsLogger

        logger = MetricsLogger(writers=[type("W", (), {
            "write": staticmethod(lambda step, scalars: None)
        })()])
        assert {
            "tokens_drafted", "tokens_accepted", "acceptance_rate",
            "rollbacks", "preemptions",
        } <= logger._last_value

    def test_spec_requires_chunked_mode_and_budget(
        self, model_and_params
    ):
        cfg, model, params = model_and_params
        with pytest.raises(ValueError, match="chunked"):
            base_engine(
                model, params, spec_k=2, prefill_token_budget=None,
                max_prompt_len=24,
            )
        with pytest.raises(ValueError, match="budget"):
            base_engine(model, params, spec_k=4)  # 4+1 > budget 4


# ---------------------------------------------------------------------------
# rollback invariants
# ---------------------------------------------------------------------------


class _ShiftedDrafter:
    """Pluggable drafter hook whose proposals are the real drafter's
    shifted by +1 mod vocab — near-certain rejection on every span,
    driving the rollback path hard while staying deterministic."""

    def __init__(self, k, vocab, window=64):
        self._inner = NGramDrafter(k, window=window)
        self.window = self._inner.window
        self._vocab = vocab

    def __call__(self, histories, lengths):
        drafts, counts = self._inner(histories, lengths)
        return (drafts + 1) % self._vocab, counts


class TestRollback:
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_always_wrong_drafter_is_harmless(
        self, layout, model_and_params
    ):
        """Rollback is 'don't write', not 'undo': a drafter that is
        wrong on (essentially) every token must still produce exact
        baseline output — on the contiguous cache (a junk committed
        row would shift later argmaxes), on paged bf16 (pages), and on
        paged int8 (a rejected row must not have grown any per-page
        quantization scale). All pages return on eviction."""
        cfg, model, params = model_and_params
        base = baseline_tokens(model, params, layout)
        eng = spec_engine(
            model, params, k=2,
            drafter=_ShiftedDrafter(2, cfg.vocab_size), **layout
        )
        res = eng.generate(PROMPTS, max_new_tokens=MAX_NEW)
        for r, b in zip(res, base):
            assert r.tokens == b
        s = eng.stats()
        assert s["tokens_drafted"] > 0
        assert s["rollbacks"] > 0
        assert s["tokens_accepted"] < s["tokens_drafted"]
        if layout.get("paged"):
            assert s["pages_used"] == 0.0  # every page came back
        # reset_stats clears the speculative counters with the rest
        eng.reset_stats()
        s = eng.stats()
        assert s["tokens_drafted"] == 0.0 and s["rollbacks"] == 0.0
        assert s["acceptance_rate"] == 0.0

    def test_spec_never_pollutes_shared_prefix_pages(
        self, model_and_params
    ):
        """Speculation composes with prefix sharing: request B maps
        A's materialized prompt pages by reference while BOTH serve
        speculative spans; token parity proves no draft row (accepted
        or rejected) ever landed in a shared page without a CoW
        fork."""
        cfg, model, params = model_and_params
        sys_prefix = list(range(40, 52))  # 3 full pages at ps=4
        pA = sys_prefix + [1, 2, 3]
        pB = sys_prefix + [7, 8]
        ref = base_engine(
            model, params, paged=True, page_size=PS,
            prefix_sharing=True,
        )
        rA0 = ref.generate([pA], max_new_tokens=6)[0]
        rB0 = ref.generate([pB], max_new_tokens=6)[0]
        eng = spec_engine(
            model, params, k=2, paged=True, page_size=PS,
            prefix_sharing=True,
        )
        rA = eng.generate([pA], max_new_tokens=6)[0]
        rB = eng.generate([pB], max_new_tokens=6)[0]
        assert rA.tokens == rA0.tokens
        assert rB.tokens == rB0.tokens
        assert eng.stats()["prefix_hits"] >= 1


# ---------------------------------------------------------------------------
# the audited one-trace contract
# ---------------------------------------------------------------------------


class TestSpecAudit:
    def test_spec_mixed_step_has_no_full_width_activation(
        self, model_and_params
    ):
        """The chunked scheduler's no-pad-width guarantee survives
        speculation: audit the traced spec mixed step (chunk + decode
        + packed K/V return) and prove no (·, capacity, hidden/vocab)
        activation exists anywhere in the program."""
        from rocm_apex_tpu.monitor import assert_no_intermediate

        cfg, model, params = model_and_params
        eng = spec_engine(model, params, k=2)
        B, S = eng.prefill_token_budget, eng.num_slots
        i32 = jnp.int32
        args = (
            eng.params, eng.cache,
            jnp.zeros((B,), i32), jnp.full((B,), S, i32),
            jnp.zeros((B,), i32), jnp.full((B,), S, i32),
            jnp.zeros((S,), i32), jnp.zeros((S,), i32),
            jnp.full((S,), -1, i32), jnp.zeros((S,), i32),
            jnp.zeros((S,), bool),
            jnp.zeros((B,), jnp.float32), jnp.zeros((S,), jnp.float32),
            jax.random.PRNGKey(0),
        )
        h, v = cfg.hidden_size, cfg.vocab_size
        report = assert_no_intermediate(
            eng.programs.mixed_fn, (1, 24, h), *args
        )
        for shape in [(S, 24, h), (1, 24, v), (1, 18, h)]:
            assert not report.has_intermediate(shape), shape


# ---------------------------------------------------------------------------
# preempt-and-requeue under pool deadlock
# ---------------------------------------------------------------------------


class TestPreemption:
    def test_deadlock_preempts_requeues_and_preserves_tokens(
        self, model_and_params
    ):
        """Two in-flight requests exhaust the pool with neither able
        to decode: the youngest lease is preempted (pages released,
        request requeued), the survivor finishes on the freed pages,
        and the preempted request recomputes via ordinary chunked
        prefill — greedy output identical to an unconstrained pool,
        with the stall/preemption counters exposing what happened."""
        cfg, model, params = model_and_params
        prompts = [list(range(1, 9)), list(range(9, 17))]
        ref = base_engine(model, params, paged=True, page_size=PS).generate(
            prompts, max_new_tokens=PREEMPT_NEW
        )
        eng = base_engine(
            model, params, paged=True, page_size=PS,
            num_pages=PREEMPT_POOL,
        )
        res = eng.generate(prompts, max_new_tokens=PREEMPT_NEW)
        for r, b in zip(res, ref):
            assert r.tokens == b.tokens
        s = eng.stats()
        assert s["preemptions"] >= 1
        assert s["pages_used"] == 0.0
        eng.reset_stats()
        assert eng.stats()["preemptions"] == 0.0

    def test_sole_request_still_raises_sizing_error(
        self, model_and_params
    ):
        """Preempting the only in-flight request would re-admit it
        straight into the same wall (livelock): the unservable-pool
        deadlock diagnosis must still raise."""
        cfg, model, params = model_and_params
        eng = base_engine(
            model, params, paged=True, page_size=PS, num_pages=1
        )
        eng.add_request(list(range(1, 9)), max_new_tokens=2)
        with pytest.raises(RuntimeError, match="deadlock"):
            for _ in range(4):
                eng.step()
