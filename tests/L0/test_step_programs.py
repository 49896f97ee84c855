"""`inference.programs.StepPrograms` with no engine around it: one tick
body under every feature set.

A feature (speculation, an adapter pool) adds OPERANDS to the one fused
chunk+decode body and nothing else, so with no drafts in the chunk and
adapter 0 on every row each feature set must sample the base set's
tokens bit for bit; every set traces each program once; and
`compatible_with` refuses each mismatch an engine's ``step_source=``
must refuse. The GPT cases reuse test_inference.py's shape tuple
(slots=2, capacity=24, budget=4, the fp32 model), the hybrid case
test_hybrid_serving.py's toy, whose mixed tick is ONE apply of the
model: the second half of this file holds that body to the two applies.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import PAGE
from _serving import SERVED, prompts_of, served_engine, served_toy
from rocm_apex_tpu.inference import (
    AdapterPool,
    KVCache,
    PagedKVCache,
    SamplingParams,
    StepPrograms,
)
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel

GREEDY = SamplingParams(temperature=0.0)
PROMPT = 3  # slot 0's whole prompt; slot 1 takes the rest of the budget
STALE = 10 ** 6

#: feature set -> what the engine would derive it from
CASES = {
    "base-contiguous": dict(),
    "base-paged": dict(paged=True),
    "spec": dict(spec_k=2),
    "lora": dict(lora=True),
    "spec-paged": dict(spec_k=2, paged=True),
    "hybrid": dict(hybrid=True, paged=True),
}


@pytest.fixture(scope="module")
def gpt():
    cfg = GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        tensor_parallel_size=1, params_dtype=jnp.float32,
        dtype=jnp.float32,
    )
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))
    return dict(model=model, params=params, slots=2, capacity=24, budget=4)


@pytest.fixture(scope="module")
def hybrid():
    model, params, _ = served_toy("hybrid")
    return dict(model=model, params=params, slots=3, capacity=64, budget=16)


def make_cache(world, paged, slots=None):
    model, slots = world["model"], slots or world["slots"]
    if not paged:
        return KVCache.for_model(
            model.cfg, slots, world["capacity"], dtype=jnp.float32)
    cache = PagedKVCache.from_spec(
        model.cache_spec(), slots, world["capacity"], page_size=PAGE,
        dtype=jnp.float32)
    # every slot owns its worst-case pages, in order
    return cache.replace(page_table=jnp.arange(
        slots * cache.pages_per_slot, dtype=jnp.int32,
    ).reshape(slots, cache.pages_per_slot))


def make_pool(world, **kw):
    cfg = world["model"].cfg
    kw.setdefault("max_rank", 4)
    return AdapterPool(cfg.num_layers, cfg.hidden_size, max_resident=4, **kw)


def build(world, paged=False, spec_k=0, lora=False, hybrid=False, **over):
    """(programs, cache, adapter buffers or None) of one feature set;
    ``over`` replaces one thing the programs are built from."""
    kw = dict(
        model=world["model"], sampling=GREEDY,
        cache=make_cache(world, paged), budget=world["budget"],
        spec_k=spec_k,
        adapter_buffers=make_pool(world).buffers if lora else None,
        donate_buffers=False)
    kw.update(over)
    programs = StepPrograms(
        kw.pop("model"), kw.pop("sampling"), kw.pop("cache"), **kw)
    return programs, make_cache(world, paged), kw["adapter_buffers"]


def one_tick_then_a_decode(world, programs, cache, adapters):
    """A mixed tick (slot 0's whole prompt, completing and, under the
    body of two applies, fed into the decode grid; the rest of the
    budget from slot 1's prompt) and a decode tick after it, each run
    twice. Returns the sampled tokens."""
    B, S = world["budget"], world["slots"]
    rest = B - PROMPT
    if getattr(cache, "counters", None) is not None:
        # what an earlier tick counted: every program starts from zero
        cache = cache.replace(counters=cache.counters + STALE)
    tokens = (np.arange(B, dtype=np.int32) * 7 + 3) % 90
    slots = np.array([0] * PROMPT + [1] * rest, np.int32)
    after = np.zeros((S,), np.int32)
    after[:2] = PROMPT, rest
    done = np.full((S,), -1, np.int32)
    done[0] = PROMPT - 1
    operands = dict(
        params=world["params"], cache=cache, adapters=adapters,
        chunk_tokens=tokens, chunk_slots=slots,
        chunk_pos=np.r_[np.arange(PROMPT), np.arange(rest)].astype(np.int32),
        commit_slots=slots,  # no draft rows: every row commits in-trace
        chunk_adp=np.zeros((B,), np.int32),
        lengths_before=np.zeros((S,), np.int32), lengths_after=after,
        completion_idx=done, dec_tokens=np.zeros((S,), np.int32),
        dec_active=np.zeros((S,), bool), dec_adp=np.zeros((S,), np.int32),
        chunk_poison=np.zeros((B,), np.float32),
        dec_poison=np.zeros((S,), np.float32), key=jax.random.PRNGKey(0),
    )
    for _ in range(2):
        out = programs.mixed(*(operands[n] for n in programs.mixed_operands))
    chunk_tok, dec_tok, chunk_bad, dec_bad, key, cache, *rest_out = out
    # the key state comes back split once, as the host would have
    np.testing.assert_array_equal(
        np.asarray(key), np.asarray(jax.random.split(operands["key"])[0]))
    assert not np.asarray(chunk_bad).any() and not np.asarray(dec_bad).any()
    if programs.lora:  # the donated buffers come back as they went in
        assert rest_out.pop(0) is not None
    if programs.spec:  # the chunk's K/V, per layer, for `commit`
        ck, cv = rest_out.pop(0)
        assert len(ck) == len(cv) == len(cache.k)
        assert ck[0].shape[0] == B
    assert not rest_out
    active = np.zeros((S,), bool)
    active[0] = True
    operands.update(
        cache=cache, tokens=np.asarray(dec_tok), active=active,
        poison=operands["dec_poison"])
    for _ in range(2):
        out = programs.decode(
            *(operands[n] for n in programs.decode_operands))
    assert len(out) == 4 + programs.lora
    return dict(
        chunk_tok=np.asarray(chunk_tok), dec_tok=np.asarray(dec_tok),
        next_tok=np.asarray(out[0]), counters=getattr(cache, "counters", None),
        next_counters=getattr(out[3], "counters", None))


@pytest.mark.parametrize("case", list(CASES))
def test_every_feature_set_runs_the_one_body(case, request):
    features = CASES[case]
    world = request.getfixturevalue(
        "hybrid" if features.get("hybrid") else "gpt")
    programs, cache, adapters = build(world, **features)
    assert programs.spec == (features.get("spec_k", 0) > 0)
    assert programs.lora == bool(features.get("lora"))
    assert len(programs.mixed_operands) == (
        13 + programs.spec + 3 * programs.lora)
    assert len(programs.decode_operands) == 6 + 2 * programs.lora
    assert (programs.fork is not None) == bool(features.get("paged"))

    got = one_tick_then_a_decode(world, programs, cache, adapters)
    assert programs.traces == {
        "prefill": 0, "decode": 1, "mixed": 1, "commit": 0}

    # the base set of the same model and cache layout, built apart
    base, base_cache, _ = build(
        world, paged=features.get("paged", False),
        hybrid=features.get("hybrid", False))
    want = one_tick_then_a_decode(world, base, base_cache, None)
    for name in ("chunk_tok", "dec_tok", "next_tok"):
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    assert got["dec_tok"][0] != 0 or got["next_tok"][0] != 0
    if features.get("hybrid"):
        # a tick's counters start from zero in either program: the
        # mixed tick forgot the stale counts it was handed, and the
        # decode tick after it (one live row) the mixed tick's
        counters, after = (
            np.asarray(got[k]) for k in ("counters", "next_counters"))
        assert 0 < counters[0] < STALE and counters.max() < STALE
        assert 0 < after[0] < counters[0]
    else:
        assert got["counters"] is None

    # what `step_source=` must refuse, each by the name it reports
    programs.compatible_with(build(world, **features)[0])
    other_model = type(world["model"])(cfg=world["model"].cfg)
    refused = {
        "model (must be the SAME object)": dict(model=other_model),
        "sampling": dict(sampling=SamplingParams(temperature=0.5)),
        "prefill_token_budget": dict(budget=world["budget"] * 2),
        "spec_k": dict(spec_k=features.get("spec_k", 0) + 1),
        "donate_buffers": dict(donate_buffers=True),
        "cache geometry": dict(cache=make_cache(
            world, features.get("paged", False), slots=world["slots"] + 1)),
    }
    if not features.get("hybrid"):
        flipped = make_cache(world, not features.get("paged", False))
        refused["paged"] = refused["cache layout"] = dict(cache=flipped)
        refused["adapter_pool presence"] = dict(
            adapter_buffers=None if features.get("lora")
            else make_pool(world).buffers)
    if features.get("lora"):
        refused["adapter pool geometry"] = dict(
            adapter_buffers=make_pool(world, max_rank=8).buffers)
    for name, change in refused.items():
        with pytest.raises(ValueError, match=re.escape(name)):
            programs.compatible_with(
                build(world, **{**features, **change})[0])


# ---------------------------------------------------------------------------
# the mixed tick of ONE apply (a model that declares `mixed_in_one_pass`)
# ---------------------------------------------------------------------------

KEPT = ("k", "v", "latent", "ssm", "conv", "window_k", "window_v", "lengths")


def served_programs(kind, two_applies, slots=3, budget=16):
    """(programs, a cache whose slots own their worst-case pages in
    order, params) of a served toy under either mixed body."""
    model, params, geometry = served_toy(kind, two_applies)
    cache = PagedKVCache.from_spec(
        model.cache_spec(), slots, geometry["capacity"],
        page_size=geometry["page_size"], dtype=jnp.float32,
        prefill_token_budget=budget)
    table = jnp.arange(
        slots * cache.pages_per_slot, dtype=jnp.int32,
    ).reshape(slots, cache.pages_per_slot)
    cache = cache.replace(page_table=table)
    if cache.window:
        pool = cache.window_k[0].shape
        full = (slots * cache.pages_per_slot,) + pool[1:]
        cache = cache.replace(
            window_table=table, window_k=tuple(
                jnp.zeros(full, jnp.float32) for _ in cache.window_k),
            window_v=tuple(
                jnp.zeros(full, jnp.float32) for _ in cache.window_v))
    programs = StepPrograms(
        model, GREEDY, cache, budget=budget, donate_buffers=False)
    assert programs.one_pass is (not two_applies)
    return programs, cache, params


def mixed_tick(programs, params, cache, segments, before, decoding,
               dec_tokens, completes):
    """One mixed tick: ``segments`` = [(slot, tokens, first position)]
    packed in slot order; ``decoding`` the grid's live slots;
    ``completes`` = {slot: chunk row of its prompt's last token}, which
    the one-pass body takes as the head's rows and the two-apply body
    does NOT feed into its grid (so its grid is the decode apply after
    the chunk apply, over other slots)."""
    S, B = before.shape[0], programs._built_for["prefill_token_budget"]
    tokens = np.zeros((B,), np.int32)
    slots = np.full((B,), S, np.int32)
    pos = np.zeros((B,), np.int32)
    after = before.copy()
    used = 0
    for slot, toks, first in segments:
        n = len(toks)
        tokens[used:used + n] = toks
        slots[used:used + n] = slot
        pos[used:used + n] = np.arange(first, first + n)
        after[slot] = first + n
        used += n
    done = np.full((S,), -1, np.int32)
    if programs.one_pass:
        for slot, row in completes.items():
            done[slot] = row
    active = np.zeros((S,), bool)
    active[list(decoding)] = True
    operands = dict(
        params=params, cache=cache, chunk_tokens=tokens, chunk_slots=slots,
        chunk_pos=pos, lengths_before=before, lengths_after=after,
        completion_idx=done, dec_tokens=dec_tokens, dec_active=active,
        chunk_poison=np.zeros((B,), np.float32),
        dec_poison=np.zeros((S,), np.float32), key=jax.random.PRNGKey(0))
    chunk_tok, dec_tok, chunk_bad, dec_bad, _, cache = programs.mixed(
        *(operands[n] for n in programs.mixed_operands))
    assert not np.asarray(chunk_bad).any() and not np.asarray(dec_bad).any()
    chunk_tok = np.asarray(chunk_tok)
    first = {
        slot: int(chunk_tok[slot if programs.one_pass else row])
        for slot, row in completes.items()}
    return first, np.asarray(dec_tok), cache, after


@pytest.mark.parametrize("kind", sorted(SERVED))
def test_one_apply_leaves_the_cache_the_two_applies_leave(kind):
    """Two mixed ticks at toy size in float32 under either body. The
    second holds every case a layer's core meets: a slot that goes on
    from a cached prefix and completes (past the window, where the model
    has one), a FRESH slot, and a decode row whose token is the first
    token the tick before emitted. What the cache keeps (K/V, latent
    rows, recurrent state and convolution tail, window pages, lengths)
    and every token agree."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 250, size=n).tolist() for n in (5, 24, 9)]
    out = {}
    for two_applies in (False, True):
        programs, cache, params = served_programs(kind, two_applies)
        before = np.zeros((3,), np.int32)
        first1, _, cache, before = mixed_tick(
            programs, params, cache,
            [(0, prompts[0], 0), (1, prompts[1][:11], 0)], before,
            decoding=[], dec_tokens=np.zeros((3,), np.int32),
            completes={0: 4})
        dec = np.zeros((3,), np.int32)
        dec[0] = first1[0]
        first2, dec_tok, cache, after = mixed_tick(
            programs, params, cache,
            [(1, prompts[1][11:], 11), (2, prompts[2][:3], 0)], before,
            decoding=[0], dec_tokens=dec, completes={1: 12})
        assert programs.traces["mixed"] == 1
        want = after.copy()
        want[0] += 1  # the grid's live row advanced by its one token
        np.testing.assert_array_equal(np.asarray(cache.lengths), want)
        out[two_applies] = dict(
            first=(first1, first2), dec=int(dec_tok[0]), kept={
                name: getattr(cache, name) for name in KEPT})
    one, two = out[False], out[True]
    assert one["first"] == two["first"] and one["dec"] == two["dec"]
    compared = 0
    for name in KEPT:
        a, b = (jax.tree_util.tree_leaves(o["kept"][name]) for o in (one, two))
        assert len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6,
                err_msg=name)
            compared += int(np.any(np.asarray(x) != 0))
    assert compared > 2  # pools that hold something, and the lengths


@pytest.mark.parametrize("kind", sorted(SERVED))
def test_a_first_token_leaves_in_tick_t_and_the_second_in_t_plus_1(kind):
    """The engine over the one-pass body: a prompt that completes in
    tick T has ONE token after it and two after T+1, where the body of
    two applies (the parent's) gives it two in T; each request's tokens
    are the parent's; a mixed tick counts one apply of the model; and
    decode rows + first tokens = generated tokens."""
    from rocm_apex_tpu.monitor.trace import Tracer

    prompts = prompts_of([5, 37, 21, 9], seed=2)
    served = {}
    for two_applies in (False, True):
        tracer = Tracer()
        eng = served_engine(kind, two_applies, tracer=tracer)
        assert eng.programs.one_pass is (not two_applies)
        for p in prompts:
            eng.add_request(p, 7)
        out, seen = {}, {}
        while eng.has_work():
            for r in eng.step():
                out[r.request_id] = r
            for st in eng._slots:
                if st is not None and st.generated:
                    seen.setdefault(st.req.request_id, []).append(
                        len(st.generated))
        ticks = [
            e["args"] for e in tracer.events()
            if e.get("name") == "engine.tick"]
        served[two_applies] = dict(
            tokens=[out[i].tokens for i in sorted(out)], seen=seen,
            ticks=ticks, stats=eng.stats())
    one, two = served[False], served[True]
    assert one["tokens"] == two["tokens"]
    assert all(len(t) == 7 for t in one["tokens"])
    # tokens a request holds after each tick since its first
    assert all(s[:3] == [1, 2, 3] for s in one["seen"].values())
    assert all(s[:2] == [2, 3] for s in two["seen"].values())
    for body, passes in ((one, 1), (two, 2)):
        mixed = [t for t in body["ticks"] if t["program"] == "mixed"]
        decode = [t for t in body["ticks"] if t["program"] == "decode"]
        assert mixed and decode
        assert {t["model_passes"] for t in mixed} == {passes}
        assert {t["model_passes"] for t in decode} == {1}
        assert (
            sum(t["decodes"] for t in body["ticks"]) + len(prompts)
            == body["stats"]["generated_tokens"] == 7 * len(prompts))
        assert body["stats"]["page_stalls"] == 0
    # a slot is held one tick longer a request, no more
    assert 0 < len(one["ticks"]) - len(two["ticks"]) <= len(prompts)


def test_the_one_pass_body_refuses_a_features_operands():
    model, params, geometry = served_toy("hybrid", False)
    world = dict(
        model=model, params=params, slots=3, capacity=64, budget=16)
    programs, cache, _ = build(world, paged=True, spec_k=2)
    operands = dict(
        params=params, cache=cache, key=jax.random.PRNGKey(0),
        **{n: np.zeros((16,), np.int32) for n in (
            "chunk_tokens", "chunk_slots", "chunk_pos", "commit_slots")},
        **{n: np.zeros((3,), np.int32) for n in (
            "lengths_before", "lengths_after", "completion_idx",
            "dec_tokens")},
        dec_active=np.zeros((3,), bool),
        chunk_poison=np.zeros((16,), np.float32),
        dec_poison=np.zeros((3,), np.float32))
    with pytest.raises(ValueError, match="one apply.*commit_slots"):
        programs.mixed(*(operands[n] for n in programs.mixed_operands))
