"""`inference.programs.StepPrograms` with no engine around it: one tick
body under every feature set.

A feature (speculation, an adapter pool) adds OPERANDS to the one fused
chunk+decode body and nothing else, so with no drafts in the chunk and
adapter 0 on every row each feature set must sample the base set's
tokens bit for bit; every set traces each program once; and
`compatible_with` refuses each mismatch an engine's ``step_source=``
must refuse. The GPT cases reuse test_inference.py's shape tuple
(slots=2, capacity=24, budget=4, the fp32 model), the hybrid case
test_hybrid_serving.py's toy.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import PAGE, hybrid_toy
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
    model, params = hybrid_toy()
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
    """A mixed tick (slot 0's whole prompt, completing and fed into the
    decode grid; the rest of the budget from slot 1's prompt) and a
    decode tick after it, each run twice. Returns the sampled tokens."""
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
