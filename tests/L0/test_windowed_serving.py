"""The window/global model (`models/windowed.py`) served by
`InferenceEngine` at toy widths, against the plain float32 reference of
its benchmark family (`benchmarks/families/smallthinker.py`): global
layers without positions and window layers with rotary positions in one
model, a group of 7 query heads a K/V head, ReGLU experts routed from
before the attention, and a cache in two groups of which the window
group's pages go back to the allocator while a request runs.

Weights are float32 here, so the engine's logits and the reference's
agree to rounding; the logits of every engine call are recorded through
the engine's own sampling hook and each served position's reference
logits must be among them.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _serving import VOCAB, prompts_of, recorded, run  # noqa: F401

from benchmarks.families import smallthinker as fam
from benchmarks.harness import rehearsal
from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
from rocm_apex_tpu.inference.paging import (
    PagedKVCache, window_pages_per_slot,
)
from rocm_apex_tpu.models.windowed import WindowedModel

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 5
BUDGET = 16
PAGE = 8


@pytest.fixture(scope="module")
def config():
    raw = json.loads(
        (ROOT / "benchmarks/configs/smallthinker-21b-a3b.json").read_text())
    config = rehearsal.shrink(raw)
    assert fam.layer_types(config) == ("global",) + ("window",) * 3 + (
        "global",) + ("window",) * 3  # two whole periods
    return config


@pytest.fixture(scope="module")
def params(config):
    return fam.make_params(config, SEED, jnp.float32)


def engine_of(config, params, slots=3, num_pages=(40, 15), capacity=128,
              **more):
    cfg = fam.model_config(
        config, params_dtype=jnp.float32, dtype=jnp.float32)
    return InferenceEngine(
        WindowedModel(cfg), params, num_slots=slots, capacity=capacity,
        sampling=SamplingParams(temperature=0.0),
        prefill_token_budget=BUDGET, paged=True, page_size=PAGE,
        num_pages=num_pages, **more)


def check_pages(eng, window):
    """After a tick: both allocators sound; no slot holds more window
    pages than the stated worst case; every position a slot's next row
    attends in a window layer is still mapped, and nothing behind it."""
    eng._allocator.assert_consistent()
    eng._window_allocator.assert_consistent()
    sentinel = eng.cache.window_pages
    worst = window_pages_per_slot(window, PAGE, BUDGET, eng.capacity)
    held = 0
    for slot, st in enumerate(eng._slots):
        mapped = np.flatnonzero(eng._window_table[slot] != sentinel)
        held += len(mapped)
        if st is None:
            assert not len(mapped)
            continue
        assert len(mapped) <= worst, (slot, len(mapped), worst)
        first_needed = max(0, st.pos + 1 - window)
        want = set(range(first_needed // PAGE, -(-st.pos // PAGE)))
        assert want <= set(mapped), (slot, st.pos, mapped)
        # one page past the written rows may be mapped for the next row
        assert set(mapped) <= want | {st.pos // PAGE}, (slot, st.pos, mapped)
    assert held == eng._window_allocator.pages_used


def assert_serves_the_reference(config, results, seen):
    """Every served token is the reference's greedy choice, and the
    reference's logits at each served position are among those the
    engine's programs sampled from."""
    for r in results:
        seq = list(r.prompt) + list(r.tokens)
        ref = fam.reference_logits(
            config, SEED, np.asarray([seq[:-1]]), stored=jnp.float32)[0]
        want = ref[len(r.prompt) - 1:]
        assert list(want.argmax(-1)) == list(r.tokens)
        for row in want:
            nearest = np.abs(seen - row[None]).max(axis=1).min()
            assert nearest < 2e-4, nearest
    assert len({tuple(r.tokens) for r in results}) > 1


def test_engine_logits_match_the_reference_past_the_window(
        config, params, recorded):
    """Prompts prefilled in chunks of 16 and then decoded, to contexts of
    up to 91 under a window of 20 and pages of 8: several pages behind
    the window are freed while a request runs and mapped again by the
    requests that follow (15 window pages serve five requests that would
    take 41 unfreed); more requests than slots."""
    eng = engine_of(config, params)
    window = fam.sizes(config)["window"]
    for p in prompts_of([5, 61, 37, 9, 50]):
        eng.add_request(p, 30)
    out, freed = {}, 0
    while eng.has_work():
        for r in eng.step():
            out[r.request_id] = r
        check_pages(eng, window)
        freed += eng._window_pages_freed
    results = [out[i] for i in sorted(out)]
    assert eng.mixed_trace_count == 1 and eng.decode_trace_count == 1
    assert freed >= 20 and eng.stats()["preemptions"] == 0
    jax.effects_barrier()
    assert_serves_the_reference(config, results, np.stack(recorded))


# what a wrong structure costs: the served logits against a reference
# built wrong on purpose lie far outside the rounding the test above holds
@pytest.mark.parametrize("wrong", [
    dict(window=19), dict(window=21), dict(rope_global=True)],
    ids=["window_of_19_keys", "window_of_21_keys", "rotary_on_a_global_layer"])
def test_a_wrong_structure_reads_far_from_the_reference(config, wrong):
    tokens = np.asarray([prompts_of([64], seed=4)[0]])
    right = fam.reference_logits(config, SEED, tokens, stored=jnp.float32)[0]
    other = fam.reference_logits(
        config, SEED, tokens, stored=jnp.float32, wrong=wrong)[0]
    # the first 19 positions see the same keys under any of these windows
    head = slice(0, 19)
    if "window" in wrong:
        assert np.abs(right[head] - other[head]).max() < 1e-5
    assert np.abs(right[30:] - other[30:]).max() > 2e-3


def test_a_preempted_request_prefilled_again_serves_the_same_tokens(
        config, params):
    """A device step that fails with no retry left preempts and requeues
    every request in flight, one of them decoding past its window; each
    is prefilled again (its prompt and the tokens it has) through pages
    freed behind it the first time, and goes on to the same tokens."""
    from rocm_apex_tpu.inference.faults import (
        Fault, FaultInjected, FaultPlan,
    )

    prompts = prompts_of([10, 43], seed=3)
    calm = run(engine_of(config, params, slots=2), prompts, 20)
    eng = engine_of(
        config, params, slots=2, max_step_retries=0,
        faults=FaultPlan([Fault(site="device_step", tick=9)]))
    for p in prompts:
        eng.add_request(p, 20)
    done, raised = {}, 0
    window = fam.sizes(config)["window"]
    while eng.has_work():
        try:
            for r in eng.step():
                done[r.request_id] = r
        except FaultInjected:
            raised += 1
            assert eng.num_active == 0 and eng.num_queued == 2
            assert eng.pages_used == 0
        check_pages(eng, window)
    assert raised == 1 and eng.stats()["preemptions"] >= 2
    assert [done[i].tokens for i in sorted(done)] == [r.tokens for r in calm]


def test_window_pages_come_back_over_200_seeded_ticks_with_preemptions(
        config, params):
    """A seeded stream of requests into pools too small for it: the
    global group runs dry, slots are preempted for pages and prefilled
    again, and after every tick both allocators are sound, no slot holds
    more window pages than its worst case, and every position a row
    still to be computed attends is mapped."""
    rng = np.random.default_rng(11)
    eng = engine_of(config, params, slots=4, num_pages=(30, 14))
    window = fam.sizes(config)["window"]
    done, ticks, used_max = {}, 0, 0
    while ticks < 200:
        if rng.random() < 0.2 and eng.num_queued < 3:
            n = int(rng.integers(3, 70))
            eng.add_request(
                rng.integers(0, VOCAB, size=n).tolist(),
                int(rng.integers(4, 40)))
        if eng.has_work():
            for r in eng.step():
                done[r.request_id] = r
            check_pages(eng, window)
            used_max = max(used_max, eng._window_allocator.pages_used)
        ticks += 1
    st = eng.stats()
    assert st["preemptions"] >= 1 and len(done) >= 8
    assert used_max <= 14 and eng.pages_total == 44
    while eng.has_work():
        eng.step()
    assert eng.pages_used == 0
    eng.reopen()  # a clean engine: both tables all sentinel


def test_the_tick_reports_the_window_groups_pages(config, params):
    """`engine.tick` carries the sum over groups under the names it had,
    the window group's under names of their own, and the tick's counters
    hold the positions the decode grid read after and before the bound."""
    from rocm_apex_tpu.monitor.trace import Tracer

    tracer = Tracer()
    eng = engine_of(config, params, slots=2, tracer=tracer)
    run(eng, prompts_of([45, 12], seed=1), 12)
    ticks = [
        e for e in tracer.events() if e.get("name") == "engine.tick"
        and "window_pages_total" in e.get("args", {})]
    assert ticks
    args = [t["args"] for t in ticks]
    assert all(a["pages_total"] == 55 and a["window_pages_total"] == 15
               for a in args)
    assert all(a["pages_used"] >= a["window_pages_used"] for a in args)
    assert sum(a["window_pages_freed"] for a in args) >= 4
    decode = [a for a in args if a["program"] == "decode"]
    assert decode
    for a in decode:
        assert 0 < a["kv_rows_read"] <= a["kv_rows_cached"]
    # past the window the six window layers read 20 keys a live slot
    late = decode[-1]
    assert late["kv_rows_read"] < late["kv_rows_cached"]


REFUSALS = {
    "paged=False": (dict(paged=False, prefill_token_budget=None),
                    "paged=False (the contiguous cache keeps every row"),
    "prefix_sharing": (dict(prefix_sharing=True),
                       "prefix_sharing (a registered page may be freed"),
    "spec_k": (dict(spec_k=2), "spec_k > 0 (the commit program writes"),
    "kv_dtype=int8": (dict(kv_dtype=jnp.int8),
                      "kv_dtype=int8 (a windowed read has no int8 form)"),
    "adapter_pool": (dict(adapter_pool=object()),
                     "adapter_pool (its projections take no adapters)"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_freed_pages_break_is_refused_by_name(config, params, what):
    options, sentence = REFUSALS[what]
    cfg = fam.model_config(
        config, params_dtype=jnp.float32, dtype=jnp.float32)
    base = dict(
        num_slots=2, capacity=128, prefill_token_budget=BUDGET, paged=True,
        page_size=PAGE)
    with pytest.raises(ValueError) as err:
        InferenceEngine(WindowedModel(cfg), params, **dict(base, **options))
    assert "frees the pages that its window layers' rows have left" in str(
        err.value)
    assert sentence in str(err.value)


def test_page_shipping_and_tensor_parallelism_are_refused(config, params):
    eng = engine_of(config, params)
    eng.add_request(prompts_of([9])[0], 4)
    eng.step()
    with pytest.raises(ValueError, match="carries no window group"):
        eng.evacuate(ship_pages=True)
    with pytest.raises(ValueError, match="no sharded layout"):
        fam.model_config(config, tensor_parallel_size=2)


def test_the_cache_groups_kv_layers_by_window():
    spec = [
        dict(kind="kv", heads=2, head_dim=16, window=None, counters=True),
        dict(kind="kv", heads=2, head_dim=16, window=20),
        dict(kind="kv", heads=2, head_dim=16, window=20),
    ]
    cache = PagedKVCache.from_spec(
        spec, num_slots=3, capacity=128, page_size=8, num_pages=(30, 12),
        dtype=jnp.float32)
    assert len(cache.k) == 1 and len(cache.window_k) == 2
    assert cache.k[0].shape[0] == 30 and cache.window_k[0].shape[0] == 12
    assert cache.window == 20 and cache.window_pages == 12
    assert cache.window_table.shape == cache.page_table.shape
    assert int(cache.window_table.min()) == 12  # all unmapped
    # no count given: the window group's worst case, which never stalls
    auto = PagedKVCache.from_spec(
        spec, num_slots=3, capacity=128, page_size=8, num_pages=30,
        dtype=jnp.float32, prefill_token_budget=16)
    assert auto.window_pages == 3 * window_pages_per_slot(20, 8, 16, 128)
    assert window_pages_per_slot(4096, 512, 512, 16384) == 10
    with pytest.raises(ValueError, match="one window group serves one"):
        PagedKVCache.from_spec(
            spec + [dict(kind="kv", heads=2, head_dim=16, window=24)],
            num_slots=3, capacity=128, page_size=8)
    with pytest.raises(ValueError, match="no layer declares a window"):
        PagedKVCache.from_spec(
            spec[:1], num_slots=3, capacity=128, page_size=8,
            num_pages=(30, 12))
    # a cache without a window group is the cache it was
    plain = PagedKVCache.from_spec(
        spec[:1], num_slots=3, capacity=128, page_size=8, num_pages=30)
    assert plain.window == 0 and plain.window_table is None
    assert not plain.window_k and plain.window_pages == 0


def test_a_chunk_of_few_slots_and_one_of_many_read_the_same(
        config, params, recorded):
    """Six slots, more than `models/windowed.py::CHUNK_SLOTS`: a chunk
    that holds the rows of up to four slots hands the kernel those
    slots' table rows alone, one that holds more (five prompts of 3 in a
    budget of 16) the whole table; either way the reference's logits."""
    from rocm_apex_tpu.models import windowed

    assert windowed.CHUNK_SLOTS == 4
    eng = engine_of(config, params, slots=6, num_pages=(60, 30))
    lengths = [3, 3, 3, 3, 3, 40, 27, 2, 2]
    results = run(eng, prompts_of(lengths, seed=8), 26)
    assert eng.mixed_trace_count == 1 and eng.decode_trace_count == 1
    jax.effects_barrier()
    assert_serves_the_reference(config, results, np.stack(recorded))
