"""The declared-layer model (`models/hybrid.py`) served by
`InferenceEngine` at toy widths, against the plain float32 reference of
its benchmark family (`benchmarks/families/granite_hybrid.py`): a
pattern of Mamba-2 and grouped-head attention mixers, routed experts of
which half are held, the recurrent state beside the paged K/V.

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

from benchmarks.families import granite_hybrid as fam
from benchmarks.harness import rehearsal
from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
from rocm_apex_tpu.models.hybrid import HybridModel

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 5
BUDGET = 16


@pytest.fixture(scope="module")
def config():
    raw = json.loads(
        (ROOT / "benchmarks/configs/granite-4.0-h-small.json").read_text())
    # multipliers that let the layers, not the token's own embedding,
    # decide the next token: served sequences are not one token repeated
    return dict(
        rehearsal.shrink(raw), embedding_multiplier=1.0,
        residual_multiplier=1.5, logits_scaling=1.0)


@pytest.fixture(scope="module")
def params(config):
    return fam.make_params(config, SEED, jnp.float32)


def engine_of(config, params, impl="flash", slots=3, num_pages=48,
              page_size=4, capacity=64, log_routes=False, **more):
    cfg = fam.model_config(
        config, params_dtype=jnp.float32, dtype=jnp.float32,
        attention_impl=impl, log_routes=log_routes)
    return InferenceEngine(
        HybridModel(cfg), params, num_slots=slots, capacity=capacity,
        sampling=SamplingParams(temperature=0.0),
        prefill_token_budget=BUDGET, paged=True, page_size=page_size,
        num_pages=num_pages, **more)




@pytest.mark.parametrize("impl", ["flash", "jnp"])
def test_engine_logits_match_the_reference(config, params, recorded, impl):
    """Prompts longer than the budget (a sequence's scan is cut across
    ticks), two slots' segments packed in one chunk (5 + 11 of the
    second in the first tick), more requests than slots (a slot is
    reused: its second request must start from a zero state)."""
    eng = engine_of(config, params, impl)
    results = run(eng, prompts_of([5, 37, 21, 9, 18]), 6)
    assert eng.mixed_trace_count == 1 and eng.decode_trace_count == 1
    jax.effects_barrier()
    seen = np.stack(recorded)
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


def test_a_preempted_request_prefilled_again_serves_the_same_tokens(
        config, params):
    """A device step that fails with no retry left preempts and requeues
    every request in flight, mid-decode; each is prefilled again (its
    prompt and the tokens it has) into a slot whose state the first
    attempt left behind, and goes on to the same tokens."""
    from rocm_apex_tpu.inference.faults import (
        Fault, FaultInjected, FaultPlan,
    )

    prompts = prompts_of([10, 19], seed=3)
    calm = run(engine_of(config, params, slots=2), prompts, 12)
    eng = engine_of(
        config, params, slots=2, max_step_retries=0,
        faults=FaultPlan([Fault(site="device_step", tick=5)]))
    for p in prompts:
        eng.add_request(p, 12)
    done, raised = {}, 0
    while eng.has_work():
        try:
            for r in eng.step():
                done[r.request_id] = r
        except FaultInjected:
            raised += 1
            assert eng.num_active == 0 and eng.num_queued == 2
    assert raised == 1 and eng.stats()["preemptions"] >= 2
    assert [done[i].tokens for i in (0, 1)] == [r.tokens for r in calm]
    assert all(0 < len(r.tokens) for r in calm)


def test_inactive_rows_leave_their_state_bit_identical(config, params):
    """A slot no request holds, and a slot whose request has finished
    while another still decodes, keep every bit of their state."""
    eng = engine_of(config, params, slots=3)
    eng.add_request(prompts_of([7])[0], 3)
    eng.add_request(prompts_of([9], seed=1)[0], 12)

    def state_of(slot):
        return [np.asarray(a[slot]) for a in eng.cache.ssm + eng.cache.conv]

    idle0 = state_of(2)
    finished = None
    while eng.has_work():
        done = eng.step()
        if finished is not None:
            for a, b in zip(finished, state_of(0)):
                assert np.array_equal(a, b)
        if any(r.request_id == 0 for r in done):
            finished = state_of(0)
    assert finished is not None and any(np.any(a != 0) for a in finished)
    for a, b in zip(idle0, state_of(2)):
        assert np.array_equal(a, b)


def test_tick_counters_ride_the_fetch_onto_the_tick(config, params):
    """Each tick's `engine.tick` span carries the layers' counters, and
    they are what the routing and the grid allow."""
    from rocm_apex_tpu.monitor.trace import Tracer

    tracer = Tracer()
    eng = engine_of(config, params, tracer=tracer)
    run(eng, prompts_of([20, 6]), 4)
    ticks = [
        e["args"] for e in tracer.events()
        if e.get("name") == "engine.tick" and e["args"]["program"] != "none"
    ]
    assert ticks and {t["program"] for t in ticks} == {"mixed", "decode"}
    held, layers = config["num_local_experts"], config["num_hidden_layers"]
    k = config["num_experts_per_tok"]
    for t in ticks:
        rows = t["decodes"] + t["chunk_tokens"]
        assert 0 < t["moe_assignments"] <= k * layers * rows
        # one apply a tick, mixed or not: a layer's experts are touched
        # once, and no slot's state is advanced by both parts
        assert t["model_passes"] == 1
        assert 0 < t["moe_experts_touched"] <= held * layers
        assert 0 < t["moe_load_max"] <= t["decodes"] + t["chunk_tokens"]
        assert t["decodes"] <= t["state_slots_live"] <= eng.num_slots
    decode = [t for t in ticks if t["program"] == "decode"]
    assert all(t["state_slots_live"] == t["decodes"] for t in decode)


REFUSED = {
    "prefix_sharing": dict(prefix_sharing=True),
    "speculation": dict(spec_k=2),
    "contiguous_cache": dict(paged=False),
    "int8_kv": dict(kv_dtype=jnp.int8),
    "adapter_pool": dict(adapter_pool=object()),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_options_that_cannot_carry_state_raise_at_construction(
        config, params, option):
    cfg = fam.model_config(
        config, params_dtype=jnp.float32, dtype=jnp.float32)
    kwargs = dict(
        num_slots=2, capacity=32, prefill_token_budget=BUDGET, paged=True,
        page_size=4)
    kwargs.update(REFUSED[option])
    with pytest.raises(ValueError, match="recurrent state"):
        InferenceEngine(HybridModel(cfg), params, **kwargs)


def test_tensor_parallel_is_refused_by_the_model(config):
    with pytest.raises(ValueError, match="tensor-parallel"):
        fam.model_config(config, tensor_parallel_size=2)


def test_shipping_pages_is_refused(config, params):
    eng = engine_of(config, params, slots=2)
    eng.add_request(prompts_of([6])[0], 4)
    eng.step()
    with pytest.raises(ValueError, match="recurrent state"):
        eng.evacuate(ship_pages=True)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.evacuate_request(0, ship_pages=True)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.resume_request([1, 2, 3], 4, 7, pages={"k": []})
    # by tokens it moves: the prefill recomputes the state
    recs = eng.evacuate()
    assert len(recs) == 1


def test_the_cache_is_built_from_the_models_declaration(config, params):
    eng = engine_of(config, params, slots=2)
    kinds = fam.layer_types(config)
    cache = eng.cache
    assert len(cache.k) == kinds.count("attention")
    assert len(cache.ssm) == len(cache.conv) == kinds.count("mamba")
    assert cache.k[0].shape[1] == config["num_key_value_heads"]
    n, inner = config["mamba_d_state"], (
        config["mamba_n_heads"] * config["mamba_d_head"])
    assert cache.ssm[0].shape == (2, n, inner)
    assert cache.ssm[0].dtype == jnp.float32
    assert cache.conv[0].shape == (2, 3, inner + 2 * n)
    assert eng.cache_bytes() > sum(
        a.size * a.dtype.itemsize for a in cache.ssm)
    # the log of chosen experts is a debugging option: off, nothing of it
    # is kept; on, every layer's words, a row a position, in pages
    assert cache.routes is None
    logged = engine_of(config, params, slots=2, log_routes=True).cache
    assert logged.routes.shape[0] == logged.k[0].shape[0]
    assert logged.routes.shape[-1] >= len(kinds) * 1
    assert logged.routes.dtype == jnp.uint32
