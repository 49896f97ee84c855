"""The sampling key is engine state on the device (ISSUE 29): every step
program splits it inside itself and hands the new state back beside the
cache; the host makes no split of its own.

The stream must be the one a host-side chain ``key, sub =
jax.random.split(key)`` per program call gives: the patched `sample`
below records the key each draw of the real `sample` was handed, and the
test walks the engine's program calls beside that chain. A retry replays
its key (the state is re-bound on success only), an exhausted retry
leaves it where it was, and engines that share programs keep a key
each.

Wall-time note: a program with a host callback in it is never read from
the compile cache, so each case compiles its toy programs (test_paging's
shape tuple) anew.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _helpers import PAGE

from rocm_apex_tpu.inference import (
    Fault,
    FaultInjected,
    FaultPlan,
    InferenceEngine,
    SamplingParams,
    shard_tp1_params,
)
from rocm_apex_tpu.inference import programs as programs_mod
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
from rocm_apex_tpu.transformer import parallel_state

PROMPTS = [
    [5, 6, 7, 8, 9, 10, 11],
    [3, 1, 4, 1, 5, 9, 2, 6, 5, 3],
    [12, 13],
    [1, 2, 3, 1, 2, 3, 1, 2],
]
MAX_NEW = 6
HOT = SamplingParams(temperature=0.8)

#: engine configuration -> the programs its ticks run
CASES = {
    "chunked-contiguous": (dict(), {"mixed", "decode"}),
    "chunked-paged": (dict(paged=True, page_size=PAGE), {"mixed", "decode"}),
    "whole-prompt": (
        dict(prefill_token_budget=None, max_prompt_len=24),
        {"prefill", "decode"}),
    # a speculative engine runs the mixed program on every tick
    "speculative": (dict(spec_k=2, prefill_token_budget=8), {"mixed"}),
    "tp2": (dict(tp=2, paged=True, page_size=PAGE), {"mixed", "decode"}),
}


def cfg_of(tp=1):
    return GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        tensor_parallel_size=tp, params_dtype=jnp.float32,
        dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def world():
    model = GPTModel(cfg_of())
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))
    return model, params


def make_engine(world, tp=1, **kw):
    model, params = world
    if tp > 1:
        mesh = parallel_state.initialize_model_parallel(
            tp, 1, devices=jax.devices()[:tp])
        model = GPTModel(cfg_of(tp))
        params = shard_tp1_params(model, params, mesh)
    kw = dict(dict(
        num_slots=2, capacity=24, prefill_token_budget=4, sampling=HOT,
        seed=7, step_retry_backoff=0.0), **kw)
    return InferenceEngine(model, params, **kw)


def serve(eng, on_fault=None):
    """All of PROMPTS to the end: {request id: tokens}."""
    for i, p in enumerate(PROMPTS):
        eng.add_request(list(p), MAX_NEW, request_id=i)
    out = {}
    while eng.has_work():
        try:
            for r in eng.step():
                out[r.request_id] = list(r.tokens)
        except FaultInjected:
            on_fault()
    assert sorted(out) == list(range(len(PROMPTS)))
    return out


def bits(key):
    return tuple(np.asarray(key).tolist())


@pytest.fixture()
def draws(monkeypatch):
    """The key of every draw the programs make, in the order the device
    ran them (`sample` itself is the real one)."""
    log = []
    real = programs_mod.sample

    def recording_sample(rng, logits, **kw):
        jax.debug.callback(lambda k: log.append(bits(k)), rng)
        return real(rng, logits, **kw)

    monkeypatch.setattr(programs_mod, "sample", recording_sample)
    return log


@pytest.mark.parametrize("case", list(CASES))
def test_the_stream_is_the_host_side_chain(case, world, draws):
    features, programs = CASES[case]
    if features.get("tp", 1) > len(jax.devices()):
        pytest.skip("needs 2 simulated devices")
    eng = make_engine(world, **features)
    calls = []
    run_program = eng._run_program

    def counting(name, *args, **kw):
        calls.append(name)
        return run_program(name, *args, **kw)

    eng._run_program = counting
    try:
        tokens = serve(eng)
        jax.effects_barrier()
    finally:
        if features.get("tp", 1) > 1:
            parallel_state.destroy_model_parallel()
    assert set(calls) == programs
    assert len({tuple(t) for t in tokens.values()}) > 1

    key, at = jax.random.PRNGKey(7), 0
    for name in calls:
        key, sub = jax.random.split(key)
        # the chunk's draw and the decode grid's take a half each
        want = (
            {bits(k) for k in jax.random.split(sub)} if name == "mixed"
            else {bits(sub)})
        got = set()
        # (two draws of one program come in either order; a tp mesh
        # reports each once a chip)
        while at < len(draws) and draws[at] in want:
            got.add(draws[at])
            at += 1
        assert got == want, (name, at)
    assert at == len(draws)
    # what the last program handed back is the chain's state
    assert bits(eng._rng) == bits(key)
    assert eng.stats()["step_retries"] == 0
    for name in programs:
        assert eng.programs.traces[name] == 1, name


FAULTS = {
    # the step fails before the program runs; after it ran (its new key
    # state came back and must be dropped with the rest of its outputs)
    "retried": (
        [Fault(site="device_step", tick=2), Fault(site="host_fetch", tick=5)],
        2),
    # no retry left: the failure surfaces and the requests start again
    "exhausted": ([Fault(site="device_step", tick=3)], 0),
}


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("case", list(FAULTS))
def test_a_failed_step_does_not_advance_the_key(case, layout, world):
    paged = dict(paged=True, page_size=PAGE) if layout == "paged" else {}
    calm = make_engine(world, **paged)
    want = serve(calm)
    faults, retries = FAULTS[case]
    eng = make_engine(
        world, step_source=calm, faults=FaultPlan(faults),
        max_step_retries=retries, **paged)
    surfaced = []

    def on_fault():
        # the key is where the last successful program left it
        surfaced.append(bits(eng._rng))
        assert eng.num_active == 0 and eng.num_queued > 0

    keys = []
    step = eng.step

    def watched_step():
        keys.append(bits(eng._rng))
        return step()

    eng.step = watched_step
    got = serve(eng, on_fault)
    if case == "retried":
        # the retry replayed the key: the fault-free run's tokens
        assert got == want and not surfaced
        assert eng.stats()["step_retries"] == 2
    else:
        assert len(surfaced) == 1 and surfaced[0] == keys[3]
        assert eng.stats()["preemptions"] >= 1
        assert all(len(t) == MAX_NEW for t in got.values())
    assert eng.mixed_trace_count == 1 and eng.decode_trace_count == 1


def test_engines_sharing_programs_keep_a_key_each(world):
    """`step_source=` shares the compiled programs and no key: the state
    is each engine's own operand."""
    first = make_engine(world, seed=1)
    same = make_engine(world, seed=1, step_source=first)
    other = make_engine(world, seed=2, step_source=first)
    assert same.programs is first.programs is other.programs
    want = serve(first)
    # stepped in turn, one tick each
    for eng in (same, other):
        for i, p in enumerate(PROMPTS):
            eng.add_request(list(p), MAX_NEW, request_id=i)
    got = {id(same): {}, id(other): {}}
    while same.has_work() or other.has_work():
        for eng in (same, other):
            for r in eng.step():
                got[id(eng)][r.request_id] = list(r.tokens)
    assert got[id(same)] == want
    assert got[id(other)] != want
    assert first.mixed_trace_count == 1 and first.decode_trace_count == 1
