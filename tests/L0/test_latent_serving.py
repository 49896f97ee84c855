"""The latent-attention model (`models/latent.py`) served by
`InferenceEngine` at toy widths, against the plain float32 reference of
its benchmark family (`benchmarks/families/longcat_flash.py`): two
latent-attention blocks, two dense gated MLPs and an expert layer on a
shortcut a layer; rotary positions; a paged cache of latent rows and no
K/V; an untied head.

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

from benchmarks.families import longcat_flash as fam
from benchmarks.harness import rehearsal
from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
from rocm_apex_tpu.inference.paging import PagedKVCache
from rocm_apex_tpu.models.latent import LatentModel

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 5
BUDGET = 16


@pytest.fixture(scope="module")
def config():
    raw = json.loads(
        (ROOT / "benchmarks/configs/longcat-flash-omni.json").read_text())
    return rehearsal.shrink(raw)


@pytest.fixture(scope="module")
def params(config):
    return fam.make_params(config, SEED, jnp.float32)


def engine_of(config, params, slots=3, num_pages=48, page_size=4,
              capacity=64, log_routes=False, **more):
    cfg = fam.model_config(
        config, params_dtype=jnp.float32, dtype=jnp.float32,
        log_routes=log_routes)
    return InferenceEngine(
        LatentModel(cfg), params, num_slots=slots, capacity=capacity,
        sampling=SamplingParams(temperature=0.0),
        prefill_token_budget=BUDGET, paged=True, page_size=page_size,
        num_pages=num_pages, **more)




def test_engine_logits_match_the_reference(config, params, recorded):
    """Prompts longer than the budget (a prompt's later chunks read its
    earlier ones from the latent pages, rotated at their own positions:
    37 = 16 + 16 + 5 crosses two chunk boundaries), two slots' segments
    packed in one chunk, more requests than slots (a slot is reused: its
    second request's positions start from 0 again over rows the first
    left behind), then decoding through the paged latent cache."""
    eng = engine_of(config, params)
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


def test_a_reused_slot_serves_what_a_fresh_engine_serves(config, params):
    """One slot, three requests in turn: each is served over pages and
    positions the one before it used, and gets the tokens it gets alone
    in a fresh engine."""
    prompts = prompts_of([19, 7, 30], seed=2)
    together = run(engine_of(config, params, slots=1), prompts, 8)
    for p, r in zip(prompts, together):
        alone = run(engine_of(config, params, slots=1), [p], 8)[0]
        assert r.tokens == alone.tokens


def test_what_a_slot_keeps_is_the_references_rows(config, params):
    """The rows a live slot holds, read back as the benchmark reads
    them: per attention block the normalised scaled latent and the
    rotated positional key, zeros in the lanes a row pads with, and the
    routing log; against the reference's forward over the same tokens."""
    eng = engine_of(config, params, slots=2, log_routes=True)
    eng.add_request(prompts_of([23], seed=4)[0], 12)
    for _ in range(8):
        eng.step()
    snap = fam.kv_snapshot(eng, fam.kv_snapshot_program(eng))
    assert snap is not None and snap["rows"] > 23
    st = eng._slots[0]
    tokens = list(st.req.prompt) + list(st.generated)
    gaps = fam.reference_latent_gaps(
        config, SEED, tokens, snap, stored=jnp.float32)
    blocks = 2 * config["num_layers"]
    assert len(gaps["latent"]) == len(gaps["rope"]) == blocks
    assert max(gaps["latent"]) < 1e-5 and max(gaps["rope"]) < 1e-5
    assert sum(gaps["routing_differs"]) == 0
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    rows = np.asarray(snap["latent"])[:, :snap["rows"]]
    assert rows.shape[-1] == 128 and np.all(rows[..., rank + rope:] == 0)
    assert np.abs(rows[..., :rank + rope]).min() > 0
    # rows rounded to float8 stand well apart (the benchmark's control)
    low = fam.reference_latent_gaps(
        config, SEED, tokens, snap, stored=jnp.float32,
        lowered=fam.CONTROL_LATENT)
    assert min(low["latent"]) > 0.02


def test_prefix_sharing_serves_the_same_tokens(config, params):
    """A latent row lives in pages like K/V: a second request with the
    first one's prompt borrows its full pages (and copies the partly
    filled one before it writes), and is served the same tokens."""
    shared = prompts_of([26], seed=6)[0]
    prompts = [shared + [3, 1, 4], shared + [1, 5, 9, 2]]
    plain = run(engine_of(config, params, slots=2), prompts, 6)
    eng = engine_of(config, params, slots=1, prefix_sharing=True)
    sharing = run(eng, prompts, 6)
    assert [r.tokens for r in sharing] == [r.tokens for r in plain]
    assert eng.stats()["prefix_hit_tokens"] >= 24


def test_tick_counters_ride_the_fetch_onto_the_tick(config, params):
    from rocm_apex_tpu.monitor.trace import Tracer

    tracer = Tracer()
    eng = engine_of(config, params, tracer=tracer)
    run(eng, prompts_of([20, 6]), 5)
    ticks = [
        e["args"] for e in tracer.events()
        if e.get("name") == "engine.tick" and e["args"]["program"] != "none"
    ]
    assert ticks and {t["program"] for t in ticks} == {"mixed", "decode"}
    layers, k = config["num_layers"], config["moe_topk"]
    held = config["n_routed_experts"]
    zero_seen = 0
    for t in ticks:
        rows = t["decodes"] + t["chunk_tokens"]
        pairs = t["moe_assignments"] + t["moe_zero_assignments"]
        assert 0 < pairs <= k * layers * rows
        assert t["model_passes"] == 1  # mixed or not: one apply a tick
        assert t["moe_experts_touched"] <= held * layers
        assert t["state_slots_live"] == 0
        zero_seen += t["moe_zero_assignments"]
    assert zero_seen > 0
    # a decode tick attends every live slot's rows, its new one among
    # them, in each of the 2 x layers blocks
    decode = [t for t in ticks if t["program"] == "decode"]
    assert all(
        t["latent_rows_read"] % (2 * layers) == 0
        and t["latent_rows_read"] >= 2 * layers * t["decodes"]
        for t in decode)
    # one request alone: every tick's decode row reads one row more than
    # the tick before it, from its 9 prompt rows and its own
    tracer.clear()
    run(eng, prompts_of([9], seed=1), 6)
    read = [
        e["args"]["latent_rows_read"] // (2 * layers)
        for e in tracer.events()
        if e.get("name") == "engine.tick" and e["args"].get("decodes") == 1
    ]
    assert read == list(range(10, 10 + len(read))) and len(read) >= 4


REFUSED = {
    "speculation": (dict(spec_k=2), "spec_k"),
    "contiguous_cache": (dict(paged=False), "paged=False"),
    "int8_kv": (dict(kv_dtype=jnp.int8), "int8"),
    "adapter_pool": (dict(adapter_pool=object()), "adapter_pool"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_what_a_latent_model_does_not_serve_with_is_refused_by_name(
        config, params, option):
    cfg = fam.model_config(
        config, params_dtype=jnp.float32, dtype=jnp.float32)
    kwargs = dict(
        num_slots=2, capacity=32, prefill_token_budget=BUDGET, paged=True,
        page_size=4)
    more, named = REFUSED[option]
    kwargs.update(more)
    with pytest.raises(ValueError, match="latent rows in pages") as e:
        InferenceEngine(LatentModel(cfg), params, **kwargs)
    assert named in str(e.value)


def test_tensor_parallel_is_refused_by_the_model(config):
    with pytest.raises(ValueError, match="tensor-parallel"):
        fam.model_config(config, tensor_parallel_size=2)


def test_shipping_pages_is_refused_and_tokens_move(config, params):
    eng = engine_of(config, params, slots=2)
    eng.add_request(prompts_of([6])[0], 4)
    eng.step()
    with pytest.raises(ValueError, match="latent rows in pages"):
        eng.evacuate(ship_pages=True)
    assert len(eng.evacuate()) == 1


def test_the_cache_is_built_from_the_models_declaration(config, params):
    eng = engine_of(config, params, slots=2)
    cache = eng.cache
    assert cache.k == () and cache.v == () and cache.ssm == ()
    assert len(cache.latent) == 2 * config["num_layers"]
    assert all(p.shape == (48, 1, 4, 128) for p in cache.latent)
    assert cache.num_pages == 48 and cache.capacity == 64
    assert cache.routes is None and cache.counters.shape == (6,)
    assert eng.cache_bytes() > sum(
        a.size * a.dtype.itemsize for a in cache.latent)
    logged = engine_of(config, params, slots=2, log_routes=True).cache
    assert logged.routes.shape == (48, 1, 4, 128)


# -- `PagedKVCache.from_spec` with latent layers -------------------------------

LATENT = dict(kind="latent", rank=512, rope=64)
KV = dict(kind="kv", heads=2, head_dim=8)


def test_from_spec_builds_latent_pools_beside_kv_or_alone():
    alone = PagedKVCache.from_spec(
        [dict(LATENT, counters=True), LATENT], 3, 64, page_size=16,
        num_pages=10)
    assert alone.k == () and len(alone.latent) == 2
    # 576 values as published, stored as whole 128-lane tiles
    assert alone.latent[0].shape == (10, 1, 16, 640)
    assert alone.num_pages == 10 and alone.page_table.shape == (3, 4)
    assert int(alone.page_table.max()) == 10
    assert alone.counters.shape == (len(PagedKVCache.COUNTER_NAMES),)
    both = PagedKVCache.from_spec([KV, LATENT, KV], 3, 64, page_size=16)
    assert len(both.k) == len(both.v) == 2 and len(both.latent) == 1
    assert both.latent[0].shape[0] == both.k[0].shape[0] == 12
    assert both.counters is None
    assert both.cache_bytes() > alone.latent[0].size * 2


def test_from_spec_forks_a_page_in_every_pool_behind_the_table():
    cache = PagedKVCache.from_spec(
        [dict(LATENT, route_words=2), KV], 2, 32, page_size=16, num_pages=4)
    cache = cache.replace(
        latent=tuple(p.at[1].set(1.5) for p in cache.latent),
        routes=cache.routes.at[1].set(7),
        k=tuple(p.at[1].set(2.5) for p in cache.k))
    forked = cache.fork_page(1, 3)
    assert float(forked.latent[0][3].min()) == 1.5
    assert int(forked.routes[3].min()) == 7
    assert float(forked.k[0][3].min()) == 2.5
    assert float(jnp.abs(forked.latent[0][2]).max()) == 0.0


def test_from_spec_says_what_it_refuses():
    with pytest.raises(ValueError, match="kind 'window'.*kv.*latent.*ssm"):
        PagedKVCache.from_spec([dict(kind="window")], 2, 32)
    ssm = dict(kind="ssm", state=(4, 8), conv=(3, 16), state_dtype=jnp.float32)
    with pytest.raises(ValueError, match="neither K/V nor latent rows"):
        PagedKVCache.from_spec([ssm], 2, 32)
    with pytest.raises(ValueError, match=r"differ in \(heads, head_dim\)"):
        PagedKVCache.from_spec([KV, dict(KV, heads=4)], 2, 32)
    with pytest.raises(ValueError, match="no int8 form"):
        PagedKVCache.from_spec([LATENT], 2, 32, quantized=True)


def test_counts_add_and_the_fullest_expert_is_a_maximum():
    cache = PagedKVCache.from_spec([dict(LATENT, counters=True)], 2, 32)
    cache = cache.count(moe_assignments=3, moe_load_max=2, latent_rows_read=5)
    cache = cache.count(moe_assignments=4, moe_load_max=1, latent_rows_read=6)
    got = dict(zip(cache.COUNTER_NAMES, np.asarray(cache.counters)))
    assert (got["moe_assignments"], got["moe_load_max"],
            got["latent_rows_read"], got["state_slots_live"]) == (7, 2, 11, 0)
    assert int(cache.start_tick().counters.sum()) == 0
