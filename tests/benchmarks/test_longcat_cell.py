"""The cell `longcat-serve-agent-sat`: its files resolve and state their
cut, its control comes out not correct at toy size, its readers give a
number on a stretch that holds their scopes and counters and nothing on
one that does not (the parent commit's capture), the benchmark's own
operation and byte counts are held to hand arithmetic, and both step
programs compile for a described v5e at the published widths. (The
cell's rehearsal end to end is `test_rehearsal.py`'s, which runs every
cell of the manifest.)"""

import importlib.util
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks import run as bench
from benchmarks.harness import peaks, xplane
from benchmarks.harness.manifest import Manifest, load_module
from benchmarks.harness.program_trace import ProgramTrace, Span

ROOT = pathlib.Path(bench.ROOT)
CELL = "longcat-serve-agent-sat"
GRANITE = "granite4hs-serve-chat"
NEW = ["mla.device_ms", "mla_decode_roofline", "moe.zero_share_pct"]
SHARED = ["moe.device_ms", "moe_experts_roofline", "moe.load_max_over_mean"]
GRANITE_OWN = ["ssm.device_ms", "ssm_scan_roofline"]
CATALOG = pathlib.Path(
    "/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def latent():
    return load_module(ROOT / "benchmarks/layer_metrics/_latent.py")


def test_the_cells_and_their_metrics_are_in_the_manifest(manifest):
    assert manifest.problems() == []
    assert {CELL, GRANITE} <= set(manifest.workloads)
    assert all(w["chips"] == 1 for w in manifest.workloads.values())
    cell = manifest.cell(CELL)
    assert cell["traffic"] == "agent-p256-o768-sat" and cell["chips"] == 1
    assert cell["config_name"] == "longcat-flash-omni"
    # above the knee the tails say how long the run was: tokens/s and
    # set-up are what the cell reports, so the accepted per-layer metrics
    # that move a tail stay the first cell's
    for name, own in ((CELL, NEW), (GRANITE, GRANITE_OWN)):
        c = manifest.cell(name)
        assert set(c["end_to_end"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert set(c["per_layer"]) == set(own + SHARED)
        for metric in own:
            assert manifest.per_layer[metric]["workloads"] == [name]
    for metric in NEW + SHARED + GRANITE_OWN:
        assert manifest.per_layer[metric]["moves"] == "serve_out_tokens_per_s"
    for metric in SHARED:
        assert manifest.per_layer[metric]["workloads"] == [GRANITE, CELL]
    for name, m in manifest.per_layer.items():
        if name not in NEW + SHARED:
            assert CELL not in m.get("workloads", [])
        if name not in GRANITE_OWN + SHARED:
            assert GRANITE not in m.get("workloads", [])


def test_the_configuration_keeps_every_published_width(manifest):
    config = manifest.cell(CELL)["config"]
    assert sorted(config["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    entry = manifest.configs["longcat-flash-omni"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    if CATALOG.exists():
        rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
        row = next(r for r in rows if r["name"] == "LongCat-Flash-Omni")
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value, key
            else:
                assert config[key] == value, key
    assert config["published"] == {
        "num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    assert (config["num_layers"], config["n_routed_experts"],
            config["router_experts"], config["experts_held"],
            config["vocab_size"], config["vocab_held"]) == (
        4, 16, 512, [0, 16], 16384, [0, 16384])
    # the guide's floors: four layers, eight experts, an eighth of the rows
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert "32 chips share each layer" in config["deployment"]
    assert "8 chips share the vocabulary" in config["deployment"]
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora", "rotary", "router",
                "softmax_scale", "activation", "latent_norms", "left_out"):
        assert config["assumed"][key]
    family = manifest.family(config)
    s = family.sizes(config)
    # one attention block 90.57 M, one dense MLP 226.49 M, by hand
    assert family.attention_params_count(s) == (
        6144 * 1536 + 1536 + 1536 * 64 * 192 + 6144 * 576 + 512
        + 512 * 64 * 256 + 8192 * 6144) == 90_572_800
    assert family.layer_params_count(s) == (
        2 * (90_572_800 + 3 * 6144 * 12288 + 2 * 6144)
        + 6144 * 768 + 768 + 16 * 3 * 6144 * 2048)
    held = family.total_params(config)
    assert held == config["parameters_held"] == 5_172_749_312
    assert 10.3e9 < 2 * held < 10.4e9
    # the readers of the expert layer's metrics find their sizes
    assert (s["hidden"], s["expert_width"], s["vocab"], s["layers"],
            s["held_hi"] - s["held_lo"]) == (6144, 2048, 16384, 4, 16)


def test_the_mix_and_engine_files_state_the_deployment(manifest):
    mix = manifest.cell(CELL)["mix"]
    engine = mix["engine"]
    assert (engine["num_slots"], engine["capacity"],
            engine["prefill_token_budget"]) == (128, 4096, 512)
    # a latent pool of 262,144 positions: half of 128 slots' worst case
    assert engine["num_pages"] * engine["page_size"] == 262144
    # a position: 4 layers x 2 blocks x 640 stored values x 2 B
    assert engine["pool_bytes"] == 262144 * 8 * 640 * 2
    assert mix["kind"] == "serve_open_loop_latent"
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert (p["median"], p["sigma"], p["min"], p["max"]) == (256, 1.0, 32, 2048)
    assert (o["median"], o["sigma"], o["min"], o["max"]) == (768, 0.6, 128, 2048)
    a = mix["arrivals"]
    assert a["rate_per_s"] == pytest.approx(1.2 * a["knee_per_s"])
    sweep = a["sweep"]
    assert len(sweep["rate_per_s"]) == len(sweep["ttft_p95_ms"]) >= 4
    assert all(f == 0 for f in sweep["failed"])
    knee = max(
        r for r, t in zip(sweep["rate_per_s"], sweep["ttft_p95_ms"])
        if t <= 1000)
    assert knee == a["knee_per_s"] < max(sweep["rate_per_s"])
    limits = mix["check"]["limits"]
    assert {"latent_gap_first_block", "latent_gap_worst_block",
            "rope_key_gap_worst_block", "routing_differs_share", "gap_max",
            "gap_mean"} == set(limits) == set(
        mix["rehearsal"]["check"]["limits"])
    for name in list(limits) + ["readings", "control"]:
        assert mix["check"]["limits_why"][name], name


# -- the control: the latent rows one step of precision lower ------------------


def test_latent_rows_rounded_to_float8_are_not_correct():
    """The cell's own run at toy size, compared with the reference's
    own rows rounded to float8 (e4m3) in the slot's place, where the
    configuration states bfloat16 (the sound run is
    `test_rehearsal.py`'s, which comes out correct). The served tokens
    stay within their limits; the rows do not."""
    from _toy import run_args, toy_cell

    manifest, cell = toy_cell(CELL)
    _, result, compared = bench.run_cell(
        manifest, cell, run_args(41, 1.5), control=True)
    assert result["correct"] is False
    over = {c["name"] for c in compared if not c["value"] <= c["limit"]}
    assert over == {"latent_gap_first_block", "latent_gap_worst_block",
                    "rope_key_gap_worst_block"}
    values = {c["name"]: c["value"] for c in compared}
    assert 0.015 < values["latent_gap_first_block"] < 0.04


# -- the counts, by hand -------------------------------------------------------


def test_latent_decode_counts_by_hand(latent):
    # one position read by one query row: 64 heads score 576 values and
    # weigh 512, 2 operations a multiply-add; the row's 1,152 B; the
    # heads' queries in and weighted latents out
    flops, nbytes = latent.mla_decode_counts(1, 1, 64, 512, 64)
    assert flops == 2 * 64 * (576 + 512) == 139_264
    assert nbytes == 1152 + 64 * (576 + 512) * 2
    # a tick of the cell: 128 slots x 880 positions x 8 blocks: 121
    # operations a byte read, under the chip's ridge of 240: memory binds
    rows = 128 * 880 * 8
    flops, nbytes = latent.mla_decode_counts(rows, 128 * 8, 64, 512, 64)
    assert flops / (rows * 1152) == pytest.approx(120.9, abs=0.1)
    v5e = peaks.chip_peaks("TPU v5 lite")
    hybrid = load_module(ROOT / "benchmarks/layer_metrics/_hybrid.py")
    assert hybrid.least_seconds(flops, nbytes, v5e) == nbytes / 819e9
    assert nbytes / 819e9 == pytest.approx(1.44e-3, rel=0.02)


# -- the readers, on hand-made stretches ---------------------------------------

MS = 1_000_000


def stretch(with_scopes):
    """Two ticks of 20 ms, one decode and one mixed, with the benchmark's
    `engine.step` spans around them; operations named after the scopes
    only ``with_scopes``."""
    host, ops = [], []
    for i, program in enumerate(("decode", "mixed")):
        t = i * 30 * MS
        counts = dict(
            program=program, decodes=120, slots=128, slots_busy=128,
            chunk_tokens=0 if program == "decode" else 400, budget=512)
        if with_scopes:
            counts.update(
                moe_assignments=120 if program == "decode" else 520,
                moe_experts_touched=55 if program == "decode" else 118,
                moe_load_max=6 if program == "decode" else 40,
                state_slots_live=0,
                moe_zero_assignments=1900 if program == "decode" else 8300,
                latent_rows_read=120 * 900 * 8)
        host.append(Span(
            xplane.SPAN_PREFIX + "engine.step", t, 20 * MS, {}, "main"))
        host.append(Span("engine.tick", t + 1000, 20 * MS - 2000,
                         {k: str(v) for k, v in counts.items()}, "main"))
        ops.append(("%fusion.1 = bf16[128,6144]{1,0} fusion(...)", t + MS, 3 * MS))
        if with_scopes:
            ops.append((
                "%mla_decode.7 = (bf16[128,64,512]{2,1,0}, f32[128,64,1]{2,1,0}) "
                "custom-call(...), custom_call_target=\"tpu_custom_call\"",
                t + 4 * MS, 4 * MS))
            ops.append((
                "%moe_experts.3 = bf16[1792,4096]{1,0} custom-call(...), "
                "custom_call_target=\"tpu_custom_call\"", t + 8 * MS, 9 * MS))
            if program == "mixed":
                ops.append((
                    "%mla_chunk.2 = (bf16[64,512,256]{2,1,0}) custom-call(...), "
                    "custom_call_target=\"tpu_custom_call\"", t + 17 * MS, 2 * MS))
                ops.append((
                    "%mla_chunk_prefix.2 = (bf16[512,64,512]{2,1,0}) "
                    "custom-call(...), custom_call_target=\"tpu_custom_call\"",
                    t + 19 * MS, 1 * MS))
    return ProgramTrace(host, ops)


def context_of(manifest, pt):
    cell = manifest.cell(CELL)
    trace = pt.as_xplane()
    return dict(
        trace=trace, program_trace=pt, t0_ns=0, t1_ns=60 * MS,
        config=cell["config"], family=manifest.family(cell["config"]),
        mix=cell["mix"], peaks=peaks.chip_peaks("TPU v5 lite"), chips=1)


def test_each_reader_gives_a_number_where_its_scope_is(manifest, latent):
    ctx = context_of(manifest, stretch(True))
    values = {
        n: manifest.layer_metric(n).read(ctx) for n in NEW + SHARED}
    # decode kernel 4 ms in each tick; the chunk's two kernels 3 ms more
    assert values["mla.device_ms"] == pytest.approx((4 + 4 + 3) / 2)
    _, nbytes = latent.mla_decode_counts(
        2 * 120 * 900 * 8, 2 * 120 * 8, 64, 512, 64)
    assert values["mla_decode_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.008)
    assert 0 < values["mla_decode_roofline"] < 100
    # 12 choices x 4 layers x (120 + 120 + 400) routed rows
    assert values["moe.zero_share_pct"] == pytest.approx(
        100 * (1900 + 8300) / (12 * 4 * 640))
    # the readers PR 26 wrote run on this cell as they are
    assert values["moe.device_ms"] == pytest.approx(9.0)
    assert 0 < values["moe_experts_roofline"] < 100
    assert values["moe.load_max_over_mean"] == pytest.approx(
        6 * 4 * 16 / 120)


def test_each_reader_gives_nothing_where_its_scope_is_absent(manifest):
    """The parent commit's capture: ticks and operations, none of this
    PR's scopes or counters. No reader raises; each returns None."""
    ctx = context_of(manifest, stretch(False))
    for name in NEW + SHARED:
        assert manifest.layer_metric(name).read(ctx) is None, name
    empty = context_of(manifest, ProgramTrace([], []))
    for name in NEW + SHARED:
        assert manifest.layer_metric(name).read(empty) is None, name


# -- both step programs, compiled for a described v5e --------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU library (libtpu) is installed here")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_chip(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    from rocm_apex_tpu.ops import _pallas

    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_both_step_programs_compile_for_v5e(manifest, one_chip, as_on_chip):
    """One layer at the published widths and the cell's engine geometry
    (64 of its 512 pages: the test builds the engine's real cache on the
    host): what the chip's compiler would refuse is refused here. The
    latent pools go in and come out in place: a pool whose rows were 576
    wide was copied by every call of the kernel."""
    cell = manifest.cell(CELL)
    family = manifest.family(cell["config"])
    config = dict(cell["config"], num_layers=1)
    mix = dict(cell["mix"], engine=dict(cell["mix"]["engine"], num_pages=64))
    params = jax.eval_shape(
        lambda: family.make_params(config, 0, jnp.bfloat16))
    engine = family.build_engine(config, mix, params)
    slots, budget = 128, 512
    i32, f32 = jnp.int32, jnp.float32

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: arr(x.shape, x.dtype), tree)

    p, cache = abstract(params), abstract(engine.cache)
    rng = arr((2,), jnp.uint32)
    programs = {
        "mixed": jax.jit(engine._mixed_fn, donate_argnums=(1,)).lower(
            p, cache, arr((budget,), i32), arr((budget,), i32),
            arr((budget,), i32), arr((slots,), i32), arr((slots,), i32),
            arr((slots,), i32), arr((slots,), i32), arr((slots,), jnp.bool_),
            arr((budget,), f32), arr((slots,), f32), rng).compile(),
        "decode": jax.jit(engine._decode_fn, donate_argnums=(1,)).lower(
            p, cache, arr((slots,), i32), arr((slots,), jnp.bool_),
            arr((slots,), f32), rng).compile(),
    }
    pool_bytes = sum(
        a.size * a.dtype.itemsize for a in engine.cache.latent)
    for name, compiled in programs.items():
        text = compiled.as_text()
        assert text.count("%mla_decode.") >= 2, name
        assert "%moe_experts." in text, name
        mem = compiled.memory_analysis()
        # both pools are updated in place, and no copy of one is made
        assert mem.alias_size_in_bytes >= pool_bytes, name
        assert mem.temp_size_in_bytes < 1e9, name
    assert "%mla_chunk." in programs["mixed"].as_text()
    assert "%mla_chunk_prefix." in programs["mixed"].as_text()
