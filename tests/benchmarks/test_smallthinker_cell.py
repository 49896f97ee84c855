"""The cell `smallthinker-serve-longmix-sat`: its files resolve and state
their cut, the parameter count is re-reckoned, its control comes out not
correct at toy size, a wrong structure is caught by the limits, its
readers give a number on a stretch that holds their scopes and counters
and nothing on one that does not (the parent commit's capture), the
benchmark's own operation and byte counts are held to hand arithmetic,
the cell rehearses on the CPU, and both step programs compile for a
described v5e at the published widths."""

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
CELL = "smallthinker-serve-longmix-sat"
CONFIG = "smallthinker-21b-a3b"
NEW = ["attn_paged.device_ms", "attn_window_decode_roofline",
       "attn_global_decode_roofline", "kv.window_rows_dropped_pct",
       "engine.window_pages_used_pct"]
SHARED = ["moe.device_ms", "moe_experts_roofline", "moe.load_max_over_mean"]
CATALOG = pathlib.Path(
    "/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def windowed():
    return load_module(ROOT / "benchmarks/layer_metrics/_windowed.py")


def test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest(
        manifest):
    assert manifest.problems() == []
    assert CELL in manifest.workloads and CONFIG in manifest.configs
    cell = manifest.cell(CELL)
    assert cell["traffic"] == "longmix-p4k-o512-sat" and cell["chips"] == 1
    assert cell["config_name"] == CONFIG
    # above the knee the tails say how long the run was
    assert set(cell["end_to_end"]) == {"serve_out_tokens_per_s", "setup_s"}
    assert set(cell["per_layer"]) == set(NEW)
    for metric in NEW:
        entry = manifest.per_layer[metric]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_out_tokens_per_s"
    # PR 26's three `moe` readers run on this cell as they are (below,
    # and on the chip: PERF.md section 5) but are NOT listed for it:
    # `test_longcat_cell.py` pins their lists to two cells, and this PR
    # may edit no file the benchmark has
    for metric in SHARED:
        assert CELL not in manifest.per_layer[metric]["workloads"]
    assert manifest.end_to_end["serve_out_tokens_per_s"]["workloads"][-1] == CELL
    # the new entries stand at the end of their lists
    assert manifest.data["configs"][-1]["name"] == CONFIG
    assert manifest.data["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest.data["per_layer"][-5:]] == NEW


def test_the_configuration_keeps_every_published_width(manifest):
    config = manifest.cell(CELL)["config"]
    cut = ["num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert sorted(config["reduced"]) == cut
    entry = manifest.configs[CONFIG]
    assert sorted(entry["reduced"]) == cut
    if CATALOG.exists():
        rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
        row = next(
            r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key == "num_hidden_layers":
                assert config["published"][key] == value == 52
            elif key in cut:  # the lists cut to their first eight
                assert config[key] == value[:8] and len(value) == 52
                assert value == value[:4] * 13
            else:
                assert config[key] == value, key
    assert (config["num_hidden_layers"], config["experts_held"],
            config["vocab_size"]) == (8, [0, 64], 151936)
    assert "ALL 64 experts" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    for key in ("router_input", "routing", "experts", "rotary", "window",
                "attention", "weights", "layouts"):
        assert config["assumed"][key]
    family = manifest.family(config)
    assert family.layer_types(config) == (
        "global", "window", "window", "window") * 2
    s = family.sizes(config)
    assert family.layer_params_count(s) == (
        2560 * 3584 * 2 + 2560 * 512 * 2 + 5120 + 163_840
        + 64 * 3 * 2560 * 768) == 398_627_840
    held = family.total_params(config)
    assert held == config["parameters_held"] == 3_966_937_600
    assert held == 8 * 398_627_840 + 2 * 388_956_160 + 2560
    assert 7.9e9 < 2 * held < 8.0e9
    # the published 52 layers: 21.5 B
    whole = dict(
        config, num_hidden_layers=52, rope_layout=[0, 1, 1, 1] * 13,
        sliding_window_layout=[0, 1, 1, 1] * 13)
    assert family.total_params(whole) == 21_506_562_560
    # the readers of the expert layer's metrics find their sizes
    assert (s["hidden"], s["expert_width"], s["vocab"], s["layers"],
            s["held_hi"] - s["held_lo"], s["heads"], s["kv_heads"],
            s["head_dim"], s["window"]) == (
        2560, 768, 151936, 8, 64, 28, 4, 128, 4096)
    # layouts that disagree are refused, not half built
    with pytest.raises(ValueError, match="agree"):
        family.layer_types(dict(config, rope_layout=[0] * 8))


def test_the_mix_and_engine_files_state_the_deployment(manifest):
    mix = manifest.cell(CELL)["mix"]
    engine = mix["engine"]
    assert (engine["capacity"], engine["page_size"],
            engine["prefill_token_budget"]) == (16384, 512, 512)
    # the window group is sized under its worst case: pages come back
    from rocm_apex_tpu.inference.paging import window_pages_per_slot

    worst = window_pages_per_slot(4096, 512, 512, 16384)
    assert worst == 10
    assert engine["window_pages"] <= engine["num_slots"] * worst
    # a position costs 2,048 B a layer; 2 global and 6 window layers
    assert engine["pool_bytes"] == 512 * 2048 * (
        2 * engine["num_pages"] + 6 * engine["window_pages"])
    assert mix["kind"] == "serve_open_loop_windowed"
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert (p["median"], p["sigma"], p["min"], p["max"]) == (
        4096, 0.8, 256, 12288)
    assert (o["median"], o["sigma"], o["min"], o["max"]) == (
        512, 0.6, 64, 2048)
    a = mix["arrivals"]
    assert a["rate_per_s"] == pytest.approx(1.2 * a["knee_per_s"], rel=0.02)
    sweep = a["sweep"]
    assert len(sweep["rate_per_s"]) == len(
        sweep["serve_out_tokens_per_s"]) >= 4
    assert all(f == 0 for f in sweep["failed"])
    limits = mix["check"]["limits"]
    assert {"kv_gap_first_layer", "kv_gap_worst_layer",
            "routing_differs_share", "gap_max", "gap_mean"} == set(
        limits) == set(mix["rehearsal"]["check"]["limits"])
    for name in list(limits) + ["readings", "control", "wrong_structure"]:
        assert mix["check"]["limits_why"][name], name


# -- the cell at toy size: sound, under the control, built wrong ---------------


def test_the_cell_rehearses_on_the_cpu():
    """`--rehearse`: the cell's own run at toy size, every phase; it
    comes out correct, and the slot whose K and V are compared has
    decoded past its window."""
    from _toy import run_args, toy_cell

    manifest, cell = toy_cell(CELL)
    runner, result, compared = bench.run_cell(manifest, cell, run_args(35, 1.5))
    assert result["correct"] is True and result["failed"] == 0
    assert {c["name"] for c in compared} == set(
        cell["mix"]["check"]["limits"])
    assert all(c["value"] <= c["limit"] for c in compared)


def test_kv_rounded_to_float8_is_not_correct():
    """The cell's own run at toy size, compared with the reference's own
    K and V rounded to float8 (e4m3) in the slot's place, where the
    configuration states bfloat16. The served tokens stay within their
    limits; the rows do not."""
    from _toy import run_args, toy_cell

    manifest, cell = toy_cell(CELL)
    _, result, compared = bench.run_cell(
        manifest, cell, run_args(41, 1.5), control=True)
    assert result["correct"] is False
    over = {c["name"] for c in compared if not c["value"] <= c["limit"]}
    assert over == {"kv_gap_first_layer", "kv_gap_worst_layer"}
    values = {c["name"]: c["value"] for c in compared}
    assert 0.015 < values["kv_gap_first_layer"] < 0.04


# (what is built wrong, the limit that must catch it)
WRONG = {
    "window_of_19_keys": (dict(window=19), "kv_gap_worst_layer"),
    "window_of_21_keys": (dict(window=21), "kv_gap_worst_layer"),
    "rotary_on_a_global_layer": (dict(rope_global=True), "kv_gap_first_layer"),
}


@pytest.mark.parametrize("case", sorted(WRONG))
def test_the_limits_catch_a_wrong_structure(manifest, case):
    """At toy size (a window of 20 keys): the K and V a program built
    wrong on purpose would cache (a reference so built stands for it:
    the served program agrees with the sound reference to 2e-4,
    `tests/L0/test_windowed_serving.py`), held against the sound
    reference's as `kinds/serve_open_loop_windowed.py` holds a slot's.
    A window one key short or long leaves layers 0 and 1 as they are (no
    window layer lies before their K and V) and moves every later layer
    by 0.03-0.07, three times the rehearsal's limit and ten times what a
    sound run reads there; rotary on a global layer turns layer 0's K."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import rehearsal, weights

    wrong, limit_name = WRONG[case]
    cell = manifest.cell(CELL)
    config = rehearsal.shrink(cell["config"])
    limits = rehearsal.shrink(cell["mix"])["check"]["limits"]
    family = manifest.family(config)
    _, dims, scalars, kinds = family._static(config)
    key = weights.seed_key(5)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 257, size=(1, 96)), jnp.int32)

    def kept(wrong):
        x = family._ref_embed(key, tokens, dims, jnp.bfloat16)
        out = []
        for i, kind in enumerate(kinds):
            x, rows = family._ref_layer(
                key, i, x, dims, kind, scalars, jnp.bfloat16, full=True,
                window=wrong.get("window") if kind == "window" else None,
                rope=True if wrong.get("rope_global") else None)
            out.append(rows)
        return out

    sound, built_wrong = kept({}), kept(wrong)
    gaps = [
        max(float(family._rel(w[n], s[n])) for n in ("k", "v"))
        for w, s in zip(built_wrong, sound)]
    first = [kinds.index(k) for k in ("global", "window")]
    values = dict(
        kv_gap_first_layer=max(gaps[i] for i in first),
        kv_gap_worst_layer=max(gaps))
    assert values[limit_name] > 1.5 * limits[limit_name], (values, gaps)
    if "window" in wrong:  # nothing before layer 2 has seen a window
        assert gaps[0] == gaps[1] == 0.0 and min(gaps[2:]) > 0.03


# -- the counts, by hand -------------------------------------------------------


def test_attention_decode_counts_by_hand(windowed):
    # one position read by one query row: 28 heads score 128 values and
    # weigh 128, 2 operations a multiply-add; K and V of 4 heads of 128
    # in bfloat16; the heads' queries in and outputs out
    flops, nbytes = windowed.attn_decode_counts(1, 1, 28, 4, 128)
    assert flops == 4 * 28 * 128 == 14_336
    assert nbytes == 2048 + 2 * 28 * 128 * 2
    # a decode tick of the cell: 48 slots at 5,600 positions; a window
    # layer reads 4,096 of them: 7 operations a byte, far under the
    # chip's ridge of 240: memory binds
    rows = 48 * 4096 * 6
    flops, nbytes = windowed.attn_decode_counts(rows, 48 * 6, 28, 4, 128)
    assert flops / (rows * 2048) == 7.0
    v5e = peaks.chip_peaks("TPU v5 lite")
    hybrid = load_module(ROOT / "benchmarks/layer_metrics/_hybrid.py")
    assert hybrid.least_seconds(flops, nbytes, v5e) == nbytes / 819e9
    assert nbytes / 819e9 == pytest.approx(2.95e-3, rel=0.02)
    # the two counters give each group's rows: 2 global layers read all
    # 5,600, 6 window layers 4,096 each
    counts = [dict(kv_rows_read=48 * (2 * 5600 + 6 * 4096),
                   kv_rows_cached=48 * 8 * 5600)]
    assert windowed.group_rows(counts, 2, 6) == (
        48 * 2 * 5600, 48 * 6 * 4096)


# -- the readers, on hand-made stretches ---------------------------------------

MS = 1_000_000


def stretch(with_scopes):
    """Two ticks of 30 ms, one decode and one mixed, with the benchmark's
    `engine.step` spans around them; operations named after the scopes
    and the window group's counters only ``with_scopes``."""
    host, ops = [], []
    for i, program in enumerate(("decode", "mixed")):
        t = i * 40 * MS
        counts = dict(
            program=program, decodes=40, slots=48, slots_busy=48,
            chunk_tokens=0 if program == "decode" else 512, budget=512,
            pages_used=800, pages_total=1216)
        if with_scopes:
            counts.update(
                moe_assignments=40 * 6 * 8 if program == "decode" else 552 * 48,
                moe_experts_touched=400 if program == "decode" else 512,
                moe_load_max=9 if program == "decode" else 80,
                state_slots_live=0, moe_zero_assignments=0,
                latent_rows_read=0,
                kv_rows_read=40 * (2 * 5600 + 6 * 4096),
                kv_rows_cached=40 * 8 * 5600,
                window_pages_used=350 + 20 * i, window_pages_total=448,
                window_pages_freed=3)
        host.append(Span(
            xplane.SPAN_PREFIX + "engine.step", t, 30 * MS, {}, "main"))
        host.append(Span("engine.tick", t + 1000, 30 * MS - 2000,
                         {k: str(v) for k, v in counts.items()}, "main"))
        ops.append(("%fusion.1 = bf16[48,2560]{1,0} fusion(...)", t + MS, 3 * MS))
        if with_scopes:
            call = "custom-call(...), custom_call_target=\"tpu_custom_call\""
            ops.append((f"%attn_global_decode.7 = (bf16[48,4,1,16,128]) {call}",
                        t + 4 * MS, 2 * MS))
            ops.append((f"%attn_window_decode.9 = (bf16[48,4,1,16,128]) {call}",
                        t + 6 * MS, 4 * MS))
            ops.append((f"%moe_experts.3 = bf16[1792,1536]{{1,0}} {call}",
                        t + 10 * MS, 9 * MS))
            if program == "mixed":
                ops.append((f"%attn_global_chunk.2 = (bf16[28,512,128]) {call}",
                            t + 20 * MS, 2 * MS))
                ops.append((f"%attn_window_chunk.4 = (bf16[28,512,128]) {call}",
                            t + 22 * MS, 3 * MS))
    return ProgramTrace(host, ops)


def context_of(manifest, pt):
    cell = manifest.cell(CELL)
    trace = pt.as_xplane()
    return dict(
        trace=trace, program_trace=pt, t0_ns=0, t1_ns=80 * MS,
        config=cell["config"], family=manifest.family(cell["config"]),
        mix=cell["mix"], peaks=peaks.chip_peaks("TPU v5 lite"), chips=1)


def test_each_reader_gives_a_number_where_its_scope_is(manifest, windowed):
    ctx = context_of(manifest, stretch(True))
    values = {
        n: manifest.layer_metric(n).read(ctx) for n in NEW + SHARED}
    # decode kernels 2 + 4 ms in each tick; the chunk's 2 + 3 ms more
    assert values["attn_paged.device_ms"] == pytest.approx((6 + 6 + 5) / 2)
    _, nbytes = windowed.attn_decode_counts(
        2 * 40 * 6 * 4096, 2 * 40 * 6, 28, 4, 128)
    assert values["attn_window_decode_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.008)
    _, nbytes = windowed.attn_decode_counts(
        2 * 40 * 2 * 5600, 2 * 40 * 2, 28, 4, 128)
    assert values["attn_global_decode_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.004)
    assert 0 < values["attn_window_decode_roofline"] < 100
    assert 0 < values["attn_global_decode_roofline"] < 100
    assert values["kv.window_rows_dropped_pct"] == pytest.approx(
        100 * (1 - (2 * 5600 + 6 * 4096) / (8 * 5600)))
    assert values["engine.window_pages_used_pct"] == pytest.approx(
        100 * (350 + 370) / 2 / 448)
    # the readers PR 26 wrote run on this cell as they are: held = all
    assert values["moe.device_ms"] == pytest.approx(9.0)
    assert 0 < values["moe_experts_roofline"] < 100
    assert values["moe.load_max_over_mean"] == pytest.approx(
        9 * 8 * 64 / (40 * 6 * 8))


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
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # the library's lock is another process's
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
    """One global and one window layer at the published widths and the
    cell's engine geometry (48 of its pages a group: the test builds the
    engine's real cache on the host): what the chip's compiler would
    refuse is refused here. The pools of both groups go in and come out
    in place, and no copy of one is made."""
    cell = manifest.cell(CELL)
    family = manifest.family(cell["config"])
    config = dict(
        cell["config"], num_hidden_layers=2, rope_layout=[0, 1],
        sliding_window_layout=[0, 1])
    geometry = dict(cell["mix"]["engine"], num_pages=48, window_pages=48)
    mix = dict(cell["mix"], engine=geometry)
    params = jax.eval_shape(
        lambda: family.make_params(config, 0, jnp.bfloat16))
    engine = family.build_engine(config, mix, params)
    slots, budget = int(geometry["num_slots"]), 512
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
    c = engine.cache
    pool_bytes = sum(
        a.size * a.dtype.itemsize
        for a in c.k + c.v + c.window_k + c.window_v)
    for name, compiled in programs.items():
        text = compiled.as_text()
        assert "%attn_global_decode." in text, name
        assert "%attn_window_decode." in text, name
        assert "%moe_experts." in text, name
        mem = compiled.memory_analysis()
        # every pool is updated in place, and no copy of one is made
        assert mem.alias_size_in_bytes >= pool_bytes, name
        assert mem.temp_size_in_bytes < 2.5e9, name
    assert "%attn_global_chunk." in programs["mixed"].as_text()
    assert "%attn_window_chunk." in programs["mixed"].as_text()
