"""The cell `granite4hs-serve-chat`: its files resolve and state their
cut, its five per-layer readers give a number on a stretch that holds
their scopes and counters and nothing on one that does not (the parent
commit's capture), and the benchmark's own operation and byte counts are
held to hand arithmetic. (The cell's rehearsal end to end is
`test_rehearsal.py`'s, which runs every cell of the manifest.)"""

import json
import pathlib

import pytest

from benchmarks import run as bench
from benchmarks.harness import peaks, xplane
from benchmarks.harness.manifest import Manifest, load_module
from benchmarks.harness.program_trace import ProgramTrace, Span

ROOT = pathlib.Path(bench.ROOT)
CELL = "granite4hs-serve-chat"
NEW = ["moe.device_ms", "ssm.device_ms", "moe_experts_roofline",
       "ssm_scan_roofline", "moe.load_max_over_mean"]
CATALOG = pathlib.Path(
    "/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def hybrid():
    return load_module(ROOT / "benchmarks/layer_metrics/_hybrid.py")


def test_the_cell_and_its_metrics_are_in_the_manifest(manifest):
    assert manifest.problems() == []
    assert len(manifest.workloads) == 3
    assert all(w["chips"] == 1 for w in manifest.workloads.values())
    cell = manifest.cell(CELL)
    assert cell["traffic"] == "chat-p512-s32" and cell["chips"] == 1
    # both tails spread over seeds by more than half their bounds (PERF.md,
    # PR 26: two sets of six seeds each), so a cell reporting either would
    # not be admitted: tokens/s and set-up are what it reports, and so the
    # accepted per-layer metrics, which all move a tail, stay the other
    # cell's, and the five new ones move tokens/s
    assert set(cell["end_to_end"]) == {"serve_out_tokens_per_s", "setup_s"}
    assert set(cell["per_layer"]) == set(NEW)
    for name in NEW:
        m = manifest.per_layer[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_out_tokens_per_s"
    for name, m in manifest.per_layer.items():
        if name not in NEW:
            assert CELL not in m.get("workloads", [])


def test_the_configuration_keeps_every_published_width(manifest):
    config = manifest.cell(CELL)["config"]
    assert sorted(config["reduced"]) == [
        "layer_types", "num_hidden_layers", "num_local_experts"]
    if CATALOG.exists():
        rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
        published = next(
            r for r in rows if r["name"] == "granite-4.0-h-small")["config"]
        for key, value in published.items():
            if key not in config["reduced"]:
                assert config[key] == value, key
        assert config["published"]["layer_types"] == published["layer_types"]
        assert config["layer_types"] == published["layer_types"][:10]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["num_local_experts"] == 72
    assert (config["num_local_experts"], config["router_experts"],
            config["experts_held"]) == (36, 72, [0, 36])
    assert config["layer_types"].count("mamba") == 9
    family = manifest.family(config)
    # 9 x 461.2 M + 400.8 M + the table and the final norm, in bf16
    held = family.total_params(config)
    assert 9.9e9 < 2 * held < 9.95e9
    s = family.sizes(config)
    assert family.layer_params_count(s, "mamba") == (
        4096 * 16768 + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192  # the mixer
        + 4096 * 72 + 36 * 3 * 4096 * 768 + 3 * 4096 * 1536  # the experts
        + 2 * 4096)


def test_the_mix_and_engine_files_state_the_deployment(manifest):
    mix = manifest.cell(CELL)["mix"]
    engine = mix["engine"]
    assert (engine["num_slots"], engine["capacity"], engine["page_size"],
            engine["num_pages"], engine["prefill_token_budget"]) == (
        32, 4096, 512, 256, 512)
    # K and V of one position: 2 x 1 attention layer x 8 heads x 128 x 2 B
    assert engine["pool_bytes"] == 256 * 512 * 4096
    assert mix["kind"] == "serve_open_loop_state"
    assert (mix["prompt_tokens"]["median"], mix["prompt_tokens"]["max"],
            mix["output_tokens"]["median"]) == (512, 3072, 128)
    a = mix["arrivals"]
    assert a["rate_per_s"] == pytest.approx(0.8 * a["knee_per_s"])
    sweep = a["sweep"]
    assert len(sweep["rate_per_s"]) == len(sweep["ttft_p95_ms"]) >= 4
    knee = max(
        r for r, t in zip(sweep["rate_per_s"], sweep["ttft_p95_ms"])
        if t <= 1000)
    assert knee == a["knee_per_s"]
    limits = mix["check"]["limits"]
    assert {"state_gap_first_layer", "state_gap_worst_layer",
            "state_coarse_share_worst_layer", "conv_tail_gap_worst_layer", "kv_gap_worst_layer",
            "routing_differs_share", "gap_max", "gap_mean"} == set(limits)
    assert mix["check"]["limits_why"]


# -- the control: the state one step of precision lower ------------------------


def test_a_state_stored_in_bfloat16_is_not_correct(capsys):
    """The cell's own run at toy size with the engine's state stored in
    bfloat16 where the configuration states float32 (the sound run is
    `test_rehearsal.py`'s, which comes out correct). Every gap against
    the float32 reference stays within its limit; the grid the kept
    state lies on does not, for the engine and for the reference
    computed in bfloat16 throughout alike."""
    from _toy import run_args, toy_cell

    manifest, cell = toy_cell(CELL)
    _, result, compared = bench.run_cell(
        manifest, cell, run_args(41, 1.5), control=True)
    assert result["correct"] is False
    name = "state_coarse_share_worst_layer"
    over = {c["name"]: c["value"] for c in compared
            if not c["value"] <= c["limit"]}
    assert over == {name: 1.0}
    assert f"'lowered_reference': {{" in capsys.readouterr().out


# -- the counts, by hand ------------------------------------------------------


def test_expert_counts_by_hand(hybrid):
    # one pair: 4096 -> 1536 and 768 -> 4096, 2 operations a multiply-add
    flops, nbytes = hybrid.moe_experts_counts(1, 1, 4096, 768)
    assert flops == 2 * (4096 * 1536 + 768 * 4096) == 18_874_368
    assert nbytes == 3 * 4096 * 768 * 2 + (4096 + 1536 + 768 + 4096) * 2
    # a decode tick of the cell: 32 tokens x 10 experts, half of them held,
    # every held expert of the 10 layers touched: its weights bind
    flops, nbytes = hybrid.moe_experts_counts(1600, 360, 4096, 768)
    assert nbytes == pytest.approx(6.795e9 + 1600 * 20992, rel=1e-3)
    v5e = peaks.chip_peaks("TPU v5 lite")
    assert hybrid.least_seconds(flops, nbytes, v5e) == pytest.approx(
        nbytes / 819e9)
    # many rows an expert (400 k pairs on the same 360): the products bind
    flops, nbytes = hybrid.moe_experts_counts(400_000, 360, 4096, 768)
    assert flops / 197e12 > nbytes / 819e9
    assert hybrid.least_seconds(flops, nbytes, v5e) == flops / 197e12


def test_state_counts_by_hand(hybrid):
    # one slot, one layer: 128 x 8192 float32 read and written
    flops, nbytes = hybrid.ssm_decode_counts(1, 128, 8192)
    assert flops == 5 * 128 * 8192
    assert nbytes == 2 * 128 * 8192 * 4 + 3 * 8192 * 4 + 2 * 128 * 4
    # 32 live slots x 9 layers: 2.4 GB, bound by memory
    flops, nbytes = hybrid.ssm_decode_counts(32 * 9, 128, 8192)
    assert nbytes == pytest.approx(2.44e9, rel=0.01)
    v5e = peaks.chip_peaks("TPU v5 lite")
    assert hybrid.least_seconds(flops, nbytes, v5e) == nbytes / 819e9


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
            program=program, decodes=20, slots=32, slots_busy=24,
            chunk_tokens=0 if program == "decode" else 400, budget=512)
        if with_scopes:
            counts.update(
                moe_assignments=1000 if program == "decode" else 21000,
                moe_experts_touched=355 if program == "decode" else 715,
                moe_load_max=11 if program == "decode" else 700,
                state_slots_live=20 if program == "decode" else 23)
        host.append(Span(
            xplane.SPAN_PREFIX + "engine.step", t, 20 * MS, {}, "main"))
        host.append(Span("engine.tick", t + 1000, 20 * MS - 2000,
                         {k: str(v) for k, v in counts.items()}, "main"))
        ops.append(("%fusion.1 = bf16[32,4096]{1,0} fusion(...)", t + MS, 3 * MS))
        if with_scopes:
            scale = 1 if program == "decode" else 2
            ops.append((
                "%moe_experts.7 = bf16[896,1536]{1,0} custom-call(...), "
                "custom_call_target=\"tpu_custom_call\"", t + 4 * MS,
                scale * 9 * MS))
            ops.append((
                "%ssm_scan.3 = (f32[32,128,8192]{2,1,0}) custom-call(...), "
                "custom_call_target=\"tpu_custom_call\"", t + 24 * MS - MS * 1,
                3 * MS))
    return ProgramTrace(host, ops)


def context_of(manifest, pt):
    cell = manifest.cell(CELL)
    trace = pt.as_xplane()
    return dict(
        trace=trace, program_trace=pt, t0_ns=0, t1_ns=60 * MS,
        config=cell["config"], family=manifest.family(cell["config"]),
        mix=cell["mix"], peaks=peaks.chip_peaks("TPU v5 lite"), chips=1)


def test_each_reader_gives_a_number_where_its_scope_is(manifest, hybrid):
    ctx = context_of(manifest, stretch(True))
    values = {n: manifest.layer_metric(n).read(ctx) for n in NEW}
    assert values["moe.device_ms"] == pytest.approx((9 + 18) / 2)
    assert values["ssm.device_ms"] == pytest.approx(3.0)
    flops, nbytes = hybrid.moe_experts_counts(22000, 1070, 4096, 768)
    least = max(flops / 197e12, nbytes / 819e9)
    assert values["moe_experts_roofline"] == pytest.approx(
        100 * least / 0.027)
    _, state_bytes = hybrid.ssm_decode_counts(40 * 9, 128, 8192)
    assert values["ssm_scan_roofline"] == pytest.approx(
        100 * (state_bytes / 819e9) / 0.006)
    assert 0 < values["ssm_scan_roofline"] < 100
    # the decode tick alone: 11 on the fullest of 10 x 36 held experts
    assert values["moe.load_max_over_mean"] == pytest.approx(
        11 * 10 * 36 / 1000)


def test_each_reader_gives_nothing_where_its_scope_is_absent(manifest):
    """The parent commit's capture: ticks and operations, none of this
    PR's scopes or counters. No reader raises; each returns None."""
    ctx = context_of(manifest, stretch(False))
    for name in NEW:
        assert manifest.layer_metric(name).read(ctx) is None, name
    empty = context_of(manifest, ProgramTrace([], []))
    for name in NEW:
        assert manifest.layer_metric(name).read(empty) is None, name
