"""The reduction from a profiler trace to busy and idle time, kernel time
and labelled idle gaps: on hand-made events where the answer is known,
and on the traces recorded on the chip that are kept beside the harness."""

import pathlib

import pytest

from benchmarks.harness import xplane
from benchmarks.harness.xplane import Trace

FIXTURES = pathlib.Path(xplane.__file__).parent / "fixtures"

DEV = "/device:TPU:0"
# an operation's name in a TPU trace is its whole HLO text
PAGED = (
    "%self_attention.134 = (bf16[2048,16,128]{2,1,0:T(8,128)(2,1)}, "
    "f32[2048,16,1]{2,1,0:T(8,128)}) custom-call(s32[128,32]{1,0:T(8,128)S(1)} "
    "%copy-done.176, s32[128]{0:T(128)S(1)} %copy-done.547), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
)


def toy_trace():
    ops = [
        ("fusion.1", 100, 50),       # 100-150
        ("all-reduce.1", 140, 60),   # 140-200, 10 hidden behind fusion.1
        ("fusion.2", 300, 100),      # 300-400
        (PAGED, 400, 100),  # 400-500
    ]
    host = {"main": [
        ("bench/engine.step", 90, 120),    # 90-210
        ("bench/generator", 210, 80),      # 210-290
        ("bench/engine.step", 290, 220),   # 290-510
        ("PjitFunction(f)", 0, 600),
    ]}
    return Trace({DEV: {xplane.OPS_LINE: ops, xplane.MODULES_LINE: []},
                  xplane.HOST_PLANE: host})


def test_merge_intervals():
    assert xplane.merge_intervals([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == [
        [1, 4], [5, 8]]


def test_busy_is_the_union_of_op_intervals():
    t = toy_trace()
    assert xplane.window_of(t) == (100, 500)
    # 100-200 and 300-500
    assert xplane.busy_seconds(t, 100, 500) == pytest.approx(300e-9)
    assert xplane.busy_seconds(t, 0, 600) == pytest.approx(300e-9)
    assert xplane.busy_seconds(t, 150, 350) == pytest.approx(100e-9)


def test_only_the_benchmarks_spans_are_host_spans():
    names = [n for n, _, _ in toy_trace().host_spans()]
    assert names == ["engine.step", "generator", "engine.step"]


def test_short_names_sum_layers_under_one_name():
    assert xplane.short_name(PAGED) == (
        "self_attention custom-call bf16[2048,16,128]")
    assert xplane.short_name(
        "%fusion.8 = bf16[29056,1024]{1,0:T(8,128)(2,1)} fusion(bf16[8192,1024]"
        "{1,0} %custom-call.446), kind=kOutput") == "fusion fusion bf16[29056,1024]"
    assert xplane.short_name("fusion.2") == "fusion"
    top = dict(xplane.top_ops(toy_trace(), 0, 600))
    assert top["fusion"] == pytest.approx(150e-9)  # fusion.1 + fusion.2


def test_kernels_are_told_by_their_hlo_text():
    from benchmarks.harness.manifest import Manifest
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[2]
    reader = Manifest(root).layer_metric("decode_paged_roofline")
    found = xplane.kernel_ops(toy_trace(), reader.is_paged_kernel)
    assert [e[2] for e in found] == [100]
    assert xplane.kernel_ops(toy_trace(), lambda hlo: False) == []


def test_idle_gaps_are_labelled_by_the_span_that_covers_them():
    gaps = dict(xplane.idle_gaps(toy_trace(), 90, 510))
    # 90-100, 200-210 and 290-300, 500-510 under engine.step;
    # 210-290 under generator
    assert gaps["engine.step"] == pytest.approx(40e-9)
    assert gaps["generator"] == pytest.approx(80e-9)
    assert sum(gaps.values()) == pytest.approx((420 - 300) * 1e-9)


def test_exposed_collective_time():
    exposed = xplane.exposed_seconds(
        toy_trace(), lambda n: n.startswith("all-reduce"), 0, 600)
    assert exposed == pytest.approx(50e-9)  # 150-200


def test_clip_and_json_round_trip(tmp_path):
    cut = xplane.clip(toy_trace(), 280, 520)
    assert [e[0] for e in cut.ops(DEV)] == ["fusion.2", PAGED]
    path = tmp_path / "t.json.gz"
    xplane.save_json(cut, path)
    assert xplane.load_json(path).planes == cut.planes


@pytest.mark.parametrize("name,outer", [
    ("serve_ticks.json.gz", "engine.step"),
    ("train_steps.json.gz", "step_dispatch"),
])
def test_recorded_chip_trace(name, outer):
    """Traces recorded on a TPU v5e during PR 23, cut to a few ticks or
    steps by `benchmarks/trace_summary.py`."""
    trace = xplane.load_json(FIXTURES / name)
    assert trace.device_planes() == [DEV]
    spans = [s for s in trace.host_spans() if s[0] == outer]
    assert spans
    t0, t1 = xplane.window_of(trace)
    busy = xplane.busy_seconds(trace, t0, t1)
    assert 0 < busy <= (t1 - t0) / 1e9
    top = xplane.top_ops(trace, t0, t1, n=10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    gaps = xplane.idle_gaps(trace, t0, t1)
    assert sum(s for _, s in gaps) == pytest.approx(
        (t1 - t0) / 1e9 - busy, rel=1e-6)
