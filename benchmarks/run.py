"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in `BENCHMARK.json`; its configuration, traffic
mix, family, kind of run and per-layer metric readers are files found by
the names given there. The last line of standard output is the result,
one JSON object; everything else (device, versions, medians, counts,
lateness, each number compared beside its limit) goes on earlier lines.

A process that finds no TPU, or fewer chips than the cell asks for,
leaves with a non-zero code and prints no result. `--rehearse` runs the
same code at toy sizes on whatever backend JAX has, to find wrong paths
before chip time is spent; it prints no result line either.
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import clock  # noqa: E402  (first: it starts the clock)

import argparse  # noqa: E402
import importlib.metadata  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import time  # noqa: E402

from benchmarks.harness import device, rehearsal, xplane  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from benchmarks.harness.peaks import chip_peaks  # noqa: E402

OUT_DIR = ROOT / ".bench_out"  # traces; listed in .gitignore


def say(*parts):
    print(*parts, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; no result line")
    return ap.parse_args(argv)


def versions():
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax"):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = "absent"
    return out


def layer_metrics(manifest, cell, context):
    """Each per-layer metric of the cell through its own reader. A
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for name in cell["per_layer"]:
        value = manifest.layer_metric(name).read(context)
        if value is not None:
            out[name] = {
                "value": float(value),
                "unit": manifest.per_layer[name]["unit"],
            }
    return out


def run_cell(manifest, cell, args, control=False, runner=None, check=True,
             free=True):
    """Build (unless ``runner`` is given), measure and check one seed.
    Returns (runner, the result object, the numbers compared)."""
    from rocm_apex_tpu.utils.compile_cache import enable_compile_cache

    if runner is None:
        say(f"compile cache at {enable_compile_cache()}")
        kind = manifest.kind(cell["mix"])
        runner = kind.Runner(cell, manifest, control=control)
        clock.mark("imports done, building")
        runner.build(args.seed)
        clock.mark("built and warmed up")
    trace_dir = None
    if args.trace:
        trace_dir = str(OUT_DIR / "trace" / cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = runner.measure(args.seed, args.seconds, trace_dir)
    setup_s = res["window_open"] - clock.T0
    peak = res.get("memory_peak_bytes") or device.memory_peak_bytes()
    for k, v in res["info"].items():
        say(f"  {k}: {v}")
    say(f"  compiles_in_window: {res['compiles_in_window']}")

    dev = dict(device.describe(), memory_peak_bytes=peak)
    result = {"attempted": res["attempted"], "failed": res["failed"]}
    if args.trace:
        context = res["context"]
        trace, t0_ns, t1_ns = xplane.load_traced_stretch(trace_dir)
        context.update(
            trace=trace, t0_ns=t0_ns, t1_ns=t1_ns,
            peaks=None if args.rehearse else chip_peaks(dev["kind"]),
        )
        dev["window_s"] = (t1_ns - t0_ns) / 1e9
        if trace.device_planes():
            dev["busy_s"] = xplane.busy_seconds(trace, t0_ns, t1_ns)
            result["breakdown"] = {
                "device_ops": xplane.top_ops(trace, t0_ns, t1_ns),
                "idle_gaps": xplane.idle_gaps(trace, t0_ns, t1_ns),
            }
        result["metrics"] = layer_metrics(manifest, cell, context)
    else:
        metrics = dict(res["end_to_end"], setup_s=setup_s)
        result["metrics"] = {
            name: {"value": float(metrics[name]),
                   "unit": manifest.end_to_end[name]["unit"]}
            for name in cell["end_to_end"]
        }
    say(f"  setup_s: {setup_s:.3f}")

    correct, comparisons = True, []
    if check:
        if free:  # the reference runs in the memory the program gives back
            runner.free()
        t = time.perf_counter()
        correct, comparisons, detail = runner.check()
        say(f"  reference check took {time.perf_counter() - t:.1f} s: {detail}")
    for c in comparisons:
        say(f"  compared {c['name']}: value {c['value']} limit {c['limit']}")
    if res["compiles_in_window"]:
        say("  NOT CORRECT: a program was compiled inside the window")
        correct = False
    result = dict(correct=bool(correct), **result, device=dev)
    return runner, result, comparisons


def main(argv=None, root=ROOT):
    args = parse(argv)
    manifest = Manifest(root)
    cell = manifest.cell(args.workload)
    if args.rehearse:
        cell["config"] = rehearsal.shrink(cell["config"])
        cell["mix"] = rehearsal.shrink(cell["mix"])
    else:
        device.require_chips(cell["chips"])
    say(f"cell {cell['name']}: config {cell['config_name']}, traffic "
        f"{cell['traffic']}, seed {args.seed}, seconds {args.seconds}, "
        f"trace {args.trace}")
    say(f"device {device.describe()} versions {versions()}")
    _, result, _ = run_cell(manifest, cell, args)
    if args.rehearse:
        say(f"rehearsal finished (correct={result['correct']}): no result "
            f"line off the chip")
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
