"""Look at one profiler trace by hand: its planes and lines, the device
operations that took most time, and the benchmark's own spans.

    python3 benchmarks/trace_summary.py <trace dir> [--fixture out.json.gz --programs 3]

`--fixture` writes the trace, cut to `--programs` whole executions of the
longest-running program from the middle of the trace (ticks or steps,
with the host spans and gaps between them), as the gzipped JSON that
`harness/xplane.load_json` reads: how the recorded fixtures under
`harness/fixtures/` were made.
"""

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks.harness import xplane  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--fixture", default="")
    ap.add_argument("--programs", type=int, default=3)
    args = ap.parse_args(argv)

    from jax.profiler import ProfileData

    path = xplane.find_xplane(args.trace_dir)
    print(f"{path}: {pathlib.Path(path).stat().st_size} bytes")
    for plane in ProfileData.from_file(path).planes:
        lines = [(line.name, sum(1 for _ in line.events)) for line in plane.lines]
        print(f"plane {plane.name!r}: {lines[:12]}{' ...' if len(lines) > 12 else ''}")
    trace = xplane.load(path)
    spans = trace.host_spans()
    names = sorted({n for n, _, _ in spans})
    print(f"benchmark spans: {len(spans)} of {names}")
    if not trace.device_planes():
        print("no device plane")
        return 0
    t0, t1 = xplane.window_of(trace)
    print(f"device ops span {(t1 - t0) / 1e9:.3f} s; busy "
          f"{xplane.busy_seconds(trace, t0, t1):.3f} s")
    for name, secs in xplane.top_ops(trace, t0, t1, n=25):
        print(f"  {secs * 1e3:10.3f} ms  {name}")
    if spans:
        for name, secs in xplane.idle_gaps(trace, spans[0][1], spans[-1][1] + spans[-1][2]):
            print(f"  idle {secs * 1e3:10.3f} ms under {name}")
    modules = {}
    for name, s, d in trace.modules(trace.device_planes()[0]):
        modules.setdefault(name.split("(")[0], []).append((s, d))
    for name, runs in modules.items():
        print(f"  program {name}: {len(runs)} runs, "
              f"{sum(d for _, d in runs) / len(runs) / 1e6:.3f} ms each")
    if args.fixture:
        runs = max(modules.values(), key=lambda r: sum(d for _, d in r))
        if len(runs) < args.programs + 2:
            raise SystemExit(f"only {len(runs)} runs of the main program")
        mid = len(runs) // 2
        a, b = runs[mid][0] - 1000, runs[mid + args.programs][0] - 1000
        cut = xplane.clip(trace, a, b)
        xplane.save_json(cut, args.fixture)
        print(f"fixture {args.fixture}: "
              f"{pathlib.Path(args.fixture).stat().st_size} bytes, "
              f"{sum(len(e) for l in cut.planes.values() for e in l.values())} events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
