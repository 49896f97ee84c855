"""Readings that the limits, the rate and the bounds are set from: many
seeds of one cell in ONE process (set-up is paid once), with the sound
program or with the control in its place, and for a serving cell a sweep
of offered rates.

    python3 benchmarks/readings.py --workload <name> --seeds 1,2,3 --seconds 12
        [--control 1] [--rates 4,8,12 --check 0] [--out file.jsonl]

Not part of a benchmark run: the driver never calls this. Each seed (and
rate) prints one JSON line with the end-to-end numbers, the run's counts
and each number compared beside its limit.
"""

import argparse
import json
import pathlib
import sys
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks import run as bench  # noqa: E402
from benchmarks.harness import device, rehearsal  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="the cell's control in the program's place: a "
                         "train cell's reference in bfloat16 throughout, "
                         "a serving cell's engine with int8 K/V")
    ap.add_argument("--check", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    manifest = Manifest(bench.ROOT)
    cell = manifest.cell(args.workload)
    if args.rehearse:
        cell["config"] = rehearsal.shrink(cell["config"])
        cell["mix"] = rehearsal.shrink(cell["mix"])
    else:
        device.require_chips(cell["chips"])
    seeds = [int(x) for x in args.seeds.split(",")]
    rates = [float(x) for x in args.rates.split(",")] if args.rates else [None]
    serving = cell["mix"]["kind"].startswith("serve")
    runner, lines = None, []
    if serving and args.check:
        # the engine stays alive between seeds, so the reference shares
        # the chip with it: one sequence at a time
        cell["mix"]["check"]["block"] = 2
    for rate in rates:
        if rate is not None:
            cell["mix"]["arrivals"]["rate_per_s"] = rate
        for seed in seeds:
            one = types.SimpleNamespace(
                seed=seed, seconds=args.seconds, trace=0,
                rehearse=args.rehearse)
            if runner is not None:
                runner.reseed(seed)
            runner, result, compared = bench.run_cell(
                manifest, cell, one, control=bool(args.control),
                runner=runner, check=bool(args.check), free=not serving)
            line = {
                "workload": args.workload, "seed": seed, "rate": rate,
                "control": args.control, "correct": result["correct"],
                "metrics": {k: v["value"] for k, v in result["metrics"].items()},
                "attempted": result["attempted"], "failed": result["failed"],
                "compared": compared,
                "memory_peak_bytes": result["device"]["memory_peak_bytes"],
            }
            print("READING " + json.dumps(line), flush=True)
            lines.append(line)
    if args.out:
        with open(args.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
