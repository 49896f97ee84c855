"""Toy-size cells for the tests that drive a run off the chip."""

import types

from benchmarks import run as bench
from benchmarks.harness import rehearsal
from benchmarks.harness.manifest import Manifest


def toy_cell(name):
    manifest = Manifest(bench.ROOT)
    cell = manifest.cell(name)
    cell["config"] = rehearsal.shrink(cell["config"])
    cell["mix"] = rehearsal.shrink(cell["mix"])
    return manifest, cell


def run_args(seed, seconds=1.0):
    return types.SimpleNamespace(
        seed=seed, seconds=seconds, trace=0, rehearse=True)
