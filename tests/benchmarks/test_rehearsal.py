"""Each kind of cell end to end at toy size on the CPU, through the
benchmark's own command: the run finishes, compares itself with the
reference and comes out correct, reports how late the generator ran, and
prints NO result line, because no number from a CPU may stand under a
metric's name."""

import json

import pytest

from benchmarks import run as bench
from benchmarks.harness.manifest import Manifest

CELLS = sorted(Manifest(bench.ROOT).workloads)


def result_lines(text):
    out = []
    for line in text.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metrics" in obj:
            out.append(obj)
    return out


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_and_prints_no_result_line(cell, trace, capsys):
    rc = bench.main([
        "--workload", cell, "--seed", str(2**31 + 77), "--seconds", "1.5",
        "--trace", str(trace), "--rehearse",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "correct=True" in out
    assert result_lines(out) == []
    assert "compiles_in_window: 0" in out
    if Manifest(bench.ROOT).cell(cell)["mix"]["kind"].startswith("serve"):
        assert "generator_lateness_ms_max" in out
        assert "requests_missing: 0" in out


def test_no_tpu_means_no_run(capsys):
    """Without `--rehearse` a process that finds no TPU leaves with a
    non-zero code before anything is built."""
    with pytest.raises(SystemExit) as exc:
        bench.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                    "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert result_lines(capsys.readouterr().out) == []
