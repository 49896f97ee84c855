"""A 25th thing never needs an edit to a file that exists: a throw-away
family (with its own key names and counts), configuration, engine
geometry, traffic mix and per-layer metric are added to a temporary copy
of the benchmark as NEW files plus manifest entries, and the harness runs
them at toy size."""

import json
import shutil

from benchmarks import run as bench


TOY_FAMILY = '''"""A throw-away decoder family: the gpt2 family's program and reference
under another source's key names, with its own sizes and counts."""

from benchmarks.families import gpt2

CAUSAL = True
_KEYS = {"n_embd": "d_model", "n_layer": "depth", "n_head": "n_heads",
         "n_inner": "d_ff", "n_positions": "max_seq", "vocab_size": "vocab"}


def _as_gpt2(config):
    return dict(config, **{k: config[v] for k, v in _KEYS.items()})


def _through(name):
    def call(config, *args, **kwargs):
        return getattr(gpt2, name)(_as_gpt2(config), *args, **kwargs)
    return call


for _name in ("sizes", "total_params", "train_flops_per_token", "model_config",
              "serve_setup", "reference_gaps", "reference_kv_gaps"):
    globals()[_name] = _through(_name)
kv_snapshot_program, kv_snapshot = gpt2.kv_snapshot_program, gpt2.kv_snapshot


def reseed(engine, config, seed):
    gpt2.reseed(engine, _as_gpt2(config), seed)
'''


def test_config_mix_and_metric_are_added_as_files_only(tmp_path, capsys):
    root = tmp_path / "copy"
    root.mkdir()
    shutil.copytree(bench.ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        p: p.read_bytes() for p in (root / "benchmarks").rglob("*") if p.is_file()
    }

    # a family of its own: another source's key names, its own counts
    (root / "benchmarks/families/toy_decoder.py").write_text(TOY_FAMILY)
    (root / "benchmarks/configs/toy-gpt.json").write_text(json.dumps({
        "family": "toy_decoder", "source": "a test", "deployment": "none",
        "d_model": 32, "depth": 1, "n_heads": 2, "d_ff": 64,
        "max_seq": 64, "vocab": 101, "layer_norm_epsilon": 1e-5,
        "reduced": [], "assumed": {}, "precision": {},
    }))
    (root / "benchmarks/engines/toy-engine.json").write_text(json.dumps({
        "why": "a test", "deployment": "none",
        "num_slots": 4, "capacity": 32, "page_size": 8,
        "num_pages": 16, "prefill_token_budget": 8,
    }))
    (root / "benchmarks/mixes/toy-burst.json").write_text(json.dumps({
        "kind": "serve_open_loop", "why": "a test",
        "arrivals": {"process": "poisson", "rate_per_s": 6.0},
        "ramp_s": 0.5, "schedule_seed": 1,
        "prompt_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2, "max": 20},
        "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 6, "max": 10},
        "engine": "toy-engine",
        "drain_limit_s": 30.0, "trace_seconds": 0.5,
        "check": {"sample_requests": 2, "limits": {
            "gap_max": 1.0, "gap_mean": 1.0, "kv_gap_worst_layer": 0.1}},
    }))
    (root / "benchmarks/layer_metrics/toy.ticks.py").write_text(
        '"""Ticks the run made: a throw-away reader."""\n\n\n'
        "def read(context):\n    return len(context['ticks'])\n"
    )
    manifest = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "toy-gpt", "source": "a test",
        "file": "benchmarks/configs/toy-gpt.json", "reduced": [], "why": "a test"})
    manifest["workloads"].append({
        "name": "toy-gpt.burst", "config": "toy-gpt", "traffic": "toy-burst",
        "chips": 1, "why": "a test"})
    for m in manifest["end_to_end"]:
        if "workloads" in m and m["name"] in (
                "ttft_p95_ms", "tpot_p95_ms", "serve_out_tokens_per_s"):
            m["workloads"].append("toy-gpt.burst")
    manifest["per_layer"].append({
        "name": "toy.ticks", "unit": "ticks", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_out_tokens_per_s", "workloads": ["toy-gpt.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    from benchmarks.harness.manifest import Manifest

    m = Manifest(root)
    assert m.problems() == []
    assert m.cell("toy-gpt.burst")["per_layer"] == ["toy.ticks"]
    rc = bench.main(
        ["--workload", "toy-gpt.burst", "--seed", "5", "--seconds", "1",
         "--trace", "1", "--rehearse"], root=root)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "correct=True" in out
    # the new reader was found by its name and read something
    context_metric = m.layer_metric("toy.ticks").read({"ticks": [1, 2, 3]})
    assert context_metric == 3
    # the new family's own counts are what a reader gets
    family = m.family(m.cell("toy-gpt.burst")["config"])
    assert family.sizes(m.cell("toy-gpt.burst")["config"])["hidden"] == 32
    assert family.train_flops_per_token(
        m.cell("toy-gpt.burst")["config"], 64) > 0
    # and no file that was there has changed
    for p, content in before.items():
        assert p.read_bytes() == content
