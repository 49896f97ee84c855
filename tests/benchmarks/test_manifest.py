"""`BENCHMARK.json` keeps to its contract, and everything it names is a
file that loads."""

import json
import pathlib

import pytest

from benchmarks.harness.manifest import Manifest, ManifestError

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


def test_manifest_breaks_none_of_its_rules(manifest):
    assert manifest.problems() == []
    assert len(json.dumps(manifest.data)) < 64 * 1024


def test_every_cell_resolves_to_its_files(manifest):
    for name in manifest.workloads:
        cell = manifest.cell(name)
        assert cell["config"]["family"]
        assert hasattr(manifest.family(cell["config"]), "model_config")
        assert hasattr(manifest.kind(cell["mix"]), "Runner")
        assert "setup_s" in cell["end_to_end"]
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


def test_every_per_layer_metric_has_a_reader_and_moves_a_metric(manifest):
    for name, metric in manifest.per_layer.items():
        assert callable(manifest.layer_metric(name).read)
        assert metric["moves"] in manifest.end_to_end
        # the cells it is read in report the metric it should move
        for cell in metric.get("workloads", []):
            assert metric["moves"] in manifest.cell(cell)["end_to_end"]


def test_layer_names_are_few_and_shared(manifest):
    layers = {m["layer"] for m in manifest.per_layer.values()}
    assert layers <= {"serving engine", "train step", "model step",
                      "parallelism", "kernels", "device"}


@pytest.mark.parametrize("key", ["source", "reduced", "assumed", "precision",
                                 "deployment", "family"])
def test_configuration_files_state_their_cut(manifest, key):
    for entry in manifest.configs.values():
        config = json.loads((ROOT / entry["file"]).read_text())
        assert key in config, f"{entry['name']} lacks {key}"
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        assert len(config["source"]) <= 200


def test_published_widths(manifest):
    gpt = manifest.cell("gpt1p3b-serve-chat")["config"]
    assert (gpt["n_embd"], gpt["n_layer"], gpt["n_head"], gpt["n_inner"],
            gpt["n_positions"], gpt["vocab_size"]) == (
        2048, 24, 16, 8192, 2048, 50257)
    bert = manifest.cell("bert345m-train-s512")["config"]
    assert (bert["hidden_size"], bert["num_hidden_layers"],
            bert["num_attention_heads"], bert["intermediate_size"],
            bert["max_position_embeddings"], bert["vocab_size"]) == (
        1024, 24, 16, 4096, 512, 29056)
    assert bert["hidden_size"] // bert["num_attention_heads"] == 64


def test_mix_files_say_why(manifest):
    for w in manifest.workloads.values():
        mix = manifest.cell(w["name"])["mix"]
        assert mix["why"] and mix["kind"]


def test_a_mix_names_its_engine_and_the_file_states_its_bytes(manifest):
    raw = json.loads((ROOT / "benchmarks/mixes/chat.json").read_text())
    assert isinstance(raw["engine"], str)
    engine = manifest.cell("gpt1p3b-serve-chat")["mix"]["engine"]
    assert engine["why"] and engine["deployment"]
    # K and V of one position: 2 x 24 layers x 2048 x 2 B
    assert engine["pool_bytes"] == (
        engine["num_pages"] * engine["page_size"] * 196608)
    assert engine["capacity"] == 2048


def test_unknown_workload_is_an_error(manifest):
    with pytest.raises(ManifestError):
        manifest.cell("no-such-cell")
