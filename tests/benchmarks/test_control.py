"""The control of each cell comes out NOT correct, at a size a test run
can hold, held to the cell's own limits:

* training (bf16 compute, fp32 masters): the plain reference in bfloat16
  throughout, so without fp32 masters, stands in the program's place;
  three steps at toy width;
* serving (bf16 weights and K/V): the program's own lower-precision
  path, the engine with its K/V cache stored as int8, run as the cell
  runs: narrow, but with the published heads of 128 and the
  deployment's pages of 512, which are what an int8 scale covers.
"""

from _toy import run_args, toy_cell

from benchmarks import run as bench


def not_within(compared):
    return {c["name"] for c in compared if not c["value"] <= c["limit"]}


def test_training_without_fp32_masters_is_not_correct(capsys):
    manifest, cell = toy_cell("bert345m-train-s512")
    _, result, compared = bench.run_cell(
        manifest, cell, run_args(21), control=True)
    assert result["correct"] is False
    assert "change_norm_gap" in not_within(compared)


def serving_cell_with_published_tiles():
    manifest, cell = toy_cell("gpt1p3b-serve-chat")
    cell["config"].update(
        n_embd=256, n_head=2, n_inner=1024, n_positions=1024)
    cell["mix"]["engine"].update(
        capacity=1024, page_size=512, num_pages=8, prefill_token_budget=64)
    cell["mix"]["prompt_tokens"].update(median=60, min=16, max=200)
    return manifest, cell


def test_serving_with_int8_kv_is_not_correct(capsys):
    manifest, cell = serving_cell_with_published_tiles()
    _, result, compared = bench.run_cell(
        manifest, cell, run_args(31, 1.5), control=True)
    assert result["correct"] is False
    assert not_within(compared) == {"kv_gap_first_layer"}


def test_serving_with_bf16_kv_is_correct_at_that_size(capsys):
    manifest, cell = serving_cell_with_published_tiles()
    _, result, compared = bench.run_cell(manifest, cell, run_args(31, 1.5))
    assert result["correct"] is True, compared
