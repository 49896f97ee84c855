"""The comparison that decides `correct` is shown to fail: the rest of a
run is driven as the command drives it (only the look for a chip is
skipped), with the timed path broken underneath, and `correct` comes out
false."""

from _toy import run_args, toy_cell

from benchmarks import run as bench


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(capsys):
    manifest, cell = toy_cell("bert345m-train-s512")
    runner = manifest.kind(cell["mix"]).Runner(cell, manifest, break_step=True)
    runner.build(11)
    _, result, compared = bench.run_cell(
        manifest, cell, run_args(11), runner=runner)
    assert result["correct"] is False
    failed = {c["name"] for c in compared if not c["value"] <= c["limit"]}
    assert "change_norm_gap" in failed


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys):
    manifest, cell = toy_cell("gpt1p3b-serve-chat")
    runner = manifest.kind(cell["mix"]).Runner(cell, manifest)
    runner.build(12)
    vocab = cell["config"]["vocab_size"]
    step = runner.engine.step

    def altered_step():
        done = step()
        for r in done:
            if len(r.tokens) > 1:
                r.tokens[1] = (r.tokens[1] + 1) % vocab
        return done

    runner.engine.step = altered_step
    _, result, compared = bench.run_cell(
        manifest, cell, run_args(12, 1.5), runner=runner)
    assert result["correct"] is False
    assert any(c["name"] == "gap_max" and c["value"] > c["limit"]
               for c in compared)


def test_the_sound_path_is_correct_at_the_same_size(capsys):
    for name, seed in (("bert345m-train-s512", 11), ("gpt1p3b-serve-chat", 12)):
        manifest, cell = toy_cell(name)
        _, result, _ = bench.run_cell(manifest, cell, run_args(seed, 1.5))
        assert result["correct"] is True
