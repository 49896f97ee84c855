"""The open-loop generator: the same seed gives the same plan, every
seed gets the same work in another order, and the lengths follow the
distributions the mix states."""

import json
import pathlib
import statistics

import numpy as np
import pytest

from benchmarks.harness import arrivals

ROOT = pathlib.Path(__file__).resolve().parents[2]
MIX = json.loads((ROOT / "benchmarks/mixes/chat.json").read_text())
BIG_SEED = 2**31 + 12345  # the driver's seeds pass 32 signed bits


def plan(seed, seconds=30):
    return arrivals.build_plan(MIX, seed, seconds, vocab=50257, capacity=2048)


def test_same_seed_same_plan():
    a, b = plan(BIG_SEED), plan(BIG_SEED)
    assert np.array_equal(a.due, b.due)
    assert a.prompts == b.prompts
    assert np.array_equal(a.max_new, b.max_new)


def test_every_seed_offers_the_same_requests_at_the_same_times():
    a, b = plan(1), plan(BIG_SEED)
    assert a.prompts != b.prompts  # token ids are the seed's own
    assert np.array_equal(a.due, b.due)
    assert [len(x) for x in a.prompts] == [len(x) for x in b.prompts]
    assert np.array_equal(a.max_new, b.max_new)
    assert a.in_window.sum() == round(MIX["arrivals"]["rate_per_s"] * 30)


def test_another_schedule_seed_is_another_order_of_the_same_work():
    other = dict(MIX, schedule_seed=MIX["schedule_seed"] + 1)
    a = plan(1)
    b = arrivals.build_plan(other, 1, 30, vocab=50257, capacity=2048)
    w = a.in_window
    assert not np.array_equal(a.due, b.due)
    assert sorted(len(x) for x, k in zip(a.prompts, w) if k) == sorted(
        len(x) for x, k in zip(b.prompts, b.in_window) if k)


@pytest.mark.parametrize("which", ["prompt_tokens", "output_tokens"])
def test_lengths_follow_the_stated_lognormal(which):
    d = MIX[which]
    xs = arrivals.lognormal_lengths(
        4000, d["median"], d["sigma"], d["min"], d["max"])
    assert min(xs) == d["min"] and max(xs) == d["max"]
    assert abs(statistics.median(xs) - d["median"]) <= 1
    inside = [x for x in xs if d["min"] < x < d["max"]]
    logs = np.log(inside)
    # clipped tails shrink the spread a little; the body keeps sigma
    assert abs(np.percentile(logs, 75) - np.percentile(logs, 25)
               - 2 * 0.6745 * d["sigma"]) < 0.15 * d["sigma"]


def test_arrivals_are_poisson_at_the_stated_rate():
    gaps = arrivals.poisson_gaps(2000, 8.0)
    assert abs(sum(gaps) - 2000 / 8.0) < 1e-9
    mean = statistics.mean(gaps)
    assert abs(statistics.pstdev(gaps) / mean - 1.0) < 0.05  # cv of 1


def test_ramp_comes_before_the_window_and_fits_the_context():
    p = plan(7)
    assert p.due[0] == pytest.approx(-MIX["ramp_s"])
    assert (np.diff(p.due) >= 0).all()
    assert p.due[-1] < 30
    for prompt, new in zip(p.prompts, p.max_new):
        assert len(prompt) + new <= 2048 and new >= 1
        assert 0 <= min(prompt) and max(prompt) < 50257


def test_an_arrival_process_that_is_not_built_is_refused():
    mix = dict(MIX, arrivals=dict(MIX["arrivals"], process="gamma"))
    with pytest.raises(ValueError):
        arrivals.build_plan(mix, 1, 10, vocab=50257, capacity=2048)
