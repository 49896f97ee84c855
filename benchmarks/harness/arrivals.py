"""The one open-loop generator: reads a traffic mix's parameters and
makes the request plan of a run.

The lengths and gaps are the distribution's own quantiles (a stratified
sample), put in an order fixed by the MIX (`schedule_seed`), not by the
run: every seed offers the same requests at the same times, and draws only
its own token ids (and, elsewhere, weights). The system this benchmark
first measured sustains under two requests a second, so a window holds a
few dozen requests and its tails follow their order: with the order drawn
from the run's seed, ttft_p95 spread by 29% between seeds at the same load
(PERF.md, PR 23). Another order is another mix file.
"""

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_lengths(n, median, sigma, lo, hi):
    """``n`` lengths at the quantiles (i + 0.5) / n of a log-normal with
    the given median and sigma, clipped to [lo, hi]."""
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def poisson_gaps(n, rate):
    """``n`` inter-arrival gaps at the quantiles of the exponential
    distribution of a Poisson process of ``rate`` per second, scaled so
    that they sum to exactly n / rate."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


@dataclasses.dataclass
class Plan:
    due: np.ndarray  # seconds from the window's opening; ramp is < 0
    prompts: list  # token id lists
    max_new: np.ndarray
    ramp_s: float
    seconds: float

    @property
    def in_window(self):
        return (self.due >= 0.0) & (self.due < self.seconds)


def _phase(mix, n, rate, rng):
    a = mix["arrivals"]
    if a["process"] != "poisson":
        raise ValueError(f"arrival process {a['process']!r} is not built")
    gaps = poisson_gaps(n, rate)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    if p["dist"] != "lognormal" or o["dist"] != "lognormal":
        raise ValueError("length distributions other than lognormal are not built")
    prompts = lognormal_lengths(n, p["median"], p["sigma"], p["min"], p["max"])
    outs = lognormal_lengths(n, o["median"], o["sigma"], o["min"], o["max"])
    # three independent orders: a long prompt is not tied to a long
    # output or a long gap
    return (
        rng.permutation(gaps), rng.permutation(prompts), rng.permutation(outs)
    )


def build_plan(mix, seed, seconds, vocab, capacity):
    """The requests of one run: a ramp of ``mix['ramp_s']`` seconds
    before the window (set-up) and the window itself, each with its own
    fixed multiset in the mix's own order; token ids from ``seed``."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    rate = float(mix["arrivals"]["rate_per_s"])
    ramp_s = float(mix["ramp_s"])
    due, plens, olens = [], [], []
    for start, span in ((-ramp_s, ramp_s), (0.0, float(seconds))):
        n = max(1, round(rate * span))
        g, p, o = _phase(mix, n, n / span, rng)
        # a request is due at the START of its gap: the first request of
        # a phase opens it, and the gaps fill the phase exactly
        due.append(start + np.cumsum(g) - g)
        plens.append(p)
        olens.append(o)
    due = np.concatenate(due)
    plens = np.concatenate(plens).astype(int)
    olens = np.concatenate(olens).astype(int)
    # prompt + output fits the context
    olens = np.minimum(olens, capacity - plens)
    if olens.min() < 1:
        raise ValueError("a prompt fills the whole context")
    ids = np.random.default_rng(int(seed))
    prompts = [ids.integers(0, vocab, size=int(n)).tolist() for n in plens]
    return Plan(due=due, prompts=prompts, max_new=olens, ramp_s=ramp_s,
                seconds=float(seconds))
