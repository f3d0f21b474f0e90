"""One general generator of serving traffic, driven by a mix's data file.

Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps, in another order with other token ids: sizes are the quantile
grid of the stated distribution over a cycle of requests. One fixed shuffle
(the mix's own, seed-free) spreads the grid over the cycle; the seed then
permutes within blocks of `BLOCK` neighbours. So seeds change which request
meets which, not how much work a run holds nor when its heavy stretches come:
a tail over a few hundred requests would otherwise measure the permutation.

An open loop knows how many requests its window holds (rate x seconds), so
its cycle is the window: every seed offers exactly the same requests and
gaps, and the last `PINNED` of them keep the mix's own order, so that the
window's end (the last completion) is not a draw either. A closed loop draws
as many requests as it completes, in cycles of the mix's `cycle`.
"""

import math
import statistics

import numpy as np

from cellbench import harness


def _grid(n):
    return (np.arange(n) + 0.5) / n


def size_grid(spec, n):
    """`n` integer sizes: the quantile grid of the distribution in `spec`."""
    dist = spec["dist"]
    if dist == "fixed":
        values = np.full(n, spec["value"], float)
    elif dist == "uniform":
        values = spec["lo"] + _grid(n) * (spec["hi"] - spec["lo"])
    elif dist == "lognormal":
        normal = statistics.NormalDist()
        values = np.asarray([
            math.exp(math.log(spec["median"]) + spec["sigma"] * normal.inv_cdf(u))
            for u in _grid(n)])
    elif dist == "choice":
        values = np.repeat(np.asarray(spec["values"], float),
                           np.round(np.asarray(spec["weights"], float)
                                    / sum(spec["weights"]) * n).astype(int))
        values = np.resize(values, n)
    else:
        raise ValueError("unknown size distribution {!r}".format(dist))
    if "lo" in spec:
        values = np.clip(values, spec["lo"], spec["hi"])
    return np.maximum(np.round(values).astype(int), 1)


def gap_grid(arrivals, rate, n):
    """`n` inter-arrival gaps with mean 1/rate: the quantile grid of an
    exponential (`poisson`) or of a Gamma with squared coefficient of
    variation `cv2` (`gamma`: bursts), rescaled to the exact mean."""
    process = arrivals.get("process", "poisson")
    if process == "poisson":
        gaps = -np.log1p(-_grid(n))
    elif process == "gamma":
        cv2 = float(arrivals["cv2"])
        sample = np.random.default_rng(0).gamma(1.0 / cv2, cv2, 200_000)
        gaps = np.quantile(sample, _grid(n))
    else:
        raise ValueError("unknown arrival process {!r}".format(process))
    return gaps / gaps.mean() / rate


BLOCK = 8
PINNED = 16


def spread(values):
    """The mix's own fixed order of a grid (no seed)."""
    return np.random.default_rng(20250925).permutation(values)


def block_permuted(values, rng, block=BLOCK, pinned=0):
    """`values` permuted within consecutive blocks of `block`; the last
    `pinned` keep their order."""
    out = np.array(values)
    for lo in range(0, len(out) - pinned, block):
        hi = min(lo + block, len(out) - pinned)
        out[lo:hi] = rng.permutation(out[lo:hi])
    return out


class Requests:
    """Request `i` of the mix for a seed: (prompt token ids, new tokens).
    Generated a cycle at a time, so a closed loop can draw as many as it
    completes."""

    def __init__(self, mix, vocab_size, max_seq_len, seed, count=None):
        self.mix, self.vocab, self.max_seq = mix, int(vocab_size), int(max_seq_len)
        # `count`: an open loop's window holds exactly this many requests.
        self.cycle = int(count or mix["cycle"])
        self.pinned = min(PINNED, self.cycle // 2) if count else 0
        self.prompts = spread(size_grid(mix["prompt_len"], self.cycle))
        self.news = spread(size_grid(mix["new_tokens"], self.cycle)[::-1])
        self.seed = seed
        shared = mix.get("shared_prefix") or {}
        self.prefix_len = int(shared.get("len", 0)) if shared else 0
        self.prefix_share = float(shared.get("share", 0.0)) if shared else 0.0
        self.prefix = harness.rng(seed, 3).integers(2, self.vocab, self.prefix_len)
        self._cache = {}

    def _cycle(self, c):
        if c not in self._cache:
            rng = harness.rng(self.seed, 1000 + c)
            prompts = block_permuted(self.prompts, rng, pinned=self.pinned)
            news = block_permuted(self.news, rng, pinned=self.pinned)
            out = []
            for p, n in zip(prompts, news):
                p = int(min(p, self.max_seq - n))
                ids = rng.integers(2, self.vocab, p)
                if self.prefix_len and p > self.prefix_len and (
                        rng.random() < self.prefix_share):
                    ids[:self.prefix_len] = self.prefix
                out.append((ids.astype(np.int32), int(n)))
            self._cache = {c: out}
        return self._cache[c]

    def __getitem__(self, i):
        return self._cycle(i // self.cycle)[i % self.cycle]

    def max_new(self):
        return int(self.news.max())

    def prompt_range(self):
        return int(self.prompts.min()), int(self.prompts.max())


def arrival_count(mix, seconds):
    return max(int(float(mix["rate_per_s"]) * seconds), 1)


def arrival_times(mix, seed, seconds):
    """Due times in [0, seconds) of an open loop at `rate_per_s`: the same
    `arrival_count` gaps for every seed, in another order."""
    n = arrival_count(mix, seconds)
    rate = float(mix["rate_per_s"])
    grid = spread(gap_grid(mix.get("arrivals", {}), rate, n))
    gaps = block_permuted(grid, harness.rng(seed, 2000), pinned=min(PINNED, n // 2))
    # The grid sums to n / rate; the last request is due half a mean gap
    # before the window's end.
    return np.cumsum(gaps) * (seconds - 0.5 / rate) / (n / rate)
