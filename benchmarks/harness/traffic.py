"""The one general generator of serving traffic. A traffic mix is a
data file of parameters; nothing here knows a mix by name.

Every seed gets the SAME multiset of (prompt length, output length)
pairs - the quantile grid of the mix's two distributions, paired by a
shuffle the mix file fixes - in another order, with other token ids:
the seed changes the order of the work, not the work.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _lengths(spec, n):
    """n lengths on the quantile grid of a clipped lognormal:
    {"dist": "lognormal", "median", "sigma", "min", "max"}, or of
    {"dist": "fixed", "value"}."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    q = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def sizes(mix):
    """The mix's fixed list of (prompt_len, output_len) pairs."""
    n = int(mix["n_sizes"])
    prompts = _lengths(mix["prompt_len"], n)
    outputs = _lengths(mix["output_len"], n)
    pairing = np.random.default_rng(int(mix["sizes_seed"])).permutation(n)
    return list(zip(prompts.tolist(), outputs[pairing].tolist()))


class RequestStream:
    """Requests for one run: the mix's sizes in the seed's order (again
    in a new order when the list runs out), token ids uniform from the
    seed."""

    def __init__(self, mix, seed, vocab):
        self.sizes = sizes(mix)
        self.rng = np.random.default_rng([int(seed), 0x73657276])
        self.vocab = int(vocab)
        self._order = []

    def next(self):
        if not self._order:
            self._order = self.rng.permutation(len(self.sizes)).tolist()
        p, n = self.sizes[self._order.pop()]
        return (self.rng.integers(0, self.vocab, p, dtype=np.int32),
                int(n))


def describe(mix):
    s = sizes(mix)
    p, o = [a for a, _ in s], [b for _, b in s]
    return (f"{len(s)} sizes; prompts mean {statistics.mean(p):.0f} "
            f"median {statistics.median(p):.0f} max {max(p)}; outputs "
            f"mean {statistics.mean(o):.0f} median "
            f"{statistics.median(o):.0f} max {max(o)}")


def percentile(values, q):
    """The q-th percentile (0-100), linear between order statistics."""
    v = sorted(values)
    if not v:
        return math.nan
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)
