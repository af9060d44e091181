"""The one general traffic generator. A traffic mix is data: the
``traffic`` object of a workload file. Every seed gets the SAME set of
request sizes (a fixed pool of stratified quantiles of the stated
distributions, paired and ordered by the file's own ``pairing_seed``)
in the SAME order; the seed gives the token ids, the sampling seeds and
the weights. A closed loop's schedule is then a function of the traffic
file alone, and two seeds differ no more than two runs of one. The
reason: a tail over some tens of requests whose latency comes in whole
engine steps jumps by a whole step when the order changes (simulated:
9-31 % between seeds with a shuffled or rotated order, PERF.md).
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(spec, n):
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of the stated
    distribution, clipped to [min, max]."""
    if spec["dist"] == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        raw = [math.exp(mu + sigma * NormalDist().inv_cdf((i + 0.5) / n))
               for i in range(n)]
    elif spec["dist"] == "fixed":
        raw = [spec["value"]] * n
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(max(round(v), spec["min"]), spec["max"])) for v in raw]


def size_pool(traffic):
    """[(prompt length, output length)] x ``pool`` in the order they are
    asked: the same for every seed. Outputs are paired with prompts, and
    the pairs then ordered, by permutations drawn from ``pairing_seed``;
    a pair over ``max_total`` gives up output tokens."""
    n = traffic["pool"]
    prompts = quantile_lengths(traffic["prompt_len"], n)
    outputs = quantile_lengths(traffic["output_len"], n)
    rng = np.random.default_rng(traffic["pairing_seed"])
    pairs = []
    for p, j in zip(prompts, rng.permutation(n)):
        o = min(outputs[j], traffic["max_total"] - p)
        if o < 1:
            raise ValueError(f"prompt of {p} leaves no room under "
                             f"max_total {traffic['max_total']}")
        pairs.append((p, o))
    return [pairs[i] for i in rng.permutation(n)]


class RequestStream:
    """An endless, seeded sequence of requests for a closed loop: the
    pool in its fixed order, over and over. The
    first ``clients`` requests (the warm-up wave) have their outputs
    capped at ``warmup_max_output`` so that set-up stays short.
    ``next()`` gives (request id, prompt token ids, sampling keywords)."""

    def __init__(self, traffic, vocab_size, seed):
        self.traffic, self.vocab, self.seed = traffic, vocab_size, seed
        self.pool = size_pool(traffic)
        self.shared = [int(t) for t in np.random.default_rng(
            [seed, 1]).integers(0, vocab_size,
                                traffic.get("shared_prefix", 0))]
        self._rng = np.random.default_rng([seed, 0])
        self.count = 0

    def next(self):
        n = self.count
        self.count += 1
        p, o = self.pool[n % len(self.pool)]
        if n < self.traffic["clients"]:
            o = min(o, self.traffic.get("warmup_max_output", o))
        own = max(p - len(self.shared), 1)
        prompt = self.shared[:p - own] + [
            int(t) for t in self._rng.integers(0, self.vocab, own)]
        sampling = {"max_new_tokens": o}
        every = self.traffic.get("sampled_every", 0)
        if every and n % every == every - 1:
            sampling.update(self.traffic["sampling"])
            sampling["seed"] = (self.seed * 1000003 + n) % (2 ** 31 - 1)
        return f"r{n}", prompt, sampling
