"""Chat-like requests: lognormal prompt and output lengths, unshared random
token ids, and (for an open loop) exponential gaps between arrivals.

A pure function of (traffic parameters, seed, vocabulary size). Request
``i`` is the same whoever asks for it and in whatever order.

Every seed gives the SAME set of sizes and gaps, in another order. The
lengths are not drawn at random: each block of ``strata`` consecutive
requests holds the ``strata`` mid-quantiles of the clipped lognormal, once
each, and the gaps of a block are the mid-quantiles of the exponential.
The seed only permutes a block (prompts, outputs and gaps each with their
own permutation) and picks the token ids. So two seeds offer the same work
at the same mean rate, any window of a run sees the whole distribution,
and what differs between seeds is order alone: a difference between runs
is then the system's, not the draw's. The cost, said plainly: arrival
counts in a window vary less than a true Poisson process's would.

Parameters read from the traffic file::

    "prompt_len": {"median": 512, "sigma": 0.8, "min": 64, "max": 2048},
    "output_len": {"median": 128, "sigma": 0.6, "min": 16, "max": 512},
    "strata": 64,
    "arrivals": {"mode": "closed", "clients": 128}
              | {"mode": "open", "rate_per_s": 9.5}
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(spec: dict, n: int) -> list[int]:
    """The ``n`` mid-quantiles of lognormal(median, sigma), clipped to
    [min, max] and rounded to whole tokens."""
    nd = NormalDist()
    out = []
    for j in range(n):
        z = nd.inv_cdf((j + 0.5) / n)
        v = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(round(min(max(v, spec["min"]), spec["max"]))))
    return out


def exponential_quantiles(rate_per_s: float, n: int) -> list[float]:
    """The ``n`` mid-quantiles of the exponential gap at ``rate_per_s``,
    scaled so that their mean is exactly 1 / rate."""
    raw = [-math.log(1.0 - (j + 0.5) / n) for j in range(n)]
    scale = n / sum(raw)
    return [g * scale / rate_per_s for g in raw]


class Schedule:
    def __init__(self, traffic: dict, seed: int, vocab_size: int):
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.strata = int(traffic.get("strata", 64))
        self.prompts = lognormal_quantiles(traffic["prompt_len"], self.strata)
        self.outputs = lognormal_quantiles(traffic["output_len"], self.strata)
        arrivals = traffic["arrivals"]
        self.open_loop = arrivals["mode"] == "open"
        self.gaps = (
            exponential_quantiles(arrivals["rate_per_s"], self.strata)
            if self.open_loop else None
        )
        self._blocks: dict[int, tuple] = {}
        self._due: list[float] = []

    def _block(self, b: int) -> tuple:
        """Block ``b``'s three permutations, from (seed, b) alone."""
        hit = self._blocks.get(b)
        if hit is None:
            rng = np.random.default_rng([self.seed, b, 0])
            hit = tuple(rng.permutation(self.strata) for _ in range(3))
            self._blocks[b] = hit
        return hit

    def lengths(self, i: int) -> tuple[int, int]:
        """(prompt tokens, output tokens) of request ``i``."""
        b, j = divmod(i, self.strata)
        perm_p, perm_o, _ = self._block(b)
        return self.prompts[perm_p[j]], self.outputs[perm_o[j]]

    def request(self, i: int) -> dict:
        """Request ``i``: its prompt (token ids uniform over 1..vocab-1,
        shared with no other request) and the tokens it asks for."""
        n_prompt, n_out = self.lengths(i)
        rng = np.random.default_rng([self.seed, i, 1])
        prompt = rng.integers(1, self.vocab_size, size=n_prompt,
                              dtype=np.int32)
        return {"index": i, "prompt": prompt, "max_new_tokens": n_out}

    def due(self, i: int) -> float:
        """Seconds after the first arrival's origin at which request ``i``
        is due (open loop only)."""
        if not self.open_loop:
            raise ValueError("a closed loop has no due times")
        while len(self._due) <= i:
            k = len(self._due)
            b, j = divmod(k, self.strata)
            gap = self.gaps[self._block(b)[2][j]]
            self._due.append((self._due[-1] if self._due else 0.0) + gap)
        return self._due[i]

    def describe(self) -> dict:
        """The drawn distributions, the same for every seed."""
        def summary(xs):
            xs = sorted(xs)
            n = len(xs)
            return {"min": xs[0], "p25": xs[n // 4], "p50": xs[n // 2],
                    "p75": xs[3 * n // 4], "max": xs[-1],
                    "mean": round(sum(xs) / n, 2)}
        out = {"strata": self.strata, "prompt_len": summary(self.prompts),
               "output_len": summary(self.outputs)}
        if self.open_loop:
            out["gap_s"] = summary(self.gaps)
        return out


def build(traffic: dict, seed: int, vocab_size: int) -> Schedule:
    return Schedule(traffic, seed, vocab_size)
