"""The arithmetic from samples to metrics, kept with the benchmark so that
every PR computes the same number in the same way.

A request is a dict the runner fills at the client's side:
``due`` (when an open-loop request was due, else when it was sent), ``sent``,
``tokens`` (the clock at which each output token reached the client),
``want`` (tokens asked for) and ``error`` (None, or why it failed or was
refused). All clocks are one ``time.perf_counter``.
"""
from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q`` of
    the samples at or below it. No interpolation, so the value is one that
    was observed. Raises on an empty sample."""
    if not samples:
        raise ValueError("percentile of no samples")
    xs = sorted(samples)
    rank = max(1, math.ceil(q * len(xs)))
    return xs[rank - 1]


def tokens_in_window(requests: Iterable[dict], t0: float, t1: float) -> int:
    """Output tokens that reached a client inside [t0, t1), whatever
    request they belong to."""
    return sum(1 for r in requests for t in r["tokens"] if t0 <= t < t1)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return count / seconds


def due_in_window(requests: Iterable[dict], t0: float, t1: float) -> list:
    """The open-loop sample: requests that were DUE inside the window,
    whenever they were sent or answered."""
    return [r for r in requests if t0 <= r["due"] < t1]


def failed(r: dict) -> bool:
    """Refused, errored, or short of the tokens it asked for."""
    return r["error"] is not None or len(r["tokens"]) < r["want"]


def ttft_ms(sample: Iterable[dict], t_seen_until: float) -> list[float]:
    """Time to first token, each timed from when the request was DUE (not
    from when the generator got round to sending it). A request that
    yielded no token is counted at the whole time it was watched
    (``t_seen_until`` - due): a lower bound, and the longest in the sample,
    so a failed request never improves a tail."""
    out = []
    for r in sample:
        if r["tokens"]:
            out.append((r["tokens"][0] - r["due"]) * 1e3)
        else:
            out.append((t_seen_until - r["due"]) * 1e3)
    return out


def gaps_ms(sample: Iterable[dict]) -> list[float]:
    """Every gap between consecutive tokens at the client, all requests
    pooled."""
    out = []
    for r in sample:
        ts = r["tokens"]
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    return out


def lateness_ms(requests: Iterable[dict]) -> list[float]:
    """How late the generator sent each request against its due time."""
    return [(r["sent"] - r["due"]) * 1e3 for r in requests]
