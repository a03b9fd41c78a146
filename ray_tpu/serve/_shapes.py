"""Shape-bucket helpers shared by the @serve.batch router path and the
LLM engine's continuous-batching scheduler.

Jitted models recompile per distinct input shape, and on TPU a recompile
is tens of seconds of XLA time in the serving hot path (SURVEY.md §7 hard
parts; arxiv 2011.03641 — static-shape batching to stay inside the compile
cache). Everything that submits work to a jitted callable therefore pads
to a CLOSED set of sizes. This module is the one place the padding rule
lives: `serve/batching.py` re-exports `pad_to_bucket` for the decorator
path, and `serve/llm/engine.py` uses it for both batch and sequence-length
dimensions.
"""
from __future__ import annotations

from typing import Sequence


def pad_to_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (last bucket if none fits)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def pow2_buckets(lo: int, hi: int) -> tuple[int, ...]:
    """Ascending powers of two covering [lo, hi]: the default bucket ladder
    for sequence lengths and batch sizes. Bounds the number of distinct
    compiled shapes at log2(hi/lo)+1 per dimension."""
    if lo < 1 or hi < lo:
        raise ValueError(f"need 1 <= lo <= hi, got lo={lo} hi={hi}")
    out = []
    b = 1
    while b < lo:
        b *= 2
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


def stepped_buckets(hi: int, wide: bool = False) -> tuple[int, ...]:
    """Ascending counts covering [1, hi] for a dimension whose padding is
    paid in full (the rows of a packed prefill step, each a q tile of
    tokens: serve/llm/engine.py): every count up to 8, then steps of at
    most a third. A count pads by under 12% on average against a power-of-
    two ladder's 33%, for about twice the programs: 12 up to 20, 8 up
    to 8. ``wide``: the ladder where a program is dear, every count up to
    4, then steps of at most a half: 8 up to 16 (1 2 3 4 6 9 13 16), 6 up
    to 8."""
    if hi < 1:
        raise ValueError(f"need hi >= 1, got {hi}")
    every, step = (4, 2) if wide else (8, 3)
    out = []
    b = 1
    while b < hi:
        out.append(b)
        b = b + 1 if b < every else b + b // step
    out.append(hi)
    return tuple(out)
