"""Kernels: how far a decode step's expert work swings: the largest count
of held real (token, expert) pairs a decode step of the window computed,
over the mean (``moe_step_pairs_decode`` of ``engine.stats()``: decode
steps by that count, all expert layers of a step together, one bucket a
count and the last bucket every count past it; the window's end less its
start). With zero-compute experts among the router's outputs a token meets
between 0 and ``top_k`` real experts, so the grouped product's rows vary
step by step; in the pod the step waits for the holder with the most.
1.0 is a load that never varies. Nothing where the program keeps no such
histogram or the window held no decode step with a pair."""


def max_over_mean(steps_by_pairs: list) -> float | None:
    """``steps_by_pairs[n]``: decode steps that computed n pairs."""
    steps = sum(steps_by_pairs)
    pairs = sum(n * c for n, c in enumerate(steps_by_pairs))
    if not steps or not pairs:
        return None
    largest = max(n for n, c in enumerate(steps_by_pairs) if c)
    return largest * steps / pairs


def read(ctx):
    after = (ctx.get("stats_after") or {}).get("moe_step_pairs_decode")
    if not after:
        return None
    before = ctx["stats_before"]["moe_step_pairs_decode"]
    return max_over_mean([a - b for a, b in zip(after, before)])
