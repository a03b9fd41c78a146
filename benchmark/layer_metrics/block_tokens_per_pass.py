"""Scheduler: tokens that reached a stream for every row-pass of a family
that generates by diffusion over blocks (``block_tokens_committed`` over
``block_passes`` of ``engine.stats()``, the window's end less its start).
A block of W tokens takes its denoising passes and one commit pass: 4 / 3 =
1.33 at W = 4 under 2 steps, less what the last blocks cut and what a first
block's prompt tail takes. An autoregressive family would read 1. Nothing
where the program keeps no such counters (the parent of the PR that added
them)."""
from benchmark import span_reduce


def read(ctx):
    tokens = span_reduce.counter_delta(ctx, "block_tokens_committed")
    passes = span_reduce.counter_delta(ctx, "block_passes")
    if tokens is None or not passes:
        return None
    return tokens / passes
