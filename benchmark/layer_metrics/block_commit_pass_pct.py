"""Scheduler: of the row-passes of a family that generates by diffusion
over blocks, the share that were COMMIT passes (``block_passes_commit``
over ``block_passes`` of ``engine.stats()``, the window's end less its
start): passes that choose no token and only leave a finished block's K/V
in the cache for good. 33.3 under 2 denoising steps a block: the share a
change that folds a block's commit into the next block's first pass could
take. Nothing where the program keeps no such counters."""
from benchmark import span_reduce


def read(ctx):
    commits = span_reduce.counter_delta(ctx, "block_passes_commit")
    passes = span_reduce.counter_delta(ctx, "block_passes")
    if commits is None or not passes:
        return None
    return 100.0 * commits / passes
