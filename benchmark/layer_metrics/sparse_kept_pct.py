"""Kernels: of the blocks a selecting layer's decode rows could have
attended in the window (every block up to the row's own, a K/V head a
layer), the share their selection kept: ``sparse_blocks_attended`` over
``sparse_blocks_visible`` of ``engine.stats()``, the window's end less its
start, both summed over the row-steps at or past ``dense_len``, the K/V
heads and the selecting layers. 64 of ``floor(t / 64) + 1``: a half at
8,192 tokens, a quarter at 16k, 7.7% at 53k. Nothing where the program
keeps no such counters or no row selected."""
from benchmark import span_reduce


def kept_pct(attended: float, visible: float) -> float:
    return 100.0 * attended / visible


def read(ctx):
    attended = span_reduce.counter_delta(ctx, "sparse_blocks_attended")
    visible = span_reduce.counter_delta(ctx, "sparse_blocks_visible")
    if attended is None or not visible:
        return None
    return kept_pct(attended, visible)
