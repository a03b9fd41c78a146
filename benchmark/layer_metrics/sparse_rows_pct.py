"""Kernels: of the window's decode row-steps, the share at or past
``dense_len`` (``sparse_row_steps`` over ``sparse_row_steps +
dense_row_steps`` of ``engine.stats()``, the window's end less its start):
whether the traffic reached the selection at all. Nothing where the program
keeps no such counters."""
from benchmark import span_reduce


def rows_pct(sparse: float, dense: float) -> float:
    return 100.0 * sparse / (sparse + dense)


def read(ctx):
    sparse = span_reduce.counter_delta(ctx, "sparse_row_steps")
    dense = span_reduce.counter_delta(ctx, "dense_row_steps")
    if sparse is None or dense is None or not sparse + dense:
        return None
    return rows_pct(sparse, dense)
