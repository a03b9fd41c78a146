"""Scheduler: how often lag-1 dispatch survives: the window's decode steps
that were fed from the pending step's device tokens, as a share of all its
decode steps (``decode_steps_steady`` over ``decode_steps`` of
``engine.stats()``). Every join, finish and prefill collapses the lag."""
from benchmark import span_reduce


def read(ctx):
    steps = span_reduce.counter_delta(ctx, "decode_steps")
    steady = span_reduce.counter_delta(ctx, "decode_steps_steady")
    if not steps or steady is None:
        return None
    return 100.0 * steady / steps
