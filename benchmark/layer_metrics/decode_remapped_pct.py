"""Scheduler: decode steps launched behind a step in flight over ANOTHER
batch than that step's (rows joined or left: their ids are gathered on the
device, ``executor.feed``), as a share of the window's decode steps
(``decode_steps_remapped`` over ``decode_steps`` of ``engine.stats()``)."""
from benchmark import span_reduce


def read(ctx):
    steps = span_reduce.counter_delta(ctx, "decode_steps")
    remapped = span_reduce.counter_delta(ctx, "decode_steps_remapped")
    if not steps or remapped is None:
        return None
    return 100.0 * remapped / steps
