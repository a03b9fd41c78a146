"""Device idle inside the traced slice while the engine's step thread was under
``engine.schedule``, ``engine.batch``, ``engine.emit`` or
``engine.account``, as a share of the slice (``span_reduce``). The six
``idle_pct.*`` add up to the slice's idle share."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.idle_pct(ctx, "scheduler")
