"""Device time by the program's own names, as a share of busy time: ``moe_route``
+ ``moe_move`` + ``moe_zero``: what stands around the grouped product: the
router, the sort, the rows' gather, the way back, the weighted combine, the
zero-compute experts (``benchmark/scope_reduce.py``; self times of the ``XLA
Ops`` events inside the window marks). The ``scope_pct.*`` of a cell and
``unnamed`` (100 - ``scope_named_pct``) add up to 100. Nothing where the part
took no time or under 90% of busy time is named."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "moe_move")
