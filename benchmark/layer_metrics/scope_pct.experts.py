"""Device time by the program's own names, as a share of busy time: ``moe_gmm``:
the grouped expert product alone (the kernel ``moe_gmm_few_rows`` or the two
``ragged-dot``) (``benchmark/scope_reduce.py``; self times of the ``XLA Ops``
events inside the window marks). The ``scope_pct.*`` of a cell and ``unnamed``
(100 - ``scope_named_pct``) add up to 100. Nothing where the part took no time
or under 90% of busy time is named."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "experts")
