"""Device time by the program's own names, as a share of busy time: ``head`` +
``sample`` + ``counters`` + ``embed`` + ``layer_stack``: the step around its
layers: the embedding and the rotary rows, the final norm and the head's
product, the sampling epilogue, the counter words, and a scanned stack's own
slices of its weights (``benchmark/scope_reduce.py``; self times of the ``XLA
Ops`` events inside the window marks). The ``scope_pct.*`` of a cell and
``unnamed`` (100 - ``scope_named_pct``) add up to 100. Nothing where the part
took no time or under 90% of busy time is named."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "head")
