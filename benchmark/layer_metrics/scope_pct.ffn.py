"""Device time by the program's own names, as a share of busy time: ``ffn`` +
``dense_ffn`` + ``moe_shared``: the feed-forward half of a layer that is no
routed expert: its norm, the dense MLP, a double layer's SwiGLUs, the shared
expert, the residual (``benchmark/scope_reduce.py``; self times of the ``XLA
Ops`` events inside the window marks). The ``scope_pct.*`` of a cell and
``unnamed`` (100 - ``scope_named_pct``) add up to 100. Nothing where the part
took no time or under 90% of busy time is named."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "ffn")
