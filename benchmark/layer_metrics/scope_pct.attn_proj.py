"""Device time by the program's own names, as a share of busy time:
``attn_proj``: an attention (or mixer) layer around its cache side: the norm
before it, the q / k / v / o products, rotary, QK-norm, a gate a head, a
latent layer's down / up and absorb products, the residual
(``benchmark/scope_reduce.py``; self times of the ``XLA Ops`` events inside
the window marks). The ``scope_pct.*`` of a cell and ``unnamed`` (100 -
``scope_named_pct``) add up to 100. Nothing where the part took no time or
under 90% of busy time is named."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "attn_proj")
