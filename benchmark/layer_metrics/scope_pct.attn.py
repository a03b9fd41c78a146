"""Device time by the program's own names, as a share of busy time:
``attn_kernel`` + ``attn_cache``: the paged / latent / window / sparse
attention call with the relayout around it, and the scatter of the step's new
rows into the pool. It CONTAINS the kernel-named shares
(``paged_attn_share_pct``, ``window_attn_share_pct``): what it reads above
them is the scatter and the glue (``benchmark/scope_reduce.py``; self times of
the ``XLA Ops`` events inside the window marks). The ``scope_pct.*`` of a cell
and ``unnamed`` (100 - ``scope_named_pct``) add up to 100. Nothing where the
part took no time or under 90% of busy time is named."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "attn")
