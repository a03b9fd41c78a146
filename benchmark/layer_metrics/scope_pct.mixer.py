"""Device time by the program's own names, as a share of busy time:
``short_conv``, ``lightning_step``, ``lightning_chunk``, ``sparse_select``,
``sparse_prefill_attention``, ``eva_summarize``: the mixers that stand where
(or beside) attention would (``benchmark/scope_reduce.py``; self times of the
``XLA Ops`` events inside the window marks). The ``scope_pct.*`` of a cell and
``unnamed`` (100 - ``scope_named_pct``) add up to 100. Nothing where the part
took no time or under 90% of busy time is named."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "mixer")
