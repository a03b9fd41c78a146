"""Device time by the program's own names, as a share of busy time:
``ssd_proj``, ``ssd_conv``, ``ssd_step``, ``ssd_chunk``, ``ssd_out``: the
parts of a Mamba-2 mixer, which in a ``falcon_h1`` layer stands BESIDE
attention and not in its place (models/falcon_h1.py;
``benchmark/scope_reduce.py``: self times of the ``XLA Ops`` events inside
the window marks). ``scope_reduce.GROUPS["mixer"]`` is a fixed tuple that
does not hold these names, so a cell that runs such layers reports this share
and not ``scope_pct.mixer``; with it the cell's ``scope_pct.*`` and
``unnamed`` (100 - ``scope_named_pct``) add up to 100. Through
``scope_pct.kda``'s ``share_pct``: the same floor, the same table. Nothing
where the part took no time, where the program names no such scope (a
checkout from before PR 58) or under 90% of busy time is named."""
from benchmark import common

SCOPES = ("ssd_proj", "ssd_conv", "ssd_step", "ssd_chunk", "ssd_out")


def read(ctx):
    return common.load_layer_metric("scope_pct.kda").share_pct(ctx, SCOPES)
