"""Device time by the program's own names, as a share of busy time:
``kda_conv``, ``kda_gate``, ``kda_step``, ``kda_chunk``, ``kda_out``: the
parts of a KDA layer's mixer that stand where attention would
(models/ling_hybrid.py; ``benchmark/scope_reduce.py``: self times of the
``XLA Ops`` events inside the window marks). ``scope_reduce.GROUPS["mixer"]``
is a fixed tuple that does not hold these names, so a cell that runs KDA
layers reports this share and not ``scope_pct.mixer``; with it the cell's
``scope_pct.*`` and ``unnamed`` (100 - ``scope_named_pct``) add up to 100.
Nothing where the part took no time, where the program names no such scope
(a checkout from before PR 52) or under 90% of busy time is named."""
from benchmark import scope_reduce

SCOPES = ("kda_conv", "kda_gate", "kda_step", "kda_chunk", "kda_out")


def share_pct(ctx, scopes=SCOPES, kind=None):
    """``scope_reduce.share_pct`` for a tuple of scopes that is no group of
    its ``GROUPS``: the same floor, the same table."""
    named = scope_reduce.named_pct(ctx)
    if named is None or named < scope_reduce.NAMED_FLOOR_PCT:
        return None
    out = scope_reduce.table(ctx)
    of = out["busy_s"] if kind is None else sum(
        out["by"].get(kind, {}).values())
    part = scope_reduce.seconds(out, scopes, kind)
    if not part or not of:
        return None
    return 100.0 * part / of


def read(ctx):
    return share_pct(ctx)
