"""Device: busy time of the traced slice whose operation resolved to a name
of ``obs.SCOPES`` (``benchmark/scope_reduce.py``: the program's compiled
texts say which part of a layer each instruction belongs to). The guard of
the ``scope_pct.*`` and ``prefill_scope_pct.*``: under 90 they are not
reported. The rest is ``unnamed``: instructions the program named under no
scope, runs of step programs whose text was not found (said, never
guessed), and programs that are no step's."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.named_pct(ctx)
