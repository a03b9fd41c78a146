"""Executor: host time to move one decode step's staging arrays on-device
(``executor.stage``: the ``jnp.asarray`` calls of one jitted call,
together), in ms a call, from ``engine.stats()`` at the window's two
ends."""
from benchmark import span_reduce


def read(ctx):
    phases = span_reduce.phase_totals(ctx, "decode")
    if not phases or not phases.get("executor.stage", [0])[0]:
        return None
    count, seconds = phases["executor.stage"]
    return 1e3 * seconds / count
