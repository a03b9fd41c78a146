"""Scheduler: of the device's idle time that no phase of an engine step
covers (``idle_pct.other.sat``), the part under the stepping thread's wait
for the engine's own lock (``engine.lock``: a client inside ``submit``, or a
reader of ``stats()``, held it), as a share of the slice
(``host_reduce.split_other``). With ``idle_pct.gc.sat``, ``idle_pct.edge.sat``
and ``idle_pct.unspanned.sat`` it adds up to ``idle_pct.other.sat``."""
from benchmark import host_reduce


def read(ctx):
    return host_reduce.other_pct(ctx, "lock")
