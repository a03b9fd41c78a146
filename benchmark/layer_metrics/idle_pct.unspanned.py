"""Device: what is left of ``idle_pct.other.sat``'s idle once the
collector's pauses, the waits for the engine's lock and the slice's edges
are taken out: the device idle INSIDE the slice while the stepping thread
was under no span at all (the step's own code between two phases), as a
share of the slice. The four parts add up to ``idle_pct.other.sat``."""
from benchmark import host_reduce


def read(ctx):
    return host_reduce.other_pct(ctx, "unspanned")
