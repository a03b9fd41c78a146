"""Device (the host process): of the device's idle time that no phase of an
engine step covers (``idle_pct.other.sat``), the part under a generation-2
collection of the process's collector on ANY thread (``host.gc``: it stops
every thread, so it is counted before the wait for the lock), as a share of
the slice (``host_reduce.split_other``)."""
from benchmark import host_reduce


def read(ctx):
    return host_reduce.other_pct(ctx, "gc")
