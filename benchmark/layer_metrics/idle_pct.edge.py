"""Device: of the device's idle time that no phase of an engine step covers
(``idle_pct.other.sat``), what no collection and no wait for the lock
covers and lies at the slice's EDGES: between the opening mark and the
first step program's run, or between the last one's end and the closing
mark (a span open when the profiler's session began is not in the trace,
and the device's tracer may start after the host's), as a share of the
slice (``host_reduce.edges``, ``split_other``)."""
from benchmark import host_reduce


def read(ctx):
    return host_reduce.other_pct(ctx, "edge")
