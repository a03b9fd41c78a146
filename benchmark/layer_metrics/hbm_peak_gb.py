"""Device: peak bytes of the chip after the window, in GB (1e9 bytes):
live arrays plus programs' temporaries, as ``common.memory_peak_bytes``
adds them. The CPU backend keeps no such count and reports nothing."""
from benchmark import common


def read(ctx):
    peak = common.memory_peak_bytes(ctx["memory_stats"])
    return peak / 1e9 if peak else None
