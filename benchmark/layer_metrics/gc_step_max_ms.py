"""Device (the host process): the most the process's collector took
between two flight records of the window, in ms (``gc_ms``: all
generations, whichever thread collected). The reader SAYS that record.
Nothing where the records carry no ``gc_ms``."""
from benchmark import host_reduce


def read(ctx):
    steps = host_reduce.window_records(ctx, "gc_ms")
    if not steps:
        return None
    worst = max(steps, key=lambda s: s["gc_ms"])
    host_reduce.say_slow_step(
        f"the record of {len(steps)} with the most collector time", worst)
    return worst["gc_ms"]
