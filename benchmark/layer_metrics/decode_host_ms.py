"""Scheduler: host time a decode step costs, in ms: the seconds of every
phase of the window's decode steps but ``engine.sync`` (which waits for the
device), over the decode dispatches, from ``engine.stats()`` at the
window's two ends. Under lag-1 dispatch this time overlaps the device's
work; where the lag collapses the device waits for it."""
from benchmark import span_reduce


def read(ctx):
    phases = span_reduce.phase_totals(ctx, "decode")
    steps = span_reduce.counter_delta(ctx, "decode_steps")
    if not phases or not steps:
        return None
    host_s = sum(seconds for name, (_, seconds) in phases.items()
                 if name != "engine.sync")
    return 1e3 * host_s / steps
