"""Scheduler: the longest decode step of the window, in ms (``dur_ms`` of
the flight recorder's decode records: launch to launch on the host). The
reader SAYS that record's own account of it: ``lock_ms`` (the wait for the
engine's lock), ``gc_ms`` (the collector), ``cpu_ms`` (what the thread ran)
since the record before it, and ``sync_ms``. Nothing where the records
carry no such fields."""
from benchmark import host_reduce


def read(ctx):
    steps = [s for s in host_reduce.window_records(ctx, "lock_ms")
             if s.get("kind") == "decode"]
    if not steps:
        return None
    longest = max(steps, key=lambda s: s["dur_ms"])
    host_reduce.say_slow_step(
        f"the longest of {len(steps)} decode steps", longest)
    return longest["dur_ms"]
