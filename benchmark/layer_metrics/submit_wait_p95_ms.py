"""Scheduler: p95 of the wait for the engine's lock in ``submit()``, from
``received`` (stamped before the lock is taken) to ``submitted`` (inside
it) in the engine's own request timelines, over the requests due in the
window. A step in flight holds the lock to its end. None where the program
stamps no ``received``."""
from benchmark import stats


def read(ctx):
    sample = stats.due_in_window(ctx["records"], ctx["t0"], ctx["t1"])
    waits = []
    for r in sample:
        events = {e["event"]: e["ts"]
                  for e in ctx["timelines"].get(r["id"], {}).get("events", [])}
        if "received" in events and "submitted" in events:
            waits.append((events["submitted"] - events["received"]) * 1e3)
    return stats.percentile(waits, 0.95) if waits else None
