"""Scheduler: p95 of the wait from ``submitted`` to ``admitted`` in the
engine's own request timelines, over the requests due in the window."""
from benchmark import stats


def read(ctx):
    sample = stats.due_in_window(ctx["records"], ctx["t0"], ctx["t1"])
    waits = []
    for r in sample:
        events = {e["event"]: e["ts"]
                  for e in ctx["timelines"].get(r["id"], {}).get("events", [])}
        if "submitted" in events and "admitted" in events:
            waits.append((events["admitted"] - events["submitted"]) * 1e3)
    return stats.percentile(waits, 0.95) if waits else None
