"""Scheduler: mean rows in a decode step, from the flight recorder's
``batch`` over the ``kind == "decode"`` records inside the window (a count,
so the host clock's fault for step TIME does not touch it)."""


def read(ctx):
    rows = [s["batch"] for s in ctx["flight"]
            if s.get("kind") == "decode" and s.get("batch")]
    return sum(rows) / len(rows) if rows else None
