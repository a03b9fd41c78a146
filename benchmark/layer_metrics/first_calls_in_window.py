"""Executor: step programs first called INSIDE the window
(``engine.stats()["programs"]`` at its close less at its opening). 0 in a
sound run: the warm-up reached every shape. Where it is not, the flight
record ``{"kind": "compile", "shape", "ms"}`` names the shape and what its
first call took, and the run SAYS them."""
from benchmark import scope_reduce
from benchmark.common import say


def read(ctx):
    before = scope_reduce.programs_of(ctx, "stats_before")
    after = scope_reduce.programs_of(ctx, "stats_after")
    if before is None or after is None:
        return None
    new = sorted(set(after) - set(before))
    if new:
        say(f"first calls inside the window: "
            f"{ {k: after[k]['first_call_s'] for k in new} }")
    return len(new)
