"""Executor: seconds the FIRST call of every step program took, summed
over the programs the process had run when the window opened
(``engine.stats()["programs"]``, ``first_call_s`` on the engine's own
clock): trace, compile or the read of the persistent compile cache, launch.
The part of ``setup_s`` that is the programs': cold it is the compiles,
warm the cache reads and the traces."""
from benchmark import scope_reduce


def read(ctx):
    programs = scope_reduce.programs_of(ctx, "stats_before")
    if not programs:
        return None
    return sum(p["first_call_s"] for p in programs.values())
