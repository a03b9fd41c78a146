"""Executor: step programs the process had run when the window opened
(``engine.stats()["programs"]``: one a (kind, tokens shape, tables shape)
of the family and configuration): what the warm-up paid a first call
for."""
from benchmark import scope_reduce


def read(ctx):
    programs = scope_reduce.programs_of(ctx, "stats_before")
    return len(programs) if programs else None
