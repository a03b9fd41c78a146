"""Executor: median device duration of the prefill programs' runs, from
the profiler's trace (the jitted ``<family>_prefill``, whole prompts)."""
from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.module_median_ms(ctx.get("trace"), "prefill")
