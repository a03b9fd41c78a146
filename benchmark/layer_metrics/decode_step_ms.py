"""Executor: median device duration of the decode program's runs, from the
profiler's trace (XLA Modules line; the program is the jitted
``<family>_decode_step``)."""
from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.module_median_ms(ctx.get("trace"), "decode_step")
