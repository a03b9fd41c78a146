"""Device time of the PREFILL programs by the program's own names, as a share of
those programs' device time in the slice: ``moe_gmm``, the grouped expert
product alone: what PERF.md 7 (w) had no reader for
(``benchmark/scope_reduce.py``). Nothing where no prefill program ran in the
slice, the part took no time, or under 90% of busy time is named."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "experts", kind="prefill")
