"""Kernels: device time in the WINDOWED paged-attention calls (operations
whose name holds ``paged_attention_window``: the sliding layers' calls),
as a share of busy time. Nothing where no such call ran (a program whose
kernel has no name of its own for the windowed variant)."""
from benchmark import trace_reduce

NEEDLE = "paged_attention_window"


def read(ctx):
    reduced = ctx.get("trace")
    if not reduced or not any(
            NEEDLE in name and v["self_s"] > 0
            for name, v in reduced["ops"].items()):
        return None
    return trace_reduce.ops_share_pct(reduced, NEEDLE)
