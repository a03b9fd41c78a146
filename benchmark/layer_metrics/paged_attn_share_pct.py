"""Kernels: device time in the paged-attention kernel's calls (operations
whose name holds ``paged_attention``), as a share of busy time."""
from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.ops_share_pct(ctx.get("trace"), "paged_attention")
