"""Kernels: device time in the short convolution's own operations (the
depthwise filter and its gate: what reads the ``short_conv_w`` leaf, and
the projections in and out of the operator, ``short_conv_in`` /
``short_conv_out``), as a share of busy time."""
from benchmark import trace_reduce


def read(ctx):
    reduced = ctx.get("trace")
    if not reduced or not any("short_conv" in name
                              for name in reduced["ops"]):
        return None
    return trace_reduce.ops_share_pct(reduced, "short_conv")
