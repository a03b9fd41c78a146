"""Kernels: device time in the expert layer's operations, as a share of
busy time. An operation of the trace is named by its HLO text, operands'
names included, and the program names the expert layer's leaves
``moe_route_*`` (router, selection bias) and ``moe_gmm_*`` (the experts'
matrices), so the router's product and the grouped products hold those
names; the grouped product itself is XLA's ``ragged-dot`` custom call.
The sort, the gathers and the weighted combine between them read no
weight and carry no such name: the share errs low by what they take.

The grouped product is the share's main term: where no operation that
took time holds ``moe_gmm`` or ``ragged-dot`` (a program without the
layer, or a later kernel under another name), nothing is reported, so
that the share cannot fall with no gain behind it."""
from benchmark import trace_reduce

GROUPED = ("moe_gmm", "ragged-dot")
NEEDLES = ("moe_route",) + GROUPED


def read(ctx):
    reduced = ctx.get("trace")
    if not reduced or not any(
            v["self_s"] > 0 and any(n in name for n in GROUPED)
            for name, v in reduced["ops"].items()):
        return None
    return trace_reduce.ops_share_pct(reduced, *NEEDLES)
