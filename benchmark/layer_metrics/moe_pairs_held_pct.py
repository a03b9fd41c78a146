"""Kernels: of the token-expert pairs the router made in decode, the share
that met an expert THIS device holds and was computed here
(``moe_pairs_held_decode`` over ``moe_pairs_decode`` of ``engine.stats()``,
the window's end less its start). 12.5% under an even router with an
eighth of the experts held: how far the experts' load in the cell is from
the deployment's, where the other chips' tokens bring the held experts
eight times as many. Nothing where the program counts no held pairs."""
from benchmark import span_reduce


def read(ctx):
    held = span_reduce.counter_delta(ctx, "moe_pairs_held_decode")
    routed = span_reduce.counter_delta(ctx, "moe_pairs_decode")
    if held is None or not routed:
        return None
    return 100.0 * held / routed
