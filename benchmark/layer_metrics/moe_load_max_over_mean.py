"""Kernels: how unevenly the router loaded the experts in the window: the
routed token-expert pairs by expert (``moe_pairs_by_expert`` of
``engine.stats()``, summed over expert layers; the window's end less its
start), the largest over the mean. 1.0 is an even load."""


def read(ctx):
    after = (ctx.get("stats_after") or {}).get("moe_pairs_by_expert")
    if not after:
        return None
    before = ctx["stats_before"]["moe_pairs_by_expert"]
    delta = [a - b for a, b in zip(after, before)]
    if not sum(delta):
        return None
    return max(delta) * len(delta) / sum(delta)
